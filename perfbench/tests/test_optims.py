"""The reference's optimizers (``optims/<name>.py``): AdamW as the
reference took it before it was moved into a file of its own, bit for
bit; Adafactor as the program's ``optim/adafactor.py`` takes it; and the
first gradient read back from the program's optimizer state."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

import reference  # noqa: E402

ADAMW = {"name": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-08,
         "weight_decay": 0.1, "decay_min_rank": 2, "clip_global_norm": 1.0,
         "lr": {"peak": 0.0003, "warmup": 100, "total": 10000,
                "floor": 0.1}}
# every leaf kind: rank 1, rank 2 and 3 with the last two dimensions
# factored, and rank 2 and 4 with one of them under the factoring size
SHAPES = {"bias": (48,), "embed": (40, 64), "stack": (2, 64, 33),
          "narrow": (8, 64), "heads": (2, 64, 4, 16)}


def seeded(seed: int, scale: float = 1.0) -> dict:
    rng = np.random.default_rng(seed)
    return {k: (scale * rng.standard_normal(s)).astype(np.float32)
            for k, s in SHAPES.items()}


def parent_adamw_step(opt):
    """The AdamW update and first-gradient read inline in the parent's
    ``reference.make_step`` and ``run.first_steps``: the oracle."""
    import jax
    import jax.numpy as jnp

    def step(params, m, v, k, g):
        f32 = jnp.float32
        count = k + 1
        bc1 = 1.0 - opt["b1"] ** count
        bc2 = 1.0 - opt["b2"] ** count
        lr = reference.lr_at(opt, k)

        def update(p, gi, mi, vi):
            mi = opt["b1"] * mi + (1 - opt["b1"]) * gi
            vi = opt["b2"] * vi + (1 - opt["b2"]) * gi * gi
            u = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
            if p.ndim >= opt["decay_min_rank"]:
                u = u + opt["weight_decay"] * p.astype(f32)
            return (p.astype(f32) - lr * u).astype(p.dtype), mi, vi

        tree = jax.tree.structure(params)
        out = [update(*leaf) for leaf in zip(
            *(jax.tree.leaves(t) for t in (params, g, m, v)))]
        return tuple(tree.unflatten([o[i] for o in out]) for i in range(3))

    def read(m):
        return reference.leaf_norms(m, 1.0 / (1.0 - opt["b1"]))

    return jax.jit(step), read


def test_adamw_matches_the_parents_inline_update_bit_for_bit():
    import jax
    import jax.numpy as jnp
    adamw = reference.optimizer("adamw")
    oracle, oracle_read = parent_adamw_step(ADAMW)
    moved = jax.jit(lambda p, g, s, k: adamw.update(p, g, s, k, ADAMW))
    params = seeded(1)
    state = adamw.init(params, ADAMW)
    p_old, m, v = params, state["m"], state["v"]
    for k in range(3):
        g = seeded(10 + k, 1e-2)
        p_old, m, v = oracle(p_old, m, v, jnp.float32(k), g)
        params, state = moved(params, g, state, jnp.float32(k))
        for name in SHAPES:
            for a, b in ((params, p_old), (state["m"], m), (state["v"], v)):
                assert np.array_equal(np.asarray(a[name]),
                                      np.asarray(b[name])), (k, name)
        if k == 0:
            assert adamw.first_grad_norms(state, ADAMW) == oracle_read(m)


def program_state(name: str, grads: dict) -> dict:
    """The program's optimizer state after one step on ``grads``."""
    import jax.numpy as jnp

    from repro.optim import Adafactor, AdamW
    optim = {"adamw": AdamW, "adafactor": Adafactor}[name]()
    params = {k: jnp.asarray(v) for k, v in seeded(2).items()}
    _, state = optim.update(grads, optim.init(params), params, 1e-3)
    return state


@pytest.mark.parametrize("name,opt", [("adamw", ADAMW),
                                      ("adafactor", tiny.ADAFACTOR)])
def test_first_grad_norms_read_a_known_gradient(name, opt):
    # a global norm under AdamW's clip of 1, so that it leaves g as it is
    grads = seeded(3, 1e-3)
    got = reference.optimizer(name).first_grad_norms(
        program_state(name, grads), opt)
    want = {k: float(np.linalg.norm(g.astype(np.float64)))
            for k, g in grads.items()}
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-5), k


def test_adafactor_follows_the_programs_update():
    import jax
    import jax.numpy as jnp

    from repro.optim import Adafactor
    opt = dict(tiny.ADAFACTOR, weight_decay=0.1)
    program = Adafactor(weight_decay=0.1)
    ours = reference.optimizer("adafactor")
    params = p_prog = seeded(4)
    state, s_prog = ours.init(params, opt), program.init(p_prog)
    step = jax.jit(lambda p, g, s, k: ours.update(p, g, s, k, opt))
    for k in range(3):
        g = seeded(20 + k, 1e-2)
        p_prog, s_prog = program.update(g, s_prog, p_prog,
                                        reference.lr_at(opt, k))
        params, state = step(params, g, state, jnp.float32(k))
        for name in SHAPES:
            np.testing.assert_allclose(params[name], p_prog[name],
                                       rtol=1e-6, atol=0)
            assert set(state["f"][name]) == set(s_prog["f"][name])
            for part, x in state["f"][name].items():
                np.testing.assert_allclose(x, s_prog["f"][name][part],
                                           rtol=1e-6, atol=0)
