"""The reference taken one stage at a time (``reference.Step``) against
the whole-tree step it replaced, kept here as the oracle: the parent's
``make_step``, which differentiates the whole parameter tree at float32
in one program, on the families' models written as one forward pass (a
scan over the layers) and their hooks as whole-tree functions of the
float32 parameters. Both sides take the same three steps from the same
weights on the same batches; they differ only in how the arithmetic is
fused and in the order of a few sums, so they agree to float32
round-off."""
from __future__ import annotations

import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

import cells  # noqa: E402
import reference  # noqa: E402

SEED = 2**31 + 41
BINS = 8
WEIGHT = 1e-3
LOSS_RTOL = 1e-6
NORM_RTOL = 1e-5


# ---------------------------------------------------------------------------
# the oracle: the whole-tree models and hooks, and the parent's step
# ---------------------------------------------------------------------------

def dense_forward(c, p, tokens, mm, stated):
    import jax
    import jax.numpy as jnp
    m = reference.family("dense")
    F32 = jnp.float32
    eps = stated["norm_eps"]
    S, hd = tokens.shape[1], c["head_dim"]
    inv = 1.0 / c["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = p["embed"][tokens].astype(F32)

    @jax.checkpoint
    def layer(x, lp):
        x = x + m.attention(c, lp, m.normalize(c, lp["norm_in"], x, eps), mm,
                            cos, sin)
        return x + m.mlp(c, lp["mlp"], m.normalize(c, lp["norm_mlp"], x, eps),
                         mm), None

    x, _ = jax.lax.scan(layer, x, p["stage_0"]["b0"])
    head = p["embed"].T if c["tie_embeddings"] else p["lm_head"]
    return m.normalize(c, p["final_norm"], x, eps), head


def ssm_forward(c, p, tokens, mm, stated):
    import jax
    import jax.numpy as jnp
    m = reference.family("ssm")
    eps = stated["norm_eps"]
    x = p["embed"][tokens].astype(jnp.float32)

    @jax.checkpoint
    def layer(x, lp):
        h = m.rmsnorm(x, lp["norm_in"]["scale"], eps)
        return x + m.mixer(c, lp["ssm"], h, mm, eps), None

    x, _ = jax.lax.scan(layer, x, p["stage_0"]["b0"])
    return m.rmsnorm(x, p["final_norm"]["scale"], eps), p["embed"].T


FORWARD = {"dense": dense_forward, "ssm": ssm_forward}


def lse_and_ce(forward, c, p32, toks, mm, stated):
    import jax
    import jax.numpy as jnp
    x, head = forward(c, p32, toks, mm, stated)
    logits = mm("bsd,dv->bsv", x[:, :-1], head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
    return lse, jnp.mean(lse - picked)


def token_load(toks):
    import jax.numpy as jnp
    return jnp.zeros((BINS,), jnp.float32).at[toks.reshape(-1) % BINS].add(1.0)


def zloss(c, p32, toks, mm, stated, state):
    import jax.numpy as jnp
    lse, ce = lse_and_ce(dense_forward, c, p32, toks, mm, stated)
    return ce + 1e-2 * jnp.mean(lse * lse)


def load_in_loss(c, p32, toks, mm, stated, state):
    import jax.numpy as jnp
    _, ce = lse_and_ce(dense_forward, c, p32, toks, mm, stated)
    return ce + WEIGHT * jnp.mean(state["token_load"])


def count_tokens(c, s, p32, toks, mm, stated):
    return {"token_load": s["token_load"] + token_load(toks)}


# each test family as the parent took it: (whole-tree loss, state_step)
HOOKS = {None: (None, None), "dense_ce": (None, None),
         "dense_zloss": (zloss, None),
         "dense_token_load": (None, count_tokens),
         "dense_load_in_loss": (load_in_loss, count_tokens)}


def parent_make_step(config, precision, rows, bench_dir, loss, state_step):
    """The parent's ``reference.make_step``, with the family's whole-tree
    forward, ``loss`` and ``state_step`` handed in."""
    import jax
    import jax.numpy as jnp
    c, stated = config["config"], config["reference"]
    opt = stated["optimizer"]
    optim = reference.optimizer(opt["name"], bench_dir)
    mm = reference.matmul(precision)
    f32 = jnp.float32
    forward = FORWARD[c["family"]]

    def block_loss(p32, toks, state):
        if loss is not None:
            return loss(c, p32, toks, mm, stated, state)
        return lse_and_ce(forward, c, p32, toks, mm, stated)[1]

    def step(params, opt_state, state, k, toks):
        p32 = jax.tree.map(lambda x: x.astype(f32), params)
        blocks = toks.reshape((-1, rows) + toks.shape[1:])

        def body(acc, blk):
            loss, g = jax.value_and_grad(block_loss)(p32, blk, state)
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), f32), jax.tree.map(jnp.zeros_like, p32))
        (loss_, g), _ = jax.lax.scan(body, zero, blocks)
        n = blocks.shape[0]
        loss_ = loss_ / n
        g = jax.tree.map(lambda x: x / n, g)
        clip = opt.get("clip_global_norm")
        if clip:
            total = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                 for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(
                1.0, clip / jnp.maximum(total, 1e-9)), g)
        if state:
            state = state_step(c, state, p32, toks, mm, stated)
        params, opt_state = optim.update(params, g, opt_state, k, opt)
        return params, opt_state, state, loss_, jax.tree.map(
            reference._norm, g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def parent_reference(config, seed, batches, *, rows, bench_dir, hooks):
    import jax
    import jax.numpy as jnp
    opt = config["reference"]["optimizer"]
    step = parent_make_step(config, "highest", rows, bench_dir, *hooks)
    params = reference.init_params(config, seed, bench_dir=bench_dir)
    opt_state = reference.optimizer(opt["name"], bench_dir).init(params, opt)
    state = reference.init_state(config, seed, bench_dir=bench_dir)
    losses, grad = [], None
    for k, toks in enumerate(batches[:reference.STEPS]):
        params, opt_state, state, loss, norms = step(
            params, opt_state, state, jnp.float32(k), jnp.asarray(toks))
        losses.append(float(loss))
        if k == 0:
            grad = dict(zip(reference.names(params), map(
                float, jax.tree.leaves(jax.device_get(norms)))))
    del opt_state
    change = reference.changes(config, seed, params, state,
                               bench_dir=bench_dir)
    return reference.Readings(losses, grad, change)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

CASES = [("tiny-dense.incr", None), ("tiny-dense-adafactor.incr", None),
         ("tiny-mamba.incr", None), ("tiny-dense.incr", "dense_ce"),
         ("tiny-dense.incr", "dense_zloss"),
         ("tiny-dense.incr", "dense_token_load"),
         ("tiny-dense.incr", "dense_load_in_loss")]


def readings(tmp_path, monkeypatch, cell, test_family):
    """The staged reference's readings with the gradient kept on the
    device and with it moved to the host stage by stage; the oracle's at
    the cell's row blocks, and at blocks of half as many rows (the same
    arithmetic with its sums over blocks in another order)."""
    root = tiny.make_root(tmp_path, families={"dense": test_family}
                          if test_family else None)
    cell = cells.load_cell(cell, root)
    cfg = cells.model_config(cell.config)
    mix = cell.mix
    batches = [reference.tokens(SEED % 2**31, k, mix["batch"],
                                mix["seq_len"], cfg.vocab_size)
               for k in range(reference.STEPS)]
    rows, kw = mix["reference_rows"], {"bench_dir": cell.bench_dir}
    oracle = [parent_reference(cell.config, SEED, batches, rows=r,
                               hooks=HOOKS[test_family], **kw)
              for r in (rows, rows // 2)]
    staged = [reference.reference(cell.config, SEED, batches, rows=rows,
                                  **kw)]
    with monkeypatch.context() as m:     # a device with no room to spare
        m.setattr(reference.Step, "_room",
                  staticmethod(lambda device, programs: 0.0))
        staged.append(reference.reference(cell.config, SEED, batches,
                                          rows=rows, **kw))
    return staged, *oracle


def close(a: float, b: float, rtol: float) -> bool:
    return math.isclose(a, b, rel_tol=rtol, abs_tol=0.0)


@pytest.mark.parametrize("cell,test_family", CASES)
def test_staged_reference_reads_as_the_whole_tree_oracle(
        tmp_path, monkeypatch, cell, test_family):
    staged, oracle, reblocked = readings(tmp_path, monkeypatch, cell,
                                         test_family)
    for read in staged:
        assert len(read.losses) == reference.STEPS
        for a, b in zip(read.losses, oracle.losses):
            assert close(a, b, LOSS_RTOL), (read.losses, oracle.losses)
        assert read.grad.keys() == oracle.grad.keys()
        for leaf in oracle.grad:
            assert close(read.grad[leaf], oracle.grad[leaf], NORM_RTOL), leaf
        assert read.change.keys() == oracle.change.keys()
        for leaf, want in oracle.change.items():
            # Adam divides by the gradient's magnitude, so a leaf whose
            # gradient is small (a key's bias under softmax) moves by more
            # than round-off where the oracle only reorders its own sums
            own = abs(reblocked.change[leaf] - want)
            assert abs(read.change[leaf] - want) \
                <= max(NORM_RTOL * want, 2 * own), leaf
        assert reference.gaps(read, read)["left_out"] \
            == reference.gaps(oracle, oracle)["left_out"]
        assert read.peak_bytes > 0


def test_host_sum_of_squares_keeps_the_small_terms():
    # a float32 dot product of 2**25 equal squares reads 0.6% high; a
    # pairwise float32 sum is within a few units of its last place
    g = np.full(2**25, 1e-3, np.float32)
    want = float(np.float32(1e-3)) ** 2 * g.size
    assert reference._host_sumsq(g) == pytest.approx(want, rel=1e-5)
    assert float(np.dot(g, g)) != pytest.approx(want, rel=1e-3)
