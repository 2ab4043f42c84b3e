"""The plain reference of the train step, and the comparison with the
program's first three steps.

Set-up builds the program's state from weights the benchmark makes
(``init_params``: ``models/<family>.py``'s ``init`` from ``--seed``, in one
jitted call on the device) and drives it through its first three steps
with the window's own call and feed. It keeps three readings of the
program (``Readings``): each step's loss; the norm, leaf by leaf,
of the first gradient as the optimizer got it, worked out from the
optimizer's state after one step (``optims/<name>.py``'s
``first_grad_norms``); and the norm, leaf by leaf, of the change over the
three steps of the parameters and of any state the family declares.

After the window, once the program's state is gone, ``reference`` takes
the same three steps from the same weights on the same batches: the
model of ``models/<family>.py`` at float32 (``highest`` matmul
precision) and the optimizer of ``optims/<name>.py`` as the configuration
file states it, in blocks of rows. It imports nothing of the program; the
batches are made by a copy of the program's synthetic data generator
(``tokens``).

A family brings its model, and may bring more of what the program's
step does:

* ``loss(c, p32, toks, mm, stated)``: the whole loss the program
  minimises, auxiliary terms included; without it the loss is the
  cross-entropy of ``forward``'s hidden states under its head;
* ``state_init(c, key)``: the program's train-state entries besides
  ``params``, ``opt``, ``step`` and ``rng``, under the same top-level keys,
  moved by no gradient; with ``state_step(c, s, p32, toks, mm, stated)``
  their value after one step from the parameters before it and the whole
  batch. A family that declares them gets them in ``loss`` as ``state=``.

``gaps`` compares the two: the widest loss gap, and for each leaf norm
the gap between the program's norm and the reference's against the
larger of the reference's norm of that leaf and of the median leaf,
taken at the worst leaf. Parameter leaves whose first gradient in the
reference is under a thousandth of the median leaf's are left out of both
norms: Adam moves them by round-off alone. Every leaf of a declared state
is compared, by its change, against the same median floor.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
STEPS = 3
SMALL_GRAD = 1e-3


# ---------------------------------------------------------------------------
# the model family, the weights and the batches
# ---------------------------------------------------------------------------

PROGRAM_STATE = ("params", "opt", "step", "rng")


def _module(kind: str, name: str, bench_dir: Path):
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind}/{name}.py at {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def family(name: str, bench_dir: Path = BENCH_DIR):
    """``models/<family>.py``: ``init(c, key)`` and ``forward(...)``, and
    the hooks ``loss``, ``state_init``, ``state_step`` where it has them."""
    return _module("models", name, bench_dir)


@functools.cache
def optimizer(name: str, bench_dir: Path = BENCH_DIR):
    """``optims/<name>.py``: ``init(params, opt)``, ``update(params, g,
    state, k, opt)`` and ``first_grad_norms(program_state, opt)``."""
    return _module("optims", name, bench_dir)


def declared_state(fam, c: dict, key) -> dict:
    """The family's declared train-state entries at step 0; none where it
    declares none."""
    if not hasattr(fam, "state_init"):
        return {}
    extra = fam.state_init(c, key)
    clash = sorted(set(extra) & set(PROGRAM_STATE))
    if clash:
        raise ValueError(f"a family's state may not take the keys {clash}")
    return extra


def weight_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def init_params(config: dict, seed: int, *, out_shardings=None,
                bench_dir: Path = BENCH_DIR):
    """The weights of ``--seed``, made on the device in one jitted call."""
    import jax
    c = config["config"]
    fam = family(c["family"], bench_dir)
    return jax.jit(lambda key: fam.init(c, key),
                   out_shardings=out_shardings)(weight_key(seed))


def init_state(config: dict, seed: int, *, bench_dir: Path = BENCH_DIR):
    """The family's declared state at step 0 for ``--seed``."""
    import jax
    c = config["config"]
    fam = family(c["family"], bench_dir)
    if not hasattr(fam, "state_init"):
        return {}
    return jax.jit(lambda key: declared_state(fam, c, key))(weight_key(seed))


def tokens(seed: int, step: int, batch: int, seq_len: int,
           vocab: int) -> np.ndarray:
    """Batch ``step`` of the program's synthetic pipeline for ``seed``: a
    copy of its generator (counter-based Philox keyed on (seed, step); each
    row drawn from one of three sources, a Zipf(1.3) draw folded into the
    source's band of the vocabulary)."""
    mixture = np.array([0.6, 0.3, 0.1])
    mixture = mixture / mixture.sum()
    rng = np.random.Generator(np.random.Philox(key=[seed, step]))
    src = rng.choice(len(mixture), size=(batch,), p=mixture)
    bands = np.linspace(0, vocab, len(mixture) + 1).astype(np.int64)
    out = np.empty((batch, seq_len), np.int32)
    for i in range(len(mixture)):
        rows = src == i
        n = int(rows.sum())
        if n == 0:
            continue
        lo, hi = int(bands[i]), max(int(bands[i + 1]), int(bands[i]) + 1)
        z = rng.zipf(1.3, size=(n, seq_len)).astype(np.int64)
        out[rows] = (lo + (z % max(hi - lo, 1))).astype(np.int32)
    return out % vocab


# ---------------------------------------------------------------------------
# matmul precisions
# ---------------------------------------------------------------------------

def matmul(precision: str):
    """``mm(subscripts, a, b)`` in float32 out. ``highest``: float32
    operands at full precision, the reference. ``int8``: each operand
    rounded to 255 levels of its largest magnitude first (symmetric,
    per tensor), the control, one precision below the configuration's
    bfloat16."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if precision == "highest":
        return lambda s, a, b: jnp.einsum(
            s, a.astype(f32), b.astype(f32),
            precision=jax.lax.Precision.HIGHEST)

    if precision != "int8":
        raise ValueError(precision)

    def q8(x):
        x = x.astype(f32)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
        return jnp.round(x / scale) * scale

    return lambda s, a, b: jnp.einsum(s, q8(a), q8(b),
                                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# leaf norms
# ---------------------------------------------------------------------------

def names(tree) -> list:
    import jax
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norm(x):
    import jax.numpy as jnp
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@functools.cache
def _norms_program():
    import jax
    return jax.jit(lambda tree: jax.tree.map(_norm, tree))


def leaf_norms(tree, scale: float = 1.0) -> dict:
    import jax
    values = jax.tree.leaves(jax.device_get(_norms_program()(tree)))
    return {n: float(v) * scale for n, v in zip(names(tree), values)}


def change_norms(params, start) -> dict:
    """``{leaf: |params - start|}``; ``start`` a tree of the same layout."""
    import jax
    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: _norm(x.astype("float32") - y.astype("float32")), a, b))
    values = jax.tree.leaves(jax.device_get(diff(params, start)))
    return {n: float(v) for n, v in zip(names(params), values)}


def changes(config: dict, seed: int, params, state: dict, *,
            bench_dir: Path = BENCH_DIR) -> dict:
    """The change norms of the parameters and of the declared state since
    step 0 of ``seed``, the state's leaves named under their keys."""
    change = change_norms(params, init_params(config, seed,
                                              bench_dir=bench_dir))
    if state:
        moved = change_norms(state, init_state(config, seed,
                                               bench_dir=bench_dir))
        clash = sorted(set(change) & set(moved))
        if clash:
            raise ValueError(f"state leaves named as parameters: {clash}")
        change.update(moved)
    return change


@dataclasses.dataclass
class Readings:
    losses: list       # each step's loss
    grad: dict         # {leaf: norm of the first gradient}
    change: dict       # {leaf: norm of the change after 3}: the parameters
                       # and the declared state, which has no gradient


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def lr_at(opt: dict, step):
    """Linear warm-up to the peak, then cosine decay to ``floor`` x peak;
    ``step`` counts from 0."""
    import jax.numpy as jnp
    lr = opt["lr"]
    step = jnp.asarray(step, jnp.float32)
    warm = lr["peak"] * (step + 1) / lr["warmup"]
    frac = jnp.clip((step - lr["warmup"])
                    / max(lr["total"] - lr["warmup"], 1), 0.0, 1.0)
    cos = lr["peak"] * (lr["floor"] + (1 - lr["floor"]) * 0.5
                        * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < lr["warmup"], warm, cos)


def make_step(config: dict, precision: str, rows: int,
              bench_dir: Path = BENCH_DIR):
    """``step(params, opt_state, state, k, tokens) -> (params, opt_state,
    state, loss, norms)``: one step of the configuration's optimizer on the
    family's loss, the batch's mean loss taken over blocks of ``rows``
    rows; ``norms`` are those of the gradient's leaves, clipped to
    ``clip_global_norm`` where the configuration states one; ``state`` is
    the family's declared state, moved by its ``state_step``."""
    import jax
    import jax.numpy as jnp
    c, stated = config["config"], config["reference"]
    opt = stated["optimizer"]
    fam = family(c["family"], bench_dir)
    optim = optimizer(opt["name"], bench_dir)
    mm = matmul(precision)
    f32 = jnp.float32

    def block_loss(p32, toks, state):
        if hasattr(fam, "loss"):
            kw = {"state": state} if hasattr(fam, "state_init") else {}
            return fam.loss(c, p32, toks, mm, stated, **kw)
        x, head = fam.forward(c, p32, toks, mm, stated)
        logits = mm("bsd,dv->bsv", x[:, :-1], head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
        return jnp.mean(lse - picked)

    def step(params, opt_state, state, k, toks):
        p32 = jax.tree.map(lambda x: x.astype(f32), params)
        blocks = toks.reshape((-1, rows) + toks.shape[1:])

        def body(acc, blk):
            loss, g = jax.value_and_grad(block_loss)(p32, blk, state)
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), f32), jax.tree.map(jnp.zeros_like, p32))
        (loss, g), _ = jax.lax.scan(body, zero, blocks)
        n = blocks.shape[0]
        loss = loss / n
        g = jax.tree.map(lambda x: x / n, g)
        clip = opt.get("clip_global_norm")
        if clip:
            total = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                 for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(
                1.0, clip / jnp.maximum(total, 1e-9)), g)
        if state:
            state = fam.state_step(c, state, p32, toks, mm, stated)
        params, opt_state = optim.update(params, g, opt_state, k, opt)
        return params, opt_state, state, loss, jax.tree.map(_norm, g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def reference(config: dict, seed: int, batches: list, *,
              precision: str = "highest", rows: int,
              bench_dir: Path = BENCH_DIR) -> Readings:
    """The readings of three reference steps from the weights of
    ``seed`` on ``batches``."""
    import jax
    import jax.numpy as jnp
    opt = config["reference"]["optimizer"]
    step = make_step(config, precision, rows, bench_dir)
    params = init_params(config, seed, bench_dir=bench_dir)
    opt_state = optimizer(opt["name"], bench_dir).init(params, opt)
    state = init_state(config, seed, bench_dir=bench_dir)
    losses, grad = [], None
    for k, toks in enumerate(batches[:STEPS]):
        params, opt_state, state, loss, norms = step(
            params, opt_state, state, jnp.float32(k), jnp.asarray(toks))
        losses.append(float(loss))
        if k == 0:
            grad = dict(zip(names(params),
                            map(float, jax.tree.leaves(jax.device_get(norms)))))
    del opt_state
    change = changes(config, seed, params, state, bench_dir=bench_dir)
    return Readings(losses, grad, change)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def gaps(got: Readings, ref: Readings) -> dict:
    """``loss_gap``, ``grad_norm_gap`` and ``update_norm_gap`` of ``got``
    against ``ref``, with the leaf each norm gap was worst at. A reading
    that is missing or not finite gives an infinite gap."""
    def gap(a, b):
        d = abs(a - b)
        return d if np.isfinite(d) else np.inf

    med = float(np.median(list(ref.grad.values())))
    kept = sorted(k for k, g in ref.grad.items() if g >= SMALL_GRAD * med)
    # the declared state has no gradient and is compared whole
    state = sorted(set(ref.change) - set(ref.grad))

    def worst(a: dict, b: dict, leaves: list):
        floor = float(np.median([b[k] for k in kept]))
        per = {k: gap(a.get(k, np.inf), b[k]) / max(b[k], floor, 1e-30)
               for k in leaves}
        leaf = max(per, key=per.get)
        return float(per[leaf]), leaf

    loss_gap = max(map(gap, got.losses, ref.losses)) \
        if len(got.losses) == len(ref.losses) else np.inf
    grad_gap, grad_leaf = worst(got.grad, ref.grad, kept)
    change_gap, change_leaf = worst(got.change, ref.change, kept + state)
    return {"loss_gap": float(loss_gap), "grad_norm_gap": grad_gap,
            "update_norm_gap": change_gap,
            "worst_leaves": {"grad_norm_gap": grad_leaf,
                             "update_norm_gap": change_leaf},
            "left_out": sorted(set(ref.grad) - set(kept))}
