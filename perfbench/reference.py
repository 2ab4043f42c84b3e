"""The plain reference of the train step, and the comparison with the
program's first three steps.

Set-up builds the program's state from weights the benchmark makes
(``init_params``: ``models/<family>.py``'s ``init`` from ``--seed``, in one
jitted call on the device) and drives it through its first three steps
with the window's own call and feed. It keeps three readings of the
program (``Readings``): each step's loss; the norm, leaf by leaf,
of the first gradient as the optimizer got it, worked out from the first
moment after one step (``m = (1 - b1) g``); and the norm, leaf by leaf,
of the parameters' change over the three steps.

After the window, once the program's state is gone, ``reference`` takes
the same three steps from the same weights on the same batches: the
model of ``models/<family>.py`` at float32 (``highest`` matmul
precision) and AdamW as the configuration file states it, in blocks of
rows. It imports nothing of the program; the batches are made by a copy
of the program's synthetic data generator (``tokens``).

``gaps`` compares the two: the widest loss gap, and for each leaf norm
the gap between the program's norm and the reference's against the
larger of the reference's norm of that leaf and of the median leaf,
taken at the worst leaf. Leaves whose first gradient in the reference
is under a thousandth of the median leaf's are left out of both norms:
Adam moves them by round-off alone.
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

@functools.cache
def family(name: str, bench_dir: Path = BENCH_DIR):
    """``models/<family>.py``: ``init(c, key)`` and ``forward(...)``."""
    path = Path(bench_dir) / "models" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reference model for family {name!r} "
                                f"at {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_models_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


@dataclasses.dataclass
class Readings:
    losses: list       # each step's loss
    grad: dict         # {leaf: norm of the first gradient}
    change: dict       # {leaf: norm of the parameters' change after 3}


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
    """``step(params, m, v, k, tokens) -> (params, m, v, loss, norms)``:
    one AdamW step as the configuration states it, the batch's mean loss
    taken over blocks of ``rows`` rows; ``norms`` are those of the clipped
    gradient's leaves."""
    import jax
    import jax.numpy as jnp
    c, stated = config["config"], config["reference"]
    opt = stated["optimizer"]
    fam = family(c["family"], bench_dir)
    mm = matmul(precision)
    f32 = jnp.float32

    def block_loss(p32, toks):
        x, head = fam.forward(c, p32, toks, mm, stated)
        logits = mm("bsd,dv->bsv", x[:, :-1], head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
        return jnp.mean(lse - picked)

    def step(params, m, v, k, toks):
        p32 = jax.tree.map(lambda x: x.astype(f32), params)
        blocks = toks.reshape((-1, rows) + toks.shape[1:])

        def body(acc, blk):
            loss, g = jax.value_and_grad(block_loss)(p32, blk)
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), f32), jax.tree.map(jnp.zeros_like, p32))
        (loss, g), _ = jax.lax.scan(body, zero, blocks)
        n = blocks.shape[0]
        loss = loss / n
        g = jax.tree.map(lambda x: x / n, g)
        total = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        clip = opt["clip_global_norm"]
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, clip / jnp.maximum(total, 1e-9)), g)
        count = k + 1
        bc1 = 1.0 - opt["b1"] ** count
        bc2 = 1.0 - opt["b2"] ** count
        lr = lr_at(opt, k)

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
        params, m, v = (tree.unflatten([o[i] for o in out]) for i in range(3))
        return params, m, v, loss, jax.tree.map(_norm, g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def reference(config: dict, seed: int, batches: list, *,
              precision: str = "highest", rows: int,
              bench_dir: Path = BENCH_DIR) -> Readings:
    """The readings of three reference steps from the weights of
    ``seed`` on ``batches``."""
    import jax
    import jax.numpy as jnp
    step = make_step(config, precision, rows, bench_dir)
    params = init_params(config, seed, bench_dir=bench_dir)
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    losses, grad = [], None
    for k, toks in enumerate(batches[:STEPS]):
        params, m, v, loss, norms = step(params, m, v, jnp.float32(k),
                                         jnp.asarray(toks))
        losses.append(float(loss))
        if k == 0:
            grad = dict(zip(names(params),
                            map(float, jax.tree.leaves(jax.device_get(norms)))))
    del m, v
    start = init_params(config, seed, bench_dir=bench_dir)
    change = change_norms(params, start)
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

    def worst(a: dict, b: dict):
        floor = float(np.median([b[k] for k in kept]))
        per = {k: gap(a.get(k, np.inf), b[k]) / max(b[k], floor, 1e-30)
               for k in kept}
        leaf = max(per, key=per.get)
        return float(per[leaf]), leaf

    loss_gap = max(map(gap, got.losses, ref.losses)) \
        if len(got.losses) == len(ref.losses) else np.inf
    grad_gap, grad_leaf = worst(got.grad, ref.grad)
    change_gap, change_leaf = worst(got.change, ref.change)
    return {"loss_gap": float(loss_gap), "grad_norm_gap": grad_gap,
            "update_norm_gap": change_gap,
            "worst_leaves": {"grad_norm_gap": grad_leaf,
                             "update_norm_gap": change_leaf},
            "left_out": sorted(set(ref.grad) - set(kept))}
