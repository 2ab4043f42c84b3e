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
file states it, in blocks of rows, one stage of the model at a time
(``Step``), so that the device never holds the whole parameter tree or
its gradient in float32. It imports nothing of the program; the batches
are made by a copy of the program's synthetic data generator
(``tokens``).

A family brings its model as ``init(c, key)``, the weights laid out as
the program stores them, and ``stages(c)``, the model as an ordered list
of ``Stage``s, each with its term of the loss the program minimises (the
cross-entropy at the head, any auxiliary term where it arises). It may
bring train state besides: ``state_init(c, key)``, the program's
train-state entries besides ``params``, ``opt``, ``step`` and ``rng``,
under the same top-level keys, moved by no gradient; with
``state_step(c, s, stats, stated)`` their value after one step from the
stages' ``stats`` of the whole batch (one list entry per stage, stacked
over the blocks). The stages get the declared state before the step as
``state``.

An optimizer (``optims/<name>.py``) keeps its state as a dict of trees
that each have the parameters' structure as a prefix, so that it can be
stepped one leaf at a time.

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
import math
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
    """``models/<family>.py``: ``init(c, key)`` and ``stages(c)``, and
    ``state_init``, ``state_step`` where it declares state."""
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


def init_params(config: dict, seed: int, *, bench_dir: Path = BENCH_DIR):
    """The weights of ``--seed``, made on the device in one jitted call."""
    import jax
    c = config["config"]
    fam = family(c["family"], bench_dir)
    return jax.jit(lambda key: fam.init(c, key))(weight_key(seed))


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


@functools.cache
def change_program():
    """``(params, start) -> {leaf: |params - start|}`` as one program."""
    import jax
    return jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: _norm(x.astype("float32") - y.astype("float32")), a, b))


def change_norms(params, start) -> dict:
    """``{leaf: |params - start|}``; ``start`` a tree of the same layout."""
    import jax
    values = jax.tree.leaves(jax.device_get(change_program()(params, start)))
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
    peak_bytes: int = 0  # the reference's largest compiled program


# ---------------------------------------------------------------------------
# the reference, one stage at a time
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


@dataclasses.dataclass(frozen=True)
class Stage:
    """One stage of a family's model. ``fn(c, i, p, x, toks, mm, stated,
    state) -> (x_next, loss_term, stats)`` for one block of rows: ``p`` the
    parameters at ``parts`` (paths of keys into the parameter tree), each
    subtree whole, or at index ``i`` of its leading (layer) axis where
    ``i`` is given, nested as in the tree, stored as in it or in float32
    (the stage upcasts, as ``mm`` does); ``x`` the previous stage's
    ``x_next`` (None into the first stage, None out of the last);
    ``toks`` the block's tokens; ``state`` the family's declared state
    before the step. ``loss_term`` is the stage's term of the block's loss
    (0 where it has none); ``stats`` what ``state_step`` reads of the
    block (None where nothing)."""
    fn: object
    parts: tuple
    i: int | None = None


def cross_entropy(mm, x, head, toks):
    """The mean next-token cross-entropy of hidden states ``x`` (B, S, d)
    under ``head`` (d, V)."""
    import jax
    import jax.numpy as jnp
    logits = mm("bsd,dv->bsv", x[:, :-1], head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - picked)


def _at(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _add(acc, piece):
    return acc + piece


def _add_at(acc, piece, i):
    return acc.at[i].add(piece)


def _sumsq(g):
    import jax.numpy as jnp
    return jnp.sum(jnp.square(g))


def _host_sumsq(g) -> float:
    """``_sumsq`` of a host array: float32 squares summed pairwise (as
    numpy's ``sum`` does) in chunks, the chunks added in float64. A float32
    dot product loses the small terms of a large array (0.6% low on 2**25
    equal squares)."""
    flat = g.reshape(-1)
    return sum(float(np.sum(np.square(c)))
               for c in np.array_split(flat, max(1, flat.size >> 22)))


class Step:
    """One step of the configuration's optimizer on the family's stages,
    over blocks of ``rows`` rows, never holding the whole parameter tree
    or its gradient in float32 on the device:

    1. forward: each stage over every block, in order, from the stored
       parameters; each stage's input is kept;
    2. backward: from the last stage to the first, ``jax.vjp`` of the
       stage over every block in order, its float32 gradient summed over
       the blocks and divided by their number, then added into its leaves'
       sums: on the device where the whole gradient fits there, else on
       the host;
    3. the declared state moved by ``state_step`` from the stages'
       ``stats``;
    4. update: the gradient clipped to ``clip_global_norm`` over all
       leaves where the configuration states one, then the optimizer leaf
       by leaf.

    Each program is compiled once per shape; ``peak_bytes`` is the largest
    ``peak_memory_in_bytes`` among them."""

    def __init__(self, config: dict, precision: str, rows: int,
                 bench_dir: Path = BENCH_DIR):
        c = config["config"]
        self.c, self.stated = c, config["reference"]
        self.opt = self.stated["optimizer"]
        self.fam = family(c["family"], bench_dir)
        self.optim = optimizer(self.opt["name"], bench_dir)
        self.mm = matmul(precision)
        self.rows = rows
        self.stages = self.fam.stages(c)
        self.compiled: dict = {}
        self.peak_bytes = 0

    # -- programs ----------------------------------------------------------

    def _program(self, key, fn, donate, *args):
        import jax
        sig = (key, jax.tree.structure(args),
               tuple((a.shape, str(a.dtype)) for a in jax.tree.leaves(args)))
        prog = self.compiled.get(sig)
        if prog is None:
            prog = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
            self.compiled[sig] = prog
            self.peak_bytes = max(
                self.peak_bytes, prog.memory_analysis().peak_memory_in_bytes)
        return prog

    def _run(self, key, fn, donate, *args):
        return self._program(key, fn, donate, *args)(*args)

    @staticmethod
    def _room(device, programs) -> float:
        """The device's free bytes less what the largest of ``programs``
        needs besides its arguments; unbounded where the device does not
        report its memory (the CPU)."""
        stats = device.memory_stats()
        if not stats:
            return math.inf
        work = max(m.peak_memory_in_bytes - m.argument_size_in_bytes
                   for m in (p.memory_analysis() for p in programs))
        return stats["bytes_limit"] - stats["bytes_in_use"] - work

    def _pick(self, stage, params, i):
        import jax
        out: dict = {}
        for path in stage.parts:
            sub = _at(params, path)
            if stage.i is not None:
                sub = jax.tree.map(lambda a: a[i], sub)
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = sub
        return out

    def forward_fn(self, stage):
        """``(params, i, x, blocks, state) -> (x_next, loss_terms,
        stats)``, each stacked over the blocks."""
        import jax
        import jax.numpy as jnp

        def forward(params, i, x, blocks, state):
            p = self._pick(stage, params, i)

            def block(xb_tb):
                x_next, term, stats = stage.fn(
                    self.c, i, p, xb_tb[0], xb_tb[1], self.mm, self.stated,
                    state)
                return x_next, jnp.asarray(term, jnp.float32), stats
            return jax.lax.map(block, (x, blocks))
        return forward

    def backward_fn(self, stage):
        """``(params, i, x, dx, blocks, state) -> (g, dx_in)``: the
        stage's float32 gradient, summed over the blocks in order and
        divided by their number, and the gradient of its input."""
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32

        def backward(params, i, x, dx, blocks, state):
            p = self._pick(stage, params, i)

            def block(acc, blk):
                xb, dxb, tb = blk

                def f(p32, xb):
                    x_next, term, _ = stage.fn(self.c, i, p32, xb, tb,
                                               self.mm, self.stated, state)
                    return x_next, jnp.asarray(term, f32)
                # upcast in the loop, so that no float32 copy of the
                # stage's parameters outlives a block
                _, vjp = jax.vjp(f, jax.tree.map(lambda a: a.astype(f32), p),
                                 xb)
                gp, gx = vjp((dxb, jnp.ones((), f32)))
                return jax.tree.map(jnp.add, acc, gp), gx

            zero = jax.tree.map(lambda a: jnp.zeros(a.shape, f32), p)
            g, dx_in = jax.lax.scan(block, zero, (x, dx, blocks))
            n = blocks.shape[0]
            return jax.tree.map(lambda a: a / n, g), dx_in
        return backward

    def update_fn(self):
        """``(p, g, s, k, scale) -> (p, s, |g|)`` for one leaf: its
        gradient scaled by the global clip where one is stated, then the
        optimizer's update."""
        clip = self.opt.get("clip_global_norm")

        def update(p, g, s, k, scale):
            if clip:
                g = g * scale
            p, s = self.optim.update(p, g, s, k, self.opt)
            return p, s, _norm(g)
        return update

    # -- the step ----------------------------------------------------------

    def __call__(self, params, opt_state, state, k: int, toks):
        """``(params, opt_state, state, loss, norms)``: ``norms`` those of
        the gradient's leaves, after the clip."""
        import jax
        import jax.numpy as jnp
        c, n_rows = self.c, self.rows
        blocks = jnp.asarray(toks).reshape((-1, n_rows) + toks.shape[1:])
        n = blocks.shape[0]
        index = [np.int32(st.i or 0) for st in self.stages]
        shapes = {nm: a.shape for nm, a in zip(names(params),
                                               jax.tree.leaves(params))}

        # forward: each stage's input, loss terms and stats
        inputs, terms, stats, x = [], [], [], None
        for st, i in zip(self.stages, index):
            inputs.append(x)
            x, term, stat = self._run(("forward", st.fn, st.parts,
                                       st.i is None), self.forward_fn(st),
                                      (), params, i, x, blocks, state)
            terms.append(term)
            stats.append(stat)
        terms = np.asarray(jax.device_get(terms), np.float32)
        loss = np.float32(0)
        for b in range(n):
            block_loss = np.float32(0)
            for term in terms[:, b]:
                block_loss = np.float32(block_loss + term)
            loss = np.float32(loss + block_loss)
        loss = np.float32(loss / np.float32(n))

        # backward: the whole gradient is kept on the device where it fits
        # twice (the stages' pieces in flight and their sums) beside what
        # the device holds and the largest stage's working set; else each
        # stage's goes to the host, added into its leaves, before the stage
        # before it starts
        leaf_names = names(params)
        leaves = jax.tree.leaves(params)
        grads = {nm: None for nm in leaf_names}
        backward = [self._program(("backward", st.fn, st.parts, st.i is None),
                                  self.backward_fn(st), (2, 3), params, i, x,
                                  dx, blocks, state)
                    for st, i, x, dx in zip(self.stages, index, inputs,
                                            inputs[1:] + [None])]
        keep = 2 * 4 * sum(a.size for a in leaves) <= self._room(
            next(iter(leaves[0].devices())), backward)

        def add(st, g):
            for nm, piece in zip(names(g), jax.tree.leaves(g)):
                if keep:
                    acc = grads[nm] if grads[nm] is not None \
                        else jnp.zeros(shapes[nm], jnp.float32)
                    grads[nm] = (self._run(("add",), _add, (0,), acc, piece)
                                 if st.i is None else
                                 self._run(("add_at",), _add_at, (0,), acc,
                                           piece, np.int32(st.i)))
                else:
                    if grads[nm] is None:
                        grads[nm] = np.zeros(shapes[nm], np.float32)
                    acc = grads[nm] if st.i is None else grads[nm][st.i]
                    acc += jax.device_get(piece)

        dx = None
        for prog, st, i in zip(reversed(backward), reversed(self.stages),
                               reversed(index)):
            g, dx = prog(params, i, inputs.pop(), dx, blocks, state)
            add(st, g)            # g nested as the parameters
        del dx, g

        # the declared state, from the stages' stats
        if state:
            state = self._run(("state",), lambda s, st: self.fam.state_step(
                c, s, st, self.stated), (), state, stats)
        del stats

        # update, leaf by leaf
        flat, tree = jax.tree.flatten(params)
        g = [grads.pop(nm) for nm in leaf_names]
        scale = np.float32(1)
        clip = self.opt.get("clip_global_norm")
        if clip:
            total = np.float32(0)
            for gi in g:
                total = np.float32(total + np.float32(
                    self._run(("sumsq",), _sumsq, (), gi) if keep
                    else _host_sumsq(gi)))
            scale = np.minimum(np.float32(1), np.float32(clip) / np.maximum(
                np.sqrt(total), np.float32(1e-9)))
        moments = {key: tree.flatten_up_to(sub)
                   for key, sub in opt_state.items()}
        norms = {}
        for j, nm in enumerate(leaf_names):
            gj, g[j] = jax.device_put(g[j]), None
            s = {key: moments[key][j] for key in moments}
            flat[j], s, norms[nm] = self._run(
                ("update",), self.update_fn(), (0, 1, 2), flat[j], gj, s,
                np.float32(k), scale)
            if not keep:          # one leaf's gradient on the device at a time
                jax.block_until_ready(flat[j])
            for key in moments:
                moments[key][j] = s[key]
        opt_state = {key: tree.unflatten(moments[key]) for key in moments}
        return tree.unflatten(flat), opt_state, state, loss, norms


def reference(config: dict, seed: int, batches: list, *,
              precision: str = "highest", rows: int,
              bench_dir: Path = BENCH_DIR) -> Readings:
    """The readings of three reference steps from the weights of
    ``seed`` on ``batches``."""
    import jax
    opt = config["reference"]["optimizer"]
    step = Step(config, precision, rows, bench_dir)
    params = init_params(config, seed, bench_dir=bench_dir)
    opt_state = optimizer(opt["name"], bench_dir).init(params, opt)
    state = init_state(config, seed, bench_dir=bench_dir)
    losses, grad = [], None
    for k, toks in enumerate(batches[:STEPS]):
        params, opt_state, state, loss, norms = step(
            params, opt_state, state, k, toks)
        losses.append(float(loss))
        if k == 0:
            grad = {n: float(v) for n, v in jax.device_get(norms).items()}
    del opt_state
    change = changes(config, seed, params, state, bench_dir=bench_dir)
    return Readings(losses, grad, change, step.peak_bytes)


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
