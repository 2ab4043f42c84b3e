"""Every program the staged reference runs fits one TPU v5e at the size of
a demanding one-chip share, and the whole-tree step it replaced does not.

The size is ``standin.py``'s: a dense model at Kimi-K2-Instruct's dense
widths, 3.45 B parameters, under Adafactor, batch 2 x 2,048 in blocks of
one row. Each program is compiled for a described ``v5e:1x1`` chip (the
TPU compiler runs without one), and its peak, less the arguments it
reads, plus all that the reference holds on the device at the time (the
parameters, the optimizer's state, the stages' saved inputs, the leaf's
gradient), has to stay within 14 GiB of the chip's 15.75 GiB.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. The persistent compilation cache is off around these compiles:
an entry written for a described chip cannot be read back without one.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import standin  # noqa: E402

import reference  # noqa: E402

GiB = 2**30
FITS = 14 * GiB
CHIP = 15.75 * GiB


@pytest.fixture(scope="module")
def chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def on(chip, tree):
    """``tree``'s shapes, placed on the described chip."""
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)


def nbytes(tree) -> int:
    import jax
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def shapes(chip):
    """The stand-in's key, parameters, optimizer state, row blocks and
    stage index as shapes on the chip."""
    import jax
    import jax.numpy as jnp
    c = standin.CONFIG["config"]
    opt = standin.CONFIG["reference"]["optimizer"]
    fam = reference.family(c["family"])
    key = on(chip, jax.eval_shape(lambda: reference.weight_key(11)))
    params = on(chip, jax.eval_shape(lambda k: fam.init(c, k), key))
    opt_state = on(chip, jax.eval_shape(
        lambda p: reference.optimizer(opt["name"]).init(p, opt), params))
    blocks = on(chip, jax.ShapeDtypeStruct(
        (standin.BATCH // standin.ROWS, standin.ROWS, standin.SEQ_LEN),
        jnp.int32))
    index = on(chip, jax.ShapeDtypeStruct((), jnp.int32))
    return key, params, opt_state, blocks, index


def test_every_program_of_the_staged_reference_fits_one_chip(chip):
    import jax
    import jax.numpy as jnp
    c = standin.CONFIG["config"]
    fam = reference.family(c["family"])
    key, params, opt_state, blocks, index = shapes(chip)
    step = reference.Step(standin.CONFIG, "highest", standin.ROWS)
    peaks = {}

    def fits(name, fn, donate, *args, resident):
        """The device's bytes at the program's peak: the peak, less the
        arguments it reads, plus all that the reference holds then."""
        prog = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        m = prog.memory_analysis()
        peaks[name] = (m.peak_memory_in_bytes - m.argument_size_in_bytes
                       + resident)

    fits("init", lambda k: fam.init(c, k), (), key, resident=0)
    inputs, x = [], None
    for st in step.stages:
        inputs.append(x)
        x = on(chip, jax.eval_shape(step.forward_fn(st), params, index, x,
                                    blocks, {})[0])
    # beside a stage's backward: the gradient of its output
    act = inputs[-1]
    held = nbytes(params) + nbytes(opt_state) + nbytes(inputs) \
        + nbytes(act) + nbytes(blocks)
    dx = None
    for st, x in zip(reversed(step.stages), reversed(inputs)):
        kind = (st.fn.__name__, st.i is None)
        if ("backward",) + kind not in peaks:
            fits(("forward",) + kind, step.forward_fn(st), (), params, index,
                 x, blocks, {}, resident=held)
            fits(("backward",) + kind, step.backward_fn(st), (2, 3), params,
                 index, x, dx, blocks, {}, resident=held)
        dx = x
    leaves, tree = jax.tree.flatten(params)
    moments = {k: tree.flatten_up_to(v) for k, v in opt_state.items()}
    scalar = on(chip, jax.ShapeDtypeStruct((), jnp.float32))
    for j, p in enumerate(leaves):
        s = {k: moments[k][j] for k in moments}
        g = on(chip, jax.ShapeDtypeStruct(p.shape, jnp.float32))
        fits(("update", p.shape), step.update_fn(), (0, 1, 2), p, g, s,
             scalar, scalar,
             resident=nbytes(params) + nbytes(opt_state) + nbytes(g))
    fits("changes", reference.change_program(), (), params, params,
         resident=2 * nbytes(params))

    over = {k: v / GiB for k, v in peaks.items() if v > FITS}
    assert not over, over
    # the stand-in is as large as it claims: 6.9 GB of bfloat16
    # parameters, which every stage's program has beside it
    assert nbytes(params) > 6.8e9
    assert min(v for k, v in peaks.items() if k[0] == "backward") \
        > nbytes(params)


def test_the_whole_tree_step_it_replaced_does_not_fit(chip):
    import jax
    import jax.numpy as jnp
    from test_staged import parent_make_step
    _, params, opt_state, blocks, _ = shapes(chip)
    toks = on(chip, jax.ShapeDtypeStruct(
        (standin.BATCH, standin.SEQ_LEN), jnp.int32))
    k = on(chip, jax.ShapeDtypeStruct((), jnp.float32))
    step = parent_make_step(standin.CONFIG, "highest", standin.ROWS,
                            reference.BENCH_DIR, None, None)
    try:
        prog = step.lower(params, opt_state, {}, k, toks).compile()
    except Exception as e:  # noqa: BLE001 — the compiler's own refusal
        assert "memory" in str(e).lower() or "RESOURCE_EXHAUSTED" in str(e)
        return
    assert prog.memory_analysis().peak_memory_in_bytes > CHIP
