"""The save path's Pallas kernels, and the train step's SSD block on a
four-chip mesh, compile for TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology. Interpret mode cannot see what it
refuses (unaligned blocks, uint8 vector arithmetic, 1-D in-kernel
gathers), so each kernel is compiled here at the block sizes the save
path feeds it, and its HLO must hold the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. The persistent compilation cache is off around these compiles
— an entry written for a described chip cannot be read back without one.
"""
import os

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config

from repro.core import cdc_scan
from repro.core.cdc import GearChunker
from repro.kernels.ckpt_codec import byteplane as bp
from repro.kernels.ckpt_codec import entropy as ent
from repro.models import ssm


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _u8(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_gear_scan_compiles_at_segment_size(one_chip):
    # the jitted segment scan exactly as GearScanner dispatches a full
    # 4 MiB segment (halo + span, padded to whole grid programs)
    ck = GearChunker(1 << 20)
    n = -(-(cdc_scan.SEGMENT_BYTES + cdc_scan.WINDOW)
          // cdc_scan.PALLAS_BLOCK) * cdc_scan.PALLAS_BLOCK
    fn = cdc_scan._pallas_scan_fn()
    _assert_kernel(fn.lower(_u8(n, one_chip), int(ck.mask_strict),
                            int(ck.mask_loose)).compile())


@pytest.mark.parametrize("itemsize", [2, 4])
def test_byteplane_forward_compiles(one_chip, itemsize):
    _assert_kernel(bp.forward_pallas.lower(
        _u8(16 << 20, one_chip), itemsize=itemsize).compile())


def test_entropy_emission_compiles(one_chip):
    # the RLE emission kernel both byteplane-rle and byteplane-rans run
    # over a 4 MiB transformed stream (1024 plane blocks)
    n = 4 << 20
    fn = jax.jit(lambda t: ent._rle_emission_pallas(t.reshape(-1, ent.B), n))
    _assert_kernel(fn.lower(_u8(n, one_chip)).compile())


def test_ssd_gradient_compiles_on_fsdp_mesh(v5e_2x2):
    # the mamba2 block's gradient as the default four-chip host mesh
    # (4, 1) lays it out: batch over "data", the projections FSDP-sharded
    # over it; the published SSM dims (chunk 256, two chunks of sequence)
    # at a narrower d_model
    cfg = replace(get_config("mamba2-780m"), d_model=512)
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    specs = {"in_proj": P("data", None), "out_proj": P(None, "data")}
    abstract = jax.eval_shape(lambda k: ssm.init_ssm(k, cfg),
                              jax.random.PRNGKey(0))
    shard = {k: NamedSharding(mesh, specs.get(k, P())) for k in abstract}
    params = {k: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shard[k])
              for k, a in abstract.items()}
    x = jax.ShapeDtypeStruct((8, 2 * cfg.ssm.chunk_size, cfg.d_model),
                             jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))

    def loss(p, x):
        return ssm.ssd_forward(p, x, cfg).astype(jnp.float32).sum()

    jax.jit(jax.grad(loss), out_shardings=shard).lower(params, x).compile()
