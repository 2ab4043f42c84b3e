"""Device-side byteplane pre-conditioning transform — checkpoint codec
front-end.

The paper's future work is "reducing the checkpoint overhead for
large-scale applications"; the save-path ceiling after the PR-4 scan
offload is the HOST touching every payload byte in the zstd stage. This
module runs the lossless byte-plane transpose + per-plane delta ON DEVICE
(the numpy oracle is ``repro.core.codec.byteplane_forward`` /
``byteplane_inverse``), so the bytes the host compresses arrive already
entropy-shaped — and the save path fuses this forward transform into the
same device round-trip as the CDC gear scan
(``core.cdc_scan.GearScanner.scan_transform_async``), keeping ONE dispatch
per payload.

Backends mirror ``core.cdc_scan``'s three-backend structure:

  numpy    the oracle (``core.codec``) — re-exported here for symmetry;
  jnp      ``forward_expr``/``inverse_expr``: traceable XLA expressions
           (the fused scan dispatch inlines ``forward_expr`` ahead of the
           gear-scan columns), plus jitted standalone entry points;
  pallas   explicit accelerator kernels. The forward kernel consumes the
           stream and its one-element-shifted copy (built by XLA) in
           lane-dense blocks and gathers each plane's delta bytes inside
           the block, so no (elements, itemsize) array is ever laid out.
           The inverse is one grid program per byte plane, and the TPU
           compiler refuses its (1, ne) block; nothing on the save or
           restore path calls it — the per-plane cumsum carry is
           inherently sequential, so each program owns a whole plane
           (VMEM-bounded: fine for shard-sized payloads; the restore path
           uses the host oracle anyway and this kernel exists for backend
           parity, pinned by interpret-mode tests).

All backends are property-tested byte-identical to the oracle
(``tests/test_byteplane.py``) — the transformed stream is the dedup
keyspace when a byteplane codec is active, so a backend that drifts by one
byte re-writes history.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.codec import byteplane_forward, byteplane_inverse  # noqa: F401
# ^ oracle re-export (the ref implementations, like .ref for the quantizer)

LANES = 128                 # forward-kernel layout: rows of one vreg width
BLOCK_ROWS = 512            # forward-kernel stream rows per grid program
                            # (a multiple of 32 × every itemsize ≤ 16)


# ---------------------------------------------------------------------------
# jnp expressions (traceable — shared with the fused scan dispatch)
# ---------------------------------------------------------------------------

def forward_expr(u8, itemsize: int):
    """Traceable forward transform of a flat uint8 stream. Matches the
    oracle bit-for-bit: plane-major delta bytes, ragged tail appended
    untransformed."""
    n = u8.shape[0]
    k = int(itemsize)
    ne = n // k
    if ne == 0:
        return u8
    x = u8[:ne * k].reshape(ne, k)
    prev = jnp.concatenate([jnp.zeros((1, k), jnp.uint8), x[:-1]])
    d = (x - prev).T.reshape(-1)
    return jnp.concatenate([d, u8[ne * k:]])


def inverse_expr(u8, itemsize: int):
    """Traceable inverse: per-plane cumsum mod 256, transposed back."""
    n = u8.shape[0]
    k = int(itemsize)
    ne = n // k
    if ne == 0:
        return u8
    d = u8[:ne * k].reshape(k, ne)
    x = jnp.cumsum(d, axis=1, dtype=jnp.uint8)     # wraps mod 256
    return jnp.concatenate([x.T.reshape(-1), u8[ne * k:]])


@partial(jax.jit, static_argnames=("itemsize",))
def forward_jnp(u8, *, itemsize: int):
    return forward_expr(u8, itemsize)


@partial(jax.jit, static_argnames=("itemsize",))
def inverse_jnp(u8, *, itemsize: int):
    return inverse_expr(u8, itemsize)


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, s_ref, o_ref, *, k: int):
    # x: stream rows; s: the same rows shifted one element (k bytes).
    # TPU rules: subtract in int32 (no uint8 vector subtract) keeping the
    # low byte — the oracle's mod-256 delta — and gather each plane's
    # bytes within 128-lane rows: byte p of the block's element e is
    # stream byte e*k + p, in row e*k // LANES at lane (e*k + p) % LANES
    rows = x_ref.shape[0] // k                    # output rows per plane
    d = (x_ref[...].astype(jnp.int32) - s_ref[...].astype(jnp.int32)) & 0xFF
    d = d.reshape(rows, k, LANES)                 # row j*k + q → [j, q]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    src_row = lane // (LANES // k)
    for p in range(k):
        idx = (lane * k + p) % LANES
        plane = jnp.take_along_axis(d[:, 0, :], idx, axis=1)
        for q in range(1, k):
            plane = jnp.where(src_row == q,
                              jnp.take_along_axis(d[:, q, :], idx, axis=1),
                              plane)
        o_ref[p] = plane.astype(jnp.uint8)


def _inv_kernel(d_ref, o_ref):
    o_ref[...] = jnp.cumsum(d_ref[...], axis=1, dtype=jnp.uint8)


def inverse_planes_2d(d, *, interpret: bool = False):
    """(k, ne) delta planes → (k, ne) byte planes (cumsum mod 256). One
    grid program per plane: the carry chain is sequential, so a plane is
    the natural program granule."""
    k, ne = d.shape
    return pl.pallas_call(
        _inv_kernel,
        grid=(k,),
        in_specs=[pl.BlockSpec((1, ne), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, ne), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, ne), jnp.uint8),
        interpret=interpret,
    )(d)


def forward_pallas_expr(u8, itemsize: int, *, interpret: bool = False):
    """Traceable pallas forward (the fused pallas scan dispatch inlines
    this, mirroring ``forward_expr`` on the jnp side). The stream and its
    one-element-shifted copy enter the kernel lane-dense, as rows of
    ``LANES`` bytes padded to whole ``BLOCK_ROWS``-row grid blocks; the
    kernel writes every plane's share of a block."""
    n = u8.shape[0]
    k = int(itemsize)
    ne = n // k
    if ne == 0:
        return u8
    if LANES % k:
        raise ValueError(f"itemsize {k} does not divide {LANES}")
    body = u8[:ne * k]
    shifted = jnp.concatenate([jnp.zeros(k, jnp.uint8), body[:-k]])
    rows = -(-ne * k // LANES)
    # a whole number of 32-row uint8 tiles per plane in every block
    block = min(BLOCK_ROWS, -(-rows // (32 * k)) * 32 * k)
    rows_p = -(-rows // block) * block

    def lay_out(a):
        return jnp.pad(a, (0, rows_p * LANES - ne * k)).reshape(rows_p,
                                                                LANES)

    spec = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        partial(_fwd_kernel, k=k),
        grid=(rows_p // block,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((k, block // k, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, rows_p // k, LANES), jnp.uint8),
        interpret=interpret,
    )(lay_out(body), lay_out(shifted))
    d = out.reshape(k, rows_p // k * LANES)[:, :ne].reshape(-1)
    return jnp.concatenate([d, u8[ne * k:]])


@partial(jax.jit, static_argnames=("itemsize", "interpret"))
def forward_pallas(u8, *, itemsize: int, interpret: bool = False):
    return forward_pallas_expr(u8, itemsize, interpret=interpret)


@partial(jax.jit, static_argnames=("itemsize", "interpret"))
def inverse_pallas(u8, *, itemsize: int, interpret: bool = False):
    n = u8.shape[0]
    k = int(itemsize)
    ne = n // k
    if ne == 0:
        return u8
    d = u8[:ne * k].reshape(k, ne)
    x = inverse_planes_2d(d, interpret=interpret)
    return jnp.concatenate([x.T.reshape(-1), u8[ne * k:]])
