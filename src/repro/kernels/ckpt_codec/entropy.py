"""Device-side plane entropy stage (byteplane-rle / byteplane-rans).

jnp/XLA and Pallas backends for the numpy oracle in ``core.codec``
(``entropy_encode_blocks`` + ``assemble_block_stream``). The encoded
framing is defined THERE — every backend must produce byte-identical
streams (property-fuzzed in tests/test_entropy.py).

Structure mirrors ``byteplane.py``: the Pallas backend runs a real kernel
for the per-block RLE emission pass (one grid program per 32 rows of 4 KiB
plane blocks — runs never span blocks, so there is no halo) and shares the
traceable jnp glue (pair compaction, histogram, lane-interleaved rANS
scan, serialization, block-choice and final stream compaction) with the
jnp backend. Both exprs are inlined by the fused scan+transform+encode
dispatch in ``core.cdc_scan`` so one device round-trip returns candidate
bitmaps plus the pre-compressed stream, and D2H shrinks to the encoded
size plus two small per-block arrays.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.codec import (            # oracle constants = format contract
    ENTROPY_BLOCK, RANS_LANES, RANS_PROB_BITS, RANS_L,
    _RANS_STEPS, _LANE_MAX,
)

B = ENTROPY_BLOCK
L = RANS_LANES
S = _RANS_STEPS
_RANS_W = 1 + 3 * 256 + 4 * L + 2 * L + L * _LANE_MAX


def _block_layout(n: int):
    """Static (trace-time) block geometry for an n-byte stream."""
    nb = -(-n // B)
    blens = np.full(nb, B, np.int32)
    if nb:
        blens[-1] = n - (nb - 1) * B
    return nb, blens


# ---------------------------------------------------------------------------
# RLE emission pass — jnp expr and Pallas kernel
# ---------------------------------------------------------------------------
# Emission semantics (== oracle ``_rle_emissions``): greedy runs cut at
# every block boundary and capped at 255; position i emits a (run_len,
# value) pair iff the run ends at i or the cap is hit. Output is the
# per-position emit mask and capped run length; compaction is shared glue.

def _emission_common(seg_start, idx, end, blen_last):
    pos = idx - seg_start
    end = end | (idx == blen_last)       # partial last block ends its run
    emit = end | (pos % 255 == 254)
    run = (pos % 255 + 1).astype(jnp.uint8)
    return emit, run


def _rle_emission_expr(blkmat, blens_np):
    """jnp emitter over the padded [nb, B] block matrix."""
    nb = blkmat.shape[0]
    idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32), (nb, B))
    one = jnp.ones((nb, 1), bool)
    change = jnp.concatenate([one, blkmat[:, 1:] != blkmat[:, :-1]], axis=1)
    end = jnp.concatenate([change[:, 1:], one], axis=1)
    seg_start = jax.lax.cummax(jnp.where(change, idx, 0), axis=1)
    last = jnp.asarray((blens_np - 1).astype(np.int32))[:, None]
    return _emission_common(seg_start, idx, end, last)


ROWS = 32                  # plane blocks per grid program (the uint8
                           # sublane tile)


def _rle_kernel(n, x_ref, emit_ref, run_ref):
    # TPU rules: int32 arithmetic, lane shifts by static slices of int32
    # (never of bool), and the run-start prefix max as twelve doubling
    # steps rather than a cummax
    x = x_ref[...].astype(jnp.int32)                    # [ROWS, B]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = pl.program_id(0) * ROWS + \
        jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    prev = jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)
    nxt = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
    change = (idx == 0) | (x != prev)
    end = (idx == B - 1) | (x != nxt)
    seg_start = jnp.where(change, idx, 0)
    k = 1
    while k < B:
        seg_start = jnp.maximum(seg_start, jnp.concatenate(
            [jnp.zeros((ROWS, k), jnp.int32), seg_start[:, :-k]], axis=1))
        k *= 2
    emit, run = _emission_common(seg_start, idx, end, n - 1 - row * B)
    emit_ref[...] = emit
    run_ref[...] = run


def _rle_emission_pallas(blkmat, n, *, interpret=False):
    """Pallas emitter: one grid program per ``ROWS`` plane blocks (the
    block matrix is padded with zero rows to a whole number of them)."""
    nb = blkmat.shape[0]
    nb_p = -(-nb // ROWS) * ROWS
    spec = pl.BlockSpec((ROWS, B), lambda b: (b, 0))
    emit, run = pl.pallas_call(
        partial(_rle_kernel, n),
        grid=(nb_p // ROWS,),
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((nb_p, B), jnp.bool_),
                   jax.ShapeDtypeStruct((nb_p, B), jnp.uint8)],
        interpret=interpret,
    )(jnp.pad(blkmat, ((0, nb_p - nb), (0, 0))))
    return emit[:nb], run[:nb]


# ---------------------------------------------------------------------------
# shared traceable glue
# ---------------------------------------------------------------------------

def _rans_stage(blkmat, valid, rowm):
    """Histogram → quantize → lane-interleaved rANS scan → serialize.
    Returns (rans_data [nb, _RANS_W] u8, rans_lens [nb] i32, eligible)."""
    nb = blkmat.shape[0]
    rows = jnp.arange(nb)
    blens = valid.sum(axis=1).astype(jnp.int32)
    sym_i = blkmat.astype(jnp.int32)
    counts = jnp.zeros((nb, 256), jnp.int32).at[rowm, sym_i].add(
        valid.astype(jnp.int32), mode="drop")
    # quantize (== oracle _rans_quantize)
    T = 1 << RANS_PROB_BITS
    nz = counts > 0
    f = jnp.where(nz, jnp.maximum(
        1, (counts * T) // jnp.maximum(blens[:, None], 1)), 0)
    imax = jnp.argmax(counts, axis=1)
    f = f.at[rows, imax].add(T - f.sum(axis=1))
    eligible = f[rows, imax] >= 1
    nsyms = nz.sum(axis=1).astype(jnp.int32)
    cum = jnp.cumsum(f, axis=1) - f
    # encode: scan steps S-1 … 0 (reverse), carry = 16 lane states
    sym_steps = sym_i.reshape(nb, S, L).transpose(1, 0, 2)     # [S, nb, L]
    val_steps = valid.reshape(nb, S, L).transpose(1, 0, 2)
    rowg = jnp.arange(nb)[:, None]

    def step(x, inp):
        s, v = inp
        fv = jnp.where(v, f[rowg, s], 1).astype(jnp.uint32)
        cv = jnp.where(v, cum[rowg, s], 0).astype(jnp.uint32)
        x_max = fv << np.uint32(8 + 23 - RANS_PROB_BITS)
        e0 = v & (x >= x_max)
        b0 = (x & np.uint32(0xFF)).astype(jnp.uint8)
        x = jnp.where(e0, x >> np.uint32(8), x)
        e1 = v & (x >= x_max)
        b1 = (x & np.uint32(0xFF)).astype(jnp.uint8)
        x = jnp.where(e1, x >> np.uint32(8), x)
        xe = ((x // fv) << np.uint32(RANS_PROB_BITS)) + (x % fv) + cv
        x = jnp.where(v, xe, x)
        return x, (b0, e0, b1, e1)

    x0 = jnp.full((nb, L), np.uint32(RANS_L), jnp.uint32)
    states, (b0, e0, b1, e1) = jax.lax.scan(
        step, x0, (sym_steps[::-1], val_steps[::-1]))
    # scan ran t = S-1 … 0; ys index t' = S-1-t. Decode order is steps
    # ascending, second byte before first → restore step order, stack
    # (b1, b0) last.
    db = jnp.stack([b1, b0], axis=-1)[::-1]            # [S, nb, L, 2]
    dv = jnp.stack([e1, e0], axis=-1)[::-1]
    db = db.transpose(1, 2, 0, 3).reshape(nb, L, 2 * S)
    dv = dv.transpose(1, 2, 0, 3).reshape(nb, L, 2 * S)
    lane_len = dv.sum(axis=-1).astype(jnp.int32)       # [nb, L]
    pos = jnp.cumsum(dv, axis=-1) - 1
    li = jnp.broadcast_to(rows[:, None, None], dv.shape)
    lj = jnp.broadcast_to(jnp.arange(L)[None, :, None], dv.shape)
    lane_buf = jnp.zeros((nb, L, _LANE_MAX), jnp.uint8).at[
        li, lj, jnp.where(dv, pos, _LANE_MAX)].set(db, mode="drop")
    # serialize (== oracle _rans_serialize)
    data = jnp.zeros((nb, _RANS_W), jnp.uint8)
    data = data.at[:, 0].set(((nsyms - 1) & 0xFF).astype(jnp.uint8))
    rank = jnp.cumsum(nz, axis=1) - 1
    rowh = jnp.broadcast_to(rows[:, None], (nb, 256))
    scol = jnp.arange(256)[None, :]
    data = data.at[rowh, jnp.where(nz, 1 + rank, _RANS_W)].set(
        jnp.broadcast_to(scol, nz.shape).astype(jnp.uint8), mode="drop")
    fo = (1 + nsyms)[:, None]
    data = data.at[rowh, jnp.where(nz, fo + 2 * rank, _RANS_W)].set(
        (f & 0xFF).astype(jnp.uint8), mode="drop")
    data = data.at[rowh, jnp.where(nz, fo + 2 * rank + 1, _RANS_W)].set(
        (f >> 8).astype(jnp.uint8), mode="drop")
    o_states = 1 + 3 * nsyms                           # [nb]
    for byte in range(4):
        cols = o_states[:, None] + 4 * jnp.arange(L) + byte
        data = data.at[rowg, cols].set(
            ((states >> np.uint32(8 * byte))
             & np.uint32(0xFF)).astype(jnp.uint8), mode="drop")
    o_lens = o_states + 4 * L
    cols = o_lens[:, None] + 2 * jnp.arange(L)
    data = data.at[rowg, cols].set(
        (lane_len & 0xFF).astype(jnp.uint8), mode="drop")
    data = data.at[rowg, cols + 1].set(
        (lane_len >> 8).astype(jnp.uint8), mode="drop")
    o_bytes = o_lens + 2 * L
    lane_off = jnp.cumsum(lane_len, axis=1) - lane_len
    kcol = jnp.arange(_LANE_MAX)[None, None, :]
    kvalid = kcol < lane_len[:, :, None]
    dst = o_bytes[:, None, None] + lane_off[:, :, None] + kcol
    data = data.at[li, jnp.where(kvalid, dst, _RANS_W)].set(
        lane_buf, mode="drop")
    rans_lens = o_bytes + lane_len.sum(axis=1)
    return data, rans_lens, eligible


def _encode_expr(t, codec: str, emitter):
    """Shared encode: ``t`` is the transformed u8 stream (device array).
    Returns (flags u8 [nb], dlens i32 [nb], stream u8 [n + 3·nb],
    total i32 scalar) — host slices stream[:total]."""
    n = t.shape[0]
    nb, blens_np = _block_layout(n)
    if nb == 0:
        return (jnp.zeros(0, jnp.uint8), jnp.zeros(0, jnp.int32),
                jnp.zeros(0, jnp.uint8), jnp.zeros((), jnp.int32))
    pad = nb * B - n
    blkmat = jnp.concatenate(
        [t, jnp.zeros(pad, jnp.uint8)]).reshape(nb, B)
    blens = jnp.asarray(blens_np)
    colm = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None, :], (nb, B))
    rowm = jnp.broadcast_to(jnp.arange(nb)[:, None], (nb, B))
    valid = colm < blens[:, None]
    emit, run = emitter(blkmat, blens_np)
    emit = emit & valid
    # pair compaction into [nb, B] (chosen rle rows always fit: len < B)
    npairs = emit.sum(axis=1).astype(jnp.int32)
    rle_lens = 2 * npairs
    rank = jnp.cumsum(emit, axis=1) - 1
    col0 = jnp.where(emit, 2 * rank, B + 1)
    rle_buf = jnp.zeros((nb, B + 2), jnp.uint8)
    rle_buf = rle_buf.at[rowm, col0].set(run, mode="drop")
    rle_buf = rle_buf.at[rowm, col0 + 1].set(blkmat, mode="drop")
    rle_buf = rle_buf[:, :B]
    flags = jnp.zeros(nb, jnp.uint8)
    dlens = blens.astype(jnp.int32)
    use_rle = rle_lens < dlens
    flags = jnp.where(use_rle, np.uint8(1), flags)
    dlens = jnp.where(use_rle, rle_lens, dlens)
    padded = jnp.where(use_rle[:, None], rle_buf, blkmat)
    if codec == "byteplane-rans":
        rans_data, rans_lens, eligible = _rans_stage(blkmat, valid, rowm)
        use_rans = eligible & (rans_lens < dlens)
        flags = jnp.where(use_rans, np.uint8(2), flags)
        dlens = jnp.where(use_rans, rans_lens, dlens)
        padded = jnp.where(use_rans[:, None], rans_data[:, :B], padded)
    padded = jnp.where(colm < dlens[:, None], padded, 0)
    # final framed-stream compaction (== oracle assemble_block_stream)
    block_lens = 3 + dlens
    offs = jnp.cumsum(block_lens) - block_lens
    total = jnp.sum(block_lens)
    out = jnp.zeros(n + 3 * nb, jnp.uint8)
    out = out.at[offs].set(flags, mode="drop")
    out = out.at[offs + 1].set((dlens & 0xFF).astype(jnp.uint8),
                               mode="drop")
    out = out.at[offs + 2].set((dlens >> 8).astype(jnp.uint8), mode="drop")
    dst = offs[:, None] + 3 + colm
    out = out.at[jnp.where(colm < dlens[:, None], dst, n + 3 * nb)].set(
        padded, mode="drop")
    return flags, dlens, out, total.astype(jnp.int32)


def encode_expr(t, codec: str):
    """jnp/XLA backend expr — inlined by the fused scan dispatch."""
    return _encode_expr(t, codec, _rle_emission_expr)


def encode_pallas_expr(t, codec: str, *, interpret: bool = False):
    """Pallas backend expr: RLE emission runs as a per-block kernel."""
    n = t.shape[0]
    return _encode_expr(
        t, codec,
        lambda blkmat, _bl: _rle_emission_pallas(
            blkmat, n, interpret=interpret))


@partial(jax.jit, static_argnames=("codec",))
def encode_stream_jnp(t, codec: str):
    return encode_expr(t, codec)


@partial(jax.jit, static_argnames=("codec", "interpret"))
def encode_stream_pallas(t, codec: str, interpret: bool = False):
    return encode_pallas_expr(t, codec, interpret=interpret)


def encode_stream(t_u8: np.ndarray, codec: str, backend: str = "jnp",
                  *, interpret: bool = False):
    """Host-callable wrapper: encode a transformed stream on device and
    return (stream np.uint8, block_lens np.int64) — the same contract as
    the oracle's ``plane_stream_encode``. Used by tests and bench."""
    dev = jnp.asarray(np.ascontiguousarray(t_u8).view(np.uint8))
    if backend == "pallas":
        flags, dlens, out, total = encode_stream_pallas(dev, codec, interpret)
    else:
        flags, dlens, out, total = encode_stream_jnp(dev, codec)
    total = int(np.asarray(total))
    stream = np.asarray(out)[:total]
    return stream, 3 + np.asarray(dlens, np.int64)
