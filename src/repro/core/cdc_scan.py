"""Batched gear-scan engine — the accelerated candidate scan under CDC.

Content-defined chunking is only "free" at save time when the rolling-hash
scan runs near memory bandwidth. The PR-2 scan is a vectorized numpy
pipeline (gather → cumsum → windowed diff → mask → nonzero); every stage
materializes a full-payload temporary, so on a bandwidth-starved host it
tops out well below the hash/write pipeline it feeds (~50 MB/s on the
reference box — the ROADMAP's CDC-throughput item). This module keeps that
numpy implementation as the *correctness oracle* and adds two accelerated
backends that compute byte-identical candidates:

  numpy    the PR-2 scan, unchanged — the oracle every other backend is
           property-tested against (cut points are the dedup keyspace:
           a backend that drifts by one byte re-writes history);

  jnp      an XLA pipeline built for exactness AND cache locality: the
           payload is cut into ~4 MiB segments (64-byte halo carries the
           rolling-window context across the cut, so segmentation is
           exact); each segment is laid out as columns of ``BLOCK`` bytes
           and scanned with ONE ``lax.scan`` whose per-step state is a
           single row of window sums — w[i] = w[i-1] + gear[enter] -
           gear[leave] — all fused by XLA into a sliding pass whose
           working set lives in cache. The device emits a per-position
           candidate byte (0 / loose / strict) plus a per-64-block hit
           bitmap, and the host only inspects blocks the bitmap flags
           (candidates are geometrically rare, so extraction is ~free).
           Measured on the 2-core reference box: 5-7× the numpy oracle at
           shard-sized payloads — and the same dispatch is async, so a
           SaveSession overlaps the scan of payload k+1 with the chunk
           hash/write of payload k;

  pallas   the same scan as an explicit accelerator kernel written to
           the TPU compiler's rules: lane-dense rows of 128 bytes, each
           seen beside the row ahead of it, int32 window sums built by
           doubling lane shifts, and the gear table looked up by two
           in-register 128-lane gathers. Requested explicitly on a host
           without an accelerator it falls back to ``jnp`` (a one-time
           warning); correctness is pinned by interpret-mode parity tests
           here and by a byte-parity phase on the chip (``chip_smoke.py``).

Backend choice is a knob (``GearChunker(scan_backend=...)``), with
``auto`` picking pallas on accelerator hosts, jnp for payloads large
enough to amortize a dispatch, and numpy below that.
"""
from __future__ import annotations

import hashlib
import threading

import numpy as np

from . import codec as codec_mod
from .errors import warn

WINDOW = 64          # rolling-hash window (bytes); boundaries depend on
                     # exactly this much trailing context
BLOCK = 1024         # jnp scan column height (positions per lax.scan step
                     # stride); chosen on the reference box sweep
SEGMENT_BYTES = 4 << 20      # per-dispatch span: large enough to amortize
                             # dispatch, small enough to stay cache-warm
MIN_ACCEL_BYTES = 2 << 20    # auto: below this the numpy oracle wins
                             # (dispatch + padding overhead)
_MIN_COLS = 16               # smallest tail bucket: 16 columns = 16 KiB
BACKENDS = ("auto", "numpy", "jnp", "pallas")


def _bucket_cols(cols: int) -> int:
    """Half-octave staging bucket for ``cols`` scan columns: sizes step
    …16, 24, 32, 48, 64… so the padded dispatch wastes ≤33% instead of
    the ≤100% a pure power-of-two ladder costs — the chunk-scan
    small-payload gap (sub-2 MiB payloads paying full padding overhead).
    Still two shapes per octave, so the per-shape jit cache and the
    staging arena stay bounded."""
    b = _MIN_COLS
    while b < cols:
        half = b + (b >> 1)
        if cols <= half:
            return half
        b *= 2
    return b


def _gear_table() -> np.ndarray:
    # uint32, not uint64: the scan is memory-bandwidth bound and no mask
    # ever needs more than 32 bits (avg_size is capped at 2^28)
    out = np.empty(256, np.uint32)
    for b in range(256):
        h = hashlib.blake2b(bytes([b]), digest_size=4,
                            person=b"repro-cdc-v1").digest()
        out[b] = int.from_bytes(h, "little")
    return out


GEAR = _gear_table()

_EMPTY = np.empty(0, np.int64)


def as_u8(payload) -> np.ndarray:
    """Zero-copy uint8 view of any buffer the save path feeds the chunker
    (bytes, memoryview, contiguous ndarray)."""
    if isinstance(payload, np.ndarray):
        return payload.reshape(-1).view(np.uint8)
    return np.frombuffer(payload, np.uint8)


# ---------------------------------------------------------------------------
# numpy backend — the correctness oracle
# ---------------------------------------------------------------------------

def scan_candidates_numpy(data: np.ndarray, mask_strict: int,
                          mask_loose: int):
    """All candidate cut *end offsets* (strict set, loose set) — the PR-2
    scan, byte for byte. Every accelerated backend is tested against this.
    """
    n = len(data)
    if n <= WINDOW:
        return _EMPTY, _EMPTY
    v = GEAR[data]
    c = np.cumsum(v, dtype=np.uint32)          # wraps mod 2^32 — intended
    # window sum ending at byte i (inclusive), for i in [WINDOW-1, n-1]
    s = c[WINDOW - 1:].copy()
    s[1:] -= c[:n - WINDOW]
    loose = np.nonzero((s & np.uint32(mask_loose)) == 0)[0] + WINDOW
    strict = loose[(s[loose - WINDOW] & np.uint32(mask_strict)) == 0]
    return strict.astype(np.int64), loose.astype(np.int64)


# ---------------------------------------------------------------------------
# jnp backend — segmented sliding-window lax.scan
# ---------------------------------------------------------------------------

def _scan_columns_expr(padded, mask_strict, mask_loose):
    """Traceable column gear scan — the shared body of the jitted segment
    scan AND the fused transform+scan dispatch.

    ``padded``: uint8 [WINDOW + nb*BLOCK] — WINDOW halo bytes (previous
    segment's tail, zeros/garbage for the payload head: every halo byte
    entering w0 is subtracted back out of the sliding-window algebra
    before the first valid position), then the span, padded up to a
    column bucket (tail positions are discarded by extraction)."""
    import jax
    import jax.numpy as jnp

    nb = (padded.shape[0] - WINDOW) // BLOCK
    gear = jnp.asarray(GEAR)
    # column layout: column b holds payload positions [b*BLOCK,
    # (b+1)*BLOCK); the scan step advances every column's sliding
    # window by one byte, so the whole per-step state is one row
    main = padded[WINDOW:].reshape(nb, BLOCK).T     # entering bytes
    lead = padded[:-WINDOW].reshape(nb, BLOCK).T    # leaving bytes
    halo = padded[:-WINDOW].reshape(nb, BLOCK)[:, :WINDOW].T
    w0 = jnp.sum(gear[halo], axis=0, dtype=jnp.uint32)

    ms = jnp.uint32(mask_strict)
    ml = jnp.uint32(mask_loose)

    def body(w, rows):
        enter, leave = rows
        w = w + gear[enter] - gear[leave]
        # loose mask bits ⊂ strict mask bits, so one AND serves both
        h = w & ms
        m = ((h & ml) == 0).astype(jnp.uint8) \
            + (h == 0).astype(jnp.uint8)
        return w, m

    _, out = jax.lax.scan(body, w0, (main, lead))   # [BLOCK, nb]
    # per-64-block hit bitmap: the host only reads blocks that hit
    flags = out.reshape(BLOCK // WINDOW, WINDOW, nb).max(axis=1)
    return out, flags


def _jnp_scan_fn():
    """Build (once) the jitted segment scan. Static args: the two masks —
    jax caches one executable per (padded length, mask pair). The input
    is donated where donation is real (accelerators free the device copy
    as soon as the scan consumes it); on CPU donation would only warn."""
    import jax

    donate = (0,) if accelerator_present() else ()
    return jax.jit(_scan_columns_expr, static_argnums=(1, 2),
                   donate_argnums=donate)


class _StagingArena:
    """Persistent staging-buffer pool for accelerated dispatches.

    ``jnp.asarray`` on CPU may zero-copy ALIAS an aligned numpy buffer
    instead of copying it (measured both behaviours on this box), so a
    staging buffer must NEVER be reused while its dispatch is in flight —
    that is the documented no-reuse rule. The arena honours it by
    recycling a buffer only after its dispatch has been extracted (the
    device outputs are materialized, so the executable that could read
    the alias has provably finished); the device-side copy is donated to
    the jit on accelerator hosts instead. Recycling is what closes the
    chunk-scan small-payload gap: a 2 MiB dispatch stops paying the
    fresh-allocation page-zeroing that dominated its fixed overhead."""

    MAX_PER_SIZE = 16   # idle buffers kept per size (≥ a ticket's
                        # in-flight window, MAX_INFLIGHT_SEGMENTS)

    def __init__(self):
        self._lock = threading.Lock()
        self._free: dict = {}           # nbytes → [np.ndarray]

    def acquire(self, n: int) -> np.ndarray:
        with self._lock:
            bufs = self._free.get(n)
            if bufs:
                return bufs.pop()
        return np.empty(n, np.uint8)

    def release(self, buf):
        if buf is None:
            return
        with self._lock:
            bufs = self._free.setdefault(buf.nbytes, [])
            if len(bufs) < self.MAX_PER_SIZE:
                bufs.append(buf)


_ARENA = _StagingArena()


def _staging(n: int) -> np.ndarray:
    """Staging buffer for one dispatch: recycled from the arena when a
    previously-extracted dispatch's buffer fits, fresh otherwise. Never
    handed out while in flight (see ``_StagingArena``)."""
    return _ARENA.acquire(n)


class _JnpBackend:
    """Per-process jnp scan state (lazily built; thread-safe — jax.jit
    executables are shareable across threads)."""

    _lock = threading.Lock()
    _fn = None

    @classmethod
    def fn(cls):
        with cls._lock:
            if cls._fn is None:
                cls._fn = _jnp_scan_fn()
            return cls._fn

    @staticmethod
    def dispatch(data: np.ndarray, start: int, seg_len: int,
                 mask_strict: int, mask_loose: int):
        """Launch one segment scan (async — jax returns before the device
        finishes). Returns (device result pair, staging buffer) — the
        caller releases the buffer to the arena once it extracts.

        Staging never zeroes: garbage in the halo head and the bucket
        tail is EXACT to leave there. Halo garbage cancels out of the
        sliding-window algebra after WINDOW steps (every halo byte
        entering w0 is subtracted as a leaving byte before the first
        valid position), and tail positions beyond ``seg_len`` are
        discarded by extraction — so the scan pays one warm memcpy and
        zero page-zeroing."""
        import jax.numpy as jnp
        cols = -(-seg_len // BLOCK)
        # half-octave tail buckets keep recompilation bounded (full
        # segments all share one shape) without doubling small dispatches
        bucket = _bucket_cols(cols)
        padded = _staging(WINDOW + bucket * BLOCK)
        halo = min(start, WINDOW)
        if halo:
            padded[WINDOW - halo:WINDOW] = data[start - halo:start]
        padded[WINDOW:WINDOW + seg_len] = data[start:start + seg_len]
        return _JnpBackend.fn()(jnp.asarray(padded), int(mask_strict),
                                int(mask_loose)), padded

    @staticmethod
    def extract(result, start: int, seg_len: int, total_len: int):
        """Device result → global candidate positions of one segment.
        Only flagged 64-blocks are inspected; positions below the first
        full window (global < WINDOW-1) and in the zero-pad tail are
        discarded — they match the oracle's validity range."""
        out, flags = result
        flags_np = np.asarray(flags)                   # [BLOCK/W, cols]
        bs, qs = np.nonzero(flags_np.T)                # sorted by position
        if not len(bs):
            return _EMPTY, _EMPTY
        out_np = np.asarray(out)                       # [BLOCK, cols]
        blocks = out_np.reshape(BLOCK // WINDOW, WINDOW, -1)
        m = blocks[qs, :, bs]                          # [hits, WINDOW]
        base = (bs.astype(np.int64) * BLOCK + qs * WINDOW)[:, None]
        pos = base + np.arange(WINDOW, dtype=np.int64)
        sel = m > 0
        p, mv = pos[sel], m[sel]                       # row-major: sorted
        gp = p + start
        ok = (p < seg_len) & (gp >= WINDOW - 1) & (gp < total_len)
        gp, mv = gp[ok], mv[ok]
        return (gp[mv == 2] + 1), (gp + 1)


# ---------------------------------------------------------------------------
# pallas backend — explicit TPU kernel, jnp fallback
# ---------------------------------------------------------------------------

LANES = 128                  # TPU vreg width: rows of the kernel layout
ROWS = 512                   # rows per grid program (a multiple of 32,
                             # the uint8 sublane tile)
PALLAS_BLOCK = ROWS * LANES  # bytes per grid program
_HALO_ROWS = 32              # smallest uint8 block that holds the row
                             # ahead of a program's first row


def _shift_lanes(x, k: int):
    """x[:, l - k] at lane l, zero for l < k (a static lane shift)."""
    import jax.numpy as jnp
    return jnp.concatenate(
        [jnp.zeros((x.shape[0], k), x.dtype), x[:, :-k]], axis=1)


def _pallas_scan_expr(padded, mask_strict, mask_loose, *,
                      interpret: bool = False):
    """Traceable blocked gear scan as a Pallas kernel. The padded span
    is laid out lane-dense as rows of ``LANES`` bytes, and each grid
    program scans ``ROWS`` rows. A program sees each row beside the row
    ahead of it: the window ending at any byte of the row starts at most
    63 bytes back, inside those two rows. The row ahead of a program's
    first row comes from a second, ``_HALO_ROWS``-row block of the same
    input (program 0 reads its own rows there; those positions fall
    below the first full window and extraction discards them).

    TPU rules the body keeps: int32 arithmetic only (uint32 sums wrap
    identically), the 256-entry gear table looked up as two 128-lane
    in-register gathers plus a select, and windows summed by six
    doubling lane shifts rather than a cumsum. Emits the same
    0/loose/strict mask byte per position as the jnp backend. Shared by
    the jitted segment scan and the fused transform+scan dispatch."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(gear_ref, halo_ref, main_ref, out_ref, *, mask_strict,
               mask_loose):
        x = main_ref[...].astype(jnp.int32)                 # [ROWS, 128]
        prev = jnp.concatenate(
            [halo_ref[_HALO_ROWS - 1:, :].astype(jnp.int32), x[:-1]],
            axis=0)                                         # row r - 1
        lo_tab = jnp.broadcast_to(gear_ref[0:1, :], x.shape)
        hi_tab = jnp.broadcast_to(gear_ref[1:2, :], x.shape)

        def gear(b):
            i = b & (LANES - 1)
            return jnp.where(b >= LANES,
                             jnp.take_along_axis(hi_tab, i, axis=1),
                             jnp.take_along_axis(lo_tab, i, axis=1))

        s = jnp.concatenate([gear(prev), gear(x)], axis=1)  # [ROWS, 256]
        k = 1
        while k < WINDOW:                   # s[l] = sum of lanes l-2k+1..l
            s = s + _shift_lanes(s, k)
            k *= 2
        h = s[:, LANES:] & mask_strict
        out_ref[...] = (((h & mask_loose) == 0).astype(jnp.int32)
                        + (h == 0).astype(jnp.int32)).astype(jnp.uint8)

    n = padded.shape[0]
    rows = padded.reshape(n // LANES, LANES)
    per = ROWS // _HALO_ROWS
    return pl.pallas_call(
        functools.partial(kernel, mask_strict=mask_strict,
                          mask_loose=mask_loose),
        grid=(n // PALLAS_BLOCK,),
        in_specs=[
            pl.BlockSpec((2, LANES), lambda i: (0, 0)),     # gear table
            pl.BlockSpec((_HALO_ROWS, LANES),
                         lambda i: (jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.uint8),
        interpret=interpret,
    )(jnp.asarray(GEAR.view(np.int32).reshape(2, LANES)), rows,
      rows).reshape(n)


def _pallas_scan_fn(interpret: bool = False):
    import jax

    def scan(padded, mask_strict, mask_loose):
        return _pallas_scan_expr(padded, mask_strict, mask_loose,
                                 interpret=interpret)

    donate = (0,) if accelerator_present() else ()
    return jax.jit(scan, static_argnums=(1, 2), donate_argnums=donate)


class _PallasBackend:
    """Pallas dispatch; one mask-byte array per segment, extracted on the
    host with a plain nonzero (accelerator hosts are not the ones starved
    for host cycles)."""

    def __init__(self, interpret: bool = False):
        self._fn = _pallas_scan_fn(interpret=interpret)

    def dispatch(self, data: np.ndarray, start: int, seg_len: int,
                 mask_strict: int, mask_loose: int):
        import jax.numpy as jnp
        # WINDOW halo bytes ahead of the segment carry the rolling-window
        # context across the segment cut (program 0 of the grid reads its
        # own block as halo; those positions land in the discarded region
        # below)
        padded_len = -(-(seg_len + WINDOW) // PALLAS_BLOCK) * PALLAS_BLOCK
        # warm staging, never zeroed: halo/tail garbage is filtered by
        # extraction (and the first-window positions it could influence
        # are below WINDOW-1)
        padded = _staging(padded_len)
        halo = min(start, WINDOW)
        if halo:
            padded[WINDOW - halo:WINDOW] = data[start - halo:start]
        padded[WINDOW:WINDOW + seg_len] = data[start:start + seg_len]
        return self._fn(jnp.asarray(padded), int(mask_strict),
                        int(mask_loose)), padded

    @staticmethod
    def extract(result, start: int, seg_len: int, total_len: int):
        mask = np.asarray(result)
        p = np.flatnonzero(mask) - WINDOW        # → segment-local positions
        p = p[(p >= 0) & (p < seg_len)]
        gp = p + start
        ok = (gp >= WINDOW - 1) & (gp < total_len)
        gp = gp[ok]
        mv = mask[p + WINDOW][ok]
        return (gp[mv == 2] + 1), (gp + 1)


def accelerator_present() -> bool:
    import jax
    return jax.default_backend() in ("gpu", "tpu", "cuda", "rocm")


# ---------------------------------------------------------------------------
# fused transform+scan — the byteplane codec's single device round-trip
# ---------------------------------------------------------------------------

_fused_lock = threading.Lock()
_fused_fns: dict = {}     # (backend, interpret, entropy) → jitted executable


def _build_fused_fn(backend: str, interpret: bool = False,
                    entropy: str | None = None):
    """Build the fused byteplane-forward + gear-scan executable: ONE
    device round-trip per payload returns the transformed bytes AND the
    candidate mask computed over them, so the byteplane codec costs no
    extra dispatch beyond the CDC scan the save queue already pays for.

    With ``entropy`` set (a chunk-encoded codec name) a THIRD stage runs
    in the same dispatch: the plane RLE/rANS block encoder over the
    transformed stream. The executable then returns the candidate mask
    plus the pre-compressed framed stream and its per-block lengths — the
    transformed bytes themselves never cross D2H, so the transfer and all
    downstream host hashing shrink to the encoded size.

    Whole-payload dispatch, unlike the segmented plain scan: the
    byteplane transform is a global permutation of the stream, so
    per-segment halos would not compose across it. jax caches one
    executable per payload length — a training job's shard shapes form a
    small fixed set, so recompilation is bounded in practice. Device
    memory grows with the payload. Compiled for TPU v5e, the outputs
    alone are twice the payload; ``memory_analysis()`` counts 64 B of
    temporaries per payload byte for the jnp transform+scan at 8 MiB,
    none for the Pallas transform+scan at 8 and 64 MiB, 8 B (8 MiB) to
    24 B (64 MiB) for the Pallas RLE stage and 140 B for the rANS stage
    at 8 MiB. A multi-GB leaf does not fit beside a training state, and
    nothing here routes such a payload elsewhere: its allocation error
    reaches the caller."""
    import jax
    import jax.numpy as jnp

    from ..kernels.ckpt_codec import byteplane as bp
    from ..kernels.ckpt_codec import entropy as ent

    if backend == "pallas":
        def impl(raw, itemsize, mask_strict, mask_loose):
            t = bp.forward_pallas_expr(raw, itemsize, interpret=interpret)
            n = raw.shape[0]
            padded_len = -(-(n + WINDOW) // PALLAS_BLOCK) * PALLAS_BLOCK
            padded = jnp.concatenate(
                [jnp.zeros(WINDOW, jnp.uint8), t,
                 jnp.zeros(padded_len - WINDOW - n, jnp.uint8)])
            scan = _pallas_scan_expr(padded, mask_strict, mask_loose,
                                     interpret=interpret)
            if entropy is None:
                return t, scan
            return (scan,) + ent.encode_pallas_expr(
                t, entropy, interpret=interpret)
    else:
        def impl(raw, itemsize, mask_strict, mask_loose):
            t = bp.forward_expr(raw, itemsize)
            n = raw.shape[0]
            bucket = _bucket_cols(-(-n // BLOCK))
            padded = jnp.concatenate(
                [jnp.zeros(WINDOW, jnp.uint8), t,
                 jnp.zeros(bucket * BLOCK - n, jnp.uint8)])
            scan = _scan_columns_expr(padded, mask_strict, mask_loose)
            if entropy is None:
                return (t,) + scan
            return scan + ent.encode_expr(t, entropy)

    donate = (0,) if accelerator_present() else ()
    return jax.jit(impl, static_argnums=(1, 2, 3), donate_argnums=donate)


def _fused_fn(backend: str, interpret: bool = False,
              entropy: str | None = None):
    key = (backend, interpret, entropy)
    with _fused_lock:
        fn = _fused_fns.get(key)
        if fn is None:
            fn = _fused_fns[key] = _build_fused_fn(backend, interpret,
                                                   entropy)
        return fn


class FusedScanTicket:
    """Handle for one fused byteplane-transform + candidate-scan
    dispatch. ``result()`` joins the device round-trip and returns
    ``((strict, loose), transformed)``: candidate end offsets computed
    OVER the transformed stream (byte-identical to the numpy oracle
    scanning the oracle transform — the transformed bytes are the dedup
    keyspace) plus the transformed payload as a host uint8 array."""

    __slots__ = ("_resolve", "_done")

    def __init__(self, resolve=None, done=None):
        self._resolve = resolve
        self._done = done

    def result(self):
        if self._done is None:
            self._done = self._resolve()
            self._resolve = None
        return self._done


class FusedEncodeTicket:
    """Handle for one fused transform + scan + plane-entropy dispatch.
    ``result()`` joins the device round-trip and returns
    ``((strict, loose), stream, block_lens)``: candidate end offsets over
    the transformed stream, the framed RLE/rANS block stream (host uint8,
    byte-identical to the oracle encoding of the oracle transform) and
    per-block encoded lengths (headers included) whose prefix sums let
    the save path slice any plane-block-aligned chunk's encoding out of
    the stream without re-encoding."""

    __slots__ = ("_resolve", "_done")

    def __init__(self, resolve=None, done=None):
        self._resolve = resolve
        self._done = done

    def result(self):
        if self._done is None:
            self._done = self._resolve()
            self._resolve = None
        return self._done


class TransformTicket:
    """Handle for one standalone async device byteplane transform (no
    candidate scan). ``result()`` returns the transformed stream as a
    host uint8 array, byte-identical to the oracle."""

    __slots__ = ("_dev", "_done")

    def __init__(self, dev=None, done=None):
        self._dev = dev
        self._done = done

    def result(self) -> np.ndarray:
        if self._done is None:
            self._done = np.asarray(self._dev)
            self._dev = None
        return self._done


def transform_async(payload, itemsize: int) -> TransformTicket:
    """Async byteplane forward transform WITHOUT a candidate scan — the
    save path uses this when the codec wants pre-conditioned bytes but
    the chunk grid is not content-defined over them (fixed chunking, or
    a replica feed). Below the acceleration threshold the host oracle
    runs inline — same bytes either way."""
    data = as_u8(payload)
    if len(data) < MIN_ACCEL_BYTES:
        return TransformTicket(
            done=codec_mod.byteplane_forward(data, itemsize))
    import jax.numpy as jnp

    from ..kernels.ckpt_codec import byteplane as bp
    if accelerator_present():
        dev = bp.forward_pallas(jnp.asarray(data), itemsize=int(itemsize))
    else:
        dev = bp.forward_jnp(jnp.asarray(data), itemsize=int(itemsize))
    return TransformTicket(dev=dev)


# ---------------------------------------------------------------------------
# the scanner
# ---------------------------------------------------------------------------

MAX_INFLIGHT_SEGMENTS = 16   # a ticket's segments dispatched and not yet
                             # extracted: 16 device buffers of 4.06 MiB
                             # (the scan's output aliases its donated
                             # input) and their staging, per ticket


def _ready(res) -> bool:
    """Whether a dispatch's device outputs are computed: extracting them
    then waits for no device work, only the copy."""
    return all(a.is_ready() for a in (res if isinstance(res, tuple)
                                      else (res,)))


class ScanTicket:
    """Handle for one (possibly in-flight) payload scan. ``result()``
    joins the device work and returns the (strict, loose) candidate end
    offsets — byte-identical to the numpy oracle.

    Dispatch is WINDOWED: the first ``MAX_INFLIGHT_SEGMENTS`` segments
    are launched by ``scan_async`` (so device work overlaps whatever the
    caller does next); the rest launch from ``result()`` as earlier
    segments extract. The window is wide because a device shares one
    queue of programs with training: a segment launched behind a queued
    train step runs only after it, so a ticket gets about one window
    through per two steps. ``blocked`` counts the extractions whose
    device result was not yet computed when ``result()`` reached it: the
    device round trips the caller waited for (None on a ticket resolved
    on the host)."""

    __slots__ = ("_pending", "_todo", "_dispatch", "_extract", "_done",
                 "blocked")

    def __init__(self, pending, todo, dispatch, extract, done=None):
        self._pending = pending         # deque of ((result, buf), start, len, n)
        self._todo = todo               # [(start, seg_len, total)] not yet launched
        self._dispatch = dispatch
        self._extract = extract
        self._done = done               # eager backends resolve immediately
        self.blocked = None if done is not None else 0

    def result(self, on_segment=None):
        """``on_segment()``, if given, is called after each segment is
        extracted (the save path's writer heartbeat)."""
        if self._done is None:
            strict, loose = [], []
            while self._pending:
                (res, buf), start, seg_len, total = self._pending.popleft()
                if not _ready(res):
                    self.blocked += 1
                s, l = self._extract(res, start, seg_len, total)
                # extraction materialized the device outputs, so the
                # dispatch that could alias this staging buffer is done —
                # the one point where recycling is provably safe
                _ARENA.release(buf)
                strict.append(s)
                loose.append(l)
                if on_segment is not None:
                    on_segment()
                if self._todo:
                    nstart, nlen, ntotal = self._todo.pop(0)
                    self._pending.append(
                        (self._dispatch(nstart, nlen), nstart, nlen, ntotal))
            self._done = (
                np.concatenate(strict) if strict else _EMPTY,
                np.concatenate(loose) if loose else _EMPTY)
            self._pending = self._todo = self._dispatch = None
        return self._done


_pallas_warned = False


class GearScanner:
    """Candidate scan for one (mask_strict, mask_loose) pair with a
    selectable backend. ``scan`` is synchronous; ``scan_async`` dispatches
    device work and returns a ticket, which is how the save path overlaps
    the scan of the next payload with the chunk hash/write of the current
    one."""

    def __init__(self, mask_strict: int, mask_loose: int, *,
                 backend: str = "auto", pallas_interpret: bool = False):
        if backend not in BACKENDS:
            raise ValueError(f"scan_backend must be one of {BACKENDS}, "
                             f"got {backend!r}")
        self.mask_strict = int(mask_strict)
        self.mask_loose = int(mask_loose)
        if self.mask_loose & ~self.mask_strict:
            # the single-AND trick in the accelerated backends (and the
            # strict-⊆-loose candidate algebra) both require nested masks
            raise ValueError("mask_loose must be a bit-subset of "
                             "mask_strict")
        self.backend = backend
        self._pallas_interpret = pallas_interpret
        self._pallas = None

    # -- backend resolution -------------------------------------------
    def resolve(self, n: int) -> str:
        """The backend a payload of ``n`` bytes actually runs on."""
        b = self.backend
        if b == "auto":
            if n < MIN_ACCEL_BYTES:
                return "numpy"     # dispatch overhead dominates below this
            return "pallas" if accelerator_present() else "jnp"
        if b == "pallas" and not (accelerator_present()
                                  or self._pallas_interpret):
            global _pallas_warned
            if not _pallas_warned:
                _pallas_warned = True
                warn("CDC_W_SCAN", "pallas scan backend requested but no "
                     "accelerator is present; falling back to the jnp "
                     "backend", backend="jnp")
            return "jnp"
        return b

    def _pallas_backend(self) -> _PallasBackend:
        if self._pallas is None:
            self._pallas = _PallasBackend(interpret=self._pallas_interpret)
        return self._pallas

    # -- scanning ------------------------------------------------------
    def scan(self, payload):
        return self.scan_async(payload).result()

    def scan_async(self, payload) -> ScanTicket:
        from collections import deque
        data = as_u8(payload)
        n = len(data)
        if n <= WINDOW:
            return ScanTicket(None, None, None, None,
                              done=(_EMPTY, _EMPTY))
        backend = self.resolve(n)
        if backend == "numpy":
            return ScanTicket(None, None, None, None,
                              done=scan_candidates_numpy(
                                  data, self.mask_strict, self.mask_loose))
        if backend == "pallas":
            eng = self._pallas_backend()
            raw_dispatch, extract = eng.dispatch, eng.extract
        else:
            raw_dispatch, extract = _JnpBackend.dispatch, _JnpBackend.extract

        def dispatch(start, seg_len):
            return raw_dispatch(data, start, seg_len, self.mask_strict,
                                self.mask_loose)

        spans = []
        pos = 0
        while pos < n:
            seg_len = min(SEGMENT_BYTES, n - pos)
            spans.append((pos, seg_len, n))
            pos += seg_len
        pending = deque(
            (dispatch(start, seg_len), start, seg_len, total)
            for start, seg_len, total in spans[:MAX_INFLIGHT_SEGMENTS])
        return ScanTicket(pending, spans[MAX_INFLIGHT_SEGMENTS:], dispatch,
                          extract)

    def scan_transform_async(self, payload, itemsize: int) \
            -> FusedScanTicket:
        """Dispatch the byteplane forward transform AND the candidate
        scan of the *transformed* stream as ONE device round-trip — the
        codec's pre-conditioning rides the scan dispatch the save queue
        already pays for. Below the acceleration threshold the host
        oracle runs both stages inline: same bytes, same candidates."""
        data = as_u8(payload)
        n = len(data)
        backend = self.resolve(n)
        if backend == "numpy" or n <= WINDOW:
            t = codec_mod.byteplane_forward(data, itemsize)
            done = (scan_candidates_numpy(t, self.mask_strict,
                                          self.mask_loose)
                    if n > WINDOW else (_EMPTY, _EMPTY))
            return FusedScanTicket(done=(done, t))
        import jax.numpy as jnp
        fn = _fused_fn(backend, self._pallas_interpret)
        raw = fn(jnp.asarray(data), int(itemsize), self.mask_strict,
                 self.mask_loose)
        if backend == "pallas":
            extract, res = _PallasBackend.extract, raw[1]
        else:
            extract, res = _JnpBackend.extract, raw[1:]

        def resolve():
            t = np.asarray(raw[0])
            return extract(res, 0, n, n), t

        return FusedScanTicket(resolve=resolve)

    def scan_transform_encode_async(self, payload, itemsize: int,
                                    entropy_codec: str) \
            -> FusedEncodeTicket:
        """Three fused stages in ONE device round-trip: byteplane forward
        transform, candidate scan of the transformed stream, and the
        plane RLE/rANS block encoder — chunks reach the host already
        compressed, so D2H and host hashing pay the encoded size. Below
        the acceleration threshold (or on the numpy backend) the host
        oracle runs all three stages inline: same bytes, same candidates,
        same encoded stream."""
        data = as_u8(payload)
        n = len(data)
        backend = self.resolve(n)
        if backend == "numpy" or n <= WINDOW:
            t = codec_mod.byteplane_forward(data, itemsize)
            cands = (scan_candidates_numpy(t, self.mask_strict,
                                           self.mask_loose)
                     if n > WINDOW else (_EMPTY, _EMPTY))
            stream, block_lens = codec_mod.plane_stream_encode(
                t, entropy_codec)
            return FusedEncodeTicket(done=(cands, stream, block_lens))
        import jax.numpy as jnp
        fn = _fused_fn(backend, self._pallas_interpret, entropy_codec)
        raw = fn(jnp.asarray(data), int(itemsize), self.mask_strict,
                 self.mask_loose)
        if backend == "pallas":
            extract, res = _PallasBackend.extract, raw[0]
            dlens, stream_dev, total = raw[2], raw[3], raw[4]
        else:
            extract, res = _JnpBackend.extract, raw[0:2]
            dlens, stream_dev, total = raw[3], raw[4], raw[5]

        def resolve():
            cands = extract(res, 0, n, n)
            tot = int(np.asarray(total))
            stream = np.asarray(stream_dev)[:tot]
            block_lens = 3 + np.asarray(dlens, np.int64)
            return cands, stream, block_lens

        return FusedEncodeTicket(resolve=resolve)
