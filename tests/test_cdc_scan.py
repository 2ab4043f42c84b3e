"""Accelerated gear-scan backends: cut-point parity against the numpy
oracle (boundaries ARE the dedup keyspace — a one-byte drift re-writes
history), async scan tickets and the window on their in-flight
segments, the writer's heartbeat through a slow device scan, auto
backend resolution, and the zero-copy chunker contract."""
import time

import numpy as np
import pytest

from repro.core import cdc_scan
from repro.core.cdc import GearChunker
from repro.core.cdc_scan import (GearScanner, ScanTicket, WINDOW,
                                 scan_candidates_numpy)

SMALL_SEGMENT = 64 << 10      # segments of the window tests: cheap on CPU


@pytest.fixture()
def small_segments(monkeypatch):
    """Segments of ``SMALL_SEGMENT`` bytes, so a payload runs past a
    ticket's ``MAX_INFLIGHT_SEGMENTS`` window at a size the CPU scans in
    moments."""
    monkeypatch.setattr(cdc_scan, "SEGMENT_BYTES", SMALL_SEGMENT)
    return cdc_scan.MAX_INFLIGHT_SEGMENTS


def _masks(avg=1024):
    ck = GearChunker(avg)
    return int(ck.mask_strict), int(ck.mask_loose)


def _assert_scan_parity(scanner, ref_scanner, payload):
    s, l = scanner.scan(payload)
    rs, rl = ref_scanner.scan(payload)
    np.testing.assert_array_equal(s, rs)
    np.testing.assert_array_equal(l, rl)


# ---------------------------------------------------------------------------
# kernel-vs-numpy parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size", [
    0, 1, WINDOW - 1, WINDOW, WINDOW + 1,          # below/at the window
    256, 1024,                                     # == min_size territory
    65_536, 300_000,                               # multi-block
    cdc_scan.SEGMENT_BYTES + 12_345,               # crosses a segment cut
    # > MAX_INFLIGHT_SEGMENTS segments (of SMALL_SEGMENT bytes): exercises
    # the windowed deferred re-dispatch inside ScanTicket.result()
    SMALL_SEGMENT * (cdc_scan.MAX_INFLIGHT_SEGMENTS + 1) + 54_321,
])
def test_jnp_candidate_parity(size, rng, small_segments):
    ms, ml = _masks()
    jnp_s = GearScanner(ms, ml, backend="jnp")
    ref = GearScanner(ms, ml, backend="numpy")
    _assert_scan_parity(jnp_s, ref, rng.bytes(size))


def test_jnp_parity_fuzz(rng):
    """Property fuzz: random sizes × random mask pairs, byte-identical
    candidate sets. Sizes deliberately straddle block and bucket edges."""
    for avg in (512, 4096):
        ms, ml = _masks(avg)
        jnp_s = GearScanner(ms, ml, backend="jnp")
        ref = GearScanner(ms, ml, backend="numpy")
        for _ in range(10):
            size = int(rng.integers(0, 200_000))
            _assert_scan_parity(jnp_s, ref, rng.bytes(size))
    # block/bucket edge sizes (BLOCK columns × _MIN_COLS bucket)
    ms, ml = _masks()
    jnp_s = GearScanner(ms, ml, backend="jnp")
    ref = GearScanner(ms, ml, backend="numpy")
    B = cdc_scan.BLOCK
    for size in (B - 1, B, B + 1, 64 * B - 1, 64 * B, 64 * B + 1):
        _assert_scan_parity(jnp_s, ref, rng.bytes(size))


def test_low_entropy_payload_parity():
    """Constant bytes: either a boundary everywhere or nowhere — the
    force-cut-at-max regime must agree exactly."""
    ms, ml = _masks()
    jnp_s = GearScanner(ms, ml, backend="jnp")
    ref = GearScanner(ms, ml, backend="numpy")
    for fill in (b"\x00", b"\xa7"):
        _assert_scan_parity(jnp_s, ref, fill * 100_000)


@pytest.mark.parametrize("size", [1000, 70_000, 200_001])
def test_pallas_interpret_parity(size, rng):
    """The Pallas kernel, run through the interpreter (this box has no
    accelerator), produces byte-identical candidates."""
    ms, ml = _masks()
    pal = GearScanner(ms, ml, backend="pallas", pallas_interpret=True)
    ref = GearScanner(ms, ml, backend="numpy")
    _assert_scan_parity(pal, ref, rng.bytes(size))


def test_cut_point_parity_through_chunker(rng):
    """End-to-end: GearChunker cut points (min/avg/max discipline applied
    over the candidate sets) are identical across backends, including the
    <WINDOW, ==min_size and force-cut-at-max-tail shapes."""
    for payload in (b"", rng.bytes(WINDOW - 1), rng.bytes(256),
                    rng.bytes(100_000), b"\x00" * 50_000,
                    rng.bytes(1 << 20)):
        ref = GearChunker(1024).cut_points(payload)
        assert GearChunker(1024, scan_backend="jnp") \
            .cut_points(payload) == ref
        assert b"".join(GearChunker(1024, scan_backend="jnp")
                        .chunk(payload)) == payload


# ---------------------------------------------------------------------------
# scanner API
# ---------------------------------------------------------------------------

def test_scan_async_matches_sync(rng):
    ms, ml = _masks()
    sc = GearScanner(ms, ml, backend="jnp")
    payloads = [rng.bytes(n) for n in (50_000, 120_000, 80_000)]
    tickets = [sc.scan_async(p) for p in payloads]
    assert all(isinstance(t, ScanTicket) for t in tickets)
    for t, p in zip(tickets, payloads):
        s, l = t.result()
        rs, rl = sc.scan(p)          # ticket result is memoized + stable
        np.testing.assert_array_equal(s, rs)
        np.testing.assert_array_equal(l, rl)
        s2, l2 = t.result()
        assert s2 is s and l2 is l


def test_scan_result_reports_each_segment(rng, small_segments):
    """``result(on_segment=...)`` fires once per extracted segment — the
    save path's writer heartbeat through a multi-GB payload scan, here
    one larger than the in-flight window."""
    ms, ml = _masks()
    sc = GearScanner(ms, ml, backend="jnp")
    n_seg = cdc_scan.MAX_INFLIGHT_SEGMENTS + 2
    payload = rng.bytes(SMALL_SEGMENT * (n_seg - 1) + 1000)
    beats = []
    s, l = sc.scan_async(payload).result(on_segment=lambda: beats.append(1))
    assert len(beats) == n_seg
    rs, rl = GearScanner(ms, ml, backend="numpy").scan(payload)
    np.testing.assert_array_equal(s, rs)
    np.testing.assert_array_equal(l, rl)


# ---------------------------------------------------------------------------
# the in-flight window
# ---------------------------------------------------------------------------

@pytest.fixture()
def in_flight(monkeypatch):
    """Counts the jnp backend's segments dispatched and not yet
    extracted, over every ticket, and the most there ever were."""
    count = {"now": 0, "peak": 0}
    dispatch, extract = (cdc_scan._JnpBackend.dispatch,
                         cdc_scan._JnpBackend.extract)

    def counted_dispatch(*a, **kw):
        out = dispatch(*a, **kw)
        count["now"] += 1
        count["peak"] = max(count["peak"], count["now"])
        return out

    def counted_extract(*a, **kw):
        count["now"] -= 1
        return extract(*a, **kw)

    monkeypatch.setattr(cdc_scan._JnpBackend, "dispatch",
                        staticmethod(counted_dispatch))
    monkeypatch.setattr(cdc_scan._JnpBackend, "extract",
                        staticmethod(counted_extract))
    return count


@pytest.mark.parametrize("extra", [
    -(cdc_scan.MAX_INFLIGHT_SEGMENTS - 1),    # one segment
    0,                                        # exactly the window
    1,                                        # one past it
])
def test_scan_async_dispatches_up_to_the_window(extra, small_segments,
                                                in_flight, rng):
    """``scan_async`` launches a payload's segments up to the window
    before ``result()`` is called (all of them where they fit), so the
    device can run them in one gap of its queue; ``result()`` launches
    the rest as it extracts."""
    w = cdc_scan.MAX_INFLIGHT_SEGMENTS
    n_seg = w + extra
    payload = rng.bytes(SMALL_SEGMENT * (n_seg - 1) + SMALL_SEGMENT // 2)
    ms, ml = _masks()
    ticket = GearScanner(ms, ml, backend="jnp").scan_async(payload)
    assert in_flight["now"] == len(ticket._pending) == min(n_seg, w)
    assert len(ticket._todo) == n_seg - min(n_seg, w)
    ticket.result()
    assert in_flight["now"] == 0 and in_flight["peak"] == min(n_seg, w)
    assert 0 <= ticket.blocked <= n_seg


@pytest.mark.parametrize("order", ["created", "reversed"])
@pytest.mark.parametrize("n_tickets", [2, 3])
def test_concurrent_tickets_stay_in_their_windows(n_tickets, order,
                                                  small_segments, in_flight,
                                                  rng):
    """Live tickets each hold at most a window of segments in flight and
    all resolve, in either order, with the oracle's candidates."""
    w = cdc_scan.MAX_INFLIGHT_SEGMENTS
    ms, ml = _masks()
    sc = GearScanner(ms, ml, backend="jnp")
    ref = GearScanner(ms, ml, backend="numpy")
    payloads = [rng.bytes(SMALL_SEGMENT * (w + 1) + 1000 * (k + 1))
                for k in range(n_tickets)]
    tickets = [sc.scan_async(p) for p in payloads]
    assert all(len(t._pending) == w for t in tickets)
    pairs = list(zip(tickets, payloads))
    for t, p in (pairs if order == "created" else pairs[::-1]):
        s, l = t.result()
        rs, rl = ref.scan(p)
        np.testing.assert_array_equal(s, rs)
        np.testing.assert_array_equal(l, rl)
    assert in_flight["peak"] <= n_tickets * w
    assert in_flight["now"] == 0


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("past", [
    12_345,                                   # into one segment past it
    cdc_scan.MAX_INFLIGHT_SEGMENTS * SMALL_SEGMENT + 1,   # twice the window
])
def test_candidates_past_the_window_match_the_oracle(past, backend,
                                                     small_segments, rng):
    """A payload larger than the window, cut into segments, gives the
    oracle's candidates byte for byte (the Pallas kernel through its
    interpreter), as does a second scan while the first is in flight."""
    ms, ml = _masks()
    sc = GearScanner(ms, ml, backend=backend, pallas_interpret=True)
    payload = rng.bytes(
        cdc_scan.MAX_INFLIGHT_SEGMENTS * SMALL_SEGMENT + past)
    ticket = sc.scan_async(payload)
    assert ticket._todo            # the rest waits for extractions
    _assert_scan_parity(sc, GearScanner(ms, ml, backend="numpy"), payload)
    s, l = ticket.result()
    rs, rl = scan_candidates_numpy(np.frombuffer(payload, np.uint8), ms, ml)
    np.testing.assert_array_equal(s, rs)
    np.testing.assert_array_equal(l, rl)


@pytest.mark.parametrize("slow", ["dispatch", "extract"])
def test_a_device_scan_slower_than_the_keepalive_keeps_the_writer_alive(
        slow, tmp_path, monkeypatch):
    """A launch waits while the device's queue of programs is full, and an
    extraction while the segment is queued behind train steps, each for
    as long as those steps run: a busy writer, not a dead one. The writer
    beats while it waits, and the save commits without a retry."""
    import jax

    from repro.core.checkpoint import CheckpointManager
    from repro.core.policy import CheckpointPolicy
    from repro.core.storage import Tier, TieredStore
    real = getattr(cdc_scan._JnpBackend, slow)

    def slow_call(*a, **kw):
        time.sleep(1.0)
        return real(*a, **kw)

    monkeypatch.setattr(cdc_scan._JnpBackend, slow, staticmethod(slow_call))
    mgr = CheckpointManager(
        TieredStore(Tier("local", tmp_path / "bb")),
        policy=CheckpointPolicy().with_overrides(
            mode="incremental", chunking="cdc", chunk_size=16 << 10,
            codec="zstd", n_writers=2, keepalive_s=0.4, max_retries=0))
    ck = mgr._chunker
    ck.scanner = GearScanner(ck.scanner.mask_strict, ck.scanner.mask_loose,
                             backend="jnp")
    rng = np.random.default_rng(5)
    state = {"w": jax.numpy.asarray(rng.normal(size=(96, 256)),
                                    jax.numpy.float32)}
    rep = mgr.save(state, 1)
    mgr.close()
    assert mgr.coordinator.metrics["keepalive_timeouts"] == 0
    assert rep["step"] == 1 and mgr.latest_step() == 1


def test_auto_backend_size_gate(rng):
    ms, ml = _masks()
    sc = GearScanner(ms, ml, backend="auto")
    assert sc.resolve(1000) == "numpy"
    # large payloads pick an accelerated backend (jnp on a CPU-only host,
    # pallas when an accelerator is attached)
    assert sc.resolve(cdc_scan.MIN_ACCEL_BYTES) in ("jnp", "pallas")


def test_pallas_without_accelerator_falls_back(rng):
    import jax
    if jax.default_backend() != "cpu":
        pytest.skip("accelerator attached — fallback not exercised")
    ms, ml = _masks()
    sc = GearScanner(ms, ml, backend="pallas")
    assert sc.resolve(1 << 20) == "jnp"
    _assert_scan_parity(sc, GearScanner(ms, ml, backend="numpy"),
                        rng.bytes(50_000))


def test_invalid_backend_rejected():
    ms, ml = _masks()
    with pytest.raises(ValueError):
        GearScanner(ms, ml, backend="cuda")
    with pytest.raises(ValueError):
        GearChunker(1024, scan_backend="nope")
    with pytest.raises(ValueError):
        # loose mask must nest inside the strict mask
        GearScanner(0x0F, 0xF0)


def test_oracle_matches_legacy_semantics(rng):
    """The extracted oracle is literally the PR-2 scan: empty below the
    window, end offsets in (WINDOW, n]."""
    ms, ml = _masks()
    s, l = scan_candidates_numpy(np.frombuffer(rng.bytes(WINDOW), np.uint8),
                                 ms, ml)
    assert len(s) == 0 and len(l) == 0
    data = np.frombuffer(rng.bytes(100_000), np.uint8)
    s, l = scan_candidates_numpy(data, ms, ml)
    assert set(s) <= set(l)
    if len(l):
        assert l.min() >= WINDOW and l.max() <= len(data)


# ---------------------------------------------------------------------------
# zero-copy chunking
# ---------------------------------------------------------------------------

def test_chunk_returns_zero_copy_views(rng):
    payload = rng.bytes(100_000)
    chunks = GearChunker(1024).chunk(payload)
    assert all(isinstance(c, memoryview) for c in chunks)
    # views alias the payload, not copies of it
    assert all(c.obj is payload for c in chunks)
    assert b"".join(chunks) == payload


def test_chunk_accepts_ndarray_views(rng):
    arr = np.frombuffer(rng.bytes(64_000), np.uint8)
    chunks = GearChunker(1024).chunk(arr)
    assert b"".join(chunks) == arr.tobytes()
    # slices share the array's memory
    assert all(np.shares_memory(np.frombuffer(c, np.uint8), arr)
               for c in chunks)
