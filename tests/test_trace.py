"""The save and restore paths' stage spans and counters
(``repro.core.trace``): one id per round, spans and counters that agree
with the save report, rounds in flight kept apart, both chunk engines,
bounded memory, error roots on aborts and failed or dropped restores, the
profiler's host plane, the launcher's stage line, and the benchmark
readers' union arithmetic."""
import gc
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_ckpt_policy
from repro.core import cdc_scan, trace
from repro.core.atomic import CrashInjector, CrashPoint
from repro.core.cas import ChunkStore
from repro.core.checkpoint import CheckpointManager
from repro.core.errors import AbortedError, NoCheckpointError
from repro.core.storage import Tier, TieredStore

PERFBENCH = Path(__file__).resolve().parents[1] / "perfbench"
KEY = jax.random.PRNGKey(3)
PERSIST_STAGES = ("ckpt.encode", "ckpt.scan_wait", "ckpt.store",
                  "ckpt.fsync", "ckpt.commit")


def _state(rows=256, salt=0):
    k = jax.random.fold_in(KEY, salt)
    return {"params": {"w": jax.random.normal(k, (rows, 256)),
                       "b": jax.random.normal(jax.random.fold_in(k, 1),
                                              (rows, 64))},
            "step": jnp.asarray(salt, jnp.int32)}


def _abstract(state):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        state)


def _manager(tmp_path, **flat):
    flat.setdefault("mode", "incremental")
    flat.setdefault("chunking", "cdc")
    flat.setdefault("chunk_size", 16 << 10)
    flat.setdefault("codec", "zstd")
    return CheckpointManager(TieredStore(Tier("local", tmp_path / "bb")),
                             policy=make_ckpt_policy(**flat))


def _roots(name, step, since_ns):
    return [r for r in trace.finished(name)
            if r.trace_id == step and r.start_ns >= since_ns]


def _one(name, step, since_ns):
    found = _roots(name, step, since_ns)
    assert len(found) == 1, (name, step, found)
    return found[0]


def _restore_root(since_ns):
    """The one ``ckpt.restore`` root opened since ``since_ns`` (a
    restore's trace id is per call, not its step)."""
    found = [r for r in trace.finished("ckpt.restore")
             if r.start_ns >= since_ns]
    assert len(found) == 1, found
    return found[0]


def _records(manifest):
    return [rec for leaf in manifest["leaves"].values()
            for rec in leaf["shards"]]


def _names(root):
    return {s.name for s in root.spans}


def test_blocking_cdc_save_records_save_and_persist_under_one_id(tmp_path):
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path)
    state = _state()
    rep = mgr.save(state, 7)
    mgr.close()
    save = _one("ckpt.save", 7, t0)
    persist = _one("ckpt.persist", 7, t0)
    assert save.error is None and persist.error is None
    assert {"ckpt.quiesce", "ckpt.registry", "ckpt.snapshot",
            "ckpt.preflight"} <= _names(save)
    assert {"ckpt.encode", "ckpt.store", "ckpt.fsync", "ckpt.commit",
            "ckpt.hooks", "ckpt.gc", "ckpt.drain"} <= _names(persist)
    # the snapshot stage comes first, on the calling thread
    assert save.end_ns <= persist.start_ns
    assert save.counters["snapshot_bytes"] == rep["bytes"]
    # one quiescence span per save, and every stage hangs from its root
    assert sum(s.name == "ckpt.quiesce" for s in save.spans) == 1
    assert {s.parent for s in save.spans} == {"ckpt.save"}
    assert {s.parent for s in persist.spans} == {"ckpt.persist"}


def test_persist_spans_lie_within_the_root_and_counters_match_report(
        tmp_path):
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path)
    rep = mgr.save(_state(), 3)
    persist = _one("ckpt.persist", 3, t0)
    for s in persist.spans:
        assert persist.start_ns <= s.start_ns <= s.end_ns <= persist.end_ns
    extent = (persist.end_ns - persist.start_ns) / 1e9
    for name in PERSIST_STAGES:
        assert 0 <= persist.union_s(name) <= extent
    # one ckpt.store per chunk the report counts; one encode per record
    assert sum(s.name == "ckpt.store" for s in persist.spans) \
        == rep["chunks"]
    assert sum(s.name == "ckpt.encode" for s in persist.spans) \
        == len(_records(mgr.load_manifest(3)))
    assert _one("ckpt.save", 3, t0).counters == {
        "snapshot_bytes": rep["bytes"]}
    # no Pallas scan on the CPU: nothing counted as sent to it
    assert "scan_bytes" not in persist.counters
    mgr.close()


def test_two_async_rounds_at_queue_depth_2_keep_their_spans_apart(
        tmp_path):
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path, persist_queue_depth=2)
    mgr.save(_state(salt=1), 1, blocking=False)
    mgr.save(_state(salt=2), 2, blocking=False)
    mgr.wait()
    chunks = {s: sum(len(rec["chunks"])
                     for rec in _records(mgr.load_manifest(s)))
              for s in (1, 2)}
    mgr.close()
    for step in (1, 2):
        root = _one("ckpt.persist", step, t0)
        assert root.error is None
        stores = [s for s in root.spans if s.name == "ckpt.store"]
        assert len(stores) == chunks[step]
        assert all(root.start_ns <= s.start_ns and s.end_ns <= root.end_ns
                   for s in root.spans)
        assert _one("ckpt.save", step, t0).error is None


def test_restore_records_read_decode_and_place(tmp_path):
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path)
    state = _state()
    mgr.save(state, 5)
    manifest = mgr.load_manifest(5)
    restored, _ = mgr.restore(_abstract(state))
    mgr.close()
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    root = _restore_root(t0)
    assert root.error is None
    assert {"restore.plan", "restore.wait", "restore.read",
            "restore.decode", "restore.place"} <= _names(root)
    n = {name: sum(s.name == name for s in root.spans)
         for name in ("restore.plan", "restore.place", "restore.read")}
    assert n == {"restore.plan": 1,
                 "restore.place": len(jax.tree.leaves(state)),
                 "restore.read": len(_records(manifest))}
    # placement runs on the calling thread, reads on the pool
    main = {s.thread for s in root.spans if s.name == "restore.place"}
    assert len(main) == 1


def test_streaming_restore_closes_its_root_at_the_gate(tmp_path):
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path)
    state = _state()
    mgr.save(state, 4)
    stream, _ = mgr.restore_streaming(_abstract(state))
    assert not [r for r in trace.finished("ckpt.restore")
                if r.start_ns >= t0]
    stream.state()
    mgr.close()
    root = _restore_root(t0)
    assert root.error is None
    assert sum(s.name == "restore.place" for s in root.spans) \
        == len(jax.tree.leaves(state))
    assert "restore.wait" in _names(root)


def test_the_serial_engine_records_its_stores_and_scans(tmp_path):
    """At ``io_threads=1`` the writer stores chunk by chunk through
    ``ChunkStore.put_payload``: the same spans, on the writer thread."""
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path, io_threads=1)
    rep = mgr.save(_state(), 6)
    mgr.close()
    persist = _one("ckpt.persist", 6, t0)
    stores = [s for s in persist.spans if s.name == "ckpt.store"]
    assert len(stores) == rep["chunks"] > 0
    scans = [s for s in persist.spans if s.name == "ckpt.scan_wait"]
    assert len(scans) == sum(
        leaf.nbytes > 0 for leaf in jax.tree.leaves(_state()))
    assert {s.thread for s in stores} == {s.thread for s in scans}
    assert persist.union_s("ckpt.store") > 0


def test_a_failed_streaming_leaf_closes_the_root_with_error(tmp_path):
    mgr = _manager(tmp_path)
    state = _state()
    mgr.save(state, 4)
    mgr.close()
    for obj in (tmp_path / "bb").rglob("*.obj"):
        obj.unlink()
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path)
    stream, _ = mgr.restore_streaming(_abstract(state))
    with pytest.raises(Exception):
        stream.wait_frontier()
    mgr.close()
    root = _restore_root(t0)
    assert root.error is not None
    assert root.trace_id not in trace._open


def test_a_dropped_restore_stream_closes_its_root(tmp_path):
    mgr = _manager(tmp_path)
    state = _state()
    mgr.save(state, 4)
    t0 = time.monotonic_ns()
    stream, _ = mgr.restore_streaming(_abstract(state))
    stream.wait_frontier()
    tid = stream._trace_id
    assert tid in trace._open
    del stream
    gc.collect()
    mgr.close()
    assert tid not in trace._open
    assert _restore_root(t0).error.startswith("ReferenceError")


def test_the_launchers_last_ckpt_line_reads_the_rounds_stages(tmp_path):
    from repro.launch.train import PERSIST_STAGES, persist_stages
    mgr = _manager(tmp_path)
    mgr.save(_state(), 13)
    mgr.close()
    line = persist_stages(13)
    persist = [r for r in trace.finished("ckpt.persist")
               if r.trace_id == 13][-1]
    for name in PERSIST_STAGES:
        assert f" {name.split('.', 1)[1]}={persist.union_s(name):.3f}s" \
            in line
    assert "hooks=" in line
    # host-scanned payloads only: no device scan waits to report
    assert "scan_blocked" not in line
    assert persist_stages(14) == ""


@pytest.mark.parametrize("point,error", [
    ("before_manifest", CrashPoint), ("rank0_before_write", AbortedError)])
def test_an_injected_abort_closes_the_root_with_error(tmp_path, point,
                                                      error):
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path, n_writers=1, max_retries=0)
    with pytest.raises(error):
        mgr.save(_state(), 9, crash=CrashInjector(point))
    mgr.close()
    root = _one("ckpt.persist", 9, t0)
    assert root.error.startswith(error.__name__)
    assert root.end_ns is not None
    assert 9 not in trace._open


def test_a_failed_restore_closes_the_root_with_error(tmp_path):
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path)
    with pytest.raises(NoCheckpointError):
        mgr.restore(_abstract(_state()))
    mgr.close()
    root = _restore_root(t0)
    assert root.error.startswith("NoCheckpointError")
    # the latest step is looked up inside the root's plan stage
    assert [s.name for s in root.spans] == ["restore.plan"]


def test_at_most_two_roots_are_kept_per_name(tmp_path):
    mgr = _manager(tmp_path, retain=2)
    for step in range(1, 7):
        mgr.save(_state(rows=64, salt=step), step, blocking=False)
    mgr.close()
    for name in ("ckpt.save", "ckpt.persist"):
        kept = trace.finished(name)
        assert len(kept) <= trace.KEEP == 2
        assert [r.trace_id for r in kept] == [5, 6]
    assert not set(range(1, 7)) & set(trace._open)


def test_spans_without_an_open_root_are_not_kept(tmp_path):
    names = ("ckpt.save", "ckpt.persist", "ckpt.restore")
    before = {n: trace.finished(n) for n in names}
    chunks = ChunkStore(TieredStore(Tier("local", tmp_path / "bb")),
                        chunk_size=4096)
    chunks.put_payload(np.arange(20000, dtype=np.uint8))
    chunks.close()
    with trace.span("ckpt.store", "no-such-round"):
        pass
    trace.count("no-such-round", objects_written=1)
    assert {n: trace.finished(n) for n in names} == before
    assert not {None, "no-such-round"} & set(trace._open)


def test_scan_counters_equal_the_payload_bytes_sent_to_the_pallas_kernel(
        tmp_path):
    """``scan_bytes`` and ``scan_segments`` count what the save sends to
    the Pallas gear scan: every CDC record whose payload the scanner
    resolves to the kernel (here the kernel's interpreter)."""
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path, n_writers=2)
    ck = mgr._chunker
    ck.scanner = cdc_scan.GearScanner(ck.scanner.mask_strict,
                                      ck.scanner.mask_loose,
                                      backend="pallas",
                                      pallas_interpret=True)
    state = {"w": jax.random.normal(KEY, (96, 256)),
             "v": jax.random.normal(jax.random.fold_in(KEY, 1), (64, 128))}
    mgr.save(state, 2)
    records = [rec for leaf in mgr.load_manifest(2)["leaves"].values()
               for rec in leaf["shards"]]
    mgr.close()
    sizes = [int(r["payload_bytes"]) for r in records
             if r["chunking"] == "cdc"
             and ck.scanner.resolve(int(r["payload_bytes"])) == "pallas"]
    c = _one("ckpt.persist", 2, t0).counters
    assert sizes and c["scan_bytes"] == sum(sizes)
    assert "ckpt.scan_wait" in _names(_one("ckpt.persist", 2, t0))


def test_scan_blocked_counts_the_rounds_waits_on_the_device(tmp_path):
    """``scan_blocked`` sits on the round's ``ckpt.persist`` root, counts
    at most one wait per segment the device scanned, and the launcher's
    "last ckpt" line prints it."""
    from repro.launch.train import persist_stages
    t0 = time.monotonic_ns()
    mgr = _manager(tmp_path, n_writers=2)
    ck = mgr._chunker
    ck.scanner = cdc_scan.GearScanner(ck.scanner.mask_strict,
                                      ck.scanner.mask_loose,
                                      backend="pallas",
                                      pallas_interpret=True)
    state = {"w": jax.random.normal(KEY, (96, 256)),
             "v": jax.random.normal(jax.random.fold_in(KEY, 1), (64, 128))}
    mgr.save(state, 4)
    records = [rec for leaf in mgr.load_manifest(4)["leaves"].values()
               for rec in leaf["shards"]]
    mgr.close()
    segments = sum(-(-int(r["payload_bytes"]) // cdc_scan.SEGMENT_BYTES)
                   for r in records if r["chunking"] == "cdc"
                   and int(r["payload_bytes"]) > cdc_scan.WINDOW)
    persist = _one("ckpt.persist", 4, t0)
    blocked = persist.counters["scan_blocked"]
    assert segments and 0 <= blocked <= segments
    assert "scan_blocked" not in _one("ckpt.save", 4, t0).counters
    assert persist_stages(4).endswith(f" scan_blocked={blocked}")


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    mgr = _manager(tmp_path)
    state = _state(rows=64)
    jax.block_until_ready(state)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        mgr.save(state, 1)
    finally:
        jax.profiler.stop_trace()
    mgr.close()
    found = sorted((tmp_path / "trace").rglob("*.xplane.pb"))
    assert found
    host = {e.name for plane in ProfileData.from_file(str(found[-1])).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert {"ckpt.save", "ckpt.snapshot", "ckpt.persist",
            "ckpt.store"} <= host


def _synthetic_root():
    root = trace.Root("ckpt.persist", 8, start_ns=0, end_ns=100)
    for name, s, e in [("ckpt.store", 10, 30), ("ckpt.store", 20, 40),
                       ("ckpt.store", 60, 70), ("ckpt.encode", 0, 100),
                       ("ckpt.store", 95, 120)]:
        root.spans.append(trace.Span(name, s, e, 1, "ckpt.persist"))
    return root


def test_union_arithmetic_of_the_program_and_the_readers_agree():
    sys.path.insert(0, str(PERFBENCH))
    try:
        import program_spans
    finally:
        sys.path.remove(str(PERFBENCH))
    root = _synthetic_root()
    # [10, 40] + [60, 70] + [95, 100] within the root; [25, 65] clips
    assert root.union_s("ckpt.store") == pytest.approx(45e-9)
    assert program_spans.union_s(root, "ckpt.store", 0, 100) \
        == pytest.approx(45e-9)
    assert root.union_s("ckpt.store", 25, 65) == pytest.approx(20e-9)
    assert program_spans.union_s(root, "ckpt.store", 25, 65) \
        == pytest.approx(20e-9)
    assert program_spans.union_s(root, "ckpt.fsync", 0, 100) == 0
