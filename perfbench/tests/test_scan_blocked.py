"""The ``persist_scan_blocked`` reader: the window's round's
``scan_blocked`` counter on a real save whose payloads the gear-scan
kernel scans (through its interpreter, at CPU size), and ``None`` on a
round that counted no device scan and on a program that keeps no
counters."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402,F401 — puts the harness and the program on the path

import cells  # noqa: E402


def read(run):
    return cells.metric_reader(cells.BENCH_DIR, "persist_scan_blocked")(run)


def save(tmp_path, step, device_scan):
    """One blocking save of step ``step``, as ``run.py`` records it."""
    import jax
    import numpy as np

    from repro.core import cdc_scan
    from repro.core.checkpoint import CheckpointManager
    from repro.core.policy import CheckpointPolicy
    from repro.core.storage import Tier, TieredStore
    mgr = CheckpointManager(
        TieredStore(Tier("local", tmp_path / "bb")),
        policy=CheckpointPolicy().with_overrides(
            mode="incremental", chunking="cdc", chunk_size=16 << 10,
            codec="zstd", n_writers=2, keepalive_s=60.0))
    if device_scan:
        ck = mgr._chunker
        ck.scanner = cdc_scan.GearScanner(
            ck.scanner.mask_strict, ck.scanner.mask_loose,
            backend="pallas", pallas_interpret=True)
    rng = np.random.default_rng(step)
    state = {"w": jax.numpy.asarray(rng.normal(size=(96, 256)),
                                    jax.numpy.float32)}
    mgr.save(state, step)
    mgr.close()
    return SimpleNamespace(save={"persist": {"step": step}}, restore={},
                           trace=None)


def test_reads_the_rounds_counter(tmp_path):
    from repro.core import trace
    run = save(tmp_path, 21, device_scan=True)
    root = [r for r in trace.finished("ckpt.persist")
            if r.trace_id == 21][-1]
    assert read(run) == root.counters["scan_blocked"] >= 0


def test_none_on_a_round_without_the_counter(tmp_path):
    assert read(save(tmp_path, 22, device_scan=False)) is None
    assert read(SimpleNamespace(save={"persist": {"step": -1}},
                                restore={}, trace=None)) is None


def test_none_where_the_program_keeps_no_counters(tmp_path, monkeypatch):
    run = save(tmp_path, 23, device_scan=True)
    import repro.core
    monkeypatch.delattr(repro.core, "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert read(run) is None
