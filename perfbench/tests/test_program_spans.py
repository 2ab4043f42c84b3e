"""The per-layer readers of the program's own stage spans
(``program_spans.py``, ``metrics/persist_*``, ``restore_*``,
``snapshot_copy_gbps``), on a real save and restore at CPU size, and on a
program that keeps no spans (``None``, no error)."""
from __future__ import annotations

import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

import cells  # noqa: E402
import run as run_mod  # noqa: E402

READERS = ["snapshot_copy_gbps", "persist_encode_s", "persist_scan_wait_s",
           "persist_store_s", "persist_fsync_s", "persist_commit_s",
           "restore_read_s", "restore_decode_s", "restore_place_s"]
PERSIST = ["persist_encode_s", "persist_scan_wait_s", "persist_store_s",
           "persist_fsync_s", "persist_commit_s"]


def read(name, run):
    return cells.metric_reader(cells.BENCH_DIR, name)(run)


@pytest.fixture(scope="module")
def measured(tmp_path_factory):
    """A run record as ``run.py`` fills it, from one save and one restore
    through the program's ``CheckpointManager``."""
    import jax
    import numpy as np

    from repro.core.checkpoint import CheckpointManager
    from repro.core.policy import CheckpointPolicy
    from repro.core.storage import Tier, TieredStore
    store = TieredStore(Tier("local", tmp_path_factory.mktemp("ck") / "bb"))
    mgr = CheckpointManager(store, policy=CheckpointPolicy().with_overrides(
        mode="incremental", chunking="cdc", chunk_size=16 << 10,
        codec="zstd", keepalive_s=60.0))
    commits = {}
    mgr.on_commit.append(
        lambda step, manifest: commits.setdefault(step, time.monotonic()))
    rng = np.random.default_rng(0)
    state = {"w": jax.numpy.asarray(rng.normal(size=(256, 256)),
                                    jax.numpy.float32),
             "step": jax.numpy.asarray(12, jax.numpy.int32)}
    t_call = time.monotonic()
    rep = mgr.save(state, 12, blocking=False)
    mgr.wait()
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    t0 = time.monotonic()
    mgr.restore(abstract)
    resident = time.monotonic() - t0
    mgr.close()
    return SimpleNamespace(
        save={"bytes": rep["bytes"], "snapshot_s": rep["snapshot_s"],
              "t_call": t_call, "t_snapshot_end": t_call + rep["snapshot_s"],
              "t_commit": commits[12], "durable_s": commits[12] - t_call,
              "persist": dict(mgr.last_report)},
        restore={"resident_s": resident, "state_bytes": rep["bytes"]},
        trace=None)


def test_every_reader_reads_the_window_round_and_the_resume(measured):
    values = {m: read(m, measured) for m in READERS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["snapshot_copy_gbps"] > 0
    assert values["persist_store_s"] > 0 and values["restore_read_s"] > 0
    persist_s = measured.save["t_commit"] - measured.save["t_snapshot_end"]
    for m in PERSIST:
        assert values[m] <= persist_s
    assert values["restore_place_s"] <= measured.restore["resident_s"]


def test_readers_give_none_for_another_round_or_no_resume(measured):
    other = SimpleNamespace(save=dict(measured.save, persist={"step": -1}),
                            restore={}, trace=None)
    assert all(read(m, other) is None for m in READERS)


def test_readers_give_none_where_the_program_keeps_no_spans(measured,
                                                           monkeypatch):
    import repro.core
    monkeypatch.delattr(repro.core, "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert all(read(m, measured) is None for m in READERS)


def test_traced_run_reports_the_program_span_metrics(tmp_path, monkeypatch):
    # a CPU trace has no device plane: the run reads a recorded TPU trace
    import reduce_trace
    sample = Path(__file__).resolve().parent / "data" / \
        "tpu_trace_sample.json.gz"
    monkeypatch.setattr(reduce_trace, "load_xplane",
                        lambda path: reduce_trace.load_events(sample))
    root = tiny.make_root(tmp_path)
    res = run_mod.run_cell(cells.load_cell("tiny-mamba.incr", root),
                           seed=2**31 + 5, seconds=0.5, trace=True,
                           require_tpu=False, compile_cache=False, root=root)
    assert res["correct"] is True
    assert set(READERS) <= set(res["metrics"])
    assert res["metrics"]["persist_store_s"]["unit"] == "s"
