"""The trace reduction, checked against hand-made events and against a
small trace recorded on a TPU v5e (``data/tpu_trace_sample.json.gz``: a
train step and the save's snapshot gap of the ``mamba2-780m-d24.incr-cdc``
window, cut from a ``--trace 1`` run's events)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import reduce_trace as rt  # noqa: E402

SAMPLE = Path(__file__).resolve().parent / "data" / "tpu_trace_sample.json.gz"


def hand_made():
    # window [100, 1100) ns on the host; ops on one device
    return {
        "devices": {"/device:TPU:0": {
            "ops": [[0, 150, "a"],           # half inside the window
                    [200, 100, "b"], [250, 100, "c"],   # overlap: 200-350
                    [600, 200, "b"],
                    [1000, 300, "d"]],       # cut at the window's end
            "modules": [[190, 170, "jit_train_step(3)"],
                        [590, 220, "jit_train_step(3)"],
                        [1000, 300, "jit_scan(9)"]],
        }},
        "host": [[100, 1000, "perfbench.window"],
                 [350, 250, "perfbench.save_call"],
                 [300, 600, "perfbench.wait_step"]],
    }


def test_union_merges_and_clips():
    assert rt.union([[0, 10], [5, 10], [20, 5]], 2, 22) == [[2, 15],
                                                            [20, 22]]


def test_hand_made_window():
    s = rt.Summary(hand_made())
    assert (s.lo, s.hi) == (100, 1100)
    # busy: 100-150, 200-350, 600-800, 1000-1100 = 50+150+200+100 ns
    assert s.busy_s == pytest.approx(500e-9)
    assert s.idle_frac == pytest.approx(0.5)
    assert s.program("jit_train_step") == (2, pytest.approx(390e-9))
    # the scan program ends after the window: not counted
    assert s.program("jit_scan") == (0, 0.0)
    ops = dict(s.top_ops())
    assert ops["b"] == pytest.approx(300e-9) and "a" not in ops
    gaps = s.idle_gaps()
    # 350-600 (250 ns, save_call is the innermost span at 475), 800-1000
    # (200 ns, wait_step), 150-200 (50 ns, no span)
    assert [g[0] for g in gaps] == ["perfbench.save_call",
                                    "perfbench.wait_step",
                                    "outside any span"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 200e-9, 50e-9])


def _timeline_busy(events, lo, hi, step=1000):
    """Busy seconds by a sampled timeline (1 us cells): a second way to
    the union, for the recorded trace."""
    ops = next(iter(events["devices"].values()))["ops"]
    n = int((hi - lo) // step) + 1
    busy = np.zeros(n, bool)
    for s, d, _ in ops:
        a = int(max(s - lo, 0) // step)
        b = int(min(s + d - lo, hi - lo) // step)
        if b > a:
            busy[a:b] = True
    return busy.sum() * step / 1e9


def test_recorded_tpu_trace():
    events = rt.load_events(SAMPLE)
    s = rt.Summary(events)
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.busy_s == pytest.approx(_timeline_busy(events, s.lo, s.hi),
                                     rel=0.02)
    steps, seconds = s.program("jit_train_step")
    assert steps >= 1 and 0 < seconds <= s.busy_s
    assert len(s.top_ops()) == 10 and len(s.idle_gaps()) <= 10


def test_readers_arithmetic_on_hand_made_events():
    import cells
    bench = Path(rt.__file__).resolve().parent

    class Run:
        trace = rt.Summary(hand_made())
        peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        flops_per_step = 1e4
        scan_payload_bytes = 300
        save = {"bytes": 8e9, "snapshot_s": 2.0, "t_snapshot_end": 10.0,
                "t_commit": 50.0}
        restore = {"state_bytes": 8e9, "resident_s": 4.0}

    read = {m: cells.metric_reader(bench, m)(Run) for m in (
        "step_mfu", "device_idle_frac", "gear_scan_roofline",
        "snapshot_gbps", "persist_gbps", "restore_gbps")}
    # 2 steps x 1e4 FLOP in 390 ns against 197 TFLOP/s
    assert read["step_mfu"] == pytest.approx(100 * 2e4 / (390e-9 * 197e12))
    assert read["device_idle_frac"] == pytest.approx(0.5)
    # the scan program runs past the window and still counts: 2 x 300 B
    # in 300 ns against 819 GB/s
    assert read["gear_scan_roofline"] == pytest.approx(
        100 * (600 / 819e9) / 300e-9)
    assert read["snapshot_gbps"] == pytest.approx(4.0)
    assert read["persist_gbps"] == pytest.approx(0.2)
    assert read["restore_gbps"] == pytest.approx(2.0)
    Run.trace = None
    assert cells.metric_reader(bench, "step_mfu")(Run) is None
