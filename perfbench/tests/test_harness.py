"""CPU tests of the benchmark harness (``JAX_PLATFORMS=cpu``, tiny
configurations). Run from the checkout's root:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

import cells  # noqa: E402
import pagecache  # noqa: E402
import run as run_mod  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    extra_mix = dict(tiny.TINY_MIX, ckpt_mode="full", chunking="fixed")
    return tiny.make_root(
        tmp_path_factory.mktemp("bench"),
        mixes={"incr": tiny.TINY_MIX, "added": extra_mix},
        extra_cells=[("tiny-mamba.added", "tiny-mamba", "added")])


def one_run(root, cell, **kw):
    kw.setdefault("seed", 2**31 + 17)
    kw.setdefault("seconds", 0.5)
    kw.setdefault("trace", False)
    return run_mod.run_cell(cells.load_cell(cell, root), require_tpu=False,
                            compile_cache=False, root=root, **kw)


# ---------------------------------------------------------------------------
# the committed benchmark
# ---------------------------------------------------------------------------

def test_benchmark_json_parts_are_found_by_name():
    bench = cells.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        cfg = cells.model_config(cell.config)
        assert cells.matmul_params_fn(cell.bench_dir, cfg.family)(cfg) > 0
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(cells.metric_reader(cell.bench_dir, m["name"]))
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    assert cells.peaks(cells.BENCH_DIR, "TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(cells.CellError):
        cells.peaks(cells.BENCH_DIR, "no such chip")


def test_config_files_hold_what_is_run():
    from repro.configs import get_config
    for c in cells.load_benchmark()["configs"]:
        config = json.loads((cells.ROOT / c["file"]).read_text())
        cfg = cells.model_config(config)
        published = get_config(config["arch"])
        assert config["reduced"] == c["reduced"]
        for k, v in config["published"].items():
            # a corrected key is as published in what is run
            have = cfg if k in config.get("corrected", []) else published
            assert getattr(have, k) == v, k
        # every width as published: only the depth is cut
        for k in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                  "vocab_size", "ssm"):
            assert getattr(cfg, k) == getattr(published, k), k


# ---------------------------------------------------------------------------
# data-driven discovery
# ---------------------------------------------------------------------------

def test_cell_added_as_files_alone_is_found(root):
    cell = cells.load_cell("tiny-mamba.added", root)
    assert cell.mix["ckpt_mode"] == "full"
    assert cell.config["name"] == "tiny-mamba"
    with pytest.raises(cells.CellError):
        cells.load_cell("no-such.cell", root)


def test_metric_reader_added_as_a_file_is_found(root):
    bench = Path(root) / "bench"
    (bench / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return run.save.get('stall_s')\n")
    assert cells.metric_reader(bench, "steps_in_window")(
        run_mod.Run(None, None, None, 0)) is None


def test_config_file_that_drifts_from_the_program_is_refused():
    config = json.loads((cells.BENCH_DIR / "configs"
                         / "mamba2-780m-d24.json").read_text())
    config["config"]["d_model"] = 2048
    with pytest.raises(cells.CellError, match="d_model"):
        cells.model_config(config)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def test_untraced_run_prints_end_to_end_metrics(root):
    res = one_run(root, "tiny-mamba.incr")
    assert list(res) == RESULT_KEYS + ["checks"]
    assert res["correct"] is True and res["attempted"] == 2 \
        and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s",
                                   "save_stall_s", "save_durable_s",
                                   "resume_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for check in res["checks"].values():
        assert set(check) == {"value", "limit"}


def test_traced_run_prints_per_layer_metrics_and_breakdown(root,
                                                           monkeypatch):
    # a CPU trace has no device plane: the run reads a recorded TPU trace
    import reduce_trace
    sample = Path(__file__).resolve().parent / "data" / \
        "tpu_trace_sample.json.gz"
    monkeypatch.setattr(reduce_trace, "load_xplane",
                        lambda path: reduce_trace.load_events(sample))
    res = one_run(root, "tiny-dense.incr", trace=True)
    assert list(res) == RESULT_KEYS + ["breakdown", "checks"]
    assert res["correct"] is True
    assert {"snapshot_gbps", "persist_gbps", "restore_gbps",
            "device_idle_frac", "step_mfu"} <= set(res["metrics"])
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["device_ops"]) <= 10


def test_full_mode_cell_runs(root):
    assert one_run(root, "tiny-mamba.added")["correct"] is True


# ---------------------------------------------------------------------------
# no chip, no program
# ---------------------------------------------------------------------------

def test_timed_path_refuses_to_run_without_a_tpu(root):
    with pytest.raises(run_mod.NoChip):
        run_mod.run_cell(cells.load_cell("tiny-mamba.incr", root),
                         seed=1, seconds=1, trace=False,
                         compile_cache=False, root=root)


def _command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "mamba2-780m-d24.incr-cdc", "--seed", "3", "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_command_exits_without_result_on_a_cpu():
    p = _command(cells.ROOT)
    assert p.returncode == 3 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_command_exits_without_result_beside_no_program(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


# ---------------------------------------------------------------------------
# cold reads
# ---------------------------------------------------------------------------

def test_page_cache_eviction_and_read_count():
    # beside the checkout, where the runs keep their checkpoints (a tmpfs
    # /tmp holds its files in the page cache itself)
    d = cells.ROOT / "runs" / "perfbench-pagecache-test"
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    try:
        f = d / "blob"
        payload = os.urandom(8 << 20)
        f.write_bytes(payload)
        assert pagecache.evict_tree(d) == (1, 1)
        before = pagecache.read_bytes()
        assert f.read_bytes() == payload
        assert pagecache.read_bytes() - before >= len(payload)
    finally:
        shutil.rmtree(d, ignore_errors=True)
