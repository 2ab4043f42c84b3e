"""A benchmark root at CPU size for the harness's tests: the real
``metrics/``, ``flops/``, ``models/``, ``optims/`` and ``peaks.json``
beside tiny configurations and a tiny mix, named in a ``BENCHMARK.json`` of
its own. A test family (``families/<name>.py``) may take the place of a
real one."""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FAMILIES = Path(__file__).resolve().parent / "families"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MIX = {"ckpt_mode": "incremental", "chunking": "cdc", "codec": None,
            "params_codec": None, "batch": 4, "seq_len": 64,
            "save_after_steps": 4, "io_threads": 4, "n_writers": 4,
            "persist_queue_depth": 1, "retain": 2, "reference_rows": 2}


REAL = {"mamba2-780m": "mamba2-780m-d24.json",
        "starcoder2-3b": "starcoder2-3b-d3.json"}

# the program's Adafactor (``optim/adafactor.py``) as a configuration
# states it: no global clip, no weight decay, the schedule of AdamW's cells
ADAFACTOR = {"name": "adafactor", "decay": 0.99, "eps": 1e-30,
             "update_eps": 1e-12, "clip_threshold": 1.0,
             "min_dim_size_to_factor": 32, "weight_decay": 0.0,
             "decay_min_rank": 2, "clip_global_norm": None,
             "lr": {"peak": 0.0003, "warmup": 100, "total": 10000,
                    "floor": 0.1}}


def tiny_config(name: str, arch: str, optimizer: dict | None = None):
    """The zoo's reduced ``arch``, with the real configuration's stated
    reference; with ``optimizer``, that optimizer in the program and in
    the reference."""
    from repro.configs import get_config, reduced
    cfg = reduced(get_config(arch))
    if optimizer is not None:
        cfg = dataclasses.replace(cfg, optimizer=optimizer["name"])
    d = json.loads(json.dumps(dataclasses.asdict(cfg), default=str))
    keys = [k for k, v in d.items() if v != json.loads(json.dumps(
        dataclasses.asdict(get_config(arch)), default=str))[k]]
    stated = json.loads((BENCH / "configs" / REAL[arch]).read_text())
    reference = dict(stated["reference"])
    if optimizer is not None:
        reference["optimizer"] = optimizer
    return {"name": name, "arch": arch, "source": "test", "reduced": keys,
            "config": d, "reference": reference}


def make_root(tmp: Path, *, mixes=None, extra_cells=(),
              families=None) -> Path:
    """``tmp`` as a benchmark root with cells ``tiny-mamba.incr``,
    ``tiny-dense.incr`` and ``tiny-dense-adafactor.incr`` (and
    ``extra_cells``: (cell, config, traffic)); ``families`` maps a family
    to the test family (``families/<name>.py``) that takes its place."""
    tmp = Path(tmp)
    bench = tmp / "bench"
    for d in ("metrics", "flops", "models", "optims"):
        shutil.copytree(BENCH / d, bench / d, dirs_exist_ok=True)
    for family, name in (families or {}).items():
        shutil.copy(FAMILIES / f"{name}.py",
                    bench / "models" / f"{family}.py")
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir(exist_ok=True)
    (bench / "mixes").mkdir(exist_ok=True)
    configs = {"tiny-mamba": tiny_config("tiny-mamba", "mamba2-780m"),
               "tiny-dense": tiny_config("tiny-dense", "starcoder2-3b"),
               "tiny-dense-adafactor": tiny_config(
                   "tiny-dense-adafactor", "starcoder2-3b", ADAFACTOR)}
    for name, config in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(config))
    for name, mix in (mixes or {"incr": TINY_MIX}).items():
        (bench / "mixes" / f"{name}.json").write_text(json.dumps(mix))
    cells = [("tiny-mamba.incr", "tiny-mamba", "incr"),
             ("tiny-dense.incr", "tiny-dense", "incr"),
             ("tiny-dense-adafactor.incr", "tiny-dense-adafactor", "incr"),
             *extra_cells]
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench_json = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": n, "source": "test",
                     "file": f"bench/configs/{n}.json", "reduced": [],
                     "why": "test"} for n in configs],
        "workloads": [{"name": c, "config": cfg, "traffic": t, "chips": 1,
                       "why": "test"} for c, cfg, t in cells],
        "end_to_end": real["end_to_end"],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                      for m in real["per_layer"]],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench_json))
    return tmp
