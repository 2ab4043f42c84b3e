"""Finds a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell is a configuration plus a traffic mix. Each part is a file of its
own, so a later change adds a configuration, a mix, a cell or a per-layer
metric by adding files and entries, never by editing one:

* ``configs/<name>.json``: the model configuration as it is run (the
  file that ``BENCHMARK.json``'s ``configs[].file`` names), with what the
  reference needs besides the sizes (``reference``: norm epsilon, the
  optimizer as stated, and the limits of the train step's comparison);
* ``models/<family>.py``: the plain reference model of a family, as
  ``init`` and an ordered list of ``stages``, each with its term of the
  loss, and, where the program's step keeps train state that no gradient
  moves, ``state_init`` and ``state_step`` (see ``reference.py``);
* ``optims/<name>.py``: the reference's optimizer, named by the
  configuration's ``reference.optimizer.name``: ``init``, ``update``, and
  ``first_grad_norms``, the first gradient read back from the program's
  optimizer state;
* ``mixes/<traffic>.json``: checkpoint mode, chunking, codec, batch,
  sequence length, the save's cadence and the pipeline's widths;
* ``metrics/<metric>.py``: one per-layer metric's reader, a module with
  ``read(run) -> float | None``;
* ``flops/<family>.py``: the matmul parameters per token of a model
  family, for the model-FLOP count of ``step_mfu``;
* ``peaks.json``: the chip's published peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class CellError(RuntimeError):
    pass


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise CellError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise CellError(f"no {what} named {name!r}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    mix: dict             # the traffic mix file's contents
    end_to_end: list      # metric entries this cell reports (trace 0)
    per_layer: list       # metric entries this cell reports (trace 1)
    bench_dir: Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    w = find(bench["workloads"], name, "workload")
    c = find(bench["configs"], w["config"], "configuration")
    bench_dir = Path(root) / bench["paths"][0]
    config = json.loads((Path(root) / c["file"]).read_text())
    mix_path = bench_dir / "mixes" / f"{w['traffic']}.json"
    if not mix_path.is_file():
        raise CellError(f"no traffic mix file {mix_path}")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                mix=json.loads(mix_path.read_text()),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                bench_dir=bench_dir)


def _module(path: Path, what: str):
    if not path.is_file():
        raise CellError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: Path, metric: str):
    """``read(run)`` of ``metrics/<metric>.py``."""
    return _module(Path(bench_dir) / "metrics" / f"{metric}.py",
                   f"reader for metric {metric!r}").read


def matmul_params_fn(bench_dir: Path, family: str):
    """``matmul_params(cfg)`` of ``flops/<family>.py``."""
    return _module(Path(bench_dir) / "flops" / f"{family}.py",
                   f"FLOP count for model family {family!r}").matmul_params


def peaks(bench_dir: Path, device_kind: str) -> dict:
    table = json.loads((Path(bench_dir) / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise CellError(f"no peaks for device kind {device_kind!r} in "
                        "peaks.json")
    return table["devices"][device_kind]


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the zoo's
    architecture with the keys the file lists under ``reduced`` set as the
    file gives them. Every key the file's ``config`` holds must then match
    what is run."""
    from repro.configs import get_config
    cfg = get_config(config["arch"])
    want = config["config"]
    changes = {}
    # ``reduced``: cut from the source; ``corrected``: where the zoo's
    # config departs from the published one
    for k in config["reduced"] + config.get("corrected", []):
        have, v = getattr(cfg, k), want[k]
        if dataclasses.is_dataclass(have) and isinstance(v, dict):
            v = dataclasses.replace(have, **v)      # a nested group, whole
        elif isinstance(have, tuple):
            v = tuple(v)
        changes[k] = v
    cfg = dataclasses.replace(cfg, **changes)
    have = json.loads(json.dumps(dataclasses.asdict(cfg), default=str))
    diff = sorted(k for k in want if have.get(k) != want[k])
    if diff:
        raise CellError(f"configuration {config['name']!r}: the program's "
                        f"{config['arch']} differs from the file in {diff}")
    return cfg
