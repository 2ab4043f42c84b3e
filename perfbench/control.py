"""The readings that the train step's limits are set from, on the chip at
a cell's own size:

    python3 perfbench/control.py --workload <cell> --seeds 11,12,13 \
        [--out readings.jsonl]

For each seed, in one process: the program's first three steps from the
benchmark's weights, as a run's set-up takes them (``run.start``,
``run.first_steps``); then the reference (float32, ``highest``), the
control (the reference in the program's place at int8, one precision
below the configuration's bfloat16), and the reference in the program's
place on half of each batch, with the mean over the rest. Each is
compared with the reference by ``reference.gaps``, and one JSON line per
seed gives the three sets of numbers. A step that returns its state
unchanged reads 1 on both norm gaps by their definition and needs no
run. Nothing is saved; no window is measured.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cells  # noqa: E402
import reference  # noqa: E402
import run as run_mod  # noqa: E402


def readings(cell, seeds, *, workdir: Path, require_tpu: bool = True):
    """One dict per seed: ``{"seed", "program", "control", "half_batch"}``,
    each the ``reference.gaps`` of that side against the reference."""
    import jax

    from repro.launch.mesh import make_host_mesh
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise run_mod.NoChip(f"no TPU: JAX found {devices[0].platform} "
                             "devices")
    cfg = cells.model_config(cell.config)
    mix = cell.mix
    mesh = make_host_mesh(devices=devices[:cell.chips])
    trainer = None
    for seed in seeds:
        t = time.monotonic()
        tcfg = run_mod.trainer_config(cell, workdir, seed)
        if trainer is None:
            trainer = run_mod.make_trainer(cfg, tcfg, mesh, workdir)
        trainer.tcfg = tcfg
        run_mod.start(trainer, cell.config, seed, cell.bench_dir)
        program = run_mod.first_steps(trainer, cell.config, seed,
                                      cell.bench_dir)
        trainer.state = None
        gc.collect()
        batches = [reference.tokens(tcfg.seed, k, mix["batch"],
                                    mix["seq_len"], cfg.vocab_size)
                   for k in range(reference.STEPS)]
        kw = {"rows": mix["reference_rows"], "bench_dir": cell.bench_dir}
        ref = reference.reference(cell.config, seed, batches, **kw)
        control = reference.reference(cell.config, seed, batches,
                                      precision="int8", **kw)
        half = reference.reference(
            cell.config, seed, [b[:len(b) // 2] for b in batches], **kw)
        out = {"seed": seed, "losses": {"reference": ref.losses,
                                        "program": program.losses}}
        for name, got in (("program", program), ("control", control),
                          ("half_batch", half)):
            out[name] = reference.gaps(got, ref)
        out["seconds"] = time.monotonic() - t
        yield out
    if trainer is not None:
        trainer.manager.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--out", default=None, help="also append the JSON "
                    "lines to this file")
    args = ap.parse_args(argv)
    root = cells.ROOT
    sys.path.insert(0, str(root / "src"))
    run_mod.enable_compile_cache(root)
    cell = cells.load_cell(args.workload, root)
    workdir = root / "runs" / "perfbench" / (cell.name + ".control")
    workdir.mkdir(parents=True, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        for line in readings(cell, seeds, workdir=workdir):
            text = json.dumps(line)
            print(text, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(text + "\n")
    except run_mod.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
