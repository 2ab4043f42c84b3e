"""One run of one benchmark cell: train with a checkpoint, then resume.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; ``cells.py`` finds their files. One process drives one chip
through the program's own ``Trainer`` and ``CheckpointManager``.

Set-up (``setup_s``, from the process's start): the configuration, a
``Trainer`` on an empty work directory, its train state built in one
jitted call from weights the benchmark makes from ``--seed``
(``reference.py``), the first three steps through the window's own call
and feed, and, for content-defined chunking, the gear-scan kernel at
every shape a save can give it. The first three steps are read for the
comparison with the reference.

Window (``--seconds``): steps through the Trainer's step program, one in
flight while the host makes the next batch. After the mix's
``save_after_steps``-th step one asynchronous ``Trainer.save`` is issued;
training goes on while its round persists. The window ends at the first
step that finishes after ``--seconds``.

After the window: the round is waited out, the checkpoint's files are
dropped from the page cache, the trainer and its device state are
dropped, and a fresh ``Trainer`` restores the checkpoint and takes one
step. Once it is gone too, the plain reference takes the first three
steps again. ``correct`` compares the program's first three steps with
the reference's (losses, the first gradient's and the parameters'
change's norms, leaf by leaf), the restored state leaf by leaf with the
state the save was handed (``fingerprint.py``), the step counter with the
steps taken, and the resumed step's loss with the loss the live run had
for that step.

The last line of standard output is one JSON object; with ``--trace 0``
its ``metrics`` are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window. Without a TPU
(or with fewer chips than the cell asks for) the run exits 3 and prints no
result; without the program (``src/repro``) beside it, 2.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cells  # noqa: E402
import fingerprint  # noqa: E402
import pagecache  # noqa: E402
import reference  # noqa: E402
from watch import CompileClock, FailureWarnings  # noqa: E402

LIMITS = json.loads((cells.BENCH_DIR / "limits.json").read_text())


class NoChip(RuntimeError):
    pass


def say(msg: str):
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def enable_compile_cache(root: Path):
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else the fixed ``<checkout>/.jax_cache``
    that the program's launchers use too. Every program is cached, however
    quickly it compiled, so that no run after the first compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def annotate(trace: bool, name: str):
    if not trace:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Run:
    """What one run measured; the per-layer readers take it."""

    def __init__(self, cell, cfg, peaks, flops_per_step):
        self.cell, self.cfg, self.peaks = cell, cfg, peaks
        self.flops_per_step = flops_per_step
        self.trace = None           # trace.Summary of the window
        self.save: dict = {}        # the window's save, on the host clock
        self.restore: dict = {}     # the fresh Trainer's restore
        self.scan_payload_bytes = 0  # payload bytes the device gear-scanned


def trainer_config(cell, workdir: Path, seed: int):
    from repro.train.loop import TrainerConfig
    mix = cell.mix
    return TrainerConfig(
        workdir=str(workdir), batch=mix["batch"], seq_len=mix["seq_len"],
        ckpt_every=0, async_ckpt=True, retain=mix["retain"],
        n_writers=mix["n_writers"], codec=mix.get("codec"),
        params_codec=mix.get("params_codec"),
        ckpt_mode=mix["ckpt_mode"], chunking=mix["chunking"],
        io_threads=mix["io_threads"],
        persist_queue_depth=mix["persist_queue_depth"],
        seed=seed % 2**31, log_every=1)


def make_trainer(cfg, tcfg, mesh, workdir: Path):
    """A Trainer on a one-tier store: the fast tier in ``workdir/bb`` and
    no slow tier, so a run writes each checkpoint byte once."""
    from repro.core.storage import Tier, TieredStore
    from repro.train.loop import Trainer
    store = TieredStore(Tier("local", workdir / "bb"))
    return Trainer(cfg, tcfg, mesh=mesh, store=store)


def start(trainer, config: dict, seed: int,
          bench_dir: Path = cells.BENCH_DIR):
    """The trainer's state at step 0, built in one jitted call from the
    weights ``reference.init_params`` makes from ``seed`` and the state the
    family declares; refused where they do not fit the program's layout."""
    import jax
    import jax.numpy as jnp
    c = config["config"]
    fam = reference.family(c["family"], bench_dir)

    def state(key):
        params = fam.init(c, key)
        return {"params": params, "opt": trainer.optimizer.init(params),
                "step": jnp.zeros((), jnp.int32),
                "rng": jax.random.key_data(jax.random.PRNGKey(0)),
                **reference.declared_state(fam, c, key)}

    key = reference.weight_key(seed)
    have = jax.tree.map(lambda a: (a.shape, a.dtype),
                        jax.eval_shape(state, key))
    want = jax.tree.map(lambda a: (a.shape, a.dtype), trainer._abstract)
    if have != want:
        raise cells.CellError("the benchmark's weights do not fit the "
                              "program's state layout")
    trainer.state = jax.jit(state, out_shardings=trainer._shardings)(key)
    trainer.data_state = trainer.pipeline.init_state(trainer.tcfg.seed)
    trainer.py_step = 0
    return trainer


def first_steps(trainer, config: dict, seed: int,
                bench_dir: Path = cells.BENCH_DIR) -> "reference.Readings":
    """The first ``reference.STEPS`` steps through the window's call and
    feed, and what the reference compares of them."""
    opt = config["reference"]["optimizer"]
    optim = reference.optimizer(opt["name"], bench_dir)
    losses, grad = [], None
    for k in range(reference.STEPS):
        losses.append(float(dispatch_step(trainer)))
        if k == 0:
            grad = optim.first_grad_norms(trainer.state["opt"], opt)
    state = {k: v for k, v in trainer.state.items()
             if k not in reference.PROGRAM_STATE}
    change = reference.changes(config, seed, trainer.state["params"], state,
                               bench_dir=bench_dir)
    return reference.Readings(losses, grad, change)


def dispatch_step(trainer):
    """One step as ``Trainer.fit`` takes it; returns the loss unfetched."""
    import jax

    from repro.sharding.partition import batch_spec
    batch, next_ds = trainer.pipeline.next(trainer.data_state)
    batch = jax.device_put(batch, batch_spec(batch, trainer.mesh))
    trainer.state, metrics = trainer.step_fn(trainer.state, batch)
    trainer.data_state = next_ds
    trainer.py_step += 1
    return metrics["loss"]


def scan_shapes(scanner) -> list:
    """Payload sizes that, together, give the Pallas gear scan every
    padded segment length a save can dispatch: each payload is one full
    segment plus a tail that pads to one more multiple of the block."""
    from repro.core import cdc_scan
    seg, block, window = (cdc_scan.SEGMENT_BYTES, cdc_scan.PALLAS_BLOCK,
                          cdc_scan.WINDOW)
    if scanner.resolve(seg + block) != "pallas":
        return []
    return [seg + k * block - window for k in range(1, seg // block + 1)]


def warm_scan(scanner) -> int:
    import numpy as np
    sizes = scan_shapes(scanner)
    if not sizes:
        return 0
    data = np.random.default_rng(0).integers(0, 256, max(sizes), np.uint8)
    for n in sizes:
        scanner.scan_async(data[:n]).result()
    return len(sizes)


def device_scanned_bytes(manifest: dict, scanner) -> int:
    """Payload bytes of the save that the device scanned: every chunked
    record whose payload the scanner sends to the Pallas kernel."""
    total = 0
    for leaf in manifest["leaves"].values():
        for rec in leaf["shards"]:
            n = rec.get("payload_bytes")
            if rec.get("chunking") == "cdc" and n \
                    and scanner.resolve(int(n)) == "pallas":
                total += int(n)
    return total


def layout(state) -> dict:
    from repro.core.split_state import leaf_paths
    return {n: (tuple(x.shape), str(x.dtype)) for n, x in leaf_paths(state)}


def hbm_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True,
             compile_cache: bool = True, root: Path = cells.ROOT,
             events_out: str | None = None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    if compile_cache:
        enable_compile_cache(root)
    import jax

    from repro.launch.mesh import make_host_mesh

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise NoChip(f"no TPU: JAX found {platform} devices")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips; JAX sees "
                     f"{len(devices)}")
    used = devices[:cell.chips]
    failures = FailureWarnings()
    logging.getLogger("repro").addHandler(failures)
    clock = CompileClock()
    cfg = cells.model_config(cell.config)
    peaks = cells.peaks(cell.bench_dir, devices[0].device_kind) \
        if require_tpu else {"bf16_flops": math.nan,
                             "hbm_bytes_per_s": math.nan}
    mix = cell.mix
    tokens_per_step = mix["batch"] * mix["seq_len"]
    matmul_params = cells.matmul_params_fn(cell.bench_dir, cfg.family)(cfg)
    run = Run(cell, cfg, peaks, 6 * matmul_params * tokens_per_step)

    workdir = Path(root) / "runs" / "perfbench" / cell.name
    trace_dir = workdir.with_name(cell.name + ".trace")
    for d in (workdir, trace_dir):
        shutil.rmtree(d, ignore_errors=True)
    workdir.mkdir(parents=True)
    mesh = make_host_mesh(devices=used)
    tcfg = trainer_config(cell, workdir, seed)
    try:
        return _run(run, cell, cfg, tcfg, mesh, used, seconds, trace,
                    trace_dir, failures, clock, tokens_per_step, platform,
                    devices, events_out, seed)
    finally:
        logging.getLogger("repro").removeHandler(failures)
        shutil.rmtree(workdir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)


def _run(run, cell, cfg, tcfg, mesh, used, seconds, trace, trace_dir,
         failures, clock, tokens_per_step, platform, devices, events_out,
         seed):
    import jax

    import reduce_trace as trace_mod
    mix = cell.mix
    workdir = Path(tcfg.workdir)

    # ---------------- set-up ----------------
    t = time.monotonic()
    trainer = start(make_trainer(cfg, tcfg, mesh, workdir), cell.config,
                    seed, cell.bench_dir)
    jax.block_until_ready(trainer.state)
    t_init = time.monotonic() - t
    t = time.monotonic()
    program = first_steps(trainer, cell.config, seed, cell.bench_dir)
    t_warm = time.monotonic() - t
    t = time.monotonic()
    # the scanner every writer rank of this manager shares
    scanner = getattr(trainer.manager._chunker, "scanner", None)
    n_scan = warm_scan(scanner) if scanner is not None else 0
    jax.block_until_ready(fingerprint.fingerprint_async(trainer.state))
    t_scan = time.monotonic() - t
    setup_compile = clock.lap()
    commits: dict = {}
    trainer.manager.on_commit.append(
        lambda step, manifest: commits.setdefault(step, time.monotonic()))
    setup_s = time.monotonic() - T_PROCESS
    say(f"set-up {setup_s:.3f}s: state from the seed {t_init:.3f}s, "
        f"{reference.STEPS} first steps {t_warm:.3f}s, gear scan at "
        f"{n_scan} shapes + fingerprint {t_scan:.3f}s; compiled "
        f"{setup_compile[1]} programs in {setup_compile[0]:.3f}s")

    # ---------------- window ----------------
    save_after = mix["save_after_steps"]
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    steps, prev, loss_after_save = 0, None, None
    t_start = time.monotonic()
    deadline = t_start + seconds
    with annotate(trace, trace_mod.WINDOW_SPAN):
        while steps <= save_after or time.monotonic() < deadline:
            with annotate(trace, "perfbench.dispatch_step"):
                loss = dispatch_step(trainer)
            steps += 1
            if prev is not None:
                with annotate(trace, "perfbench.wait_step"):
                    prev.block_until_ready()
            prev = loss
            if steps == save_after + 1:
                loss_after_save = loss
            if steps == save_after:
                with annotate(trace, "perfbench.wait_step"):
                    loss.block_until_ready()
                with annotate(trace, "perfbench.fingerprint"):
                    fp_saved = fingerprint.fingerprint_async(trainer.state)
                    jax.block_until_ready(fp_saved)
                    saved_counter = int(trainer.state["step"])
                saved = {"py_step": trainer.py_step,
                         "data_state": trainer.data_state.to_json(),
                         "layout": layout(trainer.state)}
                t_call = time.monotonic()
                with annotate(trace, "perfbench.save_call"):
                    rep = trainer.save(blocking=False)
                t_return = time.monotonic()
        with annotate(trace, "perfbench.wait_step"):
            prev.block_until_ready()
        t_end = time.monotonic()
    window_compile = clock.lap()
    window_s = t_end - t_start
    say(f"window {window_s:.3f}s: {steps} steps of {tokens_per_step} "
        f"tokens; save after step {save_after} blocked "
        f"{t_return - t_call:.3f}s; compiled {window_compile[1]} programs "
        f"in {window_compile[0]:.3f}s inside the window")

    # ---------------- after the window ----------------
    with annotate(trace, "perfbench.wait_round"):
        trainer.manager.wait()
    if trace:
        # the trace holds the whole round: every device scan of the save
        jax.profiler.stop_trace()
    t_commit = commits.get(saved["py_step"])
    if t_commit is None:
        raise RuntimeError(f"the save of step {saved['py_step']} never "
                           "committed")
    memory_peak = hbm_peak(used)
    save_warnings = list(failures.records)
    loss_live = float(loss_after_save)
    nbytes = int(rep["bytes"])
    run.save = {"stall_s": t_return - t_call, "snapshot_s":
                rep["snapshot_s"], "bytes": nbytes, "t_call": t_call,
                "t_snapshot_end": t_call + rep["snapshot_s"],
                "t_commit": t_commit, "durable_s": t_commit - t_call,
                "persist": dict(trainer.manager.last_report)}
    if scanner is not None:
        run.scan_payload_bytes = device_scanned_bytes(
            trainer.manager.load_manifest(saved["py_step"]), scanner)
    persist = run.save["persist"]
    say(f"save of step {saved['py_step']}: stall {run.save['stall_s']:.3f}s"
        f" (snapshot {rep['snapshot_s']:.3f}s), durable "
        f"{run.save['durable_s']:.3f}s after the call, state {nbytes} B, "
        f"written {persist.get('written_bytes')} B in "
        f"{persist.get('chunks', 'no')} chunks; the round's commit came "
        f"{t_commit - t_end:+.3f}s after the window's end, "
        f"{100 * min(1.0, (t_end - t_call) / (t_commit - t_call)):.1f}% of "
        "the round inside the window")
    trainer.manager.close()
    trainer.state = None
    del trainer, prev, loss, loss_after_save
    gc.collect()

    n_files, n_evicted = pagecache.evict_tree(workdir)
    io_before = pagecache.read_bytes()
    t0 = time.monotonic()
    trainer = make_trainer(cfg, tcfg, mesh, workdir).init_or_restore()
    jax.block_until_ready(trainer.state)
    t_resident = time.monotonic()
    fp_restored = fingerprint.fingerprint_async(trainer.state)
    restored = {"py_step": trainer.py_step,
                "data_state": trainer.data_state.to_json(),
                "layout": layout(trainer.state)}
    loss_resumed = float(dispatch_step(trainer))
    t_resumed = time.monotonic()
    read = pagecache.read_bytes() - io_before
    resume_s = t_resumed - t0
    run.restore = {"resident_s": t_resident - t0, "state_bytes": nbytes,
                   "read_bytes": read}
    say(f"resume {resume_s:.3f}s: restored step {trainer.restored_from} "
        f"resident after {t_resident - t0:.3f}s, {read} B read from storage "
        f"after {n_evicted} of {n_files} files left the page cache")
    restore_warnings = failures.records[len(save_warnings):]
    trainer.manager.close()
    trainer.state = None
    del trainer
    gc.collect()

    # ---------------- the reference ----------------
    t = time.monotonic()
    batches = [reference.tokens(tcfg.seed, k, mix["batch"], mix["seq_len"],
                                cfg.vocab_size)
               for k in range(reference.STEPS)]
    ref = reference.reference(cell.config, seed, batches,
                              rows=mix["reference_rows"],
                              bench_dir=cell.bench_dir)
    train = reference.gaps(program, ref)
    say(f"reference {time.monotonic() - t:.3f}s, largest program "
        f"{ref.peak_bytes} B: losses {ref.losses}, the "
        f"program's {program.losses}; worst leaves "
        f"{train['worst_leaves']}; left out {train['left_out']}")

    # ---------------- the comparison ----------------
    differ = fingerprint.differing(fingerprint.fetch(fp_saved),
                                   fingerprint.fetch(fp_restored))
    for key in ("py_step", "data_state", "layout"):
        if saved[key] != restored[key]:
            differ.append(key)
    checks = {
        "loss_gap": train["loss_gap"],
        "grad_norm_gap": train["grad_norm_gap"],
        "update_norm_gap": train["update_norm_gap"],
        "leaves_differing": len(differ),
        "step_counter_gap": abs(saved_counter - saved["py_step"]),
        "resume_loss_gap": (abs(loss_resumed - loss_live)
                            if math.isfinite(loss_resumed + loss_live)
                            else math.inf),
    }
    limits = {k: LIMITS[k]["limit"] if k in LIMITS
              else cell.config["reference"]["limits"][k] for k in checks}
    correct = all(checks[k] <= limits[k] for k in checks)
    if differ:
        say(f"leaves that differ after the restore: {differ[:8]}")
    failed = int(bool(save_warnings)) + int(bool(restore_warnings))
    for w in save_warnings + restore_warnings:
        say(f"failure warning: {w}")

    # ---------------- the result ----------------
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": 2, "failed": failed}
    if trace:
        events = trace_mod.load_xplane(trace_mod.find_xplane(trace_dir))
        if events_out:
            trace_mod.save_events(events, events_out)
        run.trace = trace_mod.Summary(events)
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        metrics = {}
        for m in cell.per_layer:
            value = cells.metric_reader(cell.bench_dir, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": run.trace.top_ops(),
                               "idle_gaps": run.trace.idle_gaps()}
        say(f"device time by program: {run.trace.programs()}")
    else:
        measured = {
            "setup_s": setup_s,
            "train_tokens_per_s": steps * tokens_per_step / window_s,
            "save_stall_s": run.save["stall_s"],
            "save_durable_s": run.save["durable_s"],
            "resume_s": resume_s,
        }
        result["metrics"] = {m["name"]: {"value": measured[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        say(f"check {k} = {v!r} (limit {limits[k]!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--events-out", default=None,
                    help="with --trace 1, also keep the trace's reduced "
                         "events (gzipped JSON) at this path")
    args = ap.parse_args(argv)
    root = cells.ROOT
    if not (root / "src" / "repro").is_dir():
        print(f"perfbench: no program under {root / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    # libtpu logs under /tmp/tpu_logs unless told otherwise: keep its
    # files inside the checkout
    os.environ.setdefault("TPU_LOG_DIR",
                          str(root / "runs" / "perfbench" / "tpu_logs"))
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(levelname)s "
                               "%(message)s")
    try:
        cell = cells.load_cell(args.workload, root)
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace),
                          events_out=args.events_out)
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
