"""Smoke run of the Trainer's checkpoint path on TPU, at full model width.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: phase (e) only

One process drives the chip; it starts no other.

  (a) environment: the device as JAX reports it, host RAM, free disk in
      the work directory, the compile-cache directory, JAX/libtpu versions;
  (b) device codec parity: the CDC gear scan (16 MiB f32 and bf16
      payloads) and the fused byteplane transform / RLE / rANS dispatches
      (one 8 MiB f32 payload) against the numpy oracles, byte for byte,
      with the backend ``resolve()`` chose and the compile seconds;
  (c) mamba2-780m at its published width through ``Trainer``: 4 steps,
      an async incremental CDC checkpoint every 2 steps;
  (d) a fresh ``Trainer`` on the same work directory restores, matches
      the params digest of (c), and takes one more step;
  (e) with ``--chips 4``: 2 steps on the Trainer's default mesh over the
      four chips and a save, then a restore onto a 1-chip mesh in the same
      process (M×N restart), an equal digest and one more step.

Every phase prints its lines as it goes. The last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``; a run that fails
anywhere exits non-zero and prints no such line. Without a TPU the run
stops before any phase. The work directory, ``.smoke_run/`` next to this
file, is emptied at start and removed at exit: each save is about 7.8 GB.
"""
from __future__ import annotations

import argparse
import gc
import json
import logging
import math
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORKDIR = ROOT / ".smoke_run"
ARCH = "mamba2-780m"
# the seed of the weights, the data and the codec payloads; the digests
# and losses recorded for this run hold for it
SEED = 0
# batch × sequence chosen from the compiled step's memory_analysis() on a
# described v5e chip: 8 × 512 needs 7.27 GiB of arguments + 3.99 GiB of
# temporaries of 15.75 GiB; 8 × 1024 needs 7.27 + 9.29 GiB, more than
# the chip holds. The sequence is a multiple of the SSM chunk (256).
BATCH, SEQ_LEN = 8, 512
SCAN_BYTES = 16 << 20
FUSED_BYTES = 8 << 20
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeError(RuntimeError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeError(msg)


def say(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


class CompileClock:
    """Sums the backend compile seconds JAX reports between laps."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1

    def lap(self) -> str:
        out = f"compile {self.seconds:.2f}s ({self.programs} programs)"
        self.seconds, self.programs = 0.0, 0
        return out


class FailureWarnings(logging.Handler):
    """Collects the warnings that mean a save or scan did not run as
    asked — a writer rank failed and was retried, a round committed
    degraded past the fast tier, a scan fell back off the device. In this
    run each is a failure, not a warning."""

    CODES = ("[CKPT_W_RETRY]", "[CKPT_W_DEGRADED]", "[CDC_W_SCAN]")

    def __init__(self):
        super().__init__(logging.WARNING)
        self.records = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith(self.CODES):
            self.records.append(msg)


class SaveLog:
    """Each save's synchronous report, and each round's persist report
    (``CheckpointManager.last_report``) once the round has landed."""

    def __init__(self, trainer):
        self.sync: dict = {}
        self.persist: dict = {}
        self._trainer = trainer
        self._save = trainer.save
        trainer.save = self._record

    def _record(self, **kw):
        rep = self._save(**kw)
        self.sync[rep["step"]] = rep
        self.collect()
        return rep

    def collect(self):
        last = self._trainer.manager.last_report
        if last:
            self.persist[last["step"]] = dict(last)


def _gib(n) -> str:
    return f"{n / 2**30:.2f} GiB"


def _mib(n) -> str:
    return f"{n / 2**20:.0f} MiB"


def _hbm_peak(dev) -> str:
    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    if peak is None:
        return "not reported"
    return _gib(peak) + ("" if limit is None else f" of {_gib(limit)}")


def step_memory(trainer) -> str:
    """What the compiler counts for the trainer's own step program, lowered
    with its live state and batch layout: the program the steps ran, which
    the compile cache serves when it is on."""
    import jax

    from repro.sharding.partition import batch_spec

    def shape(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    state = jax.tree.map(lambda x: shape(x, x.sharding), trainer.state)
    batch, _ = trainer.pipeline.next(trainer.data_state)
    batch = jax.tree.map(shape, batch, batch_spec(batch, trainer.mesh))
    m = trainer.step_fn.lower(state, batch).compile().memory_analysis()
    return (f"arguments {_gib(m.argument_size_in_bytes)}, outputs "
            f"{_gib(m.output_size_in_bytes)} (aliased "
            f"{_gib(m.alias_size_in_bytes)}), temporaries "
            f"{_gib(m.temp_size_in_bytes)}")


# ---------------------------------------------------------------------------
# (a) environment
# ---------------------------------------------------------------------------

def phase_env(cache_dir) -> list:
    import importlib.metadata

    import jax
    import jaxlib
    devs = jax.devices()
    d = devs[0]
    say("a", f"device platform={d.platform} kind={d.device_kind} "
             f"count={len(devs)}")
    ram = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    avail = None
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                avail = int(line.split()[1]) * 1024
    disk = shutil.disk_usage(WORKDIR)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("a", f"host RAM {_gib(ram)} (available "
             f"{_gib(avail) if avail is not None else 'unknown'}), "
             f"free disk in {WORKDIR} {_gib(disk.free)}")
    say("a", f"compile cache {cache_dir}")
    say("a", f"python {sys.version.split()[0]} jax {jax.__version__} "
             f"jaxlib {jaxlib.__version__} libtpu {libtpu}")
    return devs


# ---------------------------------------------------------------------------
# (b) device codec parity
# ---------------------------------------------------------------------------

def phase_codec(clock: CompileClock, *, scan_bytes=SCAN_BYTES,
                fused_bytes=FUSED_BYTES) -> set:
    """Byte parity of every device codec program with its numpy oracle.
    Returns the set of backends ``resolve()`` chose."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import cdc_scan
    from repro.core import codec as codec_mod
    from repro.core.cdc import GearChunker
    from repro.core.policy import CheckpointPolicy

    # the chunker the Trainer's incremental CDC policy builds
    policy = CheckpointPolicy().with_overrides(chunking="cdc")
    sc = GearChunker.from_policy(policy.chunking).scanner
    ms, ml = sc.mask_strict, sc.mask_loose
    rng = np.random.default_rng(SEED)
    backends = set()

    for dtype in (np.float32, jnp.bfloat16):
        n_el = scan_bytes // np.dtype(dtype).itemsize
        payload = rng.standard_normal(n_el, dtype=np.float32).astype(dtype)
        u8 = cdc_scan.as_u8(payload)
        backend = sc.resolve(len(u8))
        backends.add(backend)
        clock.lap()
        t0 = time.monotonic()
        strict, loose = sc.scan_async(payload).result()
        dt = time.monotonic() - t0
        rs, rl = cdc_scan.scan_candidates_numpy(u8, ms, ml)
        check(np.array_equal(strict, rs) and np.array_equal(loose, rl),
              f"gear scan {np.dtype(dtype).name}: candidates differ from "
              "the numpy oracle")
        say("b", f"gear scan {np.dtype(dtype).name} {_mib(len(u8))} "
                 f"backend={backend} strict={len(strict)} "
                 f"loose={len(loose)} byte-identical; first call "
                 f"{dt:.2f}s, {clock.lap()}")

    # a fresh optimizer moment is mostly zeros: one zero quarter keeps
    # the RLE branch of the entropy stage on the path too
    x = rng.standard_normal(fused_bytes // 4, dtype=np.float32)
    x[len(x) // 4:len(x) // 2] = 0.0
    u8 = cdc_scan.as_u8(x)
    backend = sc.resolve(len(u8))
    backends.add(backend)
    t_ref = codec_mod.byteplane_forward(u8, 4)
    cand_ref = cdc_scan.scan_candidates_numpy(t_ref, ms, ml)
    clock.lap()
    t0 = time.monotonic()
    (strict, loose), t = sc.scan_transform_async(x, 4).result()
    dt = time.monotonic() - t0
    check(np.array_equal(t, t_ref), "byteplane transform differs from the "
          "numpy oracle")
    check(np.array_equal(strict, cand_ref[0])
          and np.array_equal(loose, cand_ref[1]),
          "scan of the transformed stream differs from the numpy oracle")
    say("b", f"transform+scan f32 {_mib(len(u8))} backend={backend} "
             f"byte-identical; first call {dt:.2f}s, {clock.lap()}")
    for codec in ("byteplane-rle", "byteplane-rans"):
        ref_stream, ref_lens = codec_mod.plane_stream_encode(t_ref, codec)
        t0 = time.monotonic()
        cands, stream, block_lens = \
            sc.scan_transform_encode_async(x, 4, codec).result()
        dt = time.monotonic() - t0
        check(np.array_equal(stream, ref_stream)
              and np.array_equal(block_lens, ref_lens),
              f"{codec} stream differs from the numpy oracle")
        check(np.array_equal(cands[0], cand_ref[0])
              and np.array_equal(cands[1], cand_ref[1]),
              f"{codec}: scan differs from the numpy oracle")
        say("b", f"transform+scan+{codec} f32 {_mib(len(u8))} "
                 f"backend={backend} stream {len(stream)} B "
                 f"(ratio {len(u8) / len(stream):.4f}) byte-identical; "
                 f"first call {dt:.2f}s, {clock.lap()}")
    return backends


# ---------------------------------------------------------------------------
# (c)-(e) train, checkpoint, restore
# ---------------------------------------------------------------------------

def state_bytes(cfg) -> int:
    """Checkpointed bytes of ``cfg``'s train state (params + optimizer)."""
    import jax

    from repro.core.split_state import abstract_train_state
    from repro.models import Model
    from repro.optim import make_optimizer
    abstract = abstract_train_state(Model(cfg), make_optimizer(cfg))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(abstract))


def trainer_config(workdir: Path, *, batch=BATCH, seq_len=SEQ_LEN):
    """The TrainerConfig ``launch/train.py`` builds for ``--ckpt-mode
    incremental --chunking cdc --ckpt-every 2``, with ``retain`` 2 (two
    ~7.8 GB saves on the fast tier plus their drain copies)."""
    from repro.train.loop import TrainerConfig
    return TrainerConfig(workdir=str(workdir / ARCH), batch=batch,
                         seq_len=seq_len, ckpt_every=2, retain=2,
                         ckpt_mode="incremental", chunking="cdc",
                         seed=SEED, log_every=1)


def train_and_save(phase, cfg, tcfg, steps: int, clock: CompileClock, *,
                   mesh=None):
    """Fresh state, ``steps`` steps with an async save every
    ``tcfg.ckpt_every``; returns the trainer and its params digest. A
    step's time is taken once its metrics reach the host, which waits for
    the whole step program."""
    from repro.core import atomic
    from repro.train.loop import Trainer

    clock.lap()
    t0 = time.monotonic()
    trainer = Trainer(cfg, tcfg, mesh=mesh).init_or_restore()
    check(trainer.restored_from is None, "work directory was not empty")
    say(phase, f"init {time.monotonic() - t0:.2f}s on mesh "
               f"{dict(trainer.mesh.shape)}, {clock.lap()}")
    saves = SaveLog(trainer)
    report = trainer.fit(steps)
    saves.collect()
    check(report["status"] == "completed", f"fit ended {report['status']}")
    for m in trainer.history:
        check(math.isfinite(m["loss"]), f"step {m['step']}: loss "
              f"{m['loss']} is not finite")
        say(phase, f"step {m['step']}: {m['step_s']:.3f}s "
                   f"loss={m['loss']:.4f}"
                   + (" (includes compilation)" if m["step"] == 1 else ""))
    say(phase, f"train {clock.lap()}")
    for step in sorted(saves.sync):
        s, p = saves.sync[step], saves.persist.get(step)
        check(p is not None, f"save at step {step} never persisted")
        say(phase, f"save step {step}: blocking_s={s['blocking_s']:.3f} "
                   f"snapshot_s={s['snapshot_s']:.3f} "
                   f"persist_s={p['seconds']:.3f} state {_gib(s['bytes'])} "
                   f"written {_gib(p['written_bytes'])} "
                   f"chunks={p.get('chunks')}")
    committed = atomic.list_committed_steps(trainer.manager.store.fast.root)
    expected = list(range(tcfg.ckpt_every, steps + 1, tcfg.ckpt_every))
    check(sorted(committed) == expected,
          f"committed steps {sorted(committed)}, expected {expected}")
    digest = trainer.params_digest()
    say(phase, f"{len(committed)} committed saves at steps "
               f"{sorted(committed)}; params digest {digest[:16]}")
    return trainer, digest


def close(trainer):
    """Land every async round and drop the trainer's device state."""
    trainer.manager.close()
    trainer.state = None
    gc.collect()


def restore_and_step(phase, cfg, tcfg, digest: str, step: int,
                     clock: CompileClock, *, mesh=None):
    from repro.train.loop import Trainer

    clock.lap()
    t0 = time.monotonic()
    trainer = Trainer(cfg, tcfg, mesh=mesh).init_or_restore()
    restore_s = time.monotonic() - t0
    check(trainer.restored_from == step,
          f"restored step {trainer.restored_from}, expected {step}")
    got = trainer.params_digest()
    check(got == digest, f"params digest after restore {got[:16]} != "
                         f"{digest[:16]}")
    say(phase, f"restored step {step} onto mesh {dict(trainer.mesh.shape)} "
               f"in {restore_s:.2f}s; params digest equal ({got[:16]})")
    report = trainer.fit(step + 1, stop_after=1)
    m = trainer.history[-1]
    check(report["step"] == step + 1 and math.isfinite(m["loss"]),
          f"step after restore: {m}")
    say(phase, f"step {m['step']}: {m['step_s']:.3f}s loss={m['loss']:.4f} "
               f"(includes compilation), {clock.lap()}")
    close(trainer)


# ---------------------------------------------------------------------------

def run(chips: int) -> dict:
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeError(f"no TPU: JAX found {devs[0].platform} devices")
    check(chips == 1 or len(devs) == chips,
          f"--chips {chips} but JAX sees {len(devs)}")
    shutil.rmtree(WORKDIR, ignore_errors=True)
    WORKDIR.mkdir()
    failures = FailureWarnings()
    logging.getLogger("repro").addHandler(failures)
    clock = CompileClock()

    from repro.configs import get_config, param_counts
    from repro.launch.mesh import make_host_mesh

    phase_env(cache_dir)
    cfg = get_config(ARCH)
    say("a", f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
             f"vocab {cfg.vocab_size}, {param_counts(cfg)['n_total']:,} "
             f"params; batch {BATCH} × seq {SEQ_LEN}")
    tcfg = trainer_config(WORKDIR)
    say("a", f"disk for the run: each save writes up to "
             f"{_gib(state_bytes(cfg))} to the fast tier and again to its "
             f"drain copy; {_gib(shutil.disk_usage(WORKDIR).free)} free")
    if chips == 1:
        backends = phase_codec(clock)
        check(backends == {"pallas"}, f"codec backends {backends}: the "
              "Pallas kernels are the TPU route")
        one_chip = make_host_mesh(devices=devs[:1])
        trainer, digest = train_and_save("c", cfg, tcfg, 4, clock,
                                         mesh=one_chip)
        say("c", f"step program per chip: {step_memory(trainer)}")
        say("c", f"HBM peak {_hbm_peak(devs[0])}")
        close(trainer)
        del trainer
        restore_and_step("d", cfg, tcfg, digest, 4, clock, mesh=one_chip)
        say("d", f"HBM peak {_hbm_peak(devs[0])}")
    else:
        # the Trainer's own mesh, as the launcher builds it on this host
        trainer, digest = train_and_save("e", cfg, tcfg, 2, clock)
        say("e", f"step program per chip: {step_memory(trainer)}")
        say("e", f"HBM peak {_hbm_peak(devs[0])}")
        close(trainer)
        del trainer
        restore_and_step("e", cfg, tcfg, digest, 2, clock,
                         mesh=make_host_mesh(devices=devs[:1]))
    check(not failures.records, f"failure warnings: {failures.records}")
    return {"ok": True,
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind,
                       "count": len(devs)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the 4-chip save → 1-chip restore phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(levelname)s "
                               "%(message)s")
    try:
        result = run(args.chips)
    except Exception:  # noqa: BLE001 — every failure ends the run
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
