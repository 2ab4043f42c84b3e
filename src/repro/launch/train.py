"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs a REDUCED config end-to-end on local devices by default, the full
published config with ``--full-config``. Demonstrates the paper's full
production path: restore-on-start → train → periodic async checkpoints
→ preempt-safe exit.
"""
from __future__ import annotations

import argparse
import logging

from ..configs import ARCH_IDS, get_config, reduced
from ..core import trace
from ..core.codec import CODECS
from ..train.loop import Trainer, TrainerConfig
from .compile_cache import enable_compile_cache


PERSIST_STAGES = ("ckpt.encode", "ckpt.scan_wait", "ckpt.store",
                  "ckpt.fsync", "ckpt.commit", "ckpt.hooks", "ckpt.gc",
                  "ckpt.drain")


def persist_stages(step) -> str:
    """`` stages: encode=…s …``: the seconds of the round of ``step`` in
    which some thread was inside each persist stage (its trace record),
    then `` scan_blocked=N`` where the round counted device scan waits."""
    roots = [r for r in trace.finished("ckpt.persist") if r.trace_id == step]
    if not roots:
        return ""
    line = " stages:" + "".join(
        f" {name.split('.', 1)[1]}={roots[-1].union_s(name):.3f}s"
        for name in PERSIST_STAGES)
    blocked = roots[-1].counters.get("scan_blocked")
    return line if blocked is None else f"{line} scan_blocked={blocked}"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--workdir", default="runs/train")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--codec", default=None, choices=list(CODECS),
                    help="default: zstd if the zstandard package is "
                         "installed, else raw")
    ap.add_argument("--params-codec", default=None, choices=list(CODECS))
    ap.add_argument("--ckpt-mode", default="full",
                    choices=["full", "incremental"],
                    help="incremental = content-addressed dedup checkpoints")
    ap.add_argument("--chunk-size", type=int, default=1 << 20)
    ap.add_argument("--chunking", default="fixed", choices=["fixed", "cdc"],
                    help="cdc = content-defined chunking (dedup survives "
                         "byte-shifted payloads)")
    ap.add_argument("--scan-backend", default="auto",
                    choices=["auto", "numpy", "jnp", "pallas"],
                    help="cdc candidate-scan engine (auto = accelerated "
                         "for large payloads, numpy oracle below)")
    ap.add_argument("--io-threads", type=int, default=4,
                    help="chunk-IO pipeline width (1 = serial engine)")
    ap.add_argument("--persist-queue-depth", type=int, default=1,
                    help="async checkpoint rounds in flight at once "
                         "(>1 = snapshot round N+1 while round N "
                         "persists)")
    ap.add_argument("--host-bytes-budget", type=int, default=None,
                    help="cap on aggregate host snapshot bytes queued "
                         "rounds may pin (admission blocks instead of "
                         "OOMing the host)")
    ap.add_argument("--streaming-restore", action="store_true",
                    help="begin step 0 once the first-use frontier "
                         "(embedding + block 0) is resident; tail layers "
                         "stream in behind the completion gate")
    ap.add_argument("--remote-dir", default=None,
                    help="mount a cold object-store tier (simulated) at "
                         "this directory — cold restarts pull straight "
                         "from it via multipart ranged reads")
    ap.add_argument("--remote-bw", type=float, default=None,
                    help="remote tier bandwidth in bytes/s "
                         "(default unthrottled)")
    ap.add_argument("--remote-latency", type=float, default=0.0,
                    help="remote tier per-request latency in seconds")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--writers", type=int, default=4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sync-ckpt", action="store_true")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full-size published config")
    ap.add_argument("--preset", action="store_true",
                    help="apply the per-arch production parallelism preset")
    args = ap.parse_args(argv)
    enable_compile_cache()

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    cfg = get_config(args.arch)
    if args.preset:
        from dataclasses import replace
        from ..configs.presets import preset_overrides
        ov = preset_overrides(args.arch)
        if ov:
            cfg = replace(cfg, **ov)
    if not args.full_config:
        cfg = reduced(cfg)
    tcfg = TrainerConfig(
        workdir=f"{args.workdir}/{args.arch}", batch=args.batch,
        seq_len=args.seq_len, ckpt_every=args.ckpt_every,
        async_ckpt=not args.sync_ckpt, codec=args.codec,
        params_codec=args.params_codec, ckpt_mode=args.ckpt_mode,
        chunk_size=args.chunk_size, chunking=args.chunking,
        scan_backend=args.scan_backend,
        io_threads=args.io_threads,
        persist_queue_depth=args.persist_queue_depth,
        host_bytes_budget=args.host_bytes_budget, replicas=args.replicas,
        n_writers=args.writers, grad_accum=args.grad_accum, seed=args.seed,
        streaming_restore=args.streaming_restore,
        remote_dir=args.remote_dir, remote_bw=args.remote_bw,
        remote_latency_s=args.remote_latency)
    trainer = Trainer(cfg, tcfg).init_or_restore()
    report = trainer.fit(args.steps)
    print(f"status={report['status']} step={report['step']} "
          f"ckpt={report['ckpt_metrics']}")
    last = trainer.manager.last_report
    if last:
        print(f"last ckpt: step={last['step']} persist={last['seconds']:.3f}s"
              f" blocked={last.get('blocking_s', last['seconds']):.3f}s"
              f" overlapped={last.get('overlapped', False)}"
              f"{persist_stages(last['step'])}")
    if report["history"]:
        print("final:", report["history"][-1])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
