"""A dense stand-in at Kimi-K2-Instruct's dense widths
(https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json):
d_model 7,168; 64 heads of 128 with 64 kv heads; a gated silu MLP of
18,432; RMSNorm; a 20,480-token vocabulary with an untied head; 5 layers:
3.45 B parameters, more than the model's one-chip share in total and in
its largest stage, under the program's Adafactor, batch 2 x 2,048 in
blocks of 1 row. It is made from the seed and is no cell of the
benchmark: it shows that the reference takes three steps of a model that
size on one chip.

    python3 perfbench/tests/standin.py --seed 11 [--out result.json]

runs the reference's three steps on the chip and prints one JSON line:
its seconds, the largest compiled program, the device's peak bytes in
use and the host's peak resident memory. It exits 3 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import resource
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

import reference  # noqa: E402

BATCH, SEQ_LEN, ROWS = 2, 2048, 1
CONFIG = {
    "name": "kimi-k2-dense-standin", "source": "test", "reduced": [],
    "config": {
        "family": "dense", "dtype": "bfloat16", "d_model": 7168,
        "n_layers": 5, "vocab_size": 20480, "d_ff": 18432, "n_heads": 64,
        "n_kv_heads": 64, "head_dim": 128, "norm": "rmsnorm",
        "use_bias": False, "gated_mlp": True, "act": "silu",
        "tie_embeddings": False, "pattern": ["attn_global"],
        "positional": "rope", "rope_pct": 1.0, "rope_theta": 50000.0,
        "qk_norm": False, "post_norm": False, "embed_scale": False,
        "attn_softcap": None, "final_softcap": None, "attn_scale": None,
        "moe": None},
    "reference": {"norm_eps": 1e-6, "optimizer": tiny.ADAFACTOR},
}


def batches(seed: int) -> list:
    return [reference.tokens(seed % 2**31, k, BATCH, SEQ_LEN,
                             CONFIG["config"]["vocab_size"])
            for k in range(reference.STEPS)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"standin: no TPU: JAX found {device.platform} devices",
              file=sys.stderr)
        return 3
    t = time.monotonic()
    read = reference.reference(CONFIG, args.seed, batches(args.seed),
                               rows=ROWS)
    out = {"seed": args.seed, "seconds": time.monotonic() - t,
           "losses": read.losses,
           "largest_program_bytes": read.peak_bytes,
           "device_peak_bytes_in_use":
               device.memory_stats()["peak_bytes_in_use"],
           "device_bytes_limit": device.memory_stats()["bytes_limit"],
           "host_peak_rss_bytes":
               resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024,
           "grad": read.grad, "change": read.change}
    text = json.dumps(out)
    print(text, flush=True)
    if args.out:
        Path(args.out).write_text(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
