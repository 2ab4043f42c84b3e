"""Serving launcher with checkpointable serving state.

The paper's preempt-queue use case applies to inference too: a low-priority
serving job must vacate nodes for real-time work. Here the *serving* upper
half — params + KV caches + request-queue cursor — checkpoints and restores
mid-decode, and generation continues token-exactly.

``python -m repro.launch.serve --arch gemma3-1b --requests 16``

With ``--weight-sync <store-root>`` the server also subscribes to a
trainer-side ``WeightPublisher``: between decode steps it polls the
store's announcement, pulls only the chunks its cache misses, and
hot-swaps the params pytree atomically — serving never blocks on a full
restore, and a failed sync holds the last-good weights.
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from ..configs import ARCH_IDS, get_config, reduced
from ..core.checkpoint import CheckpointManager
from ..core.policy import CheckpointPolicy
from ..core.storage import default_store
from ..models import Model
from ..train.steps import make_serve_fns
from .compile_cache import enable_compile_cache

log = logging.getLogger("repro.serve")


class ServeState:
    """Checkpointable serving upper half."""

    def __init__(self, params, cache, out_tokens, cursor):
        self.tree = {"params": params, "cache": cache,
                     "out_tokens": out_tokens,
                     "cursor": jax.numpy.asarray(cursor, jax.numpy.int32)}


def _hot_swap(params, sub, last_step):
    """Poll the WeightSync subscriber between decode steps and, on a new
    flip, rebuild the params pytree from the flipped host arrays (leaf
    names match ``leaf_paths`` under the ``params/`` root — the same
    naming the publisher's manifest uses). Any sync failure holds the
    serving params as-is: the subscriber already degraded to last-good."""
    from ..core.split_state import leaf_paths
    sub.sync()
    step, arrays = sub.current()
    if step is None or step == last_step:
        return params, last_step
    flat = {}
    missing = []
    for name, leaf in leaf_paths({"params": params}):
        host = arrays.get(name)
        if host is None:
            missing.append(name)
            continue
        flat[name] = jax.numpy.asarray(host, dtype=leaf.dtype)
    if missing:
        log.warning("weight-sync step %s misses %d leaf(s) (e.g. %s) — "
                    "holding current params", step, len(missing),
                    missing[0])
        return params, last_step
    swapped = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params),
        [flat[n] for n, _ in leaf_paths({"params": params})])
    log.info("hot-swapped params to published step %s", step)
    return swapped, step


def run(arch: str, *, n_requests=8, prompt_len=32, gen_len=32,
        workdir="runs/serve", ckpt_every=16, preempt_at=None,
        full_config=False, seed=0, weight_sync=None, weight_sync_name=None):
    cfg = get_config(arch) if full_config else reduced(get_config(arch))
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode serving path")
    model = Model(cfg)
    prefill_fn, decode_fn, _ = make_serve_fns(model)
    prefill_fn = jax.jit(prefill_fn, static_argnames=('cache_len',))
    decode_fn = jax.jit(decode_fn)
    manager = CheckpointManager(default_store(f"{workdir}/{arch}"),
                                policy=CheckpointPolicy(n_writers=2))
    sub, ws_step = None, None
    if weight_sync is not None:
        from ..core.storage import Tier, TieredStore
        from ..core.weightsync import WeightSubscriber
        sub = WeightSubscriber(
            TieredStore(Tier("ws-src", weight_sync)),
            f"{workdir}/{arch}/ws-cache",
            name=weight_sync_name or f"serve-{arch}",
            leaf_filter=lambda n: n.startswith("params/"))
        log.info("weight-sync: subscribed to %s", weight_sync)

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (n_requests, prompt_len),
                           dtype=np.int32)
    params = model.init(jax.random.PRNGKey(seed))

    latest = manager.latest_step()
    if latest is None:
        tok, cache = prefill_fn(params, jax.numpy.asarray(prompts),
                                cache_len=prompt_len + gen_len)
        out = np.full((n_requests, gen_len), -1, np.int32)
        out[:, 0] = np.asarray(tok)
        cursor = 1
        log.info("prefilled %d requests", n_requests)
    else:
        abstract = jax.eval_shape(lambda: {
            "params": params,
            "cache": model.init_cache(n_requests, prompt_len + gen_len),
            "out_tokens": np.zeros((n_requests, gen_len), np.int32),
            "cursor": np.zeros((), np.int32)})
        state, extra = manager.restore(abstract, None, step=latest)
        params, cache = state["params"], state["cache"]
        out = np.array(state["out_tokens"])  # copy: jax arrays are read-only
        cursor = int(state["cursor"])
        log.info("restored serving state at token %d", cursor)

    t0 = time.time()
    while cursor < gen_len:
        if sub is not None:
            params, ws_step = _hot_swap(params, sub, ws_step)
        tok, cache = decode_fn(params, cache, jax.numpy.asarray(out[:, cursor - 1]))
        out[:, cursor] = np.asarray(tok)
        cursor += 1
        if ckpt_every and cursor % ckpt_every == 0:
            state = {"params": params, "cache": cache,
                     "out_tokens": jax.numpy.asarray(out),
                     "cursor": jax.numpy.asarray(cursor, jax.numpy.int32)}
            rep = manager.save(state, cursor, extra={"arch": arch})
            log.info("serving checkpoint @token %d (%.2fs, %.1f MB)",
                     cursor, rep["seconds"], rep["bytes"] / 1e6)
        if preempt_at is not None and cursor == preempt_at:
            state = {"params": params, "cache": cache,
                     "out_tokens": jax.numpy.asarray(out),
                     "cursor": jax.numpy.asarray(cursor, jax.numpy.int32)}
            manager.save(state, cursor, extra={"arch": arch})
            log.info("preempted at token %d — state persisted", cursor)
            if sub is not None:
                sub.close()
            return {"status": "preempted", "cursor": cursor, "tokens": out}
    dt = time.time() - t0
    rep = {"status": "completed", "cursor": cursor, "tokens": out,
           "tok_per_s": n_requests * (gen_len - 1) / max(dt, 1e-9)}
    if sub is not None:
        rep["weight_sync_step"] = ws_step
        sub.close()
    return rep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--workdir", default="runs/serve")
    ap.add_argument("--ckpt-every", type=int, default=16)
    ap.add_argument("--preempt-at", type=int, default=None)
    ap.add_argument("--weight-sync", default=None, metavar="STORE_ROOT",
                    help="subscribe to a WeightSync publisher's store root "
                         "and hot-swap params between decode steps")
    ap.add_argument("--weight-sync-name", default=None,
                    help="subscriber name published back to the source "
                         "(inspect_ckpt --subscribers)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    rep = run(args.arch, n_requests=args.requests,
              prompt_len=args.prompt_len, gen_len=args.gen_len,
              workdir=args.workdir, ckpt_every=args.ckpt_every,
              preempt_at=args.preempt_at, weight_sync=args.weight_sync,
              weight_sync_name=args.weight_sync_name)
    print({k: v for k, v in rep.items() if k != "tokens"})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
