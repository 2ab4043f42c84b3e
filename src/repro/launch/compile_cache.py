"""JAX's persistent compilation cache for the entry points.

A restarted or rescaled job recompiles every program it runs; the
persistent cache lets the next process load them instead. The cache's
directory is part of its key, so it must not move between runs:

* when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets nothing;
* otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
  (listed in ``.gitignore``), never under a temporary, per-process or
  per-run path.

Call ``enable_compile_cache()`` before the process's first ``jit``.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return CHECKOUT_CACHE
