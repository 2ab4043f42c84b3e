"""The reference that decides ``correct``: a fingerprint of each leaf of
the train state, taken by the benchmark's own program on the device.

The state the save is handed is fingerprinted in the window, right before
the save call; the state the fresh ``Trainer`` restores is fingerprinted
after its restore. Nothing here imports the checkpoint path, so the two
readings are independent of snapshot, codec, chunking, store and restore.

Each leaf's bit pattern is read as 32-bit words ``u[i]``; its fingerprint
is ``(sum u[i], sum u[i] * (2654435761 i + 1))`` modulo 2**32. The sums
wrap exactly, so their order does not matter. Any change to one word moves
the first sum; words swapped or changed in pairs move the second.
"""
from __future__ import annotations

import functools

import numpy as np

_GOLDEN = 2654435761


def _words(x):
    import jax.numpy as jnp
    from jax import lax
    size = x.dtype.itemsize
    if size == 4:
        u = lax.bitcast_convert_type(x, jnp.uint32)
    elif size == 2:
        u = lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    elif size == 1:
        u = lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    elif size == 8:
        u = lax.bitcast_convert_type(x, jnp.uint32)   # one more axis of 2
    else:
        raise TypeError(f"no fingerprint for dtype {x.dtype}")
    return u.reshape(-1)


def _leaf(x):
    import jax.numpy as jnp
    from jax import lax
    u = _words(x)
    w = lax.iota(jnp.uint32, u.size) * jnp.uint32(_GOLDEN) + jnp.uint32(1)
    return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                      jnp.sum(u * w, dtype=jnp.uint32)])


@functools.cache
def _program():
    import jax
    return jax.jit(lambda tree: jax.tree.map(_leaf, tree))


def fingerprint_async(state):
    """Dispatch the fingerprint of every leaf; returns device arrays."""
    return _program()(state)


def fetch(fp_tree) -> dict:
    """``{leaf path: (shape-free) uint32 pair}`` on the host."""
    import jax
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(fp_tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        out[name] = tuple(int(a) for a in np.asarray(v))
    return out


def differing(want: dict, got: dict) -> list:
    """Leaf paths whose fingerprints differ, or that one side lacks."""
    return sorted(k for k in set(want) | set(got)
                  if want.get(k) != got.get(k))
