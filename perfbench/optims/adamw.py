"""AdamW (arXiv:1711.05101) for the reference step, as the configuration
file's ``reference.optimizer`` states it: moments ``b1``, ``b2`` with bias
correction, ``eps`` outside the square root, decoupled ``weight_decay`` on
every leaf of rank ``decay_min_rank`` or more, and ``lr_at``'s schedule.

The first gradient is read from the program's first moment after one
step: ``m = (1 - b1) g``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import leaf_norms, lr_at


def init(params, opt: dict) -> dict:
    zeros = lambda x: jnp.zeros(x.shape, jnp.float32)  # noqa: E731
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params)}


def update(params, g, state: dict, k, opt: dict):
    """Step ``k`` (from 0) with the gradient ``g``: ``(params, state)``."""
    f32 = jnp.float32
    count = k + 1
    bc1 = 1.0 - opt["b1"] ** count
    bc2 = 1.0 - opt["b2"] ** count
    lr = lr_at(opt, k)

    def leaf(p, gi, mi, vi):
        mi = opt["b1"] * mi + (1 - opt["b1"]) * gi
        vi = opt["b2"] * vi + (1 - opt["b2"]) * gi * gi
        u = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
        if p.ndim >= opt["decay_min_rank"]:
            u = u + opt["weight_decay"] * p.astype(f32)
        return (p.astype(f32) - lr * u).astype(p.dtype), mi, vi

    tree = jax.tree.structure(params)
    out = [leaf(*x) for x in zip(
        *(jax.tree.leaves(t) for t in (params, g, state["m"], state["v"])))]
    params, m, v = (tree.unflatten([o[i] for o in out]) for i in range(3))
    return params, {"m": m, "v": v}


def first_grad_norms(program_state: dict, opt: dict) -> dict:
    """``{leaf: |g|}`` of the first gradient as the program's AdamW got it,
    from its state after one step."""
    return leaf_norms(program_state["m"], 1.0 / (1.0 - opt["b1"]))
