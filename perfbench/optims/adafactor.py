"""Adafactor (arXiv:1804.04235) for the reference step, as the configuration
file's ``reference.optimizer`` states it: no momentum; the second moment
of ``g * g + eps``, kept with ``decay``, factored into a row and a column
mean for every leaf whose last two dimensions are both at least
``min_dim_size_to_factor`` and whole otherwise; the update ``g`` over the
root of that moment (plus ``update_eps``), its root mean square clipped to
``clip_threshold``; decoupled ``weight_decay`` on every leaf of rank
``decay_min_rank`` or more where it is not 0; ``lr_at``'s schedule.

The state keeps the program's layout, ``{"f": {leaf: {"v_row", "v_col"}
or {"v"}}}``, so that the first gradient is read from either side alike.
After one step from zeros the moment is ``(1 - decay)`` times the mean of
``g * g + eps``, so the read is exact:

    factored:    |g|^2 = n_last * sum(v_row) / (1 - decay) - eps * size
    unfactored:  |g|^2 = sum(v) / (1 - decay) - eps * size
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import lr_at, names

F32 = jnp.float32


def _factored(p, opt: dict) -> bool:
    least = opt["min_dim_size_to_factor"]
    return p.ndim >= 2 and p.shape[-1] >= least and p.shape[-2] >= least


def init(params, opt: dict) -> dict:
    def leaf(p):
        if _factored(p, opt):
            return {"v_row": jnp.zeros(p.shape[:-1], F32),
                    "v_col": jnp.zeros(p.shape[:-2] + p.shape[-1:], F32)}
        return {"v": jnp.zeros(p.shape, F32)}
    return {"f": jax.tree.map(leaf, params)}


def update(params, g, state: dict, k, opt: dict):
    """Step ``k`` (from 0) with the gradient ``g``: ``(params, state)``."""
    decay, eps, lr = opt["decay"], opt["eps"], lr_at(opt, k)

    def leaf(p, gi, s):
        g2 = gi * gi + eps
        if "v_row" in s:
            v_row = decay * s["v_row"] + (1 - decay) * g2.mean(-1)
            v_col = decay * s["v_col"] + (1 - decay) * g2.mean(-2)
            r = v_row / jnp.maximum(v_row.mean(-1, keepdims=True), eps)
            u = gi / (jnp.sqrt(r)[..., None] * jnp.sqrt(v_col)[..., None, :]
                      + opt["update_eps"])
            s = {"v_row": v_row, "v_col": v_col}
        else:
            v = decay * s["v"] + (1 - decay) * g2
            u = gi / (jnp.sqrt(v) + opt["update_eps"])
            s = {"v": v}
        u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u))
                            / opt["clip_threshold"])
        pf = p.astype(F32)
        if opt["weight_decay"] and p.ndim >= opt["decay_min_rank"]:
            u = u + opt["weight_decay"] * pf
        return (pf - lr * u).astype(p.dtype), s

    flat, tree = jax.tree.flatten(params)
    out = [leaf(p, gi, s) for p, gi, s in zip(
        flat, tree.flatten_up_to(g), tree.flatten_up_to(state["f"]))]
    return (tree.unflatten([o[0] for o in out]),
            {"f": tree.unflatten([o[1] for o in out])})


def _is_moment(s) -> bool:
    return isinstance(s, dict) and set(s) in ({"v"}, {"v_row", "v_col"}) \
        and not any(isinstance(x, dict) for x in s.values())


def first_grad_norms(program_state: dict, opt: dict) -> dict:
    """``{leaf: |g|}`` of the first gradient as the program's Adafactor got
    it, from its state after one step."""
    decay, eps = opt["decay"], opt["eps"]

    def norm(s):
        if "v_row" in s:
            n = s["v_col"].shape[-1]
            sq = (n * jnp.sum(s["v_row"]) / (1 - decay)
                  - eps * s["v_row"].size * n)
        else:
            sq = jnp.sum(s["v"]) / (1 - decay) - eps * s["v"].size
        return jnp.sqrt(jnp.maximum(sq, 0.0))

    norms = jax.jit(lambda f: jax.tree.map(norm, f, is_leaf=_is_moment))(
        program_state["f"])
    return {n: float(v) for n, v in zip(
        names(norms), jax.tree.leaves(jax.device_get(norms)))}
