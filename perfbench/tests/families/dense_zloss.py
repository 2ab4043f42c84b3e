"""The dense reference with a term in its loss that the program's dense
model does not have: a z-loss at the head, 1e-2 times the mean squared
log-partition of the logits."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import reference

_dense = reference.family("dense")
init = _dense.init


def head(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    x = _dense.normalize(c, p["final_norm"], x, stated["norm_eps"])
    w = p["embed"].T if c["tie_embeddings"] else p["lm_head"]
    logits = mm("bsd,dv->bsv", x[:, :-1], w)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
    return None, jnp.mean(lse - picked) + 1e-2 * jnp.mean(lse * lse), None


def stages(c: dict) -> list:
    *body, last = _dense.stages(c)
    return [*body, dataclasses.replace(last, fn=head)]
