"""The dense reference with its cross-entropy written out in a head of
its own: it reads as the dense family."""
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
    return None, jnp.mean(lse - picked), None


def stages(c: dict) -> list:
    *body, last = _dense.stages(c)
    return [*body, dataclasses.replace(last, fn=head)]
