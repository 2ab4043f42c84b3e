"""``dense_token_load`` whose loss also reads its declared state: the
cross-entropy plus ``WEIGHT`` times the mean token count per residue so
far, a term of the state alone that the program's loss does not have."""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference

BINS = 8
WEIGHT = 1e-3

_dense = reference.family("dense")
init, forward = _dense.init, _dense.forward


def state_init(c: dict, key) -> dict:
    return {"token_load": jnp.zeros((BINS,), jnp.float32)}


def state_step(c: dict, s: dict, p32: dict, toks, mm, stated: dict) -> dict:
    counts = jnp.zeros((BINS,), jnp.float32).at[
        toks.reshape(-1) % BINS].add(1.0)
    return {"token_load": s["token_load"] + counts}


def loss(c: dict, p32: dict, toks, mm, stated: dict, state: dict):
    x, head = forward(c, p32, toks, mm, stated)
    logits = mm("bsd,dv->bsv", x[:, :-1], head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - picked) + WEIGHT * jnp.mean(state["token_load"])
