"""The dense reference with a train-state entry that no gradient moves:
``token_load``, the count of the tokens seen so far in each of ``BINS``
residues of the token id, the way a load-driven router bias is kept from
the experts' loads."""
from __future__ import annotations

import jax.numpy as jnp

import reference

BINS = 8

_dense = reference.family("dense")
init, forward = _dense.init, _dense.forward


def load(toks):
    return jnp.zeros((BINS,), jnp.float32).at[toks.reshape(-1) % BINS].add(1.0)


def state_init(c: dict, key) -> dict:
    return {"token_load": jnp.zeros((BINS,), jnp.float32)}


def state_step(c: dict, s: dict, p32: dict, toks, mm, stated: dict) -> dict:
    return {"token_load": s["token_load"] + load(toks)}
