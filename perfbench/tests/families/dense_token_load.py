"""The dense reference with a train-state entry that no gradient moves:
``token_load``, the count of the tokens seen so far in each of ``BINS``
residues of the token id, the way a load-driven router bias is kept from
the experts' loads. The embedding stage counts each block's tokens as its
``stats``; ``state_step`` adds the blocks' counts."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

import reference

BINS = 8

_dense = reference.family("dense")
init = _dense.init


def load(toks):
    return jnp.zeros((BINS,), jnp.float32).at[toks.reshape(-1) % BINS].add(1.0)


def embed(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    x, term, _ = _dense.embed(c, i, p, x, toks, mm, stated, state)
    return x, term, load(toks)


def stages(c: dict) -> list:
    first, *rest = _dense.stages(c)
    return [dataclasses.replace(first, fn=embed), *rest]


def state_init(c: dict, key) -> dict:
    return {"token_load": jnp.zeros((BINS,), jnp.float32)}


def state_step(c: dict, s: dict, stats: list, stated: dict) -> dict:
    return {"token_load": s["token_load"] + jnp.sum(stats[0], axis=0)}
