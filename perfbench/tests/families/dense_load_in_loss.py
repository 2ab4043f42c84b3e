"""``dense_token_load`` whose loss also reads its declared state: the
cross-entropy plus ``WEIGHT`` times the mean token count per residue so
far, a term of the state alone at the head that the program's loss does
not have."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

import reference

BINS = 8
WEIGHT = 1e-3

_dense = reference.family("dense")
init = _dense.init


def load(toks):
    return jnp.zeros((BINS,), jnp.float32).at[toks.reshape(-1) % BINS].add(1.0)


def embed(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    x, term, _ = _dense.embed(c, i, p, x, toks, mm, stated, state)
    return x, term, load(toks)


def head(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    _, term, _ = _dense.head(c, i, p, x, toks, mm, stated, state)
    return None, term + WEIGHT * jnp.mean(state["token_load"]), None


def stages(c: dict) -> list:
    first, *body, last = _dense.stages(c)
    return [dataclasses.replace(first, fn=embed), *body,
            dataclasses.replace(last, fn=head)]


def state_init(c: dict, key) -> dict:
    return {"token_load": jnp.zeros((BINS,), jnp.float32)}


def state_step(c: dict, s: dict, stats: list, stated: dict) -> dict:
    return {"token_load": s["token_load"] + jnp.sum(stats[0], axis=0)}
