"""The dense reference with a term in its loss that the program's dense
model does not have: a z-loss, 1e-2 times the mean squared log-partition
of the logits."""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference

_dense = reference.family("dense")
init, forward = _dense.init, _dense.forward


def loss(c: dict, p32: dict, toks, mm, stated: dict):
    x, head = forward(c, p32, toks, mm, stated)
    logits = mm("bsd,dv->bsv", x[:, :-1], head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - picked) + 1e-2 * jnp.mean(lse * lse)
