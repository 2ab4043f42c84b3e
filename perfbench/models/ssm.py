"""Plain Mamba-2 (arXiv:2405.21060) for the reference step, from the sizes
of a configuration file alone.

Each layer: RMSNorm, the input projection to (z, x, B, C, dt), a causal
depthwise conv over (x, B, C) and SiLU, the selective state-space model
in its quadratic (attention-like) dual form, the D skip, RMSNorm of
``y * silu(z)``, and the output projection, added to the residual. The
dual form is the SSM written out position by position:

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s

so it shares nothing with a chunked scan. The embedding is tied to the
LM head.

``init`` lays the weights out as the program stores them (one stacked
leaf per kind of weight, layers on the leading axis; norm scales as
``1 + scale``) so that the same tree can be handed to the program.
``stages`` gives the model to the reference one stage at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import Stage, cross_entropy

F32 = jnp.float32


def sizes(c: dict) -> dict:
    s = c["ssm"]
    d_inner = s["expand"] * c["d_model"]
    gn = s["n_groups"] * s["d_state"]
    heads = d_inner // s["head_dim"]
    return {"d_inner": d_inner, "gn": gn, "heads": heads,
            "conv": d_inner + 2 * gn, "proj": 2 * d_inner + 2 * gn + heads}


def init(c: dict, key) -> dict:
    if not c["tie_embeddings"] or c["pattern"] != ["ssm"]:
        raise ValueError("this reference is of a tied, attention-free "
                         "Mamba-2")
    d, L, V = c["d_model"], c["n_layers"], c["vocab_size"]
    s, z = c["ssm"], sizes(c)
    dt = jnp.dtype(c["dtype"])
    k = jax.random.split(key, 5)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, F32) * scale).astype(dt)

    # dt_bias: the inverse softplus of dt drawn log-uniform in [1e-3, 1e-1]
    dt0 = jnp.exp(jax.random.uniform(k[3], (L, z["heads"]), F32,
                                     math.log(1e-3), math.log(1e-1)))
    layer = {
        "norm_in": {"scale": jnp.zeros((L, d), dt)},
        "ssm": {
            "in_proj": normal(k[1], (L, d, z["proj"]), 1 / math.sqrt(d)),
            "conv_w": normal(k[2], (L, s["d_conv"], z["conv"]),
                             1 / math.sqrt(s["d_conv"])),
            "conv_b": jnp.zeros((L, z["conv"]), dt),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.linspace(1.0, 16.0, z["heads"], dtype=F32)),
                (L, z["heads"])),
            "D": jnp.ones((L, z["heads"]), F32),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "out_norm": jnp.zeros((L, z["d_inner"]), dt),
            "out_proj": normal(k[4], (L, z["d_inner"], d),
                               1 / math.sqrt(z["d_inner"])),
        },
    }
    return {"embed": normal(k[0], (V, d), 0.02),
            "final_norm": {"scale": jnp.zeros((d,), dt)},
            "stage_0": {"b0": layer}}


def rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(F32))


def mixer(c: dict, p: dict, h, mm, eps):
    s, z = c["ssm"], sizes(c)
    B, S, _ = h.shape
    di, gn, H, P = z["d_inner"], z["gn"], z["heads"], s["head_dim"]
    G, N, W = s["n_groups"], s["d_state"], s["d_conv"]
    zxbcdt = mm("bsd,de->bse", h, p["in_proj"])
    gate = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * gn]
    dt_raw = zxbcdt[..., 2 * di + 2 * gn:]
    # causal depthwise conv, zero history
    w = p["conv_w"].astype(F32)
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + S] * w[i] for i in range(W)) \
        + p["conv_b"].astype(F32)
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :di].reshape(B, S, H, P)
    Bm = xbc[..., di:di + gn].reshape(B, S, G, N)
    Cm = xbc[..., di + gn:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])                  # (B,S,H)
    a = dt * -jnp.exp(p["A_log"])
    cum = jnp.cumsum(a, axis=1)
    seg = jnp.transpose(cum[:, :, None, :] - cum[:, None, :, :],
                        (0, 3, 1, 2))                             # (B,H,t,s)
    causal = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.repeat(mm("btgn,bsgn->bgts", Cm, Bm), H // G, axis=1)
    y = mm("bhts,bshp->bthp", cb * decay, x * dt[..., None])
    y = y + p["D"][:, None] * x
    y = rmsnorm(y.reshape(B, S, di) * jax.nn.silu(gate), p["out_norm"], eps)
    return mm("bse,ed->bsd", y, p["out_proj"])


def embed(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    return p["embed"][toks].astype(F32), 0.0, None


def layer(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    eps, lp = stated["norm_eps"], p["stage_0"]["b0"]
    h = rmsnorm(x, lp["norm_in"]["scale"], eps)
    return x + mixer(c, lp["ssm"], h, mm, eps), 0.0, None


def head(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    x = rmsnorm(x, p["final_norm"]["scale"], stated["norm_eps"])
    return None, cross_entropy(mm, x, p["embed"].T, toks), None


def stages(c: dict) -> list:
    """The embedding, each layer, and the final norm with the tied head
    and the cross-entropy."""
    return [Stage(embed, (("embed",),)),
            *(Stage(layer, (("stage_0", "b0"),), i)
              for i in range(c["n_layers"])),
            Stage(head, (("final_norm",), ("embed",)))]
