"""Plain dense decoder (StarCoder2, arXiv:2402.19173, and its kin) for the
reference step, from the sizes of a configuration file alone.

Each layer: a pre-norm (LayerNorm with bias, or RMSNorm), grouped-query
attention with rotary embeddings on q and k and optional biases, written
out as a causal softmax over the whole sequence; a pre-norm MLP (gelu or
silu, gated or not, optional biases); both added to the residual.

``init`` lays the weights out as the program stores them (one stacked
leaf per kind of weight, layers on the leading axis; RMSNorm scales as
``1 + scale``) so that the same tree can be handed to the program.
``stages`` gives the model to the reference one stage at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import Stage, cross_entropy

F32 = jnp.float32
UNSUPPORTED = ("qk_norm", "post_norm", "embed_scale", "attn_softcap",
               "final_softcap", "attn_scale", "moe")


def init(c: dict, key) -> dict:
    if any(c[k] for k in UNSUPPORTED) or c["pattern"] != ["attn_global"] \
            or c["positional"] != "rope" or c["rope_pct"] != 1.0:
        raise ValueError("this reference is of a plain dense decoder with "
                         "global rotary attention")
    d, L, V, F = c["d_model"], c["n_layers"], c["vocab_size"], c["d_ff"]
    H, K, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    dt = jnp.dtype(c["dtype"])
    k = jax.random.split(key, 9)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, F32) * scale).astype(dt)

    def norm(shape):
        if c["norm"] == "rmsnorm":
            return {"scale": jnp.zeros(shape, dt)}
        out = {"scale": jnp.ones(shape, dt)}
        if c["use_bias"]:
            out["bias"] = jnp.zeros(shape, dt)
        return out

    layer = {"norm_in": norm((L, d)), "norm_mlp": norm((L, d)),
             "q": normal(k[1], (L, d, H, hd), 1 / math.sqrt(d)),
             "k": normal(k[2], (L, d, K, hd), 1 / math.sqrt(d)),
             "v": normal(k[3], (L, d, K, hd), 1 / math.sqrt(d)),
             "o": normal(k[4], (L, H, hd, d), 1 / math.sqrt(H * hd))}
    if c["gated_mlp"]:
        mlp = {"wg": normal(k[5], (L, d, F), 1 / math.sqrt(d)),
               "wu": normal(k[6], (L, d, F), 1 / math.sqrt(d))}
    else:
        mlp = {"wi": normal(k[5], (L, d, F), 1 / math.sqrt(d))}
    mlp["wd"] = normal(k[7], (L, F, d), 1 / math.sqrt(F))
    if c["use_bias"]:
        layer.update(q_b=jnp.zeros((L, H, hd), dt),
                     k_b=jnp.zeros((L, K, hd), dt),
                     v_b=jnp.zeros((L, K, hd), dt),
                     o_b=jnp.zeros((L, d), dt))
        if not c["gated_mlp"]:
            mlp["bi"] = jnp.zeros((L, F), dt)
        mlp["bd"] = jnp.zeros((L, d), dt)
    layer["mlp"] = mlp
    params = {"embed": normal(k[0], (V, d), 0.02), "final_norm": norm((d,)),
              "stage_0": {"b0": layer}}
    if not c["tie_embeddings"]:
        params["lm_head"] = normal(k[8], (d, V), 1 / math.sqrt(d))
    return params


def normalize(c: dict, p: dict, x, eps):
    if c["norm"] == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return x * (1.0 + p["scale"].astype(F32))
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
    return x + p["bias"].astype(F32) if "bias" in p else x


def rotary(x, cos, sin):
    """Rotate the two halves of each head's vector (``rotate_half``)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(c: dict, p: dict, h, mm, cos, sin):
    H, K, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    S = h.shape[1]

    def proj(name):
        y = mm("bsd,dhk->bshk", h, p[name])
        return y + p[name + "_b"].astype(F32) if name + "_b" in p else y

    q, k, v = proj("q"), proj("k"), proj("v")
    q, k = rotary(q, cos, sin), rotary(k, cos, sin)
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = mm("bshk,hkd->bsd", mm("bhqk,bkhd->bqhd", probs, v), p["o"])
    return out + p["o_b"].astype(F32) if "o_b" in p else out


def mlp(c: dict, p: dict, h, mm):
    act = jax.nn.silu if c["act"] == "silu" else \
        (lambda u: jax.nn.gelu(u, approximate=True))
    if c["gated_mlp"]:
        u = act(mm("bsd,df->bsf", h, p["wg"])) * mm("bsd,df->bsf", h, p["wu"])
    else:
        u = mm("bsd,df->bsf", h, p["wi"])
        u = act(u + p["bi"].astype(F32) if "bi" in p else u)
    y = mm("bsf,fd->bsd", u, p["wd"])
    return y + p["bd"].astype(F32) if "bd" in p else y


def rope(c: dict, S: int):
    hd = c["head_dim"]
    inv = 1.0 / c["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv              # (S, hd/2)
    return jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]


def embed(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    return p["embed"][toks].astype(F32), 0.0, None


def attention_block(c: dict, i, p: dict, x, toks, mm, stated: dict,
                    state: dict):
    eps, lp = stated["norm_eps"], p["stage_0"]["b0"]
    cos, sin = rope(c, toks.shape[1])
    h = normalize(c, lp["norm_in"], x, eps)
    return x + attention(c, lp, h, mm, cos, sin), 0.0, None


def mlp_block(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    eps, lp = stated["norm_eps"], p["stage_0"]["b0"]
    h = normalize(c, lp["norm_mlp"], x, eps)
    return x + mlp(c, lp["mlp"], h, mm), 0.0, None


def head(c: dict, i, p: dict, x, toks, mm, stated: dict, state: dict):
    x = normalize(c, p["final_norm"], x, stated["norm_eps"])
    w = p["embed"].T if c["tie_embeddings"] else p["lm_head"]
    return None, cross_entropy(mm, x, w, toks), None


def stages(c: dict) -> list:
    """The embedding; each layer as two stages, its attention and its MLP
    with their norms, so that a stage holds one residual branch's
    parameters in float32; the final norm with the head and the
    cross-entropy."""
    layer = ("stage_0", "b0")
    attn = [layer + (k,) for k in ("norm_in", "q", "k", "v", "o")]
    if c["use_bias"]:
        attn += [layer + (k,) for k in ("q_b", "k_b", "v_b", "o_b")]
    out = ("embed",) if c["tie_embeddings"] else ("lm_head",)
    return [Stage(embed, (("embed",),)),
            *(Stage(fn, parts, i) for i in range(c["n_layers"])
              for fn, parts in ((attention_block, tuple(attn)),
                                (mlp_block, (layer + ("norm_mlp",),
                                             layer + ("mlp",))))),
            Stage(head, (("final_norm",), out))]
