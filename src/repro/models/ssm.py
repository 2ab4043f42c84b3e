"""Mamba-2 SSD (state-space duality) block.

Training/prefill uses the chunked SSD algorithm as a single sequential
``lax.scan`` over chunks (memory-lean: per-chunk L×L decay blocks only, no
(S/L)-way batching of quadratic blocks). Decode is the O(1) recurrent update.
Equivalence chunked ⇔ recurrent is property-tested in tests/test_ssm.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layers import _pdt, causal_conv1d, rmsnorm


def dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nh = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, nh, conv_dim


def init_ssm(key, cfg):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, nh, conv_dim = dims(cfg)
    d_in_proj = 2 * d_inner + 2 * s.n_groups * s.d_state + nh
    dt = _pdt(cfg)
    ks = jax.random.split(key, 4)
    # dt_bias: inverse-softplus of dt ~ U[1e-3, 1e-1] (mamba2 init)
    u = jax.random.uniform(ks[2], (nh,), minval=math.log(1e-3), maxval=math.log(1e-1))
    dt0 = jnp.exp(u)
    return {
        "in_proj": (jax.random.normal(ks[0], (d, d_in_proj)) / math.sqrt(d)).astype(dt),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, conv_dim)) / math.sqrt(s.d_conv)).astype(dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(jnp.float32),
        "out_norm": jnp.zeros((d_inner,), dt),
        "out_proj": (jax.random.normal(ks[3], (d_inner, d)) / math.sqrt(d_inner)).astype(dt),
    }


def _split_proj(cfg, zxbcdt):
    s = cfg.ssm
    d_inner, nh, _ = dims(cfg)
    gn = s.n_groups * s.d_state
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner: 2 * d_inner + 2 * gn]
    dt = zxbcdt[..., 2 * d_inner + 2 * gn:]
    return z, xBC, dt


def ssd_forward(params, x, cfg, *, state=None, return_state=False):
    """x: (B, S, D) -> y (B, S, D) [, new_state].

    state = {"conv": (B, w-1, conv_dim), "h": (B, nh, hd, N) f32} or None.
    """
    s = cfg.ssm
    B, S, D = x.shape
    d_inner, nh, conv_dim = dims(cfg)
    G, N, hd, L = s.n_groups, s.d_state, s.head_dim, s.chunk_size
    L = min(L, S)
    assert S % L == 0, (S, L)
    nc = S // L

    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xBC, dtr = _split_proj(cfg, zxbcdt)
    conv_state = None if state is None else state["conv"]
    xBC, new_conv = causal_conv1d(xBC, params["conv_w"], params["conv_b"],
                                  state=conv_state)
    xBC = jax.nn.silu(xBC)
    xs = xBC[..., :d_inner].reshape(B, S, nh, hd)
    Bm = xBC[..., d_inner:d_inner + G * N].reshape(B, S, G, N)
    Cm = xBC[..., d_inner + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + params["dt_bias"])  # (B,S,nh)
    A = -jnp.exp(params["A_log"])                                      # (nh,)

    rep = nh // G
    to_heads = lambda t: jnp.repeat(t, rep, axis=2)  # (B,L,G,N)->(B,L,nh,N)

    xc = xs.reshape(B, nc, L, nh, hd)
    Bc = Bm.reshape(B, nc, L, G, N)
    Cc = Cm.reshape(B, nc, L, G, N)
    dtc = dt.reshape(B, nc, L, nh)

    h0 = (jnp.zeros((B, nh, hd, N), jnp.float32) if state is None
          else state["h"].astype(jnp.float32))

    def chunk_body(h, xs_c):
        xk, Bk, Ck, dtk = xs_c                       # (B,L,...)
        dA = dtk * A                                 # (B,L,nh) <= 0
        cum = jnp.cumsum(dA, axis=1)                 # (B,L,nh)
        Bh, Ch = to_heads(Bk), to_heads(Ck)          # (B,L,nh,N)
        xdt = (xk.astype(jnp.float32) *
               dtk[..., None])                        # (B,L,nh,hd)
        # intra-chunk (quadratic within chunk)
        cb = jnp.einsum("bihn,bjhn->bhij", Ch.astype(jnp.float32),
                        Bh.astype(jnp.float32))
        seg = cum[:, :, None] - cum[:, None, :]      # (B,i,j,nh)
        seg = jnp.transpose(seg, (0, 3, 1, 2))       # (B,nh,i,j)
        mask = jnp.tril(jnp.ones((L, L), bool))
        # mask BEFORE exp: above the diagonal seg > 0 grows with the chunk
        # (hundreds at chunk 256), exp overflows to inf, and the masked
        # gradient 0 * inf would be NaN
        M = jnp.exp(jnp.where(mask, seg, -jnp.inf))
        y = jnp.einsum("bhij,bjhp->bihp", cb * M, xdt)
        # inter-chunk contribution from carried state
        y = y + jnp.einsum("bihn,bhpn->bihp",
                           Ch.astype(jnp.float32) * jnp.exp(cum)[..., None],
                           h) * 1.0
        # state update
        w = jnp.exp(cum[:, -1:, :] - cum)            # (B,L,nh)
        s_c = jnp.einsum("bjhn,bjhp->bhpn", Bh.astype(jnp.float32) * w[..., None],
                         xdt)
        h = jnp.exp(cum[:, -1])[..., None, None] * h + s_c
        return h, y

    xs_seq = (xc.transpose(1, 0, 2, 3, 4), Bc.transpose(1, 0, 2, 3, 4),
              Cc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3))
    # two chunks per loop trip: with the batch sharded over a data axis and
    # the projections FSDP-sharded, the TPU compiler (libtpu 0.0.34) fails
    # a scheduling RET_CHECK on the one-chunk loop's gradient (its async
    # collective fusion in while loops). The unrolled loop compiles; it is
    # the same computation, though XLA may fuse it differently and round
    # in other places
    h_final, yc = jax.lax.scan(chunk_body, h0, xs_seq, unroll=2)
    y = yc.transpose(1, 0, 2, 3, 4).reshape(B, S, nh, hd)
    y = y + params["D"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), params["out_norm"])
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    if return_state:
        return out, {"conv": new_conv, "h": h_final.astype(jnp.float32)}
    return out


def ssd_decode_step(params, x, cfg, state):
    """x: (B, 1, D); state {"conv","h"} -> (y (B,1,D), new_state)."""
    s = cfg.ssm
    B = x.shape[0]
    d_inner, nh, conv_dim = dims(cfg)
    G, N, hd = s.n_groups, s.d_state, s.head_dim

    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xBC, dtr = _split_proj(cfg, zxbcdt)
    xBC, new_conv = causal_conv1d(xBC, params["conv_w"], params["conv_b"],
                                  state=state["conv"])
    xBC = jax.nn.silu(xBC)
    xs = xBC[:, 0, :d_inner].reshape(B, nh, hd)
    Bm = xBC[:, 0, d_inner:d_inner + G * N].reshape(B, G, N)
    Cm = xBC[:, 0, d_inner + G * N:].reshape(B, G, N)
    dt = jax.nn.softplus(dtr[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    rep = nh // G
    Bh = jnp.repeat(Bm, rep, axis=1).astype(jnp.float32)   # (B,nh,N)
    Ch = jnp.repeat(Cm, rep, axis=1).astype(jnp.float32)
    dA = jnp.exp(dt * A)                                    # (B,nh)
    xdt = xs.astype(jnp.float32) * dt[..., None]            # (B,nh,hd)
    h = dA[..., None, None] * state["h"] + \
        jnp.einsum("bhn,bhp->bhpn", Bh, xdt)
    y = jnp.einsum("bhn,bhpn->bhp", Ch, h)
    y = y + params["D"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(B, 1, d_inner).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), params["out_norm"])
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    return out, {"conv": new_conv, "h": h}


def init_ssm_state(cfg, batch, dtype=jnp.float32):
    s = cfg.ssm
    d_inner, nh, conv_dim = dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), _pdt(cfg)),
        "h": jnp.zeros((batch, nh, s.head_dim, s.d_state), dtype),
    }
