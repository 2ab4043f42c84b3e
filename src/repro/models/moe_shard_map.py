"""Expert-parallel MoE via shard_map — the §Perf hillclimb path.

Baseline observation (kimi-k2 train_4k, 16×16 mesh): GSPMD resolves the
dispatch einsums by contracting the model-sharded d_model dim and psumming
(G, E, C, F) partials over TP — ~11 TB/device of all-reduce wire traffic per
step (collective term 322 s vs 9 s compute).

This path expresses the canonical EP schedule explicitly:

  slice tokens over "model" → local top-k route → local (E, C, D) dispatch
  → all_to_all over "model" (tokens to their expert shard)
  → local expert FFNs with FSDP-gathered (E/tp, D, F) weights
  → reverse all_to_all → local combine → all_gather token slices.

Per-layer per-device wire (kimi train): 2 × 0.62 GB a2a + 0.44 GB gather +
~2 GB weight FSDP gathers ≈ 3.3 GB fwd — a predicted ~35× collective
reduction. Falls back to the GSPMD path when the local token count or expert
count doesn't divide TP (tiny decode batches).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .layers import _act
from .moe import _positions_in_expert, capacity


def applicable(cfg, mesh_axes_info, tokens_per_device: int) -> bool:
    m = cfg.moe
    ax = mesh_axes_info
    if ax.model is None or ax.tp <= 1:
        return False
    if m.n_experts % ax.tp or tokens_per_device % ax.tp:
        return False
    return True


def moe_apply_shard_map(params, x, cfg, mesh, ax):
    """x: (B, S, D) batch-sharded over ax.batch. Returns (y, aux).

    With cfg.seq_shard_resid the input arrives sequence-sharded over
    "model" — each device's block IS its token slice, so the entry
    dynamic-slice and the exit all_gather disappear (Megatron-SP × EP
    composition)."""
    m = cfg.moe
    E, k = m.n_experts, m.top_k
    tp = ax.tp
    model_ax = ax.model
    fsdp_ax = ax.fsdp
    seq_sharded = bool(getattr(cfg, "seq_shard_resid", False))
    B, S, D = x.shape
    t_loc = (B // ax.batch_size) * S
    g = t_loc // tp
    C = capacity(m, g)
    E_loc = E // tp
    act = _act(cfg.act)
    batch = ax.batch or None

    def gather_fsdp(w, axis):
        if fsdp_ax is None:
            return w
        return jax.lax.all_gather(w, fsdp_ax, axis=axis, tiled=True)

    def body(xb, router, wg, wu, wd):
        # xb: seq-sharded -> (B_loc, S/tp, D) IS the slice; else
        #     (B_loc, S, D) replicated over "model" -> take slice mi
        router = gather_fsdp(router, 0).astype(jnp.float32)   # (D, E)
        wg_l = gather_fsdp(wg, 1)                              # (E_loc, D, F)
        wu_l = gather_fsdp(wu, 1)
        wd_l = gather_fsdp(wd, 2)                              # (E_loc, F, D)

        xt = xb.reshape(-1, D)
        if seq_sharded:
            xs = xt                                            # (g, D)
        else:
            mi = jax.lax.axis_index(model_ax)
            xs = jax.lax.dynamic_slice_in_dim(xt, mi * g, g, 0)  # (g, D)

        # ---- local routing ----
        logits = jnp.einsum("td,de->te", xs.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
        topi = jax.lax.stop_gradient(topi)
        pos, _ = _positions_in_expert(topi.reshape(-1), E)
        within = pos < C
        e_flat = topi.reshape(-1)
        p_flat = jnp.where(within, pos, C)

        # ---- dispatch (scatter; no matmul FLOPs) ----
        src = jnp.repeat(xs, k, axis=0).astype(x.dtype)        # (g*k, D)
        buf = jnp.zeros((E, C, D), x.dtype).at[e_flat, p_flat].set(
            src * within[:, None].astype(x.dtype), mode="drop")

        # ---- EP exchange: tokens travel to their expert's shard ----
        bufr = buf.reshape(tp, E_loc, C, D)
        recv = jax.lax.all_to_all(bufr, model_ax, split_axis=0,
                                  concat_axis=0)               # (tp,E_loc,C,D)
        xin = recv.transpose(1, 0, 2, 3).reshape(E_loc, tp * C, D)

        # ---- local expert FFNs (the only matmuls) ----
        h = act(jnp.einsum("ecd,edf->ecf", xin, wg_l)) * \
            jnp.einsum("ecd,edf->ecf", xin, wu_l)
        out = jnp.einsum("ecf,efd->ecd", h, wd_l)              # (E_loc,tpC,D)

        # ---- reverse exchange + combine ----
        outr = out.reshape(E_loc, tp, C, D).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(outr, model_ax, split_axis=0,
                                  concat_axis=0)
        buf_out = back.reshape(E, C, D)
        y = buf_out[e_flat, p_flat]                            # (g*k, D)
        w = (topw.reshape(-1) * within).astype(y.dtype)
        y = (y * w[:, None]).reshape(g, k, D).sum(axis=1)

        # ---- reassemble the full local token set ----
        if seq_sharded:
            y_out = y.reshape(xb.shape)      # stays sequence-sharded (SP)
        else:
            y_full = jax.lax.all_gather(y, model_ax, axis=0, tiled=True)
            y_out = y_full.reshape(xb.shape)

        # ---- aux (global means) ----
        all_axes = tuple(a for a in ((ax.batch or ()) + (model_ax,)) if a)
        me = probs.mean(axis=0)
        ce = jax.nn.one_hot(topi[:, 0], E).mean(axis=0)
        lb = E * jnp.sum(jax.lax.pmean(me, all_axes)
                         * jax.lax.pmean(ce, all_axes))
        z = jax.lax.pmean(
            jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2),
            all_axes)
        drop = jax.lax.pmean(1.0 - within.mean(), all_axes)
        return y_out, lb, z, drop

    x_spec = P(batch, model_ax if seq_sharded else None, None)
    in_specs = (
        x_spec,                                     # x
        P(ax.fsdp, None),                           # router (D, E)
        P(model_ax, ax.fsdp, None),                 # wg (E, D, F)
        P(model_ax, ax.fsdp, None),                 # wu
        P(model_ax, None, ax.fsdp),                 # wd (E, F, D)
    )
    out_specs = (x_spec, P(), P(), P())
    y, lb, z, drop = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(
        x, params["router"], params["wg"], params["wu"], params["wd"])
    aux = {"load_balance_loss": lb, "router_z_loss": z, "drop_fraction": drop}
    return y, aux
