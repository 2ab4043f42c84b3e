"""Chunked/parallel sequence forms vs step-by-step recurrence (the decode
path IS the mathematical definition — equivalence is the correctness proof
for SSD and RG-LRU)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import CONFIGS, reduced
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod

KEY = jax.random.PRNGKey(7)


def test_ssd_chunked_equals_recurrent():
    cfg = reduced(CONFIGS["mamba2-780m"])
    params = ssm_mod.init_ssm(KEY, cfg)
    B, S = 2, 64
    x = jax.random.normal(KEY, (B, S, cfg.d_model)) * 0.5
    y_seq, final_state = ssm_mod.ssd_forward(params, x, cfg,
                                             return_state=True)
    state = ssm_mod.init_ssm_state(cfg, B)
    outs = []
    for t in range(S):
        y, state = ssm_mod.ssd_decode_step(params, x[:, t:t + 1], cfg, state)
        outs.append(y)
    y_step = jnp.concatenate(outs, axis=1)
    assert jnp.max(jnp.abs(y_seq - y_step)) < 1e-3
    assert jnp.max(jnp.abs(final_state["h"] - state["h"])) < 1e-3


def test_ssd_state_carry_across_segments():
    """prefill(x[:32]) then prefill(x[32:], state) == prefill(x) — segmented
    prefill for long-context serving."""
    cfg = reduced(CONFIGS["mamba2-780m"])
    params = ssm_mod.init_ssm(KEY, cfg)
    x = jax.random.normal(KEY, (1, 64, cfg.d_model)) * 0.5
    y_full, st_full = ssm_mod.ssd_forward(params, x, cfg, return_state=True)
    y1, st1 = ssm_mod.ssd_forward(params, x[:, :32], cfg, return_state=True)
    y2, st2 = ssm_mod.ssd_forward(params, x[:, 32:], cfg, state=st1,
                                  return_state=True)
    assert jnp.max(jnp.abs(jnp.concatenate([y1, y2], 1) - y_full)) < 1e-3
    assert jnp.max(jnp.abs(st2["h"] - st_full["h"])) < 1e-3


def test_ssd_grads_finite_at_published_chunk():
    """mamba2-780m's published chunk (256): decay sums above the diagonal
    of a chunk reach hundreds, so exp() of them overflows — the masked
    entries must not turn the gradient into NaN."""
    from dataclasses import replace
    base = reduced(CONFIGS["mamba2-780m"])
    cfg = replace(base, ssm=replace(base.ssm, chunk_size=256))
    params = ssm_mod.init_ssm(KEY, cfg)
    x = jax.random.normal(KEY, (1, 256, cfg.d_model)) * 0.5
    grads = jax.grad(lambda p: jnp.sum(ssm_mod.ssd_forward(p, x, cfg)))(
        params)
    for g in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(g)))


def test_rglru_scan_equals_recurrent():
    cfg = reduced(CONFIGS["recurrentgemma-9b"])
    params = rglru_mod.init_rglru(KEY, cfg)
    B, S = 2, 48
    x = jax.random.normal(KEY, (B, S, cfg.d_model)) * 0.5
    y_seq, final = rglru_mod.rglru_forward(params, x, cfg, return_state=True)
    state = rglru_mod.init_rglru_state(cfg, B)
    outs = []
    for t in range(S):
        y, state = rglru_mod.rglru_decode_step(params, x[:, t:t + 1], cfg,
                                               state)
        outs.append(y)
    y_step = jnp.concatenate(outs, axis=1)
    assert jnp.max(jnp.abs(y_seq - y_step)) < 1e-4
    assert jnp.max(jnp.abs(final["h"] - state["h"])) < 1e-4


def test_rglru_decay_bounded():
    """RG-LRU recurrence weight a ∈ (0,1) — stability invariant."""
    cfg = reduced(CONFIGS["recurrentgemma-9b"])
    params = rglru_mod.init_rglru(KEY, cfg)
    u = jax.random.normal(KEY, (4, 16, cfg.rglru.lru_width or cfg.d_model))
    a, b = rglru_mod._gates(params, u)
    assert bool(jnp.all(a > 0)) and bool(jnp.all(a < 1))


def test_moe_dispatch_positions():
    """positions-in-expert are unique per expert and arrival-ordered."""
    import numpy as np
    from repro.models.moe import _positions_in_expert
    idx = jax.random.randint(KEY, (512,), 0, 8)
    pos, counts = _positions_in_expert(idx, 8, block=64)
    pos, idx, counts = map(np.asarray, (pos, idx, counts))
    for e in range(8):
        mine = pos[idx == e]
        assert sorted(mine.tolist()) == list(range(len(mine)))
        assert counts[e] == len(mine)
