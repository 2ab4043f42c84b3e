"""End-to-end behaviour of the paper's system: serve-with-C/R."""
import jax
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.launch import serve as serve_mod


@pytest.mark.slow
def test_serving_preempt_and_resume_token_exact(tmp_path):
    """Preempt a serving job mid-generation; restored job must produce the
    exact same remaining tokens (paper's preempt-queue use case applied to
    inference)."""
    wd = str(tmp_path / "serve")
    full = serve_mod.run("gemma3-1b", n_requests=3, prompt_len=8, gen_len=12,
                         workdir=str(tmp_path / "full"), ckpt_every=0,
                         seed=13)
    assert full["status"] == "completed"
    pre = serve_mod.run("gemma3-1b", n_requests=3, prompt_len=8, gen_len=12,
                        workdir=wd, ckpt_every=0, preempt_at=5, seed=13)
    assert pre["status"] == "preempted" and pre["cursor"] == 5
    resumed = serve_mod.run("gemma3-1b", n_requests=3, prompt_len=8,
                            gen_len=12, workdir=wd, ckpt_every=0, seed=13)
    assert resumed["status"] == "completed"
    np.testing.assert_array_equal(resumed["tokens"], full["tokens"])


@pytest.mark.slow
def test_serve_hot_swaps_published_weights(tmp_path):
    """A trainer-side WeightPublisher commits params; a serving run with
    --weight-sync pulls and hot-swaps them before decoding, so generation
    diverges from the no-sync baseline and reports the flipped step."""
    from repro.configs import get_config, reduced
    from repro.core import (CheckpointManager, CheckpointPolicy, Tier,
                            TieredStore, WeightPublisher)
    from repro.models import Model

    base = serve_mod.run("gemma3-1b", n_requests=3, prompt_len=8,
                         gen_len=12, workdir=str(tmp_path / "base"),
                         ckpt_every=0, seed=13)
    assert base["status"] == "completed"

    # trainer: publish DIFFERENT params (another init seed) for the same
    # arch — leaf names land under params/ exactly as serve expects
    cfg = reduced(get_config("gemma3-1b"))
    published = Model(cfg).init(jax.random.PRNGKey(99))
    trainer = tmp_path / "trainer"
    mgr = CheckpointManager(
        TieredStore(Tier("fast", trainer)),
        policy=CheckpointPolicy(mode="incremental"))
    WeightPublisher(mgr)
    mgr.save({"params": published}, 0, blocking=True)
    mgr.wait()
    mgr.close()

    swapped = serve_mod.run("gemma3-1b", n_requests=3, prompt_len=8,
                            gen_len=12, workdir=str(tmp_path / "swap"),
                            ckpt_every=0, seed=13, weight_sync=trainer)
    assert swapped["status"] == "completed"
    assert swapped["weight_sync_step"] == 0
    assert not np.array_equal(swapped["tokens"], base["tokens"])
