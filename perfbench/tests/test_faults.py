"""``correct`` has to come out false when the path it judges is broken.

The control: the reference in the program's place at int8, one precision
below the configuration's bfloat16 (``control.py``, which gives the
chip's readings at the cells' own size). Then runs with the timed path
broken underneath, once for each fault a one-chip train-and-checkpoint
cell can have:

* a step that returns its state unchanged, with its step counter or
  with the counter moving on alone (an update dropped);
* half of the batch left out, the mean taken over the rest;
* an answer altered where it is produced: one byte of the snapshot the
  save persists, a leaf the restore did not read, the resumed step's
  loss, or every leaf saved through a lossy codec.

(One chip has no exchange between chips to leave out.)
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

import cells  # noqa: E402
import control  # noqa: E402
import run as run_mod  # noqa: E402

CELL = "tiny-mamba.incr"
EXACT = ("leaves_differing", "step_counter_gap", "resume_loss_gap")
TRAIN = ("loss_gap", "grad_norm_gap", "update_norm_gap")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def one_run(root, cell=CELL, codec=None):
    cell = cells.load_cell(cell, root)
    if codec is not None:       # every leaf saved through this codec
        cell.mix.update(codec=codec, params_codec=codec)
    return run_mod.run_cell(cell, seed=2**31 + 99, seconds=0.5, trace=False,
                            require_tpu=False, compile_cache=False,
                            root=root)


def failing(res) -> set:
    return {k for k, c in res["checks"].items() if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("cell", ["tiny-mamba.incr", "tiny-dense.incr",
                                  "tiny-dense-adafactor.incr"])
def test_sound_run_is_correct(root, cell):
    res = one_run(root, cell)
    assert res["correct"] is True
    assert all(res["checks"][k]["value"] == 0 for k in EXACT)


@pytest.mark.parametrize("cell", ["tiny-mamba.incr", "tiny-dense.incr",
                                  "tiny-dense-adafactor.incr"])
def test_control_reference_at_int8_is_not_correct(root, tmp_path, cell):
    cell = cells.load_cell(cell, root)
    limits = cell.config["reference"]["limits"]
    (line,) = control.readings(cell, [2**31 + 7], workdir=tmp_path,
                               require_tpu=False)
    assert all(line["program"][k] <= limits[k] for k in TRAIN)
    assert any(line["control"][k] > limits[k] for k in TRAIN)
    assert any(line["half_batch"][k] > limits[k] for k in TRAIN)


def test_lossy_codec_is_not_correct(root):
    res = one_run(root, codec="int8")
    assert res["correct"] is False
    assert res["checks"]["leaves_differing"]["value"] > 0


def test_snapshot_byte_altered_is_not_correct(root, monkeypatch):
    from repro.core import save_path
    real = save_path.snapshot_items

    def altered(state, pool):
        items = real(state, pool)
        name, rng, arr = items[0]
        arr = np.array(arr)
        arr.reshape(-1).view(np.uint8)[0] ^= 1
        return [(name, rng, arr)] + items[1:]

    monkeypatch.setattr(save_path, "snapshot_items", altered)
    res = one_run(root)
    assert res["correct"] is False
    assert failing(res) == {"leaves_differing"}
    assert res["checks"]["leaves_differing"]["value"] == 1


def broken_step(monkeypatch, breaking):
    """The program's train step, with ``breaking(step, state, batch)`` in
    its place."""
    from repro.train import loop
    real = loop.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)
        return lambda state, batch: breaking(step, state, batch)

    monkeypatch.setattr(loop, "make_train_step", make)


@pytest.mark.parametrize("cell", ["tiny-mamba.incr",
                                  "tiny-dense-adafactor.incr"])
def test_step_returning_its_state_unchanged_is_not_correct(root, monkeypatch,
                                                           cell):
    def frozen(step, state, batch):
        _, metrics = step(state, batch)
        return state, metrics

    broken_step(monkeypatch, frozen)
    res = one_run(root, cell)
    assert res["correct"] is False
    assert {"grad_norm_gap", "update_norm_gap",
            "step_counter_gap"} <= failing(res)
    assert res["checks"]["grad_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", ["tiny-mamba.incr",
                                  "tiny-dense-adafactor.incr"])
def test_update_dropped_with_the_counter_moving_is_not_correct(
        root, monkeypatch, cell):
    def dropped(step, state, batch):
        new, metrics = step(state, batch)
        return dict(state, step=new["step"]), metrics

    broken_step(monkeypatch, dropped)
    res = one_run(root, cell)
    assert res["correct"] is False
    assert res["checks"]["step_counter_gap"]["value"] == 0
    assert {"grad_norm_gap", "update_norm_gap"} <= failing(res)


@pytest.mark.parametrize("cell", ["tiny-mamba.incr", "tiny-dense.incr",
                                  "tiny-dense-adafactor.incr"])
def test_half_the_batch_left_out_is_not_correct(root, monkeypatch, cell):
    def half(step, state, batch):
        rows = batch["tokens"].shape[0] // 2
        return step(state, {"tokens": batch["tokens"][:rows]})

    broken_step(monkeypatch, half)
    res = one_run(root, cell)
    assert res["correct"] is False
    assert failing(res) & set(TRAIN)


def test_restore_handing_back_an_unread_leaf_is_not_correct(root,
                                                            monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core.checkpoint import CheckpointManager
    real = CheckpointManager.restore

    def zeroed(self, *a, **kw):
        state, extra = real(self, *a, **kw)
        leaves, tree = jax.tree.flatten(state["opt"]["v"])
        leaves[0] = jnp.zeros_like(leaves[0])
        state["opt"]["v"] = jax.tree.unflatten(tree, leaves)
        return state, extra

    monkeypatch.setattr(CheckpointManager, "restore", zeroed)
    res = one_run(root)
    assert res["correct"] is False
    assert res["checks"]["leaves_differing"]["value"] >= 1


def test_resumed_loss_altered_is_not_correct(root, monkeypatch):
    real = run_mod.dispatch_step

    def altered(trainer):
        loss = real(trainer)
        return loss + 1e-3 if trainer.restored_from is not None else loss

    monkeypatch.setattr(run_mod, "dispatch_step", altered)
    res = one_run(root)
    assert res["correct"] is False
    assert failing(res) == {"resume_loss_gap"}
