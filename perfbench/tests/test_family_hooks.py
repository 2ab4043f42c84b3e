"""What a model family brings to the train step's reference besides its
model (``reference.py``): its whole loss, and train state that no gradient
moves. Each hook is shown by a test family (``families/``) that takes the
real dense family's place in a tiny root, with nothing else edited."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

import cells  # noqa: E402
import control  # noqa: E402
import reference  # noqa: E402
import run as run_mod  # noqa: E402

CELL = "tiny-dense.incr"
SEED = 2**31 + 23
BINS = 8


def make_root(tmp_path_factory, family=None):
    return tiny.make_root(tmp_path_factory.mktemp("bench"),
                          families={"dense": family} if family else None)


def one_run(root):
    return run_mod.run_cell(cells.load_cell(CELL, root), seed=SEED,
                            seconds=0.5, trace=False, require_tpu=False,
                            compile_cache=False, root=root)


def failing(res) -> set:
    return {k for k, c in res["checks"].items()
            if not c["value"] <= c["limit"]}


def reference_readings(root):
    cell = cells.load_cell(CELL, root)
    cfg = cells.model_config(cell.config)
    mix = cell.mix
    batches = [reference.tokens(SEED % 2**31, k, mix["batch"],
                                mix["seq_len"], cfg.vocab_size)
               for k in range(reference.STEPS)]
    return reference.reference(cell.config, SEED, batches,
                               rows=mix["reference_rows"],
                               bench_dir=cell.bench_dir)


def test_family_loss_is_the_loss_the_reference_takes(tmp_path_factory):
    # the cross-entropy written out as a family's loss reads as the
    # family without one
    plain = reference_readings(make_root(tmp_path_factory))
    written = reference_readings(make_root(tmp_path_factory, "dense_ce"))
    assert written == plain


def test_family_loss_is_handed_the_declared_state(tmp_path_factory):
    # a term of the state alone: the parameters move as without it, and
    # each step's loss is up by the weight times the mean count so far
    plain = reference_readings(make_root(tmp_path_factory))
    read = reference_readings(make_root(tmp_path_factory,
                                        "dense_load_in_loss"))
    cell = cells.load_cell(CELL, make_root(tmp_path_factory))
    per_step = cell.mix["batch"] * cell.mix["seq_len"] / BINS
    assert read.grad == plain.grad
    assert {k: v for k, v in read.change.items() if k in plain.change} \
        == plain.change
    assert "token_load" in read.change
    for k, (a, b) in enumerate(zip(read.losses, plain.losses)):
        assert a - b == pytest.approx(1e-3 * per_step * k, abs=1e-5), k


def test_family_loss_with_a_term_the_program_lacks_fails_loss_gap(
        tmp_path_factory):
    res = one_run(make_root(tmp_path_factory, "dense_zloss"))
    assert res["correct"] is False
    assert "loss_gap" in failing(res)


def program_with_token_load(monkeypatch, scale: float):
    """The program's Trainer with a train-state entry ``token_load`` that
    each step adds ``scale`` times the batch's token counts per residue
    to; no gradient moves it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.train import loop
    abstract, shardings, make = (loop.abstract_train_state,
                                 loop.state_shardings, loop.make_train_step)

    def core(state):
        return {k: v for k, v in state.items() if k != "token_load"}

    def with_load(model, optimizer):
        return dict(abstract(model, optimizer),
                    token_load=jax.ShapeDtypeStruct((BINS,), jnp.float32))

    def load_shardings(state, mesh, optimizer):
        return dict(shardings(core(state), mesh, optimizer),
                    token_load=NamedSharding(mesh, P()))

    def make_step(*a, **kw):
        step = make(*a, **kw)

        def stepped(state, batch):
            new, metrics = step(core(state), batch)
            counts = jnp.zeros((BINS,), jnp.float32).at[
                batch["tokens"].reshape(-1) % BINS].add(1.0)
            return dict(new, token_load=state["token_load"]
                        + scale * counts), metrics
        return stepped

    monkeypatch.setattr(loop, "abstract_train_state", with_load)
    monkeypatch.setattr(loop, "state_shardings", load_shardings)
    monkeypatch.setattr(loop, "make_train_step", make_step)


def test_declared_state_moved_as_the_family_says_is_correct(
        tmp_path_factory, monkeypatch):
    program_with_token_load(monkeypatch, 1.0)
    res = one_run(make_root(tmp_path_factory, "dense_token_load"))
    assert res["correct"] is True
    assert res["checks"]["leaves_differing"]["value"] == 0


def test_declared_state_moved_otherwise_fails_update_norm_gap(
        tmp_path_factory, tmp_path, monkeypatch):
    program_with_token_load(monkeypatch, 2.0)
    cell = cells.load_cell(CELL, make_root(tmp_path_factory,
                                           "dense_token_load"))
    limits = cell.config["reference"]["limits"]
    (line,) = control.readings(cell, [SEED], workdir=tmp_path,
                               require_tpu=False)
    got = line["program"]
    assert got["update_norm_gap"] > limits["update_norm_gap"]
    assert got["worst_leaves"]["update_norm_gap"] == "token_load"
    assert "token_load" not in got["left_out"]
    assert got["loss_gap"] <= limits["loss_gap"]
    assert got["grad_norm_gap"] <= limits["grad_norm_gap"]


def test_state_entry_the_family_does_not_declare_is_refused(
        tmp_path_factory, monkeypatch):
    program_with_token_load(monkeypatch, 1.0)
    with pytest.raises(cells.CellError, match="state layout"):
        one_run(make_root(tmp_path_factory))
