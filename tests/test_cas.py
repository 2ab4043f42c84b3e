"""Content-addressed chunk store: chunking/digest properties, object-store
semantics (dedup, replicas, corruption), refcount invariants across
save/save/gc, and the headline dedup guarantee — re-saving identical state
writes ~0 new object bytes."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cas
from repro.core import codec as codec_mod
from repro.core.cas import ChunkStore, chunk_digest, object_rel, split_payload
from conftest import make_ckpt_policy
from repro.core.checkpoint import CheckpointManager
from repro.core.errors import CorruptShardError, MissingShardError
from repro.core.storage import Tier, TieredStore

KEY = jax.random.PRNGKey(0)

CODECS = ["raw", "int8"] + (["zstd"] if codec_mod.HAVE_ZSTD else [])


def _store(tmp_path, name="fast"):
    return TieredStore(Tier(name, tmp_path / name))


def _mgr(tmp_path, **kw):
    kw.setdefault("codec", "raw")
    kw.setdefault("n_writers", 3)
    kw.setdefault("chunk_size", 512)
    kw.setdefault("mode", "incremental")
    # shared test policy: keepalive_s=60 (CI fsync stalls ≠ dead ranks)
    return CheckpointManager(_store(tmp_path), policy=make_ckpt_policy(**kw))


def _state(dtype=jnp.float32):
    return {
        "params": {"w": jax.random.normal(KEY, (32, 16), dtype),
                   "frozen": jax.random.normal(jax.random.PRNGKey(9),
                                               (64, 8), dtype)},
        # distinct values per chunk — all-zero leaves would dedup WITHIN one
        # save (correct, but it breaks the exact per-digest refcount asserts)
        "opt": {"m": jnp.arange(32 * 16, dtype=jnp.float32).reshape(32, 16)},
        "step": jnp.asarray(0, jnp.int32),
    }


def _abstract(state):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        state)


# ---------------------------------------------------------------------------
# chunking properties (hand-rolled — hypothesis is optional in this env)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size", [0, 1, 255, 256, 257, 1000, 3 * 256 + 7])
def test_split_roundtrip_at_boundaries(size):
    rng = np.random.default_rng(size)
    payload = rng.bytes(size)
    chunks = split_payload(payload, 256)
    assert b"".join(chunks) == payload
    assert all(len(c) == 256 for c in chunks[:-1])
    if size:
        assert 1 <= len(chunks[-1]) <= 256
    else:
        assert chunks == []


def test_digest_stability_and_sensitivity():
    data = b"x" * 1000
    assert chunk_digest(data) == chunk_digest(b"x" * 1000)
    assert chunk_digest(data) != chunk_digest(b"x" * 999 + b"y")
    assert len(chunk_digest(data)) == 2 * cas.DIGEST_BYTES
    # object paths are fan-out sharded by digest prefix
    rel = object_rel(chunk_digest(data))
    assert rel.startswith(f"{cas.OBJECTS_DIR}/{chunk_digest(data)[:2]}/")


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("dtype", ["float32", "int32"])
def test_chunked_payload_roundtrip_across_codecs(tmp_path, codec, dtype,
                                                 rng):
    if codec == "int8" and dtype == "int32":
        pytest.skip("int leaves never use the lossy codec")
    arr = (rng.standard_normal((37, 13)).astype(dtype)
           if dtype == "float32"
           else rng.integers(-9, 9, (37, 13)).astype(dtype))
    payload, meta = codec_mod.encode(arr, codec)
    cs = ChunkStore(_store(tmp_path), chunk_size=100)
    digests, new = cs.put_payload(payload)
    assert new == len(payload)
    assert digests == [chunk_digest(c) for c in split_payload(payload, 100)]
    back = cs.read_payload(digests, len(payload))
    out = codec_mod.decode(back, codec, arr.shape, arr.dtype, meta)
    if codec == "int8":
        assert np.max(np.abs(out - arr)) <= np.abs(arr).max() / 127 + 1e-6
    else:
        np.testing.assert_array_equal(out, arr)


def test_put_dedups_and_get_verifies(tmp_path):
    cs = ChunkStore(_store(tmp_path), chunk_size=128)
    data = b"a" * 300
    d = chunk_digest(data)
    assert cs.put(d, data) == 300
    assert cs.put(d, data) == 0          # dedup hit
    assert cs.get(d) == data
    # corrupt the object in place → digest verification catches it
    p = cs.store.fast.root / object_rel(d)
    p.write_bytes(b"b" * 300)
    with pytest.raises(CorruptShardError):
        cs.get(d)
    with pytest.raises(MissingShardError):
        cs.get(chunk_digest(b"never stored"))


def test_replicated_objects_survive_primary_corruption(tmp_path):
    cs = ChunkStore(_store(tmp_path), chunk_size=128, replicas=2)
    data = b"c" * 200
    d = chunk_digest(data)
    assert cs.put(d, data) == 400        # primary + buddy copy
    (cs.store.fast.root / object_rel(d)).write_bytes(b"z" * 200)
    assert cs.get(d) == data             # served from .r1


def test_slow_tier_fallback(tmp_path):
    store = TieredStore(Tier("fast", tmp_path / "fast"),
                        Tier("slow", tmp_path / "slow"))
    cs = ChunkStore(store, chunk_size=128)
    data = b"d" * 64
    d = chunk_digest(data)
    cs.put(d, data)
    # simulate burst-buffer eviction: object only on the slow tier
    store.slow.write_file(object_rel(d), data)
    (store.fast.root / object_rel(d)).unlink()
    assert cs.get(d) == data


# ---------------------------------------------------------------------------
# dedup through the full checkpoint path
# ---------------------------------------------------------------------------

def test_identical_resave_writes_no_new_object_bytes(tmp_path):
    mgr = _mgr(tmp_path)
    state = _state()
    r1 = mgr.save(state, 1)
    assert r1["new_object_bytes"] > 0
    r2 = mgr.save(state, 2)
    assert r2["new_object_bytes"] == 0           # every chunk deduped
    assert r2["chunks"] == r1["chunks"]
    restored, _ = mgr.restore(_abstract(state), step=2)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_partial_change_writes_only_changed_chunks(tmp_path):
    mgr = _mgr(tmp_path)
    state = _state()
    r1 = mgr.save(state, 1)
    # touch 1 of 4 leaves — steady-state cadence
    state["params"]["w"] = state["params"]["w"] + 1.0
    r2 = mgr.save(state, 2)
    assert 0 < r2["new_object_bytes"] < r1["new_object_bytes"]
    assert r2["dedup_ratio"] > 2.0
    restored, _ = mgr.restore(_abstract(state))
    np.testing.assert_array_equal(np.asarray(state["params"]["w"]),
                                  np.asarray(restored["params"]["w"]))


@pytest.mark.parametrize("codec", CODECS)
def test_incremental_roundtrip_across_codecs(tmp_path, codec):
    mgr = _mgr(tmp_path, codec=codec)
    state = _state()
    mgr.save(state, 1)
    restored, _ = mgr.restore(_abstract(state))
    if codec_mod.lossy(codec):
        w0 = np.asarray(state["params"]["w"])
        w1 = np.asarray(restored["params"]["w"])
        assert np.max(np.abs(w0 - w1)) <= np.abs(w0).max() / 127 + 1e-6
    else:
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cdc_mode_dedups_byte_shifted_leaf_fixed_does_not(tmp_path):
    """End-to-end acceptance property: a leaf whose bytes SHIFT between
    steps (insert-at-front churn) dedups to near-zero under
    chunking="cdc" and re-writes nearly everything under fixed-size
    chunking, at equal average chunk size."""
    rng = np.random.default_rng(7)
    base = rng.bytes(96 * 1024)

    def state_of(buf: bytes):
        return {"blob": jnp.asarray(np.frombuffer(buf, np.uint8))}

    shifted = (rng.bytes(16) + base)[:len(base)]   # 16-byte front insert
    results = {}
    for chunking in ("fixed", "cdc"):
        mgr = CheckpointManager(
            _store(tmp_path, chunking),
            policy=make_ckpt_policy(mode="incremental", codec="raw",
                                    n_writers=2, chunk_size=1024,
                                    chunking=chunking))
        mgr.save(state_of(base), 1)
        rep = mgr.save(state_of(shifted), 2)
        results[chunking] = rep["new_object_bytes"]
        restored, _ = mgr.restore(_abstract(state_of(shifted)))
        np.testing.assert_array_equal(
            np.asarray(restored["blob"]),
            np.frombuffer(shifted, np.uint8))
    # fixed-size: every boundary moved → ~everything re-written
    assert results["fixed"] > len(base) // 2
    # cdc: only chunks overlapping the edit (+ resync) re-written
    assert results["cdc"] < len(base) // 8
    assert results["cdc"] < results["fixed"]


# ---------------------------------------------------------------------------
# refcount invariants
# ---------------------------------------------------------------------------

def test_refcounts_published_and_consistent_after_saves_and_gc(tmp_path):
    mgr = _mgr(tmp_path, retain=3)
    state = _state()
    mgr.save(state, 1)
    mgr.save(state, 2)                  # identical → same digests, refs += 1
    refs = mgr.chunks.load_refs()
    assert refs and all(v == 2 for v in refs.values())
    live = mgr._live_chunk_refs()
    assert dict(live) == refs
    fsck = mgr.chunks.fsck(live)
    assert fsck["ok"], fsck

    # retention drop (retain=1) must decrement via mark-and-sweep, not leak
    mgr.retain = 1
    state["params"]["w"] = state["params"]["w"] * 2.0
    mgr.save(state, 3)                  # gc retires steps 1 and 2
    refs = mgr.chunks.load_refs()
    live = mgr._live_chunk_refs()
    assert dict(live) == refs
    assert all(v == 1 for v in refs.values())
    fsck = mgr.chunks.fsck(live)
    assert fsck["ok"], fsck
    # sweep actually reclaimed the dropped step-specific objects
    assert mgr.last_gc_report["cas"]["swept"] >= 0
    restored, _ = mgr.restore(_abstract(state))
    np.testing.assert_array_equal(np.asarray(state["params"]["w"]),
                                  np.asarray(restored["params"]["w"]))


def test_aborted_round_publishes_no_refs_and_gc_reclaims_orphans(tmp_path):
    """An abort must leak nothing: no refcounts published, and any chunk
    objects the dead round managed to write are swept as orphans."""
    mgr = _mgr(tmp_path, n_writers=2, max_retries=0)
    state = _state()
    mgr.save(state, 1)
    refs_before = mgr.chunks.load_refs()
    state["params"]["w"] = state["params"]["w"] + 7.0
    from repro.core.atomic import CrashInjector, CrashPoint
    from repro.core.errors import AbortedError
    try:
        mgr.save(state, 2, crash=CrashInjector("rank0_after_chunk_write"))
    except (AbortedError, CrashPoint):
        pass
    mgr2 = _mgr(tmp_path, n_writers=2)
    assert mgr2.chunks.load_refs() == refs_before
    rep = mgr2.gc()
    live = mgr2._live_chunk_refs()
    fsck = mgr2.chunks.fsck(live)
    assert fsck["ok"], fsck             # zero orphans / missing after sweep
    assert mgr2.latest_step() == 1


def test_fast_tier_eviction_bounds_burst_buffer_growth(tmp_path):
    """Two-tier store, retain=1: the slow tier keeps full history, but the
    fast tier must only pin chunks referenced by ITS OWN retained
    manifests — slow-only-referenced objects are evicted (never deleting
    the last copy). Without eviction the burst buffer grows O(history)."""
    store = TieredStore(Tier("fast", tmp_path / "fast"),
                        Tier("slow", tmp_path / "slow"), drain_async=False)
    mgr = CheckpointManager(store, policy=make_ckpt_policy(
        mode="incremental", codec="raw", n_writers=2, chunk_size=512,
        retain=1))
    state = _state()
    fast_counts = []
    for s in (1, 2, 3, 4, 5):
        state["params"]["w"] = state["params"]["w"] + float(s)
        mgr.save(state, s)
        fast_counts.append(len(
            list((store.fast.root / cas.OBJECTS_DIR).rglob("*.obj"))))
    # bounded, not linear: the last two rounds hold the same object count
    assert fast_counts[-1] == fast_counts[-2]
    assert mgr.last_gc_report["cas"]["evicted"] > 0
    # global fsck stays clean and every copy evicted from fast still has a
    # slow-tier copy: old steps restore from the slow tier alone
    assert mgr.chunks.fsck(mgr._live_chunk_refs())["ok"]
    import shutil as _sh
    _sh.rmtree(store.fast.root)
    store.fast.root.mkdir(parents=True)
    mgr2 = CheckpointManager(store, n_writers=2)
    restored, _ = mgr2.restore(_abstract(state), step=5)
    np.testing.assert_array_equal(np.asarray(state["params"]["w"]),
                                  np.asarray(restored["params"]["w"]))


def test_gc_fails_safe_on_unreadable_manifest(tmp_path):
    """A destructive sweep with an incomplete mark set would delete chunks
    a committed checkpoint still references — an unreadable manifest must
    skip the sweep, not contribute zero refs."""
    mgr = _mgr(tmp_path, retain=1)
    state = _state()
    mgr.save(state, 1)
    state["params"]["w"] = state["params"]["w"] + 1.0
    mgr.save(state, 2)
    mpath = mgr.store.root / "step_00000002" / "_META" / "manifest.json"
    good = mpath.read_bytes()
    mpath.write_text("{corrupt json")
    mgr2 = CheckpointManager(_store(tmp_path), mode="incremental",
                             codec="raw", chunk_size=512)
    rep = mgr2.gc()
    assert rep["cas"].get("skipped") and rep["cas"]["swept"] == 0
    # repair the manifest: every chunk must still be there
    mpath.write_bytes(good)
    restored, _ = CheckpointManager(_store(tmp_path)).restore(
        _abstract(state), step=2)
    np.testing.assert_array_equal(np.asarray(state["params"]["w"]),
                                  np.asarray(restored["params"]["w"]))


def test_gc_never_deletes_live_chunks(tmp_path):
    mgr = _mgr(tmp_path, retain=2)
    states = []
    state = _state()
    for s in (1, 2, 3, 4):
        state = jax.tree.map(lambda x: x, state)
        state["params"]["w"] = state["params"]["w"] + float(s)
        states.append(jax.tree.map(np.asarray, state))
        mgr.save(state, s)
    # steps 1, 2 retired; 3, 4 restorable bit-exact after all sweeps
    for s in (3, 4):
        restored, _ = mgr.restore(_abstract(state), step=s)
        np.testing.assert_array_equal(states[s - 1]["params"]["w"],
                                      np.asarray(restored["params"]["w"]))


def test_slow_leaf_encode_keeps_rank_alive(tmp_path, monkeypatch):
    """A whole-leaf encode longer than the keepalive (zstd over a
    multi-GB optimizer moment takes seconds per GB) is a busy rank, not a
    dead one: the writer beats while the encode runs, and the save
    commits without a retry."""
    real = codec_mod.encode

    def slow_encode(arr, codec):
        time.sleep(1.0)
        return real(arr, codec)

    monkeypatch.setattr(codec_mod, "encode", slow_encode)
    mgr = _mgr(tmp_path, codec="int8", keepalive_s=0.4, max_retries=0)
    state = _state()
    rep = mgr.save(state, 1)
    assert mgr.coordinator.metrics["keepalive_timeouts"] == 0
    assert mgr.latest_step() == 1 and rep["step"] == 1
