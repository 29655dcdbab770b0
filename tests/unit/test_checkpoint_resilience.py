"""Checkpoint resilience tests (ISSUE 2): crash-safe saves, verified loads,
resume-from-latest-valid, retries, retention, preemption saves, and the
NaN/overflow train-loop watchdog — driven by the fault-injection harness in
fault_injection.py.

The headline invariant, proved here the way CheckFreq/Orbax prove it: a save
killed at ANY byte leaves ``latest`` pointing at the previous complete
checkpoint, and ``load_checkpoint(fallback_to_valid=True)`` restores it with
bit-identical leaves.
"""

import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime import checkpointing as ckpt
from deepspeed_tpu.runtime.checkpointing import (CheckpointError, check_checkpoint_tag,
                                                 find_latest_valid_tag, get_latest_tag,
                                                 is_valid_tag, list_tags,
                                                 save_checkpoint_dir, sweep_retention)
from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import NativeCheckpointEngine
from deepspeed_tpu.runtime.engine import NonFiniteLossError

from .fault_injection import (FaultyCheckpointEngine, SimulatedCrash, corrupt_leaf,
                              drop_metadata, truncate_leaf)
from .simple_model import init_mlp_params, mlp_loss_fn, random_batch

HIDDEN = 16


def make_engine(extra_cfg=None, ckpt_cfg=None):
    params = init_mlp_params(jax.random.PRNGKey(0), hidden=HIDDEN)
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": False},  # fp32: bit-identical restore checks
        "steps_per_print": 100,
    }
    if ckpt_cfg:
        cfg["checkpoint"] = ckpt_cfg
    if extra_cfg:
        cfg.update(extra_cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(loss_fn=mlp_loss_fn,
                                               model_parameters=params, config=cfg)
    return engine


def train(engine, steps, seed=1):
    losses = []
    for s in range(steps):
        batch = random_batch(engine.train_batch_size, hidden=HIDDEN, seed=seed + s)
        losses.append(float(engine.train_batch(batch).loss))
    return losses


# ------------------------------------------------------------- atomic save shape
def test_save_layout_manifest_and_index(tmp_path):
    engine = make_engine()
    train(engine, 2)
    tag = engine.save_checkpoint(str(tmp_path))
    assert not [d for d in os.listdir(tmp_path) if d.startswith(ckpt.TMP_PREFIX)]
    assert get_latest_tag(str(tmp_path)) == tag
    assert list_tags(str(tmp_path)) == [tag]
    meta = ckpt.read_metadata(str(tmp_path / tag))
    assert meta["format_version"] == ckpt.FORMAT_VERSION
    for entry in meta["manifest"]:
        path = tmp_path / tag / (entry["key"] + ".npy")
        assert entry["nbytes"] == os.path.getsize(path)
        assert entry["crc32"] == ckpt._file_crc32(str(path))
    assert check_checkpoint_tag(str(tmp_path), tag, verify_integrity=True) == []


def test_commit_runs_after_rename_and_before_latest(tmp_path):
    """Satellite: a plug-in engine's commit(tag) must see a COMPLETE final tag
    dir (metadata included) — the old protocol committed before metadata.json
    existed — and must run before ``latest`` flips."""
    observed = {}

    class RecordingEngine(NativeCheckpointEngine):
        def commit(self, tag):
            final = tmp_path / tag
            observed["final_dir"] = final.is_dir()
            observed["metadata"] = (final / ckpt.METADATA_FILE).exists()
            latest = tmp_path / ckpt.LATEST_FILE
            observed["latest_already_flipped"] = (latest.exists()
                                                  and latest.read_text().strip() == tag)
            return True

    engine = make_engine()
    train(engine, 1)
    engine._ckpt_engine = RecordingEngine()
    tag = engine.save_checkpoint(str(tmp_path))
    assert observed == {"final_dir": True, "metadata": True,
                       "latest_already_flipped": False}
    assert get_latest_tag(str(tmp_path)) == tag


# --------------------------------------------------------------- crash mid-save
def test_kill_mid_save_preserves_latest_and_fallback_restores(tmp_path):
    engine = make_engine()
    train(engine, 3)
    tag_a = engine.save_checkpoint(str(tmp_path))
    params_a = engine.get_fp32_params()
    step_a = engine.global_steps

    train(engine, 2)
    engine._ckpt_engine = FaultyCheckpointEngine(kill_after_bytes=1500)
    with pytest.raises(SimulatedCrash):
        engine.save_checkpoint(str(tmp_path), tag="global_step_doomed")

    # the dying save never touched the published state
    assert get_latest_tag(str(tmp_path)) == tag_a
    assert not (tmp_path / "global_step_doomed").exists()
    staging = [d for d in os.listdir(tmp_path) if d.startswith(ckpt.TMP_PREFIX)]
    assert staging, "expected the crashed save's staging dir to remain"

    # a fresh process resumes from the intact checkpoint, bit-identical
    engine2 = make_engine()
    loaded_tag, client = engine2.load_checkpoint(str(tmp_path), fallback_to_valid=True)
    assert loaded_tag == tag_a
    assert engine2.global_steps == step_a
    params_b = engine2.get_fp32_params()
    for k in params_a:
        np.testing.assert_array_equal(params_a[k]["w"], params_b[k]["w"])

    # the next healthy save sweeps the crashed staging dir
    engine2._ckpt_engine = None
    train(engine2, 1)
    engine2.save_checkpoint(str(tmp_path))
    assert not [d for d in os.listdir(tmp_path) if d.startswith(ckpt.TMP_PREFIX)]


def test_kill_between_leaves_preserves_latest(tmp_path):
    engine = make_engine()
    train(engine, 1)
    tag_a = engine.save_checkpoint(str(tmp_path))
    engine._ckpt_engine = FaultyCheckpointEngine(kill_after_leaves=3)
    with pytest.raises(SimulatedCrash):
        engine.save_checkpoint(str(tmp_path), tag="doomed")
    assert get_latest_tag(str(tmp_path)) == tag_a
    assert is_valid_tag(str(tmp_path), tag_a, verify_integrity=True)


def test_resave_same_tag_parks_old_copy_until_published(tmp_path):
    """Replacing an existing tag must never pass through a window where the
    only copy is deleted: the old dir is parked at ``<tag>.prev`` (a loadable
    tag) until ``latest`` flips, then cleaned up."""
    engine = make_engine()
    train(engine, 1)
    engine.save_checkpoint(str(tmp_path), tag="t")

    class CommitBomb(NativeCheckpointEngine):
        def commit(self, tag):
            raise SimulatedCrash("die between rename and latest flip")

    train(engine, 1)
    engine._ckpt_engine = CommitBomb()
    with pytest.raises(SimulatedCrash):
        engine.save_checkpoint(str(tmp_path), tag="t")
    # crash mid-replace: BOTH the renamed new copy and the parked old copy are
    # complete checkpoints — nothing was ever rmtree'd before publication
    assert is_valid_tag(str(tmp_path), "t", verify_integrity=True)
    assert is_valid_tag(str(tmp_path), "t.prev", verify_integrity=True)
    # a healthy re-save cleans the parked copy after `latest` flips
    engine._ckpt_engine = None
    engine.save_checkpoint(str(tmp_path), tag="t")
    assert not (tmp_path / "t.prev").exists()
    assert get_latest_tag(str(tmp_path)) == "t"


def test_sweep_skips_in_flight_staging_dir(tmp_path):
    """A reentrant save (SIGTERM preemption handler interrupting a regular
    save) must not sweep the staging dir the interrupted save is writing."""
    live = tmp_path / (ckpt.TMP_PREFIX + "inflight")
    stale = tmp_path / (ckpt.TMP_PREFIX + "crashed")
    live.mkdir(), stale.mkdir()
    ckpt._ACTIVE_STAGING.add(str(live))
    try:
        swept = ckpt._sweep_stale_tmp(str(tmp_path))
    finally:
        ckpt._ACTIVE_STAGING.discard(str(live))
    assert swept == [ckpt.TMP_PREFIX + "crashed"]
    assert live.is_dir() and not stale.exists()


def test_malformed_manifest_entry_reads_as_invalid_not_keyerror(tmp_path):
    engine = make_engine()
    train(engine, 1)
    engine.save_checkpoint(str(tmp_path), tag="good")
    train(engine, 1)
    engine.save_checkpoint(str(tmp_path), tag="bad")
    meta_path = tmp_path / "bad" / ckpt.METADATA_FILE
    meta_path.write_text(json.dumps({"manifest": [{}], "client_state": {}}))
    problems = check_checkpoint_tag(str(tmp_path), "bad")
    assert any("malformed" in p for p in problems)
    # the fallback walk skips it instead of dying on a KeyError
    assert find_latest_valid_tag(str(tmp_path)) == "good"
    loaded_tag, _ = make_engine().load_checkpoint(str(tmp_path), fallback_to_valid=True)
    assert loaded_tag == "good"


# ---------------------------------------------------------- verified load + walk
def test_truncated_leaf_fails_size_check_and_falls_back(tmp_path):
    engine = make_engine()
    train(engine, 2)
    tag_a = engine.save_checkpoint(str(tmp_path), tag="step_a")
    params_a = engine.get_fp32_params()
    train(engine, 2)
    tag_b = engine.save_checkpoint(str(tmp_path), tag="step_b")
    truncate_leaf(str(tmp_path / tag_b), "params.layer_0.w")

    problems = check_checkpoint_tag(str(tmp_path), tag_b)
    assert any("size" in p for p in problems)

    engine2 = make_engine()
    with pytest.raises(CheckpointError, match="step_b"):
        engine2.load_checkpoint(str(tmp_path))  # no fallback: loud failure

    loaded_tag, _ = engine2.load_checkpoint(str(tmp_path), fallback_to_valid=True)
    assert loaded_tag == tag_a
    params = engine2.get_fp32_params()
    for k in params_a:
        np.testing.assert_array_equal(params_a[k]["w"], params[k]["w"])


def test_bitflip_detected_only_with_verify_integrity(tmp_path):
    engine = make_engine()
    train(engine, 1)
    engine.save_checkpoint(str(tmp_path), tag="step_a")
    train(engine, 1)
    tag_b = engine.save_checkpoint(str(tmp_path), tag="step_b")
    corrupt_leaf(str(tmp_path / tag_b), "params.layer_0.w")  # size-preserving

    # size/completeness checks can't see a same-size bitflip...
    assert is_valid_tag(str(tmp_path), tag_b)
    # ...the CRC pass can
    assert not is_valid_tag(str(tmp_path), tag_b, verify_integrity=True)

    engine2 = make_engine(ckpt_cfg={"verify_integrity": True})
    with pytest.raises(CheckpointError, match="crc32"):
        engine2.load_checkpoint(str(tmp_path))
    loaded_tag, _ = engine2.load_checkpoint(str(tmp_path), fallback_to_valid=True)
    assert loaded_tag == "step_a"


def test_dropped_metadata_falls_back(tmp_path):
    engine = make_engine()
    train(engine, 1)
    engine.save_checkpoint(str(tmp_path), tag="step_a")
    train(engine, 1)
    engine.save_checkpoint(str(tmp_path), tag="step_b")
    drop_metadata(str(tmp_path / "step_b"))
    assert find_latest_valid_tag(str(tmp_path)) == "step_a"
    engine2 = make_engine()
    loaded_tag, _ = engine2.load_checkpoint(str(tmp_path), fallback_to_valid=True)
    assert loaded_tag == "step_a"


def test_no_valid_checkpoint_raises_clear_error(tmp_path):
    engine = make_engine()
    train(engine, 1)
    tag = engine.save_checkpoint(str(tmp_path))
    drop_metadata(str(tmp_path / tag))
    engine2 = make_engine()
    with pytest.raises(CheckpointError, match="no valid checkpoint"):
        engine2.load_checkpoint(str(tmp_path), fallback_to_valid=True)


# ------------------------------------------------------------------- tag errors
def test_empty_latest_file_is_a_checkpoint_error(tmp_path):
    engine = make_engine()
    train(engine, 1)
    tag = engine.save_checkpoint(str(tmp_path))
    (tmp_path / ckpt.LATEST_FILE).write_text("  \n")
    with pytest.raises(CheckpointError, match="empty"):
        get_latest_tag(str(tmp_path))
    engine2 = make_engine()
    with pytest.raises(CheckpointError, match="empty"):
        engine2.load_checkpoint(str(tmp_path))
    # fallback ignores the torn latest and walks the index
    loaded_tag, _ = engine2.load_checkpoint(str(tmp_path), fallback_to_valid=True)
    assert loaded_tag == tag


def test_latest_pointing_at_missing_dir_is_a_checkpoint_error(tmp_path):
    tmp_path.mkdir(exist_ok=True)
    (tmp_path / ckpt.LATEST_FILE).write_text("ghost_tag")
    engine = make_engine()
    with pytest.raises(CheckpointError, match="ghost_tag"):
        engine.load_checkpoint(str(tmp_path))
    with pytest.raises(CheckpointError, match="fallback_to_valid"):
        engine.load_checkpoint(str(tmp_path), tag="also_missing")


def test_no_latest_and_no_tag_is_a_checkpoint_error(tmp_path):
    engine = make_engine()
    with pytest.raises(CheckpointError, match="no 'latest'"):
        engine.load_checkpoint(str(tmp_path))


# ------------------------------------------------------------------ retry loop
def test_transient_oserrors_absorbed_by_retries(tmp_path):
    engine = make_engine(ckpt_cfg={"save_retries": 3, "retry_backoff_secs": 0.0})
    train(engine, 1)
    faulty = FaultyCheckpointEngine(transient_errors=2)
    engine._ckpt_engine = faulty
    tag = engine.save_checkpoint(str(tmp_path))
    assert faulty.transients_raised == 2
    assert is_valid_tag(str(tmp_path), tag, verify_integrity=True)
    engine2 = make_engine()
    engine2.load_checkpoint(str(tmp_path))
    assert engine2.global_steps == engine.global_steps


def test_retry_budget_exhaustion_raises(tmp_path):
    engine = make_engine(ckpt_cfg={"save_retries": 1, "retry_backoff_secs": 0.0})
    train(engine, 1)
    engine._ckpt_engine = FaultyCheckpointEngine(transient_errors=10)
    with pytest.raises(OSError, match="injected transient"):
        engine.save_checkpoint(str(tmp_path))
    assert get_latest_tag(str(tmp_path)) is None  # nothing ever published


# -------------------------------------------------------------------- retention
def test_keep_last_n_gc(tmp_path):
    engine = make_engine(ckpt_cfg={"keep_last_n": 2})
    for i in range(4):
        train(engine, 1)
        engine.save_checkpoint(str(tmp_path), tag=f"step_{i}")
    assert list_tags(str(tmp_path)) == ["step_2", "step_3"]
    assert get_latest_tag(str(tmp_path)) == "step_3"
    assert not (tmp_path / "step_0").exists() and not (tmp_path / "step_1").exists()


def test_retention_never_deletes_only_valid_checkpoint(tmp_path):
    engine = make_engine()
    for i in range(3):
        train(engine, 1)
        engine.save_checkpoint(str(tmp_path), tag=f"step_{i}")
    # everything in the would-be retention window is corrupt
    drop_metadata(str(tmp_path / "step_1"))
    drop_metadata(str(tmp_path / "step_2"))
    deleted = sweep_retention(str(tmp_path), keep_last_n=1)
    assert "step_0" not in deleted
    assert (tmp_path / "step_0").is_dir()
    assert find_latest_valid_tag(str(tmp_path)) == "step_0"


# ------------------------------------------------------------------- client_state
def test_client_state_numpy_and_jax_leaves_serialize(tmp_path):
    """Satellite: _jsonable must survive np.ndarray / jax.Array / np.bool_
    values in client_state (previously TypeError deep in json.dump)."""
    engine = make_engine()
    train(engine, 1)
    tag = engine.save_checkpoint(str(tmp_path), client_state={
        "mask": np.array([True, False]),
        "counts": np.arange(3, dtype=np.int64),
        "flag": np.bool_(True),
        "scale": np.float32(1.5),
        "dev": jnp.ones((2, ), jnp.float32),
    })
    with open(tmp_path / tag / ckpt.METADATA_FILE) as fh:
        client = json.load(fh)["client_state"]
    assert client["mask"] == [True, False]
    assert client["counts"] == [0, 1, 2]
    assert client["flag"] is True
    assert client["scale"] == 1.5
    assert client["dev"] == [1.0, 1.0]
    _, restored = make_engine().load_checkpoint(str(tmp_path))
    assert restored["flag"] is True


def test_legacy_manifest_without_crc_still_validates(tmp_path):
    """Pre-resilience checkpoints (no nbytes/crc32 in the manifest) must keep
    loading: the size/CRC checks are skipped per-entry when absent."""
    engine = make_engine()
    train(engine, 1)
    tag = engine.save_checkpoint(str(tmp_path))
    meta_path = tmp_path / tag / ckpt.METADATA_FILE
    meta = json.loads(meta_path.read_text())
    for entry in meta["manifest"]:
        entry.pop("nbytes"), entry.pop("crc32")
    meta_path.write_text(json.dumps(meta))
    assert is_valid_tag(str(tmp_path), tag, verify_integrity=True)
    make_engine().load_checkpoint(str(tmp_path))


# ----------------------------------------------------------- multi-host streaming
def test_streaming_declines_non_fully_addressable(tmp_path, monkeypatch, mesh8):
    """Satellite: multi-host leaves (is_fully_addressable False) must take the
    collective gather path — streaming only local shards would persist zeros."""
    from jax.sharding import NamedSharding, PartitionSpec
    arr = jax.device_put(np.arange(128, dtype=np.float32).reshape(8, 16),
                         NamedSharding(mesh8.mesh, PartitionSpec("data")))
    target = str(tmp_path / "leaf.npy")
    assert ckpt._write_leaf_streaming(arr, target, NativeCheckpointEngine()) is True
    os.remove(target)
    monkeypatch.setattr(ckpt, "_leaf_fully_addressable", lambda leaf: False)
    assert ckpt._write_leaf_streaming(arr, target, NativeCheckpointEngine()) is False
    assert not os.path.exists(target)


def test_streaming_writes_each_shard_index_exactly_once(tmp_path, monkeypatch, mesh8):
    from jax.sharding import NamedSharding, PartitionSpec
    writes = []
    real_open_memmap = np.lib.format.open_memmap

    def counting_open_memmap(path, mode="r", dtype=None, shape=None):
        mm = real_open_memmap(path, mode=mode, dtype=dtype, shape=shape)

        class Counting:
            def __setitem__(self, idx, val):
                writes.append(repr(idx))
                mm[idx] = val

            def flush(self):
                mm.flush()

        return Counting()

    monkeypatch.setattr(np.lib.format, "open_memmap", counting_open_memmap)
    src = np.arange(128, dtype=np.float32).reshape(8, 16)

    sharded = jax.device_put(src, NamedSharding(mesh8.mesh, PartitionSpec("data")))
    target = str(tmp_path / "sharded.npy")
    assert ckpt._write_leaf_streaming(sharded, target, NativeCheckpointEngine())
    assert len(writes) == 8 and len(set(writes)) == 8  # one write per shard
    np.testing.assert_array_equal(np.load(target), src)

    writes.clear()
    replicated = jax.device_put(src, NamedSharding(mesh8.mesh, PartitionSpec()))
    target2 = str(tmp_path / "replicated.npy")
    assert ckpt._write_leaf_streaming(replicated, target2, NativeCheckpointEngine())
    assert len(writes) == 1  # 8 replicated shards share one index: dedup'd
    np.testing.assert_array_equal(np.load(target2), src)


# ---------------------------------------------------------------- preemption save
def test_sigterm_triggers_best_effort_save(tmp_path):
    original = signal.getsignal(signal.SIGTERM)
    chained = []
    try:
        signal.signal(signal.SIGTERM, lambda *a: chained.append(a))
        engine = make_engine(ckpt_cfg={"save_on_preemption": True})
        train(engine, 2)
        engine.save_checkpoint(str(tmp_path))  # arms the handler
        train(engine, 1)
        os.kill(os.getpid(), signal.SIGTERM)
        want, deadline = f"preempt_step{engine.global_steps}", time.monotonic() + 30
        while (tag := get_latest_tag(str(tmp_path))) != want and time.monotonic() < deadline:
            time.sleep(0.01)  # the handler runs at a bytecode boundary of this thread, then saves
        assert tag == want
        assert is_valid_tag(str(tmp_path), tag, verify_integrity=True)
        _, client = make_engine().load_checkpoint(str(tmp_path))
        assert client["preempted"] is True
        assert chained, "previous SIGTERM handler was not chained"
    finally:
        signal.signal(signal.SIGTERM, original)


# -------------------------------------------------------------------- watchdog
def test_watchdog_aborts_after_consecutive_nonfinite(tmp_path):
    engine = make_engine(extra_cfg={"max_consecutive_skips": 3})
    train(engine, 1)
    bad = random_batch(engine.train_batch_size, hidden=HIDDEN, seed=0)
    bad["x"] = np.full_like(bad["x"], np.nan)
    for _ in range(2):
        engine.train_batch(bad)  # below the limit: counted, not fatal
    with pytest.raises(NonFiniteLossError, match="3 consecutive"):
        engine.train_batch(bad)


def test_watchdog_resets_on_good_step():
    # driven through _watchdog_check directly: a real NaN step poisons fp32
    # weights for good (no overflow-skip), so alternation can't be produced by
    # actual batches — the counter semantics are what's under test
    from deepspeed_tpu.runtime.engine import StepMetrics

    def metrics(loss):
        return StepMetrics(loss=jnp.float32(loss), grad_norm=jnp.float32(loss),
                           lr=jnp.float32(1e-2), skipped=jnp.asarray(False),
                           loss_scale=jnp.float32(1.0))

    engine = make_engine(extra_cfg={"max_consecutive_skips": 2})
    for _ in range(4):
        engine._watchdog_check(metrics(np.nan))  # 1 bad...
        assert engine._consecutive_bad_steps == 1
        engine._watchdog_check(metrics(0.5))  # ...then good: streak resets
        assert engine._consecutive_bad_steps == 0


def test_watchdog_disabled_by_default():
    engine = make_engine()
    bad = random_batch(engine.train_batch_size, hidden=HIDDEN, seed=0)
    bad["x"] = np.full_like(bad["x"], np.nan)
    for _ in range(5):
        engine.train_batch(bad)  # silently tolerated when the watchdog is off


# --------------------------------------------------------------- telemetry trail
def test_resilience_events_land_in_jsonl(tmp_path):
    jsonl = tmp_path / "telemetry.jsonl"
    engine = make_engine(
        extra_cfg={"telemetry": {"jsonl_path": str(jsonl)}},
        ckpt_cfg={"save_retries": 2, "retry_backoff_secs": 0.0})
    train(engine, 1)
    engine.save_checkpoint(str(tmp_path / "ck"), tag="step_a")
    faulty = FaultyCheckpointEngine(transient_errors=1)
    engine._ckpt_engine = faulty
    train(engine, 1)
    engine.save_checkpoint(str(tmp_path / "ck"), tag="step_b")
    truncate_leaf(str(tmp_path / "ck" / "step_b"), "params.layer_0.w")
    engine.load_checkpoint(str(tmp_path / "ck"), fallback_to_valid=True)
    engine.telemetry.close()
    records = [json.loads(line) for line in jsonl.read_text().splitlines()]
    events = {r["event"] for r in records if r.get("kind") == "resilience"}
    assert "save_retry" in events
    assert "fallback_load" in events
    fb = next(r for r in records if r.get("event") == "fallback_load")
    assert fb["requested"] == "step_b" and fb["fallback"] == "step_a"


# ------------------------------------------------------------- async engine path
def test_async_engine_roundtrip_with_atomic_protocol(tmp_path):
    engine = make_engine(ckpt_cfg={"checkpoint_engine": "async"})
    train(engine, 2)
    tag = engine.save_checkpoint(str(tmp_path))
    assert is_valid_tag(str(tmp_path), tag, verify_integrity=True)
    engine2 = make_engine(ckpt_cfg={"checkpoint_engine": "async"})
    engine2.load_checkpoint(str(tmp_path))
    p1, p2 = engine.get_fp32_params(), engine2.get_fp32_params()
    for k in p1:
        np.testing.assert_array_equal(p1[k]["w"], p2[k]["w"])


# --------------------------------------------------------------------------
# multi-rank resume-tag consensus (elastic fault tolerance, PR 7): ranks with
# DIVERGENT newest tags — one torn by the crash that triggered the restart —
# must converge on the newest tag valid across EVERY rank's directory
def _two_rank_dirs(tmp_path, steps=3):
    """Per-rank checkpoint layout (<dir>/rank<R>/) with identical tag history:
    one engine, every step saved to both rank dirs (the consensus walk only
    reads tag lists + manifests, not tensor provenance)."""
    engine = make_engine()
    dirs = [str(tmp_path / "ck" / f"rank{r}") for r in range(2)]
    for _ in range(steps):
        train(engine, 1)
        for d in dirs:
            engine.save_checkpoint(d)
    return engine, dirs


def test_consensus_skips_tag_torn_on_one_rank(tmp_path):
    from deepspeed_tpu.elasticity import select_consensus_tag
    _, dirs = _two_rank_dirs(tmp_path)
    newest = list_tags(dirs[0])[-1]
    # rank1's newest save was interrupted: torn leaf, size check catches it
    truncate_leaf(os.path.join(dirs[1], newest), "params.layer_0.w")
    assert is_valid_tag(dirs[0], newest)            # rank0 still thinks newest is fine
    tag = select_consensus_tag(dirs)
    assert tag == list_tags(dirs[0])[-2]            # whole group steps back
    assert tag != newest


def test_consensus_with_bitflip_needs_integrity_pass(tmp_path):
    from deepspeed_tpu.elasticity import select_consensus_tag
    _, dirs = _two_rank_dirs(tmp_path)
    newest = list_tags(dirs[0])[-1]
    corrupt_leaf(os.path.join(dirs[1], newest), "params.layer_0.w")  # size-preserving
    # size/completeness checks can't see a same-size bitflip...
    assert select_consensus_tag(dirs) == newest
    # ...the CRC pass can, and the consensus walk steps the whole group back
    assert select_consensus_tag(dirs, verify_integrity=True) == list_tags(dirs[0])[-2]


def test_consensus_when_one_rank_never_saved_newest(tmp_path):
    from deepspeed_tpu.elasticity import select_consensus_tag
    engine, dirs = _two_rank_dirs(tmp_path, steps=2)
    train(engine, 1)
    engine.save_checkpoint(dirs[0])  # rank1 died before its step-3 save landed
    assert len(list_tags(dirs[0])) == 3 and len(list_tags(dirs[1])) == 2
    assert select_consensus_tag(dirs) == list_tags(dirs[1])[-1]


def test_consensus_dropped_metadata_steps_back(tmp_path):
    from deepspeed_tpu.elasticity import select_consensus_tag
    _, dirs = _two_rank_dirs(tmp_path)
    newest = list_tags(dirs[0])[-1]
    drop_metadata(os.path.join(dirs[1], newest))
    assert select_consensus_tag(dirs) == list_tags(dirs[0])[-2]


def test_consensus_none_when_no_common_valid_tag(tmp_path):
    from deepspeed_tpu.elasticity import select_consensus_tag
    engine = make_engine()
    train(engine, 1)
    d0 = str(tmp_path / "rank0")
    engine.save_checkpoint(d0)
    assert select_consensus_tag([d0, str(tmp_path / "rank1_empty")]) is None
    assert select_consensus_tag([]) is None
    assert select_consensus_tag(["", None]) is None


def test_agent_resume_pin_matches_fallback_walk(tmp_path):
    """The agent's consensus choice must equal what a single rank's
    fallback_to_valid load would pick over the same (damaged) directory —
    same validation, same walk order."""
    from deepspeed_tpu.elasticity import select_consensus_tag
    engine, dirs = _two_rank_dirs(tmp_path)
    newest = list_tags(dirs[1])[-1]
    truncate_leaf(os.path.join(dirs[1], newest), "params.layer_0.w")
    tag = select_consensus_tag(dirs)
    assert tag == find_latest_valid_tag(dirs[1])
    engine2 = make_engine()
    loaded_tag, _ = engine2.load_checkpoint(dirs[1], fallback_to_valid=True)
    assert loaded_tag == tag


@pytest.mark.slow
def test_agent_consensus_skips_harness_corrupted_tag_end_to_end(tmp_path):
    """Full loop with the distributed fault-injection harness: rank 1
    truncates a leaf of its newest tag (torn save) and crashes; the agent's
    consensus walk must step the WHOLE group past the torn tag, and the next
    generation (respawned at the same world — min valid size) must resume
    from it and finish with reference-exact losses."""
    import subprocess
    import sys

    from deepspeed_tpu.elasticity import DSElasticAgent

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    worker_cmd = [sys.executable, "-u",
                  os.path.join(root, "tests", "unit", "elastic_worker.py")]
    tmp = str(tmp_path)
    faults = [
        # order matters: truncate the newest tag (global_step2), THEN die —
        # both fire on rank 1's step 3, before the step-3 save lands; the
        # crash awaits global_step1 everywhere so the consensus walk always
        # has the common tag this test asserts on (startup skew de-raced)
        {"mode": "corrupt_newest", "rank": 1, "step": 3, "gen": 0},
        {"mode": "crash", "rank": 1, "step": 3, "gen": 0,
         "await_tag": "global_step1"},
    ]
    env = dict(os.environ, ELASTIC_TMP=tmp, ELASTIC_STEPS="6",
               ELASTIC_FAULTS=json.dumps(faults))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    agent = DSElasticAgent(
        worker_cmd, world_size=2,
        # min valid world == 2: the respawn keeps BOTH ranks, so the consensus
        # walk must span both checkpoint dirs (incl. the corrupted one)
        elastic_config={"max_train_batch_size": 8, "micro_batch_sizes": [1, 2],
                        "min_gpus": 2, "max_gpus": 2},
        max_restarts=2, poll_interval=0.1, env=env,
        checkpoint_dir=os.path.join(tmp, "ckpt"), per_rank_checkpoints=True,
        term_grace_secs=10.0)
    from tests.unit.test_elastic_agent import run_bounded
    assert run_bounded(agent, timeout=120.0) == 0
    assert agent.restart_count == 1
    # rank1's dir held gs1 + TORN gs2 at the crash: consensus must land on gs1
    assert agent.resume_tags[1] == "global_step1"
    for rank in range(2):
        marker = os.path.join(tmp, f"resume.gen1.rank{rank}")
        assert open(marker).read().strip() == "global_step1"
        assert os.path.exists(os.path.join(tmp, f"done.gen1.rank{rank}"))


def test_engine_honors_agent_pinned_resume_tag(tmp_path, monkeypatch):
    """load_checkpoint(tag=None) resumes from DSTPU_RESUME_TAG when the
    elastic agent pinned one — 'latest' would point each rank at its own
    (possibly divergent) newest; an explicit tag argument still wins."""
    from deepspeed_tpu.runtime.heartbeat import RESUME_TAG_ENV

    engine = make_engine()
    train(engine, 1)
    tag1 = engine.save_checkpoint(str(tmp_path))
    train(engine, 1)
    tag2 = engine.save_checkpoint(str(tmp_path))
    assert tag1 != tag2

    monkeypatch.setenv(RESUME_TAG_ENV, tag1)
    engine2 = make_engine()
    loaded, _ = engine2.load_checkpoint(str(tmp_path))  # pin beats 'latest'
    assert loaded == tag1
    engine3 = make_engine()
    loaded, _ = engine3.load_checkpoint(str(tmp_path), tag=tag2)  # arg beats pin
    assert loaded == tag2

    monkeypatch.delenv(RESUME_TAG_ENV)
    engine4 = make_engine()
    loaded, _ = engine4.load_checkpoint(str(tmp_path))  # no pin: 'latest'
    assert loaded == tag2


def test_resume_pin_scoped_to_agent_checkpoint_dir(tmp_path, monkeypatch):
    """The pin only applies where the pinned tag exists: a worker loading a
    base/warm-start checkpoint from an UNRELATED directory must get that
    directory's own 'latest', not a hijacked (and there nonexistent) tag."""
    from deepspeed_tpu.runtime.heartbeat import RESUME_TAG_ENV

    engine = make_engine()
    train(engine, 1)
    train_tag = engine.save_checkpoint(str(tmp_path / "train"))  # global_step1
    train(engine, 1)
    base_tag = engine.save_checkpoint(str(tmp_path / "base"))    # global_step2
    assert train_tag != base_tag

    monkeypatch.setenv(RESUME_TAG_ENV, train_tag)
    engine2 = make_engine()
    # pinned tag absent from base/: 'latest' there, no CheckpointError
    loaded, _ = engine2.load_checkpoint(str(tmp_path / "base"))
    assert loaded == base_tag
    # ...while the agent-supervised dir still honors the pin
    engine3 = make_engine()
    loaded, _ = engine3.load_checkpoint(str(tmp_path / "train"))
    assert loaded == train_tag


def test_resume_pin_dir_scoping_beats_identical_tag_names(tmp_path, monkeypatch):
    """Tag names are the generic global_step<N>, so an unrelated base dir can
    hold a tag NAMED like the pin — the agent-exported DSTPU_RESUME_DIR must
    keep the pin from hijacking that load."""
    from deepspeed_tpu.runtime.heartbeat import RESUME_DIR_ENV, RESUME_TAG_ENV

    engine = make_engine()
    train(engine, 1)
    pin_tag = engine.save_checkpoint(str(tmp_path / "train"))      # global_step1
    train(engine, 1)
    engine.save_checkpoint(str(tmp_path / "train"))                # global_step2
    engine_b = make_engine()
    train(engine_b, 1)
    clash = engine_b.save_checkpoint(str(tmp_path / "base"))       # global_step1 too!
    train(engine_b, 1)
    base_latest = engine_b.save_checkpoint(str(tmp_path / "base"))  # global_step2
    assert clash == pin_tag and base_latest != pin_tag

    monkeypatch.setenv(RESUME_TAG_ENV, pin_tag)
    monkeypatch.setenv(RESUME_DIR_ENV, str(tmp_path / "train"))
    eng = make_engine()
    # base/ has an identically-NAMED tag, but it is outside the resume dir:
    # the warm-start load keeps its own 'latest'
    loaded, _ = eng.load_checkpoint(str(tmp_path / "base"))
    assert loaded == base_latest
    # the supervised dir still honors the pin over its newer 'latest'
    eng2 = make_engine()
    loaded, _ = eng2.load_checkpoint(str(tmp_path / "train"))
    assert loaded == pin_tag


def test_pinned_tag_validation_failure_refuses_fallback(tmp_path, monkeypatch):
    """A rank whose copy of the agent-pinned tag fails validation must FAIL
    (so the agent restarts and re-runs consensus), never silently fall back
    to its own per-rank newest valid tag — resuming a different tag than the
    peers is the exact divergence the pin exists to prevent."""
    from deepspeed_tpu.runtime.heartbeat import RESUME_TAG_ENV
    from .fault_injection import truncate_leaf

    engine = make_engine()
    train(engine, 1)
    tag1 = engine.save_checkpoint(str(tmp_path))
    train(engine, 1)
    tag2 = engine.save_checkpoint(str(tmp_path))
    truncate_leaf(os.path.join(str(tmp_path), tag2), "params.layer_0.w")

    monkeypatch.setenv(RESUME_TAG_ENV, tag2)
    engine2 = make_engine()
    with pytest.raises(CheckpointError, match="pinned resume tag"):
        engine2.load_checkpoint(str(tmp_path), fallback_to_valid=True)
    # without a pin the same fallback_to_valid load walks back normally
    monkeypatch.delenv(RESUME_TAG_ENV)
    engine3 = make_engine()
    loaded, _ = engine3.load_checkpoint(str(tmp_path), fallback_to_valid=True)
    assert loaded == tag1
