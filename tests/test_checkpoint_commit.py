"""Checkpoint commit protocol (ISSUE 6): two-phase sharded saves,
inventory verification, torn-dir garbage collection, and resume-exact
ingest state over streaming_split iterators.

Uses the module-scoped shared cluster only for the ingest tests (object
store); the commit-protocol tests are pure-filesystem.
"""

import json
import os
import pickle
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu import data as rd
from ray_tpu.train import Checkpoint, verify_sharded_checkpoint
from ray_tpu.train._internal.storage import StorageContext
from ray_tpu.train.checkpoint import _done_markers, is_committed
from ray_tpu.util.chaos import ChaosFault, FaultSchedule
from ray_tpu._private import chaos as chaos_core


@pytest.fixture(autouse=True)
def _reset_chaos():
    yield
    chaos_core.reset()


def _tree():
    import jax.numpy as jnp

    return {
        "w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
        "b": jnp.ones((4,)),
        "step": 3,
    }


# ---------------------------------------------------------------------------
# Two-phase save: DONE markers, inventory, atomicity
# ---------------------------------------------------------------------------

def test_save_writes_done_marker_and_inventory(tmp_path):
    train.save_pytree(str(tmp_path), _tree())
    markers = _done_markers(str(tmp_path))
    assert 0 in markers
    files = markers[0]["files"]
    # Every shard/idx/scalar file plus the treedef is inventoried with its
    # true size; the manifest deliberately is not (merge rewrites it).
    assert "treedef.pkl" in files
    assert any(f.endswith(".npy") for f in files)
    assert any(f.endswith(".idx.json") for f in files)
    for rel, meta in files.items():
        assert os.path.getsize(os.path.join(tmp_path, rel)) == meta["size"]
    assert "manifest.json" not in files
    ok, reason = verify_sharded_checkpoint(str(tmp_path))
    assert ok, reason
    # Atomic small-file writes: no tmp leftovers anywhere in the tree.
    leftovers = [
        os.path.join(root, f)
        for root, _, names in os.walk(tmp_path)
        for f in names
        if ".tmp." in f
    ]
    assert leftovers == []


def test_verify_rejects_missing_marker_and_corruption(tmp_path):
    train.save_pytree(str(tmp_path), _tree())

    # Corrupt one inventoried shard file → CRC/size mismatch.
    shard_dir = os.path.join(tmp_path, "shards", "p0")
    npy = next(f for f in os.listdir(shard_dir) if f.endswith(".npy"))
    with open(os.path.join(shard_dir, npy), "ab") as f:
        f.write(b"garbage")
    ok, reason = verify_sharded_checkpoint(str(tmp_path))
    assert not ok and npy in reason

    with pytest.raises(IOError, match="inventory verification"):
        train.load_pytree(str(tmp_path))


def test_verify_rejects_torn_save_without_done(tmp_path):
    train.save_pytree(str(tmp_path), _tree())
    os.remove(os.path.join(tmp_path, "DONE.p0"))
    ok, reason = verify_sharded_checkpoint(str(tmp_path))
    assert not ok and "DONE.p0" in reason


def test_verify_rejects_missing_writer_rank(tmp_path):
    # A sharded save that claims two writers but only rank 0 landed.
    train.save_pytree(str(tmp_path), _tree(), world_size=2)
    ok, reason = verify_sharded_checkpoint(str(tmp_path))
    assert not ok and "DONE.p1" in reason


def test_verify_passes_opaque_user_dir(tmp_path):
    with open(tmp_path / "weights.bin", "wb") as f:
        f.write(b"\x00" * 64)
    ok, reason = verify_sharded_checkpoint(str(tmp_path))
    assert ok


def test_midsave_failpoint_leaves_unverifiable_dir(tmp_path):
    """A kill between shard write and commit marker (the chaos failpoint
    models SIGKILL) leaves a dir that verification rejects."""
    chaos_core.install(
        FaultSchedule(seed=0, fail_points={"train.checkpoint.mid_save": 1}),
        export_env=False,
    )
    with pytest.raises(ChaosFault):
        train.save_pytree(str(tmp_path), _tree())
    # Shards are on disk but no DONE marker: torn, and verification says so.
    assert os.path.isdir(os.path.join(tmp_path, "shards", "p0"))
    ok, _ = verify_sharded_checkpoint(str(tmp_path))
    assert not ok
    with pytest.raises(IOError):
        train.load_pytree(str(tmp_path))


# ---------------------------------------------------------------------------
# Leaf-key escaping / collisions
# ---------------------------------------------------------------------------

def test_leaf_key_separator_escaping_roundtrip(tmp_path):
    import jax.numpy as jnp

    tree = {
        "a.b": jnp.full((2,), 1.0),
        "a": {"b": jnp.full((2,), 2.0)},
        "x/y": jnp.full((2,), 3.0),
    }
    train.save_pytree(str(tmp_path), tree)
    loaded = train.load_pytree(str(tmp_path))
    np.testing.assert_array_equal(np.asarray(loaded["a.b"]), [1.0, 1.0])
    np.testing.assert_array_equal(np.asarray(loaded["a"]["b"]), [2.0, 2.0])
    np.testing.assert_array_equal(np.asarray(loaded["x/y"]), [3.0, 3.0])


# ---------------------------------------------------------------------------
# StorageContext: commit stamp, GC, fallback
# ---------------------------------------------------------------------------

def _mk_ckpt_dir(tmp_path, name="src"):
    import tempfile

    src = tempfile.mkdtemp(prefix=name)
    train.save_pytree(src, _tree())
    return src


def test_persist_stamps_commit_and_cleans_staging(tmp_path):
    storage = StorageContext(str(tmp_path), "exp")
    persisted = storage.persist(Checkpoint(_mk_ckpt_dir(tmp_path)), {"loss": 1.0})
    assert is_committed(persisted.path)
    with open(os.path.join(persisted.path, "COMMIT.json")) as f:
        commit = json.load(f)
    assert commit["metrics"] == {"loss": 1.0}
    assert not any(
        n.endswith(".staging") for n in os.listdir(storage.trial_dir)
    )


def test_persist_refuses_torn_checkpoint(tmp_path):
    storage = StorageContext(str(tmp_path), "exp")
    src = _mk_ckpt_dir(tmp_path)
    os.remove(os.path.join(src, "DONE.p0"))
    with pytest.raises(IOError, match="torn"):
        storage.persist(Checkpoint(src), {})
    assert storage.latest_checkpoint() is None


def test_precommit_failpoint_then_reconcile(tmp_path):
    """Kill between staging and COMMIT: the next StorageContext GCs the
    staging leftover and recovery sees only the previous committed dir."""
    storage = StorageContext(str(tmp_path), "exp")
    first = storage.persist(Checkpoint(_mk_ckpt_dir(tmp_path)), {"step": 0})

    chaos_core.install(
        FaultSchedule(seed=0, fail_points={"train.storage.pre_commit": 1}),
        export_env=False,
    )
    with pytest.raises(ChaosFault):
        storage.persist(Checkpoint(_mk_ckpt_dir(tmp_path)), {"step": 1})
    chaos_core.reset()
    assert any(
        n.endswith(".staging") for n in os.listdir(storage.trial_dir)
    )

    fresh = StorageContext(str(tmp_path), "exp")
    assert not any(
        n.endswith(".staging") for n in os.listdir(fresh.trial_dir)
    )
    assert fresh.latest_checkpoint().path == first.path


def test_load_state_gcs_uncommitted_and_adopts_committed(tmp_path):
    storage = StorageContext(str(tmp_path), "exp")
    committed = storage.persist(Checkpoint(_mk_ckpt_dir(tmp_path)), {"step": 0})

    # An uncommitted dir (crash before COMMIT) sorting AFTER the committed
    # one: the old code would hand it to recovery and crash-loop.
    torn = os.path.join(storage.trial_dir, "checkpoint_000007")
    os.makedirs(os.path.join(torn, "shards", "p0"))
    with open(os.path.join(torn, "manifest.json"), "w") as f:
        json.dump({"leaves": {}, "world_size": 1}, f)

    fresh = StorageContext(str(tmp_path), "exp")
    assert not os.path.isdir(torn)
    assert fresh.latest_checkpoint().path == committed.path


def test_load_state_survives_torn_state_file(tmp_path):
    storage = StorageContext(str(tmp_path), "exp")
    committed = storage.persist(Checkpoint(_mk_ckpt_dir(tmp_path)), {"step": 0})
    # Torn .storage_state.json (crash mid-json.dump in the old code).
    with open(storage._state_path, "w") as f:
        f.write('{"index": 1, "kept": [["')
    fresh = StorageContext(str(tmp_path), "exp")
    assert fresh.latest_checkpoint().path == committed.path
    # And the index advanced past the adopted dir: no overwrite next save.
    assert fresh._index >= 1


def test_latest_checkpoint_falls_back_past_tampered_dir(tmp_path):
    storage = StorageContext(str(tmp_path), "exp")
    first = storage.persist(Checkpoint(_mk_ckpt_dir(tmp_path)), {"step": 0})
    second = storage.persist(Checkpoint(_mk_ckpt_dir(tmp_path)), {"step": 1})
    os.remove(os.path.join(second.path, "COMMIT.json"))
    assert storage.latest_checkpoint().path == first.path
    assert not os.path.isdir(second.path)


# ---------------------------------------------------------------------------
# Resume-exact ingest: iterator state over streaming_split
# ---------------------------------------------------------------------------

def _consume(iterator, batches, batch_size=8):
    out = []
    it = iterator.iter_batches(batch_size=batch_size, batch_format="numpy")
    for _ in range(batches):
        try:
            out += [int(x) for x in next(it)["id"]]
        except StopIteration:
            break
    return out


def test_iterator_state_dict_resume_equal_world(ray_start_shared):
    ds = rd.range(100, parallelism=5).materialize()
    shards = ds.streaming_split(2)
    assert all(s.supports_state for s in shards)

    seen = [_consume(s, batches=3) for s in shards]
    states = [s.state_dict() for s in shards]
    assert all(st["rows"] == 24 for st in states)

    resumed = ds.streaming_split(2, resume_from={
        "world_size": 2, "per_rank": states,
    })
    rest = [
        [int(x) for x in b["id"]]
        for s in resumed
        for b in s.iter_batches(batch_size=8)
    ]
    all_ids = sorted(
        i for chunk in seen for i in chunk
    ) + sorted(i for chunk in rest for i in chunk)
    # Exact parity: no sample dropped, none duplicated.
    assert sorted(all_ids) == list(range(100))


def test_iterator_state_dict_resume_shrunken_world(ray_start_shared):
    ds = rd.range(96, parallelism=6).materialize()
    shards = ds.streaming_split(3)
    seen = []
    states = []
    for s in shards:
        seen += _consume(s, batches=2, batch_size=4)
        states.append(s.state_dict())

    # Restart at world size 1: the single survivor re-reads exactly the
    # remaining sample space of all three old ranks.
    resumed = ds.streaming_split(1, resume_from={
        "world_size": 3, "per_rank": states,
    })
    rest = [
        int(x)
        for b in resumed[0].iter_batches(batch_size=16)
        for x in b["id"]
    ]
    assert sorted(seen + rest) == list(range(96))


def test_iterator_epoch_advances_and_resume_is_one_shot(ray_start_shared):
    ds = rd.range(20, parallelism=2).materialize()
    shard = ds.streaming_split(1)[0]
    first = [
        int(x) for b in shard.iter_batches(batch_size=8) for x in b["id"]
    ]
    assert sorted(first) == list(range(20))
    st = shard.state_dict()
    assert st["epoch"] == 1 and st["rows"] == 0

    # Resume mid-epoch, finish it, then the NEXT pass is a full epoch again.
    shard2 = ds.streaming_split(1)[0]
    got = _consume(shard2, batches=1, batch_size=6)
    state = shard2.state_dict()
    shard3 = ds.streaming_split(1, resume_from={
        "world_size": 1, "per_rank": [state],
    })[0]
    rest = [
        int(x) for b in shard3.iter_batches(batch_size=6) for x in b["id"]
    ]
    assert sorted(got + rest) == list(range(20))
    full_again = [
        int(x) for b in shard3.iter_batches(batch_size=6) for x in b["id"]
    ]
    assert sorted(full_again) == list(range(20))


def test_factory_iterator_reports_no_state_support(ray_start_shared):
    ds = rd.range(10, parallelism=1)
    it = ds.iterator()
    assert not it.supports_state
    with pytest.raises(ValueError):
        it.load_state_dict({"epoch": 0, "rows": 0, "spans": []})


# ---------------------------------------------------------------------------
# Trainer end-to-end: mid-save kill → resume from previous committed ckpt;
# mid-epoch kill → resume-exact ingest at equal world size.
# ---------------------------------------------------------------------------

def _midsave_kill_loop(config):
    """Rank 0 arms the mid-save chaos failpoint once (marker-guarded) and
    hard-exits when it fires — modeling a SIGKILL between shard write and
    commit marker."""
    from ray_tpu.util.chaos import ChaosFault, FaultSchedule

    ctx = train.get_context()
    start = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        state, _ = train.load_pytree_checkpoint(ckpt)
        start = int(state["step"]) + 1
    for step in range(start, config["steps"]):
        checkpoint = None
        if ctx.get_world_rank() == 0:
            if step == config["kill_step"] and not os.path.exists(
                config["marker"]
            ):
                # The kill is to find a committed checkpoint to fall back
                # to, and this rank cannot count on one: a rank runs a
                # report ahead of the driver, which commits a round only
                # once every rank has reported it (and a rank that exits
                # right after its poll was answered can take that answer
                # with it). So wait for the commit itself.
                deadline = time.monotonic() + 120
                while not any(
                    is_committed(os.path.join(config["trial_dir"], name))
                    for name in os.listdir(config["trial_dir"])
                    if name.startswith("checkpoint_")
                ):
                    assert time.monotonic() < deadline, "nothing was committed"
                    time.sleep(0.05)
                open(config["marker"], "w").close()
                chaos_core.install(
                    FaultSchedule(
                        seed=0,
                        fail_points={"train.checkpoint.mid_save": 1},
                    ),
                    export_env=False,
                )
            try:
                checkpoint = train.save_pytree_checkpoint({"step": step})
            except ChaosFault:
                os._exit(1)
        train.report(
            {"step": step, "resumed": start > 0}, checkpoint=checkpoint
        )


def test_trainer_recovers_from_midsave_kill(ray_start_shared, tmp_path):
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig, ScalingConfig

    marker = str(tmp_path / "killed")
    trainer = JaxTrainer(
        _midsave_kill_loop,
        train_loop_config={
            "steps": 6, "kill_step": 2, "marker": marker,
            "trial_dir": StorageContext(str(tmp_path), "midsave").trial_dir,
        },
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            name="midsave",
            storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=2),
        ),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert os.path.exists(marker)  # the kill really happened
    assert result.metrics["step"] == 5
    assert result.metrics["resumed"] is True
    # Every surviving checkpoint dir is committed and inventory-verified —
    # the torn mid-save dir never reached storage.
    storage = StorageContext(str(tmp_path), "midsave")
    for ckpt, _ in storage.checkpoints():
        assert is_committed(ckpt.path)
        ok, reason = verify_sharded_checkpoint(ckpt.path)
        assert ok, reason
    state, _ = train.load_pytree_checkpoint(result.checkpoint)
    assert int(state["step"]) == 5
    assert any(r["reason"] == "gang_died" for r in result.resizes)


def _ingest_parity_loop(config):
    """Consume the dataset shard, logging delivered ids to a per-process
    file; rank 0 hard-exits mid-epoch once (marker-guarded)."""
    ctx = train.get_context()
    shard = train.get_dataset_shard("train")
    log = os.path.join(
        config["log_dir"],
        f"consumed_r{ctx.get_world_rank()}_{os.getpid()}.jsonl",
    )
    step = 0
    for batch in shard.iter_batches(batch_size=config["batch_size"]):
        ids = [int(x) for x in batch["id"]]
        with open(log, "a") as f:
            f.write(json.dumps(ids) + "\n")
        checkpoint = None
        if ctx.get_world_rank() == 0:
            checkpoint = train.save_pytree_checkpoint({"step": step})
        if (
            ctx.get_world_rank() == 0
            and step == config["kill_step"]
            and not os.path.exists(config["marker"])
        ):
            open(config["marker"], "w").close()
            os._exit(1)
        train.report(
            {"step": step, "world_size": ctx.get_world_size()},
            checkpoint=checkpoint,
        )
        step += 1
    train.report({"step": step, "epoch_done": True})


def _logged_ids(log_dir):
    ids = []
    for name in os.listdir(log_dir):
        if not name.startswith("consumed_"):
            continue
        with open(os.path.join(log_dir, name)) as f:
            for line in f:
                ids += json.loads(line)
    return ids


def test_trainer_ingest_resume_exact_equal_world(ray_start_shared, tmp_path):
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig, ScalingConfig

    n, batch = 96, 8
    ds = rd.range(n, parallelism=4).materialize()
    log_dir = tmp_path / "logs"
    log_dir.mkdir()
    trainer = JaxTrainer(
        _ingest_parity_loop,
        train_loop_config={
            "batch_size": batch,
            "kill_step": 2,
            "marker": str(tmp_path / "killed"),
            "log_dir": str(log_dir),
        },
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            name="ingest-equal",
            storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=2),
        ),
        datasets={"train": ds},
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert os.path.exists(tmp_path / "killed")
    ids = _logged_ids(str(log_dir))
    # Exact sample-set parity: the union of delivered samples is the full
    # dataset — nothing silently dropped across the kill/restart.
    assert sorted(set(ids)) == list(range(n))
    # Bounded duplication: only rows delivered after the last committed
    # round replay. A rank can be at most one lockstep round ahead of the
    # driver, and the round whose poll reply the death interrupted is also
    # lost — so at most 3 batches per rank replay (documented bound in
    # docs/fault_tolerance.md).
    assert len(ids) - n <= 3 * batch * 2
