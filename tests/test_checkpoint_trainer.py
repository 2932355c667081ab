"""Checkpoint subsystem + ElasticTrainer + elastic data input.

The headline behavior under test is the reference's hardest trick made
native: save at one world size, restore at another
(``fsdp_save_util.py``'s reshard-on-load), via GSPMD + Orbax.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.checkpoint import (
    CheckpointInterval,
    ElasticCheckpointManager,
    abstract_like,
)
from dlrover_tpu.parallel.accelerate import accelerate
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.trainer.data import (
    ElasticDataLoader,
    ElasticDistributedSampler,
)
from dlrover_tpu.trainer.elastic import ElasticTrainer


def _mlp_init(rng):
    k1, k2 = jax.random.split(rng)
    return {
        "w1": jax.random.normal(k1, (16, 32)) * 0.1,
        "w2": jax.random.normal(k2, (32, 8)) * 0.1,
    }


def _mlp_loss(params, batch, rng):
    h = jnp.tanh(batch["x"] @ params["w1"])
    logits = h @ params["w2"]
    loss = jnp.mean((logits - batch["y"]) ** 2)
    return loss, {}


def _batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "x": rng.normal(size=(n, 16)).astype(np.float32),
        "y": rng.normal(size=(n, 8)).astype(np.float32),
    }


def _build(strategy, devices=None):
    return accelerate(
        _mlp_init, _mlp_loss, optax.adam(1e-2), _batch(),
        strategy=strategy, devices=devices,
    )


class TestCheckpointInterval:
    def test_step_cadence(self):
        iv = CheckpointInterval(steps=10)
        assert not iv.should_save(5)
        assert iv.should_save(10)
        iv.mark_saved(10)
        assert not iv.should_save(15)
        assert iv.should_save(20)


class TestElasticCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        res = _build(Strategy(mesh=MeshPlan(data=-1)))
        state = res.init_fn(jax.random.PRNGKey(0))
        mgr = ElasticCheckpointManager(str(tmp_path), async_save=False)
        assert mgr.save(0, state, metadata={"k": 1}, force=True)
        mgr.wait()

        target = abstract_like(state, res.state_sharding)
        out = mgr.restore(target)
        assert out is not None
        assert out["meta"]["k"] == 1
        np.testing.assert_allclose(
            np.asarray(out["state"].params["w1"]),
            np.asarray(state.params["w1"]),
        )
        mgr.close()

    def test_host_dram_staging_mirror_and_restore(self, tmp_path):
        """Flash-checkpoint parity: after the async save commits, the
        step is mirrored to the staging dir, and restore prefers it even
        when the primary directory is gone (the remote-storage-outage /
        fast-restart case)."""
        import os
        import shutil

        res = _build(Strategy(mesh=MeshPlan(data=-1)))
        state = res.init_fn(jax.random.PRNGKey(0))
        primary = tmp_path / "primary"
        staging = tmp_path / "shm_staging"
        mgr = ElasticCheckpointManager(
            str(primary), staging_dir=str(staging)
        )
        assert mgr.save(3, state, metadata={"k": 7}, force=True)
        mgr.wait()
        assert mgr.staged_step() == 3
        # only the newest step is kept staged
        state2, _ = res.train_step(
            state, res.shard_batch(_batch()), jax.random.PRNGKey(1)
        )
        assert mgr.save(5, state2, force=True)
        mgr.wait()
        assert mgr.staged_step() == 5
        assert not os.path.isdir(str(staging / "3"))

        # nuke the primary step dir: restore must come from staging
        shutil.rmtree(str(primary / "5"))
        target = abstract_like(state, res.state_sharding)
        out = mgr.restore(target, step=5)
        assert out is not None and out["step"] == 5
        np.testing.assert_allclose(
            np.asarray(out["state"].params["w1"]),
            np.asarray(state2.params["w1"]),
        )
        mgr.close()

    @pytest.mark.parametrize("path", ["restore", "restore_from_staging"])
    def test_a_restore_says_what_it_read(self, tmp_path, monkeypatch, path):
        """``ckpt_restore`` carries the ``source`` that was read, the
        restored state's ``bytes`` and, where a mirror is kept and was
        passed over, why (``mirror_skipped``), on both restore paths."""
        import shutil

        from dlrover_tpu.telemetry import read_events

        events = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events)
        res = _build(Strategy(mesh=MeshPlan(data=-1)))
        state = res.init_fn(jax.random.PRNGKey(0))
        size = sum(x.nbytes for x in jax.tree.leaves(state))
        staging = tmp_path / "shm_staging"
        mgr = ElasticCheckpointManager(str(tmp_path / "primary"),
                                       staging_dir=str(staging))
        assert mgr.save(3, state, force=True)
        mgr.wait()
        assert mgr.staged_step() == 3
        target = abstract_like(state, res.state_sharding)

        def restored():
            out = getattr(mgr, path)(target)
            assert out is not None and out["step"] == 3
            return [r for r in read_events(events)
                    if r["kind"] == "ckpt_restore"][-1]

        event = restored()
        assert event["source"] == "staging" and event["bytes"] == size
        assert event["restore_seconds"] >= 0
        assert "mirror_skipped" not in event
        if path == "restore":
            # a mirror that no longer matches the primary is passed
            # over, and one that is gone is absent: both are said
            with open(str(staging / "3.digest"), "w") as f:
                f.write("not the primary's")
            event = restored()
            assert event["source"] == "directory"
            assert event["mirror_skipped"] == "digest"
            assert event["bytes"] == size
            shutil.rmtree(str(staging / "3"))
            assert restored()["mirror_skipped"] == "absent"
        mgr.close()

    def test_stale_staging_from_previous_job_is_ignored(self, tmp_path):
        """A mirror left in tmpfs by a PREVIOUS job at the same
        checkpoint path must never be restored as the new job's weights:
        the staged digest is validated against the primary step dir."""
        import shutil

        res = _build(Strategy(mesh=MeshPlan(data=-1)))
        primary = tmp_path / "primary"
        staging = tmp_path / "shm_staging"

        old_state = res.init_fn(jax.random.PRNGKey(0))
        m1 = ElasticCheckpointManager(str(primary),
                                      staging_dir=str(staging))
        assert m1.save(5, old_state, force=True)
        m1.wait()
        assert m1.staged_step() == 5
        m1.close()

        # operator wipes the checkpoint dir and starts a fresh run at
        # the same path; the stale tmpfs mirror survives the restart
        shutil.rmtree(str(primary))
        new_state = res.init_fn(jax.random.PRNGKey(42))
        m2 = ElasticCheckpointManager(str(primary),
                                      staging_dir=str(staging))
        assert m2.save(5, new_state, force=True)
        m2.wait()

        out = m2.restore(
            abstract_like(new_state, res.state_sharding), step=5
        )
        np.testing.assert_allclose(
            np.asarray(out["state"].params["w1"]),
            np.asarray(new_state.params["w1"]),
        )
        assert not np.allclose(
            np.asarray(out["state"].params["w1"]),
            np.asarray(old_state.params["w1"]),
        )
        m2.close()

    def test_fresh_job_with_only_stale_staging_restores_nothing(
        self, tmp_path
    ):
        """A fresh job whose empty primary coexists with a stale staging
        mirror must get 'no checkpoint' (None), not a crash and not the
        old job's weights."""
        import shutil

        res = _build(Strategy(mesh=MeshPlan(data=-1)))
        primary = tmp_path / "primary"
        staging = tmp_path / "shm_staging"
        state = res.init_fn(jax.random.PRNGKey(0))
        m1 = ElasticCheckpointManager(str(primary),
                                      staging_dir=str(staging))
        assert m1.save(7, state, force=True)
        m1.wait()
        m1.close()

        # fresh job: wiped primary, stale mirror survives in tmpfs
        shutil.rmtree(str(primary))
        m2 = ElasticCheckpointManager(str(primary),
                                      staging_dir=str(staging))
        target = abstract_like(state, res.state_sharding)
        assert m2.restore(target) is None  # from scratch, no crash
        m2.close()

    def test_reshard_on_load_across_world_sizes(self, tmp_path):
        """Save on an 8-device fsdp mesh, restore onto a 4-device mesh."""
        res8 = _build(Strategy(mesh=MeshPlan(data=2, fsdp=4)))
        state = res8.init_fn(jax.random.PRNGKey(0))
        state, _ = res8.train_step(
            state, res8.shard_batch(_batch()), jax.random.PRNGKey(1)
        )
        mgr = ElasticCheckpointManager(str(tmp_path), async_save=False)
        mgr.save(int(state.step), state, force=True)
        mgr.wait()

        devices4 = jax.devices()[:4]
        res4 = _build(
            Strategy(mesh=MeshPlan(data=2, fsdp=2)),
            devices=devices4,
        )
        abstract = jax.eval_shape(res4.init_fn, jax.random.PRNGKey(0))
        target = abstract_like(abstract, res4.state_sharding)
        out = mgr.restore(target)
        assert out is not None
        restored = out["state"]
        # Values identical to the 8-device state, now on the 4-device mesh.
        np.testing.assert_allclose(
            np.asarray(restored.params["w1"]),
            np.asarray(state.params["w1"]),
            rtol=1e-6,
        )
        assert restored.params["w1"].sharding.mesh.devices.size == 4
        # And the restored state trains.
        restored, metrics = res4.train_step(
            restored, res4.shard_batch(_batch()), jax.random.PRNGKey(2)
        )
        assert np.isfinite(float(metrics["loss"]))
        mgr.close()

    def test_shard_checkpoint_rides_along(self, tmp_path):
        res = _build(Strategy(mesh=MeshPlan(data=-1)))
        state = res.init_fn(jax.random.PRNGKey(0))
        mgr = ElasticCheckpointManager(str(tmp_path), async_save=False)
        mgr.save(0, state, shard_checkpoint='{"todo": [[0, 64]]}', force=True)
        mgr.wait()
        out = mgr.restore(abstract_like(state, res.state_sharding))
        assert out["shard_checkpoint"] == '{"todo": [[0, 64]]}'
        mgr.close()

    def test_wait_surfaces_mirror_timeout(self, tmp_path):
        """A staging mirror that never commits must not be silently
        forgotten: wait() returns timed_out=True, logs the
        CKPT_MIRROR_TIMEOUT error code, and keeps the thread joinable
        for a later wait (ISSUE 3 satellite — the preemption drain
        needs to TELL that the mirror never committed)."""
        import threading

        mgr = ElasticCheckpointManager(str(tmp_path), async_save=False)
        release = threading.Event()
        stuck = threading.Thread(target=release.wait, daemon=True,
                                 name="stuck-mirror")
        stuck.start()
        mgr._mirror_threads = [stuck]
        assert mgr.wait(mirror_timeout=0.05) is True
        assert mgr._mirror_threads == [stuck]  # observable, not dropped
        # an already-flagged thread is only POLLED: back-to-back waits
        # (the preemption drain) must not re-pay the join timeout
        t0 = time.monotonic()
        assert mgr.wait(mirror_timeout=60.0) is True
        assert time.monotonic() - t0 < 5.0
        release.set()
        stuck.join(timeout=5.0)
        assert mgr.wait(mirror_timeout=5.0) is False
        assert mgr._mirror_threads == []
        mgr.close()

    def test_superseded_step_mirror_stops_polling(self, tmp_path):
        """max_to_keep can delete a step dir before its mirror thread
        ever sees it; the poll must bail when a NEWER step committed
        instead of spinning to the 600 s deadline (and stalling wait()
        for the full join timeout on every exit path)."""
        import time as _time

        mgr = ElasticCheckpointManager(
            str(tmp_path / "ckpt"), async_save=False,
            staging_dir=str(tmp_path / "shm"),
        )
        # a newer committed step exists; step 1 never will
        (tmp_path / "ckpt" / "5").mkdir()
        t0 = _time.monotonic()
        mgr._wait_and_mirror(1, deadline_s=30.0)
        assert _time.monotonic() - t0 < 5.0
        assert mgr.staged_step() != 1
        mgr.close()


class TestElasticTrainer:
    def test_train_and_resume(self, tmp_path):
        trainer = ElasticTrainer(
            _mlp_init, _mlp_loss, optax.adam(1e-2), _batch(),
            strategy=Strategy(mesh=MeshPlan(data=-1)),
            ckpt_dir=str(tmp_path),
        )
        state = trainer.prepare()
        losses = []
        batch = _batch()
        for _ in range(5):
            state, metrics = trainer.step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        trainer.save(state)
        trainer.finalize()

        # A fresh trainer resumes from the checkpoint.
        trainer2 = ElasticTrainer(
            _mlp_init, _mlp_loss, optax.adam(1e-2), _batch(),
            strategy=Strategy(mesh=MeshPlan(data=-1)),
            ckpt_dir=str(tmp_path),
        )
        state2 = trainer2.prepare()
        assert int(state2.step) == 5
        np.testing.assert_allclose(
            np.asarray(state2.params["w1"]), np.asarray(state.params["w1"])
        )
        trainer2.finalize()

    def test_on_world_change_reshards_state(self):
        trainer = ElasticTrainer(
            _mlp_init, _mlp_loss, optax.adam(1e-2), _batch(),
            strategy=Strategy(mesh=MeshPlan(data=2, fsdp=4)),
        )
        state = trainer.prepare()
        state, _ = trainer.step(state, _batch())
        w1_before = np.asarray(state.params["w1"])
        state = trainer.on_world_change(state)
        np.testing.assert_allclose(
            np.asarray(state.params["w1"]), w1_before
        )
        state, metrics = trainer.step(state, _batch(seed=3))
        assert np.isfinite(float(metrics["loss"]))


class TestElasticSampler:
    def test_partition_covers_all_indices(self):
        samplers = [
            ElasticDistributedSampler(100, num_shards=4, shard_rank=r,
                                      shuffle=False, drop_last=True)
            for r in range(4)
        ]
        seen = sorted(i for s in samplers for i in s)
        assert seen == list(range(100))

    def test_resume_skips_consumed(self):
        s = ElasticDistributedSampler(100, num_shards=2, shard_rank=0,
                                      shuffle=False)
        s.record_batch(40)
        remaining = list(s)
        assert min(remaining) >= 40
        assert len(remaining) == 30

    def test_reshard_after_world_change(self):
        s = ElasticDistributedSampler(96, num_shards=4, shard_rank=0,
                                      shuffle=False, drop_last=True)
        s.record_batch(32)
        s.reshard(num_shards=2, shard_rank=0)
        part0 = list(s)
        s.reshard(num_shards=2, shard_rank=1)
        part1 = list(s)
        assert sorted(part0 + part1) == list(range(32, 96))

    def test_pad_larger_than_remainder(self):
        # 1 remaining index, 4 shards: every shard must still yield one
        # sample (tiled padding) or SPMD hosts desync at the epoch tail.
        counts = []
        for r in range(4):
            s = ElasticDistributedSampler(97, num_shards=4, shard_rank=r,
                                          shuffle=False)
            s.record_batch(96)
            counts.append(len(list(s)))
        assert counts == [1, 1, 1, 1]

    def test_state_dict_roundtrip(self):
        s = ElasticDistributedSampler(50, shuffle=True, seed=7)
        s.set_epoch(2)
        s.record_batch(10)
        s2 = ElasticDistributedSampler(50, shuffle=True, seed=7)
        s2.load_state_dict(s.state_dict())
        assert list(s2) == list(s)


class TestElasticDataLoader:
    def test_batches_and_runtime_resize(self):
        data = [{"x": np.full((4,), i, np.float32)} for i in range(32)]
        loader = ElasticDataLoader(data, batch_size=8)
        batches = list(loader)
        assert len(batches) == 4
        assert batches[0]["x"].shape == (8, 4)
        loader.set_batch_size(16)
        assert len(list(loader)) == 2
