"""A save takes a device-side snapshot and a saver thread stages it
behind the next steps (ISSUE 30).

Deterministic: the saver is held on ``threading.Event``s, the tests
join and read events; nothing sleeps and nothing is timed against a
limit.
"""

import json
import logging
import os
import socket
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import dlrover_tpu.checkpoint.manager as manager_module
from dlrover_tpu.checkpoint import (
    CheckpointInterval,
    ElasticCheckpointManager,
    abstract_like,
)
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry import EventKind, get_registry, names as tm
from dlrover_tpu.telemetry.events import read_events
from dlrover_tpu.trainer.conf import Configuration
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.executor import TrainExecutor

HELD_S = 60  # a held saver gives up (and the test fails) after this


def _make_trainer(tmp_path, every=3, **kwargs):
    def init_fn(rng):
        return {"w": jax.random.normal(rng, (4, 2)), "b": jnp.zeros((2,))}

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    rngs = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(rngs[0], (16, 4))
    batch = {"x": x, "y": x @ jax.random.normal(rngs[1], (4, 2))}
    trainer = ElasticTrainer(
        init_fn, loss_fn, optax.sgd(0.1), batch,
        strategy=Strategy(mesh=MeshPlan(data=-1)),
        ckpt_dir=str(tmp_path / "ckpt"),
        ckpt_interval=CheckpointInterval(steps=every), **kwargs)
    return trainer, batch


def _host(tree):
    """A host copy that no later donation can touch."""
    return jax.tree.map(lambda x: np.array(x, copy=True),
                        jax.device_get(tree))


def _same_bytes(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


def _restored(trainer, state, directory, step=None):
    mgr = ElasticCheckpointManager(directory, async_save=False)
    try:
        return mgr.restore(abstract_like(
            state, trainer.accelerated.state_sharding), step=step)
    finally:
        mgr.close()


class _HeldStage:
    """``manager._stage`` behind a gate: staging begins only once
    ``gate`` is set, and ``log`` says in which order things happened."""

    def __init__(self, mgr, gate=None):
        self.gate = gate or threading.Event()
        self.log = []
        self.threads = []
        self._real = mgr._stage
        mgr._stage = self

    def __call__(self, step, *args, **kwargs):
        self.threads.append(threading.current_thread())
        assert self.gate.wait(HELD_S), "nobody opened the saver's gate"
        self.log.append(("stage", step))
        return self._real(step, *args, **kwargs)


def _events(path, kind):
    return [r for r in read_events(path) if r["kind"] == kind]


@pytest.fixture
def events_file(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
    return path


@pytest.fixture
def logged(caplog):
    """The program's log lines (its logger does not propagate)."""
    logger = logging.getLogger("dlrover_tpu")
    logger.addHandler(caplog.handler)
    yield caplog
    logger.removeHandler(caplog.handler)


class _Stats:
    """A device whose ``memory_stats()`` a test dictates."""

    def __init__(self, **stats):
        self.stats = stats

    def memory_stats(self):
        return dict(self.stats) if self.stats else None


class TestSnapshotSave:
    def test_save_returns_before_staging_and_the_snapshot_is_the_save_steps_state(
            self, tmp_path, events_file):
        trainer, batch = _make_trainer(tmp_path, every=3)
        held = _HeldStage(trainer._ckpt)
        state = trainer.prepare()
        for _ in range(3):
            state, metrics = trainer.step(state, batch)
        # the save step has returned: the save is begun, nothing staged
        assert trainer.saves_begun == 1
        (begun,) = _events(events_file, EventKind.CKPT_SAVE)
        assert begun["step"] == 3 and begun["mode"] == "snapshot"
        assert not _events(events_file, EventKind.CKPT_SAVE_STAGED)
        assert held.log == []
        want = _host(state)
        # the next steps donate the live state while the snapshot waits
        for _ in range(2):
            state, metrics = trainer.step(state, batch)
        assert float(metrics["loss"]) == float(metrics["loss"])
        assert held.log == []
        held.gate.set()
        assert trainer._ckpt.wait() is False
        assert held.log == [("stage", 3)]
        assert held.threads[0] is not threading.main_thread()
        out = _restored(trainer, state, trainer._ckpt.directory)
        assert out["step"] == 3
        assert _same_bytes(out["state"], want)
        assert not _same_bytes(out["state"], _host(state))
        trainer.finalize()

    def test_a_nonfinite_save_step_writes_nothing_and_a_later_one_commits(
            self, tmp_path, events_file, logged):
        trainer, batch = _make_trainer(tmp_path, every=2)
        nan_batch = {"x": batch["x"] * jnp.nan, "y": batch["y"]}
        state = trainer.prepare()
        mgr = trainer._ckpt
        dropped_before = get_registry().counter(tm.CKPT_DROPPED_SAVES).value
        state, _ = trainer.step(state, batch)
        state, metrics = trainer.step(state, nan_batch)  # step 2: due
        assert not bool(metrics["finite"])
        assert mgr.latest_step() is None  # drains the saver
        assert "skipping checkpoint at step 2: non-finite state" in (
            logged.text)
        assert not _events(events_file, EventKind.CKPT_SAVE_STAGED)
        # the training thread announced the save before the flag could
        # be read; the saver thread says that nothing came of it
        (begun,) = _events(events_file, EventKind.CKPT_SAVE)
        (dropped,) = _events(events_file, EventKind.CKPT_SAVE_DROPPED)
        assert begun["step"] == dropped["step"] == 2
        assert dropped["reason"] == "non_finite"
        assert dropped["mono"] > begun["mono"]
        assert get_registry().counter(
            tm.CKPT_DROPPED_SAVES).value == dropped_before + 1
        assert mgr.interval.should_save(3)  # the cadence is as it was
        # a finite state, e.g. after the executor's rollback or a halt's
        # restart: the next due step commits
        state = trainer.prepare(trainer.accelerated.init_fn(
            jax.random.PRNGKey(1)))
        mgr.interval.mark_saved(0)
        for _ in range(2):
            state, metrics = trainer.step(state, batch)
        assert trainer.latest_checkpoint_step() == 2
        (staged,) = _events(events_file, EventKind.CKPT_SAVE_STAGED)
        assert staged["step"] == 2
        assert len(_events(events_file, EventKind.CKPT_SAVE_DROPPED)) == 1
        trainer.finalize()

    @pytest.mark.parametrize("reader", ["wait", "latest_checkpoint_step",
                                        "finalize", "restore_state"])
    def test_readers_of_the_manager_drain_the_saver(
            self, tmp_path, reader):
        """The saver stages only once somebody is draining it, so a
        reader that did not drain would see no step (and the held saver
        would give up)."""
        trainer, batch = _make_trainer(tmp_path, every=3)
        mgr = trainer._ckpt
        draining = threading.Event()
        held = _HeldStage(mgr, gate=draining)
        real_drain = mgr._drain

        def drain():
            draining.set()
            real_drain()

        state = trainer.prepare()
        for _ in range(3):
            state, _ = trainer.step(state, batch)
        want = _host(state)
        state, _ = trainer.step(state, batch)
        assert held.log == []
        mgr._drain = drain
        if reader == "wait":
            assert mgr.wait() is False
        elif reader == "latest_checkpoint_step":
            # the executor's rollback precondition
            assert trainer.latest_checkpoint_step() == 3
        elif reader == "finalize":
            assert trainer.finalize() is False
        else:  # the executor's rollback itself
            back = trainer.restore_state()
            assert int(back.step) == 3 and _same_bytes(back, want)
        assert held.log == [("stage", 3)]
        assert mgr._saver is None
        out = _restored(trainer, state, mgr.directory)
        assert out["step"] == 3 and _same_bytes(out["state"], want)
        if reader != "finalize":
            trainer.finalize()

    def test_the_executors_rollback_restores_the_newest_commit(
            self, tmp_path, events_file):
        """Save at step 2 (snapshot, staged behind steps 3 and 4), NaN
        at step 4: the rollback drains the saver and restores step 2."""
        trainer, batch = _make_trainer(tmp_path, every=2)
        nan_batch = {"x": batch["x"] * jnp.nan, "y": batch["y"]}
        armed = [True]

        def stream():  # the loop asks again after the rollback
            for i in range(10):
                if i == 3 and armed:
                    armed.clear()
                    yield nan_batch
                else:
                    yield batch

        TrainExecutor(
            trainer, train_iter_fn=stream,
            conf=Configuration({
                "train_steps": 6, "log_every_steps": 0,
                "train_window": 2, "preemption_grace": False,
                "check_finite_every_steps": 1,
                "on_nonfinite": "rollback"}),
        ).train_and_evaluate()
        (rolled,) = _events(events_file, EventKind.ROLLBACK_RESTORED)
        assert rolled["restored_step"] == 2
        saved = [e["step"] for e in _events(events_file,
                                            EventKind.CKPT_SAVE_STAGED)]
        assert saved[0] == 2 and 4 not in saved[:1]

    def test_a_run_that_fails_one_step_after_a_save_step_still_commits_it(
            self, tmp_path, events_file):
        """The save of step 3 is with the saver thread when the run
        raises: the executor commits it before the error comes out, and
        leaves no thread behind to write into a later run's files."""
        trainer, batch = _make_trainer(tmp_path, every=3)
        mgr = trainer._ckpt
        draining, failed = threading.Event(), []
        held = _HeldStage(mgr, gate=draining)
        real_drain = mgr._drain

        def drain():
            if failed:
                draining.set()
            real_drain()

        mgr._drain = drain

        def stream():
            for _ in range(4):
                yield batch
            failed.append(True)
            raise RuntimeError("boom one step after the save")

        with pytest.raises(RuntimeError, match="boom one step after"):
            TrainExecutor(
                trainer, train_iter_fn=stream,
                conf=Configuration({
                    "train_steps": 9, "log_every_steps": 0,
                    "train_window": 2, "preemption_grace": False}),
            ).train_and_evaluate()
        assert held.log == [("stage", 3)]
        assert mgr._saver is None
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("ckpt-saver")]
        assert os.path.isdir(os.path.join(mgr.directory, "3"))
        assert mgr.latest_step() == 3
        (staged,) = _events(events_file, EventKind.CKPT_SAVE_STAGED)
        assert staged["step"] == 3
        trainer.finalize()

    def test_the_saver_is_no_daemon_thread(self, tmp_path):
        """An exiting process joins it: no thread is left inside JAX
        or Orbax while the interpreter is torn down."""
        trainer, batch = _make_trainer(tmp_path, every=1)
        held = _HeldStage(trainer._ckpt)
        state = trainer.prepare()
        trainer.step(state, batch)
        assert trainer._ckpt._saver.daemon is False
        held.gate.set()
        trainer.finalize()

    @pytest.mark.parametrize("given", ["the_step", "nothing"])
    def test_a_save_is_labelled_with_its_states_step(self, tmp_path, given):
        """The loop hands ``save`` the step it dispatched; a caller
        that hands none has the step read from the state it passes,
        which need not be the newest one."""
        trainer, batch = _make_trainer(tmp_path, every=100)
        state = trainer.prepare()
        for _ in range(2):
            state, _ = trainer.step(state, batch)
        older = jax.tree.map(jnp.copy, state)
        want = _host(older)
        state, _ = trainer.step(state, batch)
        assert trainer._host_step == 3
        if given == "the_step":
            assert trainer.save(older, step=2)
        else:
            assert trainer.save(older)
        assert trainer.latest_checkpoint_step() == 2
        out = _restored(trainer, state, trainer._ckpt.directory)
        assert out["step"] == 2 and _same_bytes(out["state"], want)
        trainer.finalize()

    def test_a_second_save_waits_for_the_first_snapshot_and_both_commit_in_order(
            self, tmp_path, events_file):
        trainer, batch = _make_trainer(tmp_path, every=1)
        mgr = trainer._ckpt
        held = _HeldStage(mgr)
        real_drain = mgr._drain
        alive_at_drain = []

        def drain():
            # the second save comes for the manager while the first
            # snapshot is alive: only now may the first be staged
            alive_at_drain.append(
                mgr._saver is not None and mgr._saver.is_alive())
            held.gate.set()
            real_drain()
            alive_at_drain.append(mgr._saver is not None)

        state = trainer.prepare()
        state, _ = trainer.step(state, batch)  # save 1: its saver is held
        first = mgr._saver
        assert first.is_alive() and held.log == []
        mgr._drain = drain
        state, _ = trainer.step(state, batch)  # save 2 waits for save 1
        assert alive_at_drain == [True, False]
        assert not first.is_alive() and mgr._saver is not first
        mgr.wait()
        assert held.log == [("stage", 1), ("stage", 2)]
        staged = [e["step"] for e in _events(events_file,
                                             EventKind.CKPT_SAVE_STAGED)]
        assert staged == [1, 2]
        assert sorted(mgr._manager.all_steps()) == [1, 2]
        trainer.finalize()

    def test_a_failed_staging_is_raised_where_the_manager_is_next_used(
            self, tmp_path):
        trainer, batch = _make_trainer(tmp_path, every=2)
        mgr = trainer._ckpt

        def broken(*args, **kwargs):
            raise OSError("disk gone")

        mgr._stage = broken
        state = trainer.prepare()
        for _ in range(2):
            state, _ = trainer.step(state, batch)  # save() itself returns
        with pytest.raises(OSError, match="disk gone"):
            mgr.wait()
        mgr.close()  # raised once


class TestMemoryRule:
    GB = 10 ** 9

    def _mgr(self, tmp_path):
        return ElasticCheckpointManager(str(tmp_path / "rule"))

    def test_no_stat_has_room(self, tmp_path):
        mgr = self._mgr(tmp_path)
        assert mgr._room_for_snapshot({_Stats(): 4 * self.GB})
        assert mgr._room_for_snapshot({})
        mgr.close()

    @pytest.mark.parametrize("stats,room", [
        # the elastic cell's restarted worker on a v5e (chip, PR 30)
        (dict(bytes_limit=16_909_336_064, bytes_in_use=4_074_193_920,
              bytes_reserved=5_567_709_184,
              peak_bytes_in_use=8_968_520_192), True),
        # the step program's scratch leaves no room
        (dict(bytes_limit=16 * GB, bytes_in_use=4 * GB,
              bytes_reserved=9 * GB, peak_bytes_in_use=4 * GB), False),
        # what the allocator once held does not count: it is free now
        (dict(bytes_limit=16 * GB, bytes_in_use=4 * GB,
              bytes_reserved=0, peak_bytes_in_use=13 * GB), True),
        # exactly the state's bytes are enough
        (dict(bytes_limit=16 * GB, bytes_in_use=4 * GB,
              bytes_reserved=8 * GB), True),
        (dict(bytes_limit=16 * GB, bytes_in_use=4 * GB + 1,
              bytes_reserved=8 * GB), False),
    ])
    def test_room_is_read_from_the_devices_stats(self, tmp_path, stats,
                                                 room):
        mgr = self._mgr(tmp_path)
        need = 4_028_268_582 if stats["bytes_limit"] > 16 * self.GB else (
            4 * self.GB)
        assert mgr._room_for_snapshot({_Stats(**stats): need}) is room
        # every device must have room
        assert mgr._room_for_snapshot(
            {_Stats(**stats): need, _Stats(): need}) is room
        assert not mgr._room_for_snapshot({
            _Stats(**stats): need,
            _Stats(bytes_limit=need, bytes_in_use=1): need})
        mgr.close()

    def test_every_process_of_a_job_takes_the_same_path(self, tmp_path):
        """Two processes, one global mesh. At the first save process 1
        has no room, at the second both have: both stage the live state
        the first time and both snapshot the second, both steps commit,
        and each process restores its own rows."""
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        worker = os.path.join(os.path.dirname(__file__), "testdata",
                              "ckpt_room_worker.py")
        root = os.path.dirname(os.path.dirname(__file__))
        procs = [subprocess.Popen(
            [sys.executable, worker, str(port), str(p),
             str(tmp_path / "ckpt")],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                 "PYTHONPATH": root,
                 "DLROVER_TPU_EVENTS_FILE": str(tmp_path / f"ev{p}.jsonl")},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for p in range(2)]
        try:
            outs = [proc.communicate(timeout=240) for proc in procs]
        finally:
            for proc in procs:
                proc.kill()
        for p, (proc, (out, err)) in enumerate(zip(procs, outs)):
            assert proc.returncode == 0, err[-3000:]
            assert json.loads(out.strip().splitlines()[-1]) == {
                "process": p, "modes": ["blocking", "snapshot"],
                "latest": 2}

    def test_one_process_agrees_with_itself_without_a_coordinator(
            self, tmp_path):
        mgr = self._mgr(tmp_path)
        assert mgr._every_process_has_room(True) is True
        assert mgr._every_process_has_room(False) is False
        mgr.close()

    @pytest.mark.parametrize("mode", ["snapshot", "blocking", "sync"])
    def test_either_mode_writes_the_same_checkpoint(
            self, tmp_path, events_file, monkeypatch, mode):
        trainer, batch = _make_trainer(tmp_path, every=100)
        state = trainer.prepare()
        state, metrics = trainer.step(state, batch)
        want = _host(state)
        mgr = ElasticCheckpointManager(
            str(tmp_path / mode), async_save=(mode != "sync"))
        if mode == "blocking":  # a device without room
            monkeypatch.setattr(
                "dlrover_tpu.checkpoint.manager._bytes_by_device",
                lambda tree: {_Stats(bytes_limit=100, bytes_in_use=60): 50})
        before = {m: mgr._c_mode[m].value for m in mgr._c_mode}
        assert mgr.save(1, state, metadata={"k": 1}, force=True,
                        finite=metrics["finite"])
        if mode == "sync":  # returns when the step is on disk
            assert (tmp_path / mode / "1").is_dir()
        mgr.wait()
        (begun,) = _events(events_file, EventKind.CKPT_SAVE)
        (staged,) = _events(events_file, EventKind.CKPT_SAVE_STAGED)
        expect = "snapshot" if mode == "snapshot" else "blocking"
        assert begun["mode"] == expect and begun["forced"] is True
        assert begun["step"] == staged["step"] == 1
        on_devices = sum(shard.data.nbytes
                         for x in jax.tree.leaves(state)
                         for shard in x.addressable_shards)
        assert staged["snapshot_bytes"] == (
            on_devices if mode == "snapshot" else 0)
        after = {m: mgr._c_mode[m].value for m in mgr._c_mode}
        assert {m: after[m] - before[m] for m in after} == {
            "snapshot": int(expect == "snapshot"),
            "blocking": int(expect == "blocking")}
        mgr.close()
        out = _restored(trainer, state, str(tmp_path / mode))
        assert out["step"] == 1 and out["meta"]["k"] == 1
        assert _same_bytes(out["state"], want)
        trainer.finalize()

    def test_a_blocking_save_of_a_nonfinite_state_writes_nothing(
            self, tmp_path, events_file, logged):
        mgr = ElasticCheckpointManager(str(tmp_path / "b"), async_save=False)
        state = {"w": jnp.ones((4,))}
        assert not mgr.save(5, state, force=True,
                            finite=jnp.array([True, False]))
        assert "skipping checkpoint at step 5" in logged.text
        assert mgr.latest_step() is None
        assert not _events(events_file, EventKind.CKPT_SAVE)
        assert mgr.save(6, state, force=True, finite=jnp.array(True))
        assert mgr.latest_step() == 6
        mgr.close()


class TestWhatTheEventsMean:
    def test_stage_seconds_is_the_training_threads_and_copy_seconds_the_savers(
            self, tmp_path, events_file, monkeypatch):
        """The staging is held until the save step has returned, so the
        training thread cannot have spent it; the saver's event is its
        own thread's."""
        trainer, batch = _make_trainer(tmp_path, every=2)
        mgr = trainer._ckpt
        held = _HeldStage(mgr)
        emitted = {}
        real_emit = manager_module.emit_event

        def emit(kind, **fields):
            emitted[kind] = threading.current_thread()
            return real_emit(kind, **fields)

        monkeypatch.setattr(manager_module, "emit_event", emit)
        state = trainer.prepare()
        for _ in range(2):
            state, _ = trainer.step(state, batch)
        (begun,) = _events(events_file, EventKind.CKPT_SAVE)
        assert emitted[EventKind.CKPT_SAVE] is threading.main_thread()
        assert EventKind.CKPT_SAVE_STAGED not in emitted
        # the branch's seconds hold the manager call's
        assert 0 <= begun["stage_seconds"] <= trainer.save_seconds
        held.gate.set()
        mgr.wait()
        (staged,) = _events(events_file, EventKind.CKPT_SAVE_STAGED)
        assert emitted[EventKind.CKPT_SAVE_STAGED] is held.threads[0]
        assert staged["copy_seconds"] >= 0
        assert staged["mono"] > begun["mono"]
        assert get_registry().counter(tm.CKPT_SNAPSHOT_SAVES).value >= 1
        trainer.finalize()


class TestTheLayoutOnDisk:
    @pytest.mark.parametrize("writer", ["parent", "change"])
    def test_a_checkpoint_of_the_parent_restores_under_the_change_and_the_reverse(
            self, tmp_path, writer):
        """The parent (PR 29) wrote ``Composite(state=StandardSave,
        meta=JsonSave, data_shards=JsonSave)`` through Orbax's manager
        and read it back the same way; so does the change."""
        import orbax.checkpoint as ocp

        trainer, batch = _make_trainer(tmp_path, every=100)
        state = trainer.prepare()
        state, _ = trainer.step(state, batch)
        want = _host(state)
        directory = str(tmp_path / writer)
        target = abstract_like(state, trainer.accelerated.state_sharding)
        if writer == "parent":
            raw = ocp.CheckpointManager(
                directory, options=ocp.CheckpointManagerOptions(
                    max_to_keep=3, enable_async_checkpointing=True))
            raw.save(1, args=ocp.args.Composite(
                state=ocp.args.StandardSave(jax.tree.map(jnp.copy, state)),
                meta=ocp.args.JsonSave({"strategy": "s"}),
                data_shards=ocp.args.JsonSave({"checkpoint": "shards"})))
            raw.wait_until_finished()
            raw.close()
            out = _restored(trainer, state, directory)
            got, meta, shards = (out["state"], out["meta"],
                                 out["shard_checkpoint"])
        else:
            mgr = ElasticCheckpointManager(directory)
            assert mgr.save(1, state, metadata={"strategy": "s"},
                            shard_checkpoint="shards", force=True)
            mgr.wait()
            mgr.close()
            raw = ocp.CheckpointManager(
                directory, options=ocp.CheckpointManagerOptions(
                    enable_async_checkpointing=False, read_only=True))
            back = raw.restore(1, args=ocp.args.Composite(
                state=ocp.args.StandardRestore(target),
                meta=ocp.args.JsonRestore(),
                data_shards=ocp.args.JsonRestore()))
            raw.close()
            got, meta, shards = (back["state"], back["meta"],
                                 back["data_shards"]["checkpoint"])
        assert _same_bytes(got, want)
        assert meta["strategy"] == "s" and shards == "shards"
        trainer.finalize()


class TestTheCadence:
    def test_a_save_that_wrote_nothing_hands_the_cadence_back(self):
        interval = CheckpointInterval(steps=5)
        assert interval.should_save(5)
        was = interval.mark_saved(5)
        assert not interval.should_save(6)
        interval.unmark(5, was)
        assert interval.should_save(6)
        # unless a later save was marked meanwhile
        was = interval.mark_saved(10)
        interval.mark_saved(15)
        interval.unmark(10, was)
        assert not interval.should_save(16)
