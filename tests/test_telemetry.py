"""Telemetry subsystem: metrics registry + exposition, event timeline,
derived MTTR (preempt drain / NaN rollback in-process; hang relaunch is
covered by the chaos tests), the host spans in the profiler's trace,
the instrumented-run pins (zero recompiles, ≤5% overhead), lagged
master reporting, the exporter, the profiling windows (scheduled and
on demand) and the boot events."""

import glob
import json
import os
import signal
import tempfile
import time

import jax
import jax.numpy as jnp
import optax
import pytest

from dlrover_tpu.common.config import get_context
from dlrover_tpu.telemetry import events as events_mod
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry import (
    EventKind,
    derive_incidents,
    emit_event,
    mttr_report,
    names as tm,
    read_events,
    span,
)
from dlrover_tpu.telemetry.cli import main as telemetry_cli
from dlrover_tpu.telemetry.metrics import (
    Histogram,
    MetricsRegistry,
    get_registry,
    process_registry,
)
from dlrover_tpu.trainer.conf import Configuration
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.executor import (
    ReportModelInfoHook,
    TrainExecutor,
    TrainHook,
)


@pytest.fixture(autouse=True)
def _telemetry_on():
    """Every test starts from the default-enabled state and leaves the
    process-global Context clean for the rest of the tier-1 run."""
    ctx = get_context()
    prev = ctx.telemetry_enabled
    ctx.telemetry_enabled = True
    yield
    ctx.telemetry_enabled = prev


def _make_trainer(aux=None, **kwargs):
    def init_fn(rng):
        return {"w": jax.random.normal(rng, (4, 2)), "b": jnp.zeros((2,))}

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2), dict(aux or {})

    rngs = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(rngs[0], (16, 4))
    batch = {"x": x, "y": x @ jax.random.normal(rngs[1], (4, 2))}
    trainer = ElasticTrainer(
        init_fn, loss_fn, optax.sgd(0.1), batch,
        strategy=Strategy(mesh=MeshPlan(data=-1)), **kwargs,
    )
    return trainer, batch


# -- registry ---------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_gauge_histogram_roundtrip(self):
        reg = MetricsRegistry()
        c = reg.counter(tm.TRAIN_STEPS)
        c.inc()
        c.inc(2.0)
        assert c.value == 3.0
        g = reg.gauge(tm.DISPATCH_WINDOW_OCCUPANCY)
        g.set(4)
        g.dec()
        assert g.value == 3.0
        h = reg.histogram(tm.STEP_TIME)
        for v in (0.001, 0.002, 0.004, 0.1):
            h.observe(v)
        assert h.count == 4 and h.sum == pytest.approx(0.107)

    def test_creation_is_idempotent_and_type_checked(self):
        reg = MetricsRegistry()
        assert reg.counter(tm.TRAIN_STEPS) is reg.counter(tm.TRAIN_STEPS)
        with pytest.raises(ValueError):
            reg.gauge(tm.TRAIN_STEPS)

    def test_percentiles_from_buckets(self):
        h = Histogram("h", buckets=(0.01, 0.1, 1.0))
        for _ in range(90):
            h.observe(0.005)
        for _ in range(10):
            h.observe(0.5)
        p50, p95 = h.percentile(0.5), h.percentile(0.95)
        assert p50 is not None and p50 <= 0.01
        assert 0.1 < p95 <= 1.0
        assert Histogram("e", buckets=(1,)).percentile(0.5) is None

    def test_overflow_marker_on_clamped_tails(self):
        """A quantile landing in the +Inf bucket clamps to the last
        finite bound — with_overflow exposes the clamp so diagnosis
        verdicts treat the value as a LOWER bound, not a measurement."""
        h = Histogram("h", buckets=(0.01, 0.1, 1.0))
        for _ in range(10):
            h.observe(50.0)  # way past the last finite bound
        value, overflow = h.percentile(0.5, with_overflow=True)
        assert value == 1.0 and overflow is True
        assert h.percentile(0.5) == 1.0  # legacy shape unchanged
        h2 = Histogram("h2", buckets=(0.01, 0.1, 1.0))
        h2.observe(0.05)
        value, overflow = h2.percentile(0.5, with_overflow=True)
        assert overflow is False and value <= 0.1
        # empty histogram: (None, False)
        h3 = Histogram("h3", buckets=(1.0,))
        assert h3.percentile(0.5, with_overflow=True) == (None, False)

    def test_labeled_series_share_one_exposition_family(self):
        reg = MetricsRegistry()
        reg.gauge(tm.NODE_RSS_MB, labels={"node": "0"}).set(10)
        reg.gauge(tm.NODE_RSS_MB, labels={"node": "1"}).set(20)
        text = reg.render_prometheus()
        assert text.count("# TYPE dlrover_node_rss_mb gauge") == 1
        assert 'dlrover_node_rss_mb{node="0"} 10' in text
        assert 'dlrover_node_rss_mb{node="1"} 20' in text
        assert reg.get(tm.NODE_RSS_MB, labels={"node": "1"}).value == 20
        # a family must hold ONE kind — a labeled sibling of another
        # kind would make the rendered TYPE header lie
        with pytest.raises(ValueError):
            reg.counter(tm.NODE_RSS_MB, labels={"node": "2"})

    def test_windowed_percentile_from_count_deltas(self):
        # the speed log diffs two snapshots so a late regression shows
        # up even after many fast observations (lifetime-cumulative
        # quantiles would bury it)
        from dlrover_tpu.telemetry.metrics import percentile_from_counts

        h = Histogram("h", buckets=(0.01, 0.1, 1.0))
        for _ in range(1000):
            h.observe(0.005)  # long fast history
        snap = h.snapshot_counts()
        for _ in range(10):
            h.observe(0.5)  # the regression window
        window = [c - p for c, p in zip(h.snapshot_counts(), snap)]
        p50 = percentile_from_counts(h.bounds, window, 0.5)
        assert p50 is not None and p50 > 0.1  # window-only, not 0.005
        assert h.percentile(0.5) <= 0.01  # cumulative stays fast

    def test_prometheus_exposition_format(self):
        reg = MetricsRegistry()
        reg.counter(tm.TRAIN_STEPS, help="steps").inc(5)
        h = reg.histogram(tm.STEP_TIME, buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.render_prometheus()
        assert "# TYPE dlrover_train_steps_total counter" in text
        assert "dlrover_train_steps_total 5" in text
        # buckets are CUMULATIVE and +Inf equals the total count
        assert 'dlrover_step_time_seconds_bucket{le="0.1"} 1' in text
        assert 'dlrover_step_time_seconds_bucket{le="1"} 2' in text
        assert 'dlrover_step_time_seconds_bucket{le="+Inf"} 3' in text
        assert "dlrover_step_time_seconds_count 3" in text

    def test_disabled_knob_hands_out_null_handles(self):
        get_context().telemetry_enabled = False
        reg = get_registry()
        c = reg.counter(tm.TRAIN_STEPS)
        c.inc(100)
        assert c.value == 0.0
        assert reg.render_prometheus() == ""
        get_context().telemetry_enabled = True
        assert isinstance(get_registry(), MetricsRegistry)


# -- events + MTTR derivation ----------------------------------------------


class TestEventTimeline:
    def test_emit_and_read_roundtrip(self, tmp_path, monkeypatch):
        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
        rec = emit_event(EventKind.CKPT_SAVE, step=7, stage_seconds=0.1)
        assert rec["seq"] > 0 and rec["pid"] == os.getpid()
        emit_event(EventKind.WORKER_FAILED, error_code="EXIT_9")
        out = read_events(path)
        assert [r["kind"] for r in out] == [
            EventKind.CKPT_SAVE, EventKind.WORKER_FAILED]
        assert out[0]["step"] == 7
        assert out[1]["error_code"] == "EXIT_9"
        assert {"ts", "mono", "pid", "node"} <= set(out[0])

    def test_malformed_lines_are_skipped(self, tmp_path):
        path = tmp_path / "events.jsonl"
        path.write_text(
            '{"kind": "train_start", "ts": 1.0}\n'
            "{torn write\n"
            '{"kind": "train_end", "ts": 2.0}\n'
        )
        assert [r["kind"] for r in read_events(str(path))] == [
            "train_start", "train_end"]

    def test_disabled_telemetry_emits_nothing(self, tmp_path,
                                              monkeypatch):
        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
        get_context().telemetry_enabled = False
        assert emit_event(EventKind.CKPT_SAVE) == {}
        assert not os.path.exists(path)

    def test_size_capped_rotation_keeps_the_pair_readable(
            self, tmp_path, monkeypatch):
        """Past DLROVER_TPU_EVENTS_MAX_MB the file rotates to `.1`;
        read_events (and so mttr/goodput) reads the rotated pair, so a
        failure edge in the old file still pairs with a recovery edge
        in the new one."""
        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
        # ~2 KB cap: a handful of records trigger rotation
        monkeypatch.setenv("DLROVER_TPU_EVENTS_MAX_MB",
                           str(2048 / (1024 * 1024)))
        emit_event(EventKind.WORKER_FAILED, error_code="EXIT_9")
        for i in range(20):
            emit_event(EventKind.CKPT_SAVE, step=i, stage_seconds=0.01)
        assert os.path.exists(path + ".1"), "never rotated"
        emit_event(EventKind.WORKERS_STARTED, round=1)
        records = read_events(path)
        kinds = [r["kind"] for r in records]
        assert EventKind.WORKERS_STARTED in kinds
        # the failure edge may have aged out past the retained pair on
        # aggressive caps, but with this cadence it must survive here
        assert EventKind.WORKER_FAILED in kinds
        rep = mttr_report(records)
        assert rep["detail"]["by_scenario"]["worker_failure"]["count"] == 1

    def test_writer_follows_an_external_rotation(self, tmp_path,
                                                 monkeypatch):
        """Multi-process semantics: after ANOTHER process renames the
        shared file, this process's cached fd no longer matches the
        path's inode — the next emit must reopen the fresh file, not
        keep appending to the rotated one forever."""
        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
        monkeypatch.delenv("DLROVER_TPU_EVENTS_MAX_MB", raising=False)
        emit_event(EventKind.TRAIN_START, step=0)
        os.rename(path, path + ".1")  # "the other process rotated"
        emit_event(EventKind.TRAIN_END, step=5)
        # the new record landed in a FRESH file at the shared path
        assert os.path.exists(path)
        fresh = [r["kind"] for r in events_mod._read_one(path)]
        assert fresh == [EventKind.TRAIN_END]
        # and the pair view still shows both
        assert [r["kind"] for r in read_events(path)] == [
            EventKind.TRAIN_START, EventKind.TRAIN_END]


def _ev(kind, ts, mono=None, pid=1, **kw):
    rec = {"kind": kind, "ts": ts, "pid": pid,
           "mono": mono if mono is not None else ts, "node": "0"}
    rec.update(kw)
    return rec


class TestMttrDerivation:
    def test_pairs_each_failure_kind_with_its_recovery(self):
        events = [
            _ev(EventKind.WORKERS_STARTED, 0.0),  # boot: not a recovery
            _ev(EventKind.WORKER_FAILED, 10.0, error_code="EXIT_137"),
            _ev(EventKind.WORKERS_STARTED, 12.5),
            _ev(EventKind.NONFINITE_STEP, 20.0),
            _ev(EventKind.ROLLBACK_RESTORED, 21.0),
            _ev(EventKind.PREEMPT_NOTICE, 30.0),
            _ev(EventKind.PREEMPT_DRAIN_DONE, 30.75),
            _ev(EventKind.HANG_DETECTED, 40.0),
            _ev(EventKind.WORKERS_STARTED, 44.0),
        ]
        rep = mttr_report(events)
        by = rep["detail"]["by_scenario"]
        assert rep["detail"]["incidents"] == 4
        assert by["worker_failure"]["mean_s"] == 2.5
        assert by["nonfinite_rollback"]["mean_s"] == 1.0
        assert by["preemption_drain"]["mean_s"] == 0.75
        assert by["hang"]["mean_s"] == 4.0
        assert rep["value"] == pytest.approx(
            (2.5 + 1 + 0.75 + 4) / 4, abs=1e-3)  # report rounds to ms
        assert "error" not in rep

    def test_failure_burst_is_one_incident(self):
        events = [
            _ev(EventKind.WORKER_FAILED, 10.0),
            _ev(EventKind.WORKER_FAILED, 10.1),
            _ev(EventKind.WORKER_FAILED, 10.2),
            _ev(EventKind.WORKERS_STARTED, 15.0),
        ]
        rep = mttr_report(events)
        assert rep["detail"]["incidents"] == 1
        # anchored at the FIRST failure edge
        assert rep["value"] == 5.0

    def test_monotonic_clock_used_within_a_process(self):
        # wall clocks disagree wildly; mono deltas are the truth
        events = [
            _ev(EventKind.NONFINITE_STEP, 100.0, mono=50.0, pid=7),
            _ev(EventKind.ROLLBACK_RESTORED, 900.0, mono=52.0, pid=7),
        ]
        assert mttr_report(events)["value"] == 2.0
        # different pids: mono is meaningless, fall back to wall
        events[1]["pid"] = 8
        assert mttr_report(events)["value"] == 800.0

    def test_unrecovered_incident_is_reported_as_error(self):
        rep = mttr_report([_ev(EventKind.HANG_DETECTED, 1.0)])
        assert rep["detail"]["unrecovered"] == 1
        assert "error" in rep


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata", "elastic_kill_resume.events.jsonl")


class TestMttrEndsAtTheFirstTrainedStep:
    """One definition of a recovery: the operator's tool follows a
    worker failure to the restarted worker's first trained step, where
    the benchmark's ``resume_s`` ends."""

    def test_a_recorded_kill_and_resume(self, capsys):
        """``events.jsonl`` of one run of the benchmark's elastic cell
        (``tests/testdata``: the toy configuration on the CPU). That
        run's result line read ``resume_s`` 8.803 and ``detect_s``
        0.935: the SIGKILL itself is on the runner's clock."""
        events = read_events(RECORDED)
        failed = next(e for e in events
                      if e["kind"] == EventKind.WORKER_FAILED)
        boots = [e for e in events if e["kind"] == EventKind.WORKER_BOOT]
        assert [b["restart_round"] for b in boots] == [0, 1]
        first_steps = [e for e in events
                       if e["kind"] == EventKind.COMPILE_FIRST_STEP]
        assert [e["pid"] for e in first_steps] == [b["pid"] for b in boots]
        assert telemetry_cli(["mttr", "--events", RECORDED,
                              "--target", "5"]) == 0
        rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rep["detail"]["incidents"] == 1
        (inc,) = rep["detail"]["to_first_step"]
        assert inc["scenario"] == "worker_failure"
        # the restarted worker's first step, not the first worker's
        assert inc["first_step_seconds"] == pytest.approx(
            first_steps[1]["ts"] - failed["ts"], abs=1e-3)
        assert inc["first_step_seconds"] == pytest.approx(
            8.803 - 0.935, abs=0.5)
        # the seconds to workers_started stay where they were
        assert inc["recovery_seconds"] < 0.1
        assert list(inc["phases"]) == [
            "respawn", "import", "backend", "script", "ckpt_manager",
            "build", "restore", "state", "hooks", "first_step",
            "remainder"]
        assert sum(inc["phases"].values()) == pytest.approx(
            inc["first_step_seconds"], abs=0.02)
        assert inc["phases"]["restore"] > 0
        assert abs(inc["phases"]["respawn"]) < 0.5
        assert abs(inc["phases"]["remainder"]) < 0.5
        # the headline and the target judge the recovery to the step
        assert rep["value"] == inc["first_step_seconds"]
        assert rep["vs_baseline"] == pytest.approx(rep["value"] / 5, abs=1e-3)
        by = rep["detail"]["by_scenario"]["worker_failure"]
        assert by == {"count": 1, "total_s": rep["value"],
                      "max_s": rep["value"], "mean_s": rep["value"]}

    def test_no_later_first_step_leaves_the_incident_as_it_was(self):
        """A job that was stopped, a worker that never trains, a step
        of the failed round itself: the incident ends at
        ``workers_started`` and has no further key."""
        events = [
            _ev(EventKind.WORKER_BOOT, 1.0, pid=5, restart_round=0,
                process_start_ts=0.5),
            _ev(EventKind.COMPILE_FIRST_STEP, 4.0, pid=5, seconds=1.0),
            _ev(EventKind.WORKER_FAILED, 10.0, restart_round=0),
            _ev(EventKind.WORKERS_STARTED, 12.5, restart_round=1),
            # the failed round's own worker, still writing
            _ev(EventKind.COMPILE_FIRST_STEP, 13.0, pid=5, seconds=1.0),
            _ev(EventKind.WORKER_BOOT, 14.0, pid=6, restart_round=1,
                process_start_ts=12.6),
        ]
        rep = mttr_report(events)
        assert rep["value"] == 2.5
        assert rep["detail"]["to_first_step"] == []
        (inc,) = derive_incidents(events)
        assert set(inc) == {
            "scenario", "failure_kind", "recovery_kind", "error_code",
            "node", "started_ts", "recovered_ts", "recovery_seconds"}

    def test_a_hang_is_followed_past_the_newest_booted_round(self):
        """``hang_detected`` names no round: the round that hung is the
        newest one booted before it."""
        events = [
            _ev(EventKind.WORKER_BOOT, 1.0, pid=5, restart_round=2,
                process_start_ts=0.5),
            _ev(EventKind.HANG_DETECTED, 40.0),
            _ev(EventKind.WORKERS_STARTED, 44.0, restart_round=3),
            _ev(EventKind.WORKER_BOOT, 50.0, pid=6, restart_round=3,
                process_start_ts=44.5, import_seconds=4.0,
                backend_seconds=1.5),
            _ev(EventKind.TRAINER_READY, 60.0, pid=6, script_seconds=1.0,
                ckpt_manager_seconds=2.0, build_seconds=0.5,
                state_seconds=6.0),
            _ev(EventKind.CKPT_RESTORE, 59.9, pid=6, restore_seconds=5.0),
            _ev(EventKind.TRAIN_START, 60.5, pid=6,
                hooks_begin_seconds=0.25),
            _ev(EventKind.COMPILE_FIRST_STEP, 64.5, pid=6, seconds=4.0),
        ]
        (inc,) = derive_incidents(events)
        assert inc["scenario"] == "hang"
        assert inc["recovery_seconds"] == 4.0
        assert inc["first_step_seconds"] == 24.5
        assert inc["phases"] == {
            "respawn": 4.5, "import": 4.0, "backend": 1.5, "script": 1.0,
            "ckpt_manager": 2.0, "build": 0.5, "restore": 5.0,
            "state": 1.0, "hooks": 0.25, "first_step": 4.0,
            "remainder": 0.75}
        assert mttr_report(events)["value"] == 24.5

    def test_a_boot_is_what_the_worker_wrote_up_to_its_first_step(self):
        """A worker that began from a fresh init and restored later in
        its life (the live-recovery path builds and restores in the
        loop): that ``ckpt_restore`` and that ``trainer_ready`` are no
        part of its boot."""
        from dlrover_tpu.telemetry.mttr import boot_phases

        events = [
            _ev(EventKind.WORKER_BOOT, 6.0, pid=7, restart_round=0,
                process_start_ts=0.0, import_seconds=4.0,
                distributed_seconds=0.0, backend_seconds=2.0),
            _ev(EventKind.TRAINER_READY, 10.0, pid=7, script_seconds=0.5,
                ckpt_manager_seconds=1.0, build_seconds=0.5,
                state_seconds=2.0),
            _ev(EventKind.TRAIN_START, 10.5, pid=7,
                hooks_begin_seconds=0.5),
            _ev(EventKind.COMPILE_FIRST_STEP, 13.5, pid=7, seconds=3.0),
            # long after: a live recovery restores from the mirror
            _ev(EventKind.CKPT_RESTORE, 90.0, pid=7, restore_seconds=7.0,
                source="staging"),
            _ev(EventKind.TRAINER_READY, 91.0, pid=7, script_seconds=None,
                ckpt_manager_seconds=0.0, build_seconds=1.0,
                state_seconds=7.5),
        ]
        for order in (events, events[::-1][:2] + events[:4]):
            out = boot_phases(order, 7)
            assert out["total_seconds"] == 13.5
            assert out["phases"] == {
                "import": 4.0, "backend": 2.0, "script": 0.5,
                "ckpt_manager": 1.0, "build": 0.5, "restore": 0.0,
                "state": 2.0, "hooks": 0.5, "first_step": 3.0,
                "remainder": 0.0}


class TestMttrFromChaosRuns:
    """`python -m dlrover_tpu.telemetry mttr` over timelines produced by
    REAL executor fault paths (the chaos tests add the agent-level hang
    relaunch scenario on top of these)."""

    def _mttr(self, path, capsys):
        rc = telemetry_cli(["mttr", "--events", path])
        report = json.loads(capsys.readouterr().out.strip())
        return rc, report

    def test_preempt_drain_mttr_derived(self, tmp_path, monkeypatch,
                                        capsys):
        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
        trainer, batch = _make_trainer(ckpt_dir=str(tmp_path / "ckpt"))

        class PreemptAt(TrainHook):
            def before_step(self, step):
                if step == 6:
                    os.kill(os.getpid(), signal.SIGTERM)

        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * 100,
            hooks=[PreemptAt()],
            conf=Configuration({
                "train_steps": 50, "log_every_steps": 0,
                "train_window": 4,
            }),
        )
        out = executor.train_and_evaluate()
        assert out.get("preempted") is True
        rc, report = self._mttr(path, capsys)
        assert rc == 0, report
        drain = report["detail"]["by_scenario"]["preemption_drain"]
        assert drain["count"] == 1
        assert report["value"] > 0

    def test_nan_rollback_mttr_derived(self, tmp_path, monkeypatch,
                                       capsys):
        from dlrover_tpu.checkpoint import CheckpointInterval

        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
        trainer, batch = _make_trainer(
            ckpt_dir=str(tmp_path / "ckpt"),
            ckpt_interval=CheckpointInterval(steps=2),
        )
        nan_batch = {"x": batch["x"] * jnp.nan, "y": batch["y"]}
        poisoned = {"armed": True}

        def batches():
            for i in range(100):
                if i == 3 and poisoned["armed"]:
                    poisoned["armed"] = False
                    yield nan_batch
                else:
                    yield batch

        executor = TrainExecutor(
            trainer, train_iter_fn=batches,
            conf=Configuration({
                "train_steps": 6, "log_every_steps": 0,
                "check_finite_every_steps": 1,
                "on_nonfinite": "rollback", "preemption_grace": False,
            }),
        )
        out = executor.train_and_evaluate()
        assert out["step"] >= 6
        rc, report = self._mttr(path, capsys)
        assert rc == 0, report
        rb = report["detail"]["by_scenario"]["nonfinite_rollback"]
        assert rb["count"] == 1
        kinds = [r["kind"] for r in read_events(path)]
        assert EventKind.NONFINITE_STEP in kinds
        assert EventKind.ROLLBACK_RESTORED in kinds
        assert EventKind.CKPT_SAVE in kinds


# -- the instrumented-run acceptance pins ----------------------------------


def _host_span_names(trace_dir):
    """The names, without their ``#key=value#`` tails, of the events in
    the ``/host:CPU`` plane of the newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    dumps = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    assert dumps, f"no profile under {trace_dir}"
    return {
        event.name.split("#", 1)[0]
        for plane in ProfileData.from_file(dumps[-1]).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for event in line.events}


class _TimedRegion(TrainHook):
    def __init__(self, trainer, warmup):
        self.trainer = trainer
        self.warmup = warmup
        self.t0 = None
        self.cache_at_t0 = None

    def before_step(self, step):
        if step == self.warmup + 1 and self.t0 is None:
            self.cache_at_t0 = (
                self.trainer.accelerated.compiled_cache_size())
            self.t0 = time.perf_counter()


def _timed_loop(telemetry_on, steps=480, warmup=8):
    get_context().telemetry_enabled = telemetry_on
    trainer, batch = _make_trainer()
    timer = _TimedRegion(trainer, warmup)
    executor = TrainExecutor(
        trainer,
        train_iter_fn=lambda: iter([batch] * (warmup + steps)),
        hooks=[timer],
        conf=Configuration({
            "train_steps": warmup + steps, "log_every_steps": 0,
            "check_finite_every_steps": 1, "train_window": 4,
            "preemption_grace": False,
        }),
    )
    executor.train_and_evaluate()
    dt = time.perf_counter() - timer.t0
    recompiles = (trainer.accelerated.compiled_cache_size()
                  - timer.cache_at_t0)
    get_context().telemetry_enabled = True
    return dt, recompiles


class TestInstrumentedRunPins:
    def test_exposition_trace_overhead_and_zero_recompiles(self):
        """The acceptance pin: one short instrumented run yields a
        well-formed Prometheus exposition and a Perfetto-openable trace,
        with zero recompiles and ≤5% step-loop overhead vs the bare
        loop. Run-to-run drift on a shared 1-core host (±10%) dwarfs
        the real per-step cost (~1-2µs), so the gate compares
        BACK-TO-BACK pairs (alternating order) and takes the median of
        per-pair ratios — adjacent runs share the drift."""
        steps = 480
        process_registry().reset()
        recompiles = 0
        inst_runs = 0

        def leg(instrumented, best_of):
            """One timed leg; ``best_of`` > 1 takes the MIN over
            repeats — the classic floor estimator that filters one-off
            scheduler stalls, which on this box are the whole residual
            flake (the true cost is a lower envelope)."""
            nonlocal recompiles, inst_runs
            best = None
            for _ in range(best_of):
                dt, rc = _timed_loop(instrumented, steps)
                recompiles += rc
                if instrumented:
                    inst_runs += 1
                best = dt if best is None else min(best, dt)
            return best

        def paired_median(pairs=3, best_of=1):
            ratios = []
            for i in range(pairs):
                if i % 2 == 0:
                    dt_b = leg(False, best_of)
                    dt_i = leg(True, best_of)
                else:
                    dt_i = leg(True, best_of)
                    dt_b = leg(False, best_of)
                ratios.append(dt_i / dt_b)
            return sorted(ratios)[len(ratios) // 2]

        # De-flake (ISSUE 9 satellite): a single attempt's median
        # still failed ~1/3 of CLEAN-tree runs on this shared 1-core
        # box. Up to 3 attempts, gate on the MINIMUM of the attempt
        # medians, stopping early on the first pass (the common case
        # stays one attempt of 3 pairs). Min-selection is DELIBERATELY
        # biased low — noise on a baseline leg can deflate a ratio
        # too, so a marginal real regression (~6-7%) could slip one
        # attempt — and that is the accepted trade: the gate is a
        # tripwire for the LARGE instrumentation regressions this
        # suite has actually caught (≥10%, e.g. PR 8's capture
        # placement at 11-15%), where every attempt fails, while a
        # clean tree stops failing tier-1 one run in three.
        # Retry attempts escalate to BEST-OF-2 legs (ISSUE 15
        # satellite): min-of-medians alone still left a ~1/27 residual
        # flake — one scheduler stall landing on a baseline leg of
        # every attempt. Taking each retry leg as the min of two runs
        # floors out single-run stalls on either side; the common case
        # (first attempt passes) costs exactly what it used to.
        medians = [paired_median()]
        while medians[-1] - 1.0 > 0.05 and len(medians) < 3:
            medians.append(paired_median(best_of=2))
        assert recompiles == 0, "recompile inside the timed region"
        overhead = min(medians) - 1.0
        assert overhead <= 0.05, (
            f"telemetry overhead {overhead:.1%} above the 5% budget "
            f"(attempt medians {[round(m, 3) for m in medians]})"
        )

        # Prometheus exposition reflects the instrumented runs
        text = process_registry().render_prometheus()
        assert "# TYPE dlrover_step_time_seconds histogram" in text
        assert "# TYPE dlrover_train_steps_total counter" in text
        h = process_registry().get(tm.STEP_TIME)
        assert h.count >= inst_runs * steps
        c = process_registry().get(tm.TRAIN_STEPS)
        assert c.value >= inst_runs * steps
        assert process_registry().get(
            tm.STEP_DISPATCH_TIME).count >= inst_runs * steps
        assert process_registry().get(tm.STEP_HOST_SYNC_TIME).count > 0

        # a profile taken through the executor's window carries the
        # pipeline spans on the profiler's own clock
        with tempfile.TemporaryDirectory() as d:
            trainer, batch = _make_trainer()
            TrainExecutor(
                trainer, train_iter_fn=lambda: [batch] * 12,
                conf=Configuration({
                    "train_steps": 12, "log_every_steps": 0,
                    "train_window": 2, "preemption_grace": False,
                    "trace_dir": d, "trace_start_step": 3,
                    "trace_num_steps": 4,
                }),
            ).train_and_evaluate()
            names_seen = _host_span_names(d)
        assert "dlrover:step_dispatch" in names_seen
        assert "dlrover:host_sync" in names_seen
        assert "dlrover:input_wait" in names_seen

    def test_window_and_lag_gauges_track_the_pipeline(self):
        process_registry().reset()
        trainer, batch = _make_trainer()
        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * 40,
            conf=Configuration({
                "train_steps": 40, "log_every_steps": 0,
                "train_window": 4, "preemption_grace": False,
            }),
        )
        executor.train_and_evaluate()
        g = process_registry().get(tm.DISPATCH_WINDOW_OCCUPANCY)
        lag = process_registry().get(tm.LAGGED_METRIC_AGE)
        assert g is not None and 0 <= g.value <= 4
        # after the final drain the lag of the LAST materialization is 0
        assert lag is not None and lag.value == 0


# -- lagged master reporting (stats reporter under the async window) --------


class _MaterializeTracker(TrainHook):
    """Records the newest step whose metrics have reached the host —
    placed BEFORE the report hook, so at report time it reflects what
    has genuinely materialized."""

    def __init__(self):
        self.newest = 0

    def after_step(self, step, metrics):
        self.newest = max(self.newest, step)


class TestLaggedReporting:
    def test_reported_global_step_never_ahead_of_materialized(self):
        tracker = _MaterializeTracker()
        reported = []

        class Client:
            def report_global_step(self, step, **kw):
                # the invariant under train_window > 0: a step may only
                # be reported once its metrics are host-materialized
                assert step <= tracker.newest, (
                    f"reported step {step} ahead of materialized "
                    f"{tracker.newest}"
                )
                reported.append(step)

            def report_model_info(self, info):
                pass

        trainer, batch = _make_trainer()
        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * 64,
            hooks=[tracker, ReportModelInfoHook(Client(), every_steps=4)],
            conf=Configuration({
                "train_steps": 64, "log_every_steps": 0,
                "train_window": 4, "preemption_grace": False,
            }),
        )
        executor.train_and_evaluate()
        assert reported == list(range(4, 65, 4))

    def test_dead_master_counts_failures_and_never_raises(self):
        process_registry().reset()

        class DeadClient:
            def report_global_step(self, step, **kw):
                raise ConnectionError("master gone")

            def report_model_info(self, info):
                raise ConnectionError("master gone")

        trainer, batch = _make_trainer()
        hook = ReportModelInfoHook(DeadClient(), param_count=10,
                                   every_steps=1)
        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * 6,
            hooks=[hook],
            conf=Configuration({
                "train_steps": 6, "log_every_steps": 0,
                "train_window": 4, "preemption_grace": False,
            }),
        )
        out = executor.train_and_evaluate()  # must not raise
        assert out["step"] == 6
        failures = process_registry().get(tm.MASTER_REPORT_FAILURES)
        # 6 per-step reports + the begin() model-info report
        assert failures is not None and failures.value == 7
        ok = process_registry().get(tm.MASTER_REPORTS)
        assert ok is None or ok.value == 0


# -- exporter + CLI ---------------------------------------------------------


class TestExporterAndCli:
    def test_http_exposition_and_events(self):
        import urllib.request

        from dlrover_tpu.telemetry.exporter import MetricsExporter

        process_registry().counter(tm.TRAIN_STEPS).inc(3)
        emit_event(EventKind.TRAIN_START, step=0)
        exporter = MetricsExporter(port=0).start()
        try:
            base = f"http://127.0.0.1:{exporter.port}"
            body = urllib.request.urlopen(
                base + "/metrics", timeout=5).read().decode()
            assert "dlrover_train_steps_total" in body
            events = json.loads(urllib.request.urlopen(
                base + "/events?n=5", timeout=5).read().decode())
            assert isinstance(events, list) and events
            assert urllib.request.urlopen(
                base + "/healthz", timeout=5).status == 200
        finally:
            exporter.stop()

    def test_tpurun_metrics_dumps_local_registry(self, capsys):
        from dlrover_tpu.trainer.run import main as tpurun

        process_registry().counter(tm.TRAIN_STEPS).inc()
        assert tpurun(["metrics"]) == 0
        assert "dlrover_train_steps_total" in capsys.readouterr().out

    def test_cli_events_filter(self, tmp_path, capsys):
        path = str(tmp_path / "ev.jsonl")
        with open(path, "w") as fh:
            fh.write(json.dumps(
                {"kind": "train_start", "ts": 1.0}) + "\n")
            fh.write(json.dumps(
                {"kind": "ckpt_save", "ts": 2.0}) + "\n")
        assert telemetry_cli(
            ["events", "--events", path, "--kind", "ckpt_save"]) == 0
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 1 and json.loads(out[0])["kind"] == "ckpt_save"

    def test_mttr_cli_requires_a_timeline(self, monkeypatch):
        monkeypatch.delenv("DLROVER_TPU_EVENTS_FILE", raising=False)
        get_context().telemetry_events_file = ""
        assert telemetry_cli(["mttr"]) == 2


# -- host spans --------------------------------------------------------------


class TestSpansNeedNoJax:
    """``span`` is a profiler annotation where JAX is loaded and nothing
    elsewhere: the master, the agent, the launcher and the benchmark's
    runner never hold the chip, and stay off JAX."""

    def test_control_plane_and_runner_stay_off_jax(self):
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "import importlib.util, sys\n"
            "import dlrover_tpu.master.main, dlrover_tpu.trainer.run\n"
            "import dlrover_tpu.agent.training_agent\n"
            "import dlrover_tpu.agent.rendezvous, dlrover_tpu.rpc.client\n"
            "spec = importlib.util.spec_from_file_location(\n"
            "    'chipbench_run', 'chipbench/run.py')\n"
            "spec.loader.exec_module(importlib.util.module_from_spec(spec))\n"
            "from dlrover_tpu.telemetry import span\n"
            "with span('rendezvous', category='rdzv', round=1):\n"
            "    pass\n"
            "loaded = sorted(m for m in sys.modules\n"
            "                if m == 'jax' or m.startswith('jax.'))\n"
            "assert not loaded, loaded[:5]\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]

    def test_a_span_is_an_annotation_with_its_arguments(self):
        annotation = span("ckpt_save", step=7)
        assert isinstance(annotation, jax.profiler.TraceAnnotation)
        get_context().telemetry_enabled = False
        with span("ckpt_save", step=7) as nothing:
            assert nothing is None


# -- on-demand device-profile window ----------------------------------------


class _Profiler:
    """Stands in for ``jax.profiler.start_trace``/``stop_trace``."""

    def __init__(self, monkeypatch):
        self.started, self.options, self.stops = [], [], 0
        self.on_start = self.on_stop = lambda: None
        monkeypatch.setattr(jax.profiler, "start_trace", self._start)
        monkeypatch.setattr(jax.profiler, "stop_trace", self._stop)

    def _start(self, log_dir, profiler_options=None):
        self.started.append(log_dir)
        self.options.append(profiler_options)
        self.on_start()

    def _stop(self):
        self.stops += 1
        self.on_stop()


class _KickAt(TrainHook):
    def __init__(self, *steps):
        self._steps = steps

    def before_step(self, step):
        if step in self._steps:
            os.kill(os.getpid(), signal.SIGUSR2)


class _SeenSteps(TrainHook):
    def __init__(self):
        self.steps = []

    def after_step(self, step, metrics):
        self.steps.append(step)


def _run_with_windows(tmp_path, monkeypatch, kicks, steps=16, **conf):
    """A run of ``steps`` steps with USR2 sent before each step of
    ``kicks``: the profiler's calls and the timeline's windows."""
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
    profiler = _Profiler(monkeypatch)
    trainer, batch = _make_trainer(aux=conf.pop("aux", None))
    TrainExecutor(
        trainer, train_iter_fn=lambda: [batch] * steps,
        hooks=[_KickAt(*kicks)],
        conf=Configuration({
            "train_steps": steps, "log_every_steps": 0,
            "train_window": 2, "preemption_grace": False,
            "profile_signal": "USR2", "trace_num_steps": 2, **conf,
        }),
    ).train_and_evaluate()
    windows = [r for r in read_events(path)
               if r["kind"] == EventKind.PROFILE_WINDOW]
    return profiler, windows


class TestProfileSignalWindow:
    def test_sigusr2_opens_one_bounded_window(self, tmp_path, monkeypatch):
        profiler, windows = _run_with_windows(tmp_path, monkeypatch, [4],
                                              steps=12)
        # the profiler's first start in the process is thrown away
        scratch, target = profiler.started
        assert "dlrover_tpu_xprof" in target and scratch != target
        assert not os.path.exists(scratch)
        assert profiler.stops == 2
        # the spans, not every Python call
        assert all(o.python_tracer_level == 0 and o.host_tracer_level == 2
                   for o in profiler.options)
        # disposition restored: a later USR2 must not re-arm profiling
        assert signal.getsignal(signal.SIGUSR2) in (
            signal.SIG_DFL, signal.Handlers.SIG_DFL)
        (window,) = windows
        assert window["dir"] == target
        assert (window["first_step"], window["last_step"]) == (5, 6)
        assert window["steps"] == 2 and window["saves_begun"] == 0
        assert window["start_ts"] <= window["end_ts"] <= window["ts"]
        for key in ("dispatch_seconds", "host_sync_seconds",
                    "input_wait_seconds", "start_seconds",
                    "stop_seconds"):
            assert window[key] >= 0, key
        assert window["dispatch_seconds"] > 0
        assert "step_counters" not in window  # the loss counts nothing

    @pytest.mark.parametrize("name", tm.StepCounter.ALL)
    def test_a_windows_step_counters_are_sums_over_its_steps(
            self, tmp_path, monkeypatch, name):
        """What a loss function's aux counts under a name of
        ``StepCounter`` is summed over the window's steps alone; an aux
        value of another name is not the window's."""
        value = 1.0 + tm.StepCounter.ALL.index(name)
        _, (window,) = _run_with_windows(
            tmp_path, monkeypatch, [4], steps=12, trace_num_steps=3,
            aux={name: jnp.float32(value), "other": jnp.float32(7.0)})
        assert window["steps"] == 3
        assert window["step_counters"] == {name: 3 * value}

    def test_a_second_signal_opens_a_second_window(self, tmp_path,
                                                   monkeypatch):
        target = str(tmp_path / "xprof")
        profiler, windows = _run_with_windows(
            tmp_path, monkeypatch, [4, 10], trace_dir=target,
            trace_start_step=-1)
        # one throwaway start, then one start a signal; no window was
        # scheduled at a step, though a directory is given
        assert profiler.started[1:] == [target, target]
        assert profiler.stops == 3
        assert [(w["first_step"], w["last_step"]) for w in windows] == [
            (5, 6), (11, 12)]
        assert all(w["dir"] == target for w in windows)

    def test_a_directory_alone_schedules_one_window(self, tmp_path,
                                                    monkeypatch):
        target = str(tmp_path / "xprof")
        profiler, windows = _run_with_windows(
            tmp_path, monkeypatch, [], trace_dir=target,
            trace_start_step=3)
        assert profiler.started[1:] == [target]
        assert [(w["first_step"], w["last_step"]) for w in windows] == [
            (4, 5)]

    def test_a_window_open_at_the_end_is_closed_and_reported(
            self, tmp_path, monkeypatch):
        profiler, windows = _run_with_windows(
            tmp_path, monkeypatch, [8], steps=8, trace_num_steps=5)
        assert profiler.stops == 2
        (window,) = windows
        assert (window["first_step"], window["last_step"]) == (9, 8)
        assert window["steps"] == 0

    def test_the_save_branch_is_kept_apart_from_dispatch(
            self, tmp_path, monkeypatch):
        """What the save branch holds the training thread (the
        device copy enqueued, or a blocking save's wait and copy) is
        the window's ``save_seconds``, not part of
        ``dispatch_seconds``."""
        from dlrover_tpu.checkpoint import CheckpointInterval

        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
        _Profiler(monkeypatch)
        trainer, batch = _make_trainer(
            ckpt_dir=str(tmp_path / "ckpt"),
            ckpt_interval=CheckpointInterval(steps=5))
        real_save = trainer.save

        def slow_save(state, **kwargs):
            time.sleep(0.2)
            return real_save(state, **kwargs)

        trainer.save = slow_save
        TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * 9,
            conf=Configuration({
                "train_steps": 9, "log_every_steps": 0,
                "train_window": 2, "preemption_grace": False,
                "trace_dir": str(tmp_path / "xprof"),
                "trace_start_step": 3, "trace_num_steps": 4,
            }),
        ).train_and_evaluate()
        (window,) = [r for r in read_events(path)
                     if r["kind"] == EventKind.PROFILE_WINDOW]
        assert (window["first_step"], window["last_step"]) == (4, 7)
        assert window["saves_begun"] == 1
        assert window["save_seconds"] >= 0.2
        assert window["dispatch_seconds"] < 0.2

    # -- a window ends when its steps have run, not when they have been
    # dispatched (ISSUE 27) --------------------------------------------

    def _executor_with_a_window_after_a_save(self, tmp_path, monkeypatch,
                                             steps=9, trace_num_steps=2,
                                             step_fn=None, hooks=()):
        """The signal comes before step 5, whose dispatch saves and so
        waits for every step in flight: the window opens on an empty
        train window and its steps are dispatched at once."""
        from dlrover_tpu.checkpoint import CheckpointInterval

        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
        profiler = _Profiler(monkeypatch)
        trainer, batch = _make_trainer(
            ckpt_dir=str(tmp_path / "ckpt"),
            ckpt_interval=CheckpointInterval(steps=5))
        if step_fn is not None:
            real_step, calls = trainer.step, [0]

            def step(state, batch):
                calls[0] += 1
                return step_fn(calls[0], *real_step(state, batch))

            trainer.step = step
        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * steps,
            hooks=[_KickAt(5), *hooks],
            conf=Configuration({
                "train_steps": steps, "log_every_steps": 0,
                "train_window": 2, "preemption_grace": False,
                "profile_signal": "USR2",
                "trace_num_steps": trace_num_steps,
            }),
        )
        return executor, profiler, lambda: [
            r for r in read_events(path)
            if r["kind"] == EventKind.PROFILE_WINDOW]

    @pytest.mark.parametrize("steps", [9, 7])
    def test_a_window_on_a_drained_train_window_holds_its_steps(
            self, tmp_path, monkeypatch, steps):
        """The trace stops where the loop materializes the window's last
        step: behind two later dispatches (9 steps), or in the drain at
        the end of the run (7 steps)."""
        @jax.jit
        def slow(x):  # a quarter of a second of device work on the CPU
            a = jnp.full((256, 256), 1e-3) + x
            return jax.lax.fori_loop(
                0, 400, lambda i, a: jnp.tanh(a @ a), a)[0, 0]

        made = {}

        def step_fn(call, state, metrics):
            if call > 5:  # the window's steps
                metrics = {**metrics, "slow": slow(metrics["loss"])}
            made[call] = metrics
            return state, metrics

        seen = _SeenSteps()
        executor, profiler, windows = \
            self._executor_with_a_window_after_a_save(
                tmp_path, monkeypatch, steps=steps, step_fn=step_fn,
                hooks=[seen])
        at_stop = []
        profiler.on_stop = lambda: at_stop.append({
            # on the device whose copy the loop pulls
            "ran": [all(leaf.addressable_data(0).is_ready()
                        for leaf in jax.tree.leaves(made[call]))
                    for call in (6, 7) if call in made],
            "in_flight": [e.last_step for e in executor._window],
            "seen": list(seen.steps)})
        executor.train_and_evaluate()
        (window,) = windows()
        assert (window["first_step"], window["last_step"]) == (6, 7)
        assert window["saves_begun"] == 0  # step 5 saved before it opened
        # the stop of the window (the first was the thrown-away start):
        # steps 6 and 7 had both run, and the steps dispatched behind
        # them were still in flight: the chip had work while the trace
        # stopped
        stop = at_stop[1]
        assert stop["ran"] == [True, True]
        assert stop["in_flight"] == list(range(8, steps + 1))
        # no step was materialized early for it: step 7 had been pulled
        # when the trace stopped, its hooks came after, and every step
        # reached the hooks once, in order
        assert stop["seen"] == list(range(1, 7))
        assert seen.steps == list(range(1, steps + 1))

    def test_the_stop_is_in_none_of_the_windows_counters(
            self, tmp_path, monkeypatch):
        """The event's counters are read when the window's last step is
        dispatched: what the loop does until that step has run, and the
        stop itself, are in none of them."""
        executor, profiler, windows = \
            self._executor_with_a_window_after_a_save(tmp_path,
                                                      monkeypatch)
        counters = {}
        profiler.on_start = lambda: counters.update(
            opened=executor._loop_counters())
        profiler.on_stop = lambda: time.sleep(0.2)
        end = executor._end_profile_window

        def end_and_note(step):
            end(step)
            counters["ended"] = executor._loop_counters()

        executor._end_profile_window = end_and_note
        executor.train_and_evaluate()
        (window,) = windows()
        for key, opened in counters["opened"].items():
            assert window[key] == pytest.approx(
                counters["ended"][key] - opened, abs=2e-6), key
        assert window["host_sync_seconds"] < 0.2
        assert window["stop_seconds"] >= 0.2

    @pytest.mark.parametrize("the_wait", ["returns", "raises"])
    def test_closing_a_failed_runs_window_does_not_raise(
            self, tmp_path, monkeypatch, the_wait):
        """The run's ``finally`` closes a window that a failed run left
        open: the run's own error comes out, whether the wait for the
        steps still in flight returns or finds them failed too."""
        failed = []

        def step_fn(call, state, metrics):
            if call == 7:
                failed.append(call)
                raise RuntimeError("boom at step 7")
            return state, metrics

        executor, profiler, windows = \
            self._executor_with_a_window_after_a_save(
                tmp_path, monkeypatch, trace_num_steps=50,
                step_fn=step_fn)
        real_wait, waits = jax.block_until_ready, []

        def wait(tree):
            if failed:
                waits.append(tree)
                if the_wait == "raises":
                    raise RuntimeError("device failed")
            return real_wait(tree)

        monkeypatch.setattr(jax, "block_until_ready", wait)
        with pytest.raises(RuntimeError, match="boom at step 7"):
            executor.train_and_evaluate()
        assert len(waits) == 1  # step 6 was in flight
        assert profiler.stops == 2
        (window,) = windows()
        assert (window["first_step"], window["last_step"]) == (6, 6)


class TestBootOnTheTimeline:
    def test_worker_boot_and_trainer_ready(self, tmp_path, monkeypatch):
        from dlrover_tpu.trainer.bootstrap import init_worker

        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)
        t0 = time.time()
        init_worker()
        trainer, batch = _make_trainer(ckpt_dir=str(tmp_path / "ckpt"))
        state = trainer.prepare()
        assert int(state.step) == 0
        boot, ready = read_events(path)
        assert boot["kind"] == EventKind.WORKER_BOOT
        # this process started before the test did; everything up to
        # init_worker counts as interpreter and imports
        assert boot["process_start_ts"] < t0
        assert boot["import_seconds"] == pytest.approx(
            t0 - boot["process_start_ts"], abs=1.0)
        assert 0 <= boot["backend_seconds"] < 60
        assert boot["platform"] == "cpu" and boot["device_count"] >= 1
        assert ready["kind"] == EventKind.TRAINER_READY
        assert ready["step"] == 0 and ready["pid"] == boot["pid"]
        for key in ("build_seconds", "ckpt_manager_seconds",
                    "state_seconds"):
            assert ready[key] >= 0, key
        assert ready["build_seconds"] > 0

    def test_the_process_start_is_on_the_records_clock(self):
        """``process_start_ts`` is the process table's start tick read
        against the boot-time clock itself. ``psutil`` adds the same
        ticks to ``/proc/stat``'s whole-second ``btime``: the two differ
        by the fraction of a second that ``btime`` drops, to within the
        tick."""
        import psutil

        from dlrover_tpu.trainer.bootstrap import process_start_ts

        started = process_start_ts()
        now = time.time()
        assert started <= now
        dropped = (now - time.clock_gettime(time.CLOCK_BOOTTIME)
                   - psutil.boot_time())
        tick = 1.0 / os.sysconf("SC_CLK_TCK")
        assert started == pytest.approx(
            psutil.Process().create_time() + dropped, abs=tick + 0.005)

    def test_a_worker_rounds_phases_reach_its_first_step(
            self, tmp_path, monkeypatch):
        """From process start to the first trained step every second is
        in one named phase: the phases of ``boot_phases`` sum to
        ``compile_first_step.ts - worker_boot.process_start_ts`` within
        3% or half a second, each of the four events carries the
        compile ledger's totals so far, and the last names its dearest
        programs."""
        from dlrover_tpu.telemetry.mttr import BOOT_PHASES, boot_phases
        from dlrover_tpu.trainer.bootstrap import init_worker

        path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", path)

        class Slow(TrainHook):
            def begin(self, executor):
                time.sleep(0.2)

        init_worker()
        time.sleep(0.3)  # the script builds its job
        trainer, batch = _make_trainer(ckpt_dir=str(tmp_path / "ckpt"))
        TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * 6, hooks=[Slow()],
            conf=Configuration({
                "train_steps": 6, "log_every_steps": 0,
                "train_window": 2, "preemption_grace": False}),
        ).train_and_evaluate()
        events = read_events(path)
        by_kind = {}
        for e in events:
            by_kind.setdefault(e["kind"], e)
        boot, ready, start, step = (by_kind[k] for k in (
            EventKind.WORKER_BOOT, EventKind.TRAINER_READY,
            EventKind.TRAIN_START, EventKind.COMPILE_FIRST_STEP))
        assert boot["distributed_seconds"] >= 0
        assert 0.3 <= ready["script_seconds"] < 5
        assert 0.2 <= start["hooks_begin_seconds"] < 5
        parts = ("capture_seconds", "input_wait_seconds",
                 "dispatch_seconds", "sync_seconds", "rest_seconds")
        assert all(step[k] >= 0 for k in parts[:4])
        assert sum(step[k] for k in parts) == pytest.approx(
            step["seconds"], abs=2e-3)
        assert step["dispatch_seconds"] > 0
        # the ledger only grows from one event to the next, and the
        # step's own programs lie between train_start and the step
        ledgers = [e["compile"] for e in (boot, ready, start, step)]
        for a, b in zip(ledgers, ledgers[1:]):
            assert set(a) >= {"programs", "hits", "misses",
                              "trace_seconds", "lower_seconds",
                              "backend_seconds", "cache_read_seconds"}
            assert all(b[k] >= a[k] for k in a), (a, b)
        assert ledgers[3]["programs"] > ledgers[2]["programs"]
        assert "train_step" in {r["fun_name"] for r in step["programs"]}
        assert len(step["programs"]) <= 16

        out = boot_phases(events, os.getpid())
        total = step["ts"] - boot["process_start_ts"]
        assert out["total_seconds"] == pytest.approx(total, abs=1e-3)
        phases = out["phases"]
        assert list(phases) == [n for n, _, _ in BOOT_PHASES] + [
            "remainder"]
        assert sum(phases.values()) == pytest.approx(total, abs=0.01)
        assert phases["script"] == pytest.approx(
            ready["script_seconds"], abs=1e-3)
        assert phases["hooks"] >= 0.2 and phases["restore"] == 0
        # what no phase names stays small, and is reported
        assert abs(phases["remainder"]) < max(0.5, 0.03 * total)
        # a worker that never trained has no such account
        assert boot_phases(events, os.getpid() + 1) is None
