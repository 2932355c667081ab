"""Data-plane observability (ISSUE 9): shard-dispatch & input-pipeline
accounting with input-bound diagnosis.

Worker side: ShardingClient fetch/complete instruments + the batch-done
credit-restore fix, DevicePreloader queue-depth/wait instruments, the
executor's input-wait fraction (absent-not-zero). Master side:
per-dataset shard-lifecycle gauges (created at first dispatch,
retracted at completion), timeout-recovery events, mid-epoch
checkpoint-resume accounting. Diagnosis + control: the straggler
verdict's input-bound label, the runtime optimizer's input-bound
replan gate, the goodput input-wait column, and the ``tpurun data``
CLI (live + forensic must agree on shard counts). The e2e wedge: one
node's dataloader injected ~30 ms/batch slow is labeled INPUT-bound
(not comm/compute) and program replans are declined with
``PLAN_REJECTED reason=input_bound`` until the injection clears."""

import io
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.sharding_client import (
    IndexShardingClient,
    ShardingClient,
)
from dlrover_tpu.common import comm
from dlrover_tpu.common.config import get_context
from dlrover_tpu.master.local_master import start_local_master
from dlrover_tpu.master.monitor.node_series import NodeRuntimeStore
from dlrover_tpu.master.monitor.straggler import StragglerDetector
from dlrover_tpu.master.optimizer import RuntimeOptimizer
from dlrover_tpu.master.shard.task_manager import TaskManager
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry import (
    EventKind,
    names as tm,
    read_events,
    recent_events,
)
from dlrover_tpu.telemetry.events import clear_ring
from dlrover_tpu.telemetry.goodput import derive_goodput
from dlrover_tpu.telemetry.metrics import process_registry
from dlrover_tpu.trainer.conf import Configuration
from dlrover_tpu.trainer.data import DevicePreloader, ElasticDataLoader
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.executor import (
    ElasticDataShardReportHook,
    NodeRuntimeReportHook,
    TrainExecutor,
    TrainHook,
)

BOUNDS = [0.001, 0.005, 0.01, 0.05, 0.1, 1.0]


@pytest.fixture(autouse=True)
def _telemetry_on():
    ctx = get_context()
    prev = ctx.telemetry_enabled
    ctx.telemetry_enabled = True
    yield
    ctx.telemetry_enabled = prev


def _make_trainer(**kwargs):
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
        strategy=Strategy(mesh=MeshPlan(data=-1)), **kwargs,
    )
    return trainer, batch


def _run_json_cli(argv):
    """Invoke `tpurun <argv>` capturing stdout as parsed JSON."""
    from dlrover_tpu.trainer.run import main as tpurun

    buf, prev = io.StringIO(), sys.stdout
    sys.stdout = buf
    try:
        rc = tpurun(argv)
    finally:
        sys.stdout = prev
    return rc, json.loads(buf.getvalue())


# -- worker side: sharding client ---------------------------------------------


class _FlakyClient:
    """Minimal master-client stand-in whose batch-done RPC fails N
    times before succeeding."""

    def __init__(self, failures=0):
        self.failures = failures
        self.records = []

    def report_dataset_shard_params(self, **kw):
        pass

    def get_task(self, name):
        return None

    def report_batch_done(self, name, records):
        if self.failures > 0:
            self.failures -= 1
            raise OSError("master briefly away")
        self.records.append(records)


class TestShardingClientInstrumentation:
    def test_fetch_and_complete_instruments(self):
        process_registry().reset()
        master = start_local_master()
        try:
            client = MasterClient(master.addr, node_id=0)
            sc = ShardingClient(client, "inst-ds", batch_size=4,
                                dataset_size=16,
                                num_minibatches_per_shard=2)
            while sc.fetch_shard() is not None:
                sc.report_task_done()
            reg = process_registry()
            assert reg.get(tm.DATA_SHARDS_FETCHED).value == 2
            assert reg.get(tm.DATA_SHARDS_COMPLETED).value == 2
            # the fetch RPC latency was measured (one probe returns
            # None at exhaustion — observed too, it is a real wait)
            assert reg.get(tm.DATA_SHARD_FETCH_TIME).count >= 2
            client.close()
        finally:
            master.stop()

    def test_failed_batch_report_restores_the_credit(self):
        """The lost-credit fix: a failed report RPC must re-queue the
        pending count (and count the retry) so the shard completes by
        the NEXT report instead of a timeout re-dispatch that re-reads
        consumed data."""
        process_registry().reset()
        fake = _FlakyClient(failures=1)
        sc = ShardingClient(fake, "flaky-ds", batch_size=4,
                            dataset_size=16)
        with pytest.raises(OSError):
            sc.report_batch_done(2)
        # the credit survived the failure and was counted as a retry
        assert process_registry().get(
            tm.DATA_BATCH_REPORT_RETRIES).value == 1
        sc.report_batch_done(1)
        # 2 restored + 1 new = 3 batches x 4 records
        assert fake.records == [12]

    def test_successful_report_clears_the_pending_count(self):
        fake = _FlakyClient()
        sc = ShardingClient(fake, "ok-ds", batch_size=4, dataset_size=16)
        sc.report_batch_done(2)
        sc.report_batch_done(1)
        assert fake.records == [8, 4]


# -- worker side: prefetcher --------------------------------------------------


class TestDevicePreloaderInstrumentation:
    def test_foreground_depth_and_producer_wait(self):
        process_registry().reset()
        pl = DevicePreloader([{"x": i} for i in range(8)],
                             put_fn=lambda b: b)
        assert len(list(pl)) == 8
        reg = process_registry()
        assert reg.get(tm.DATA_PRODUCER_WAIT_TIME).count >= 7
        assert reg.get(tm.DATA_PREFETCH_QUEUE_DEPTH) is not None

    def test_background_consumer_wait_marks_a_slow_producer(self):
        process_registry().reset()

        def slow_source():
            for i in range(4):
                time.sleep(0.02)
                yield {"x": i}

        pl = DevicePreloader(slow_source(), put_fn=lambda b: b,
                             background=True)
        assert len(list(pl)) == 4
        h = process_registry().get(tm.DATA_CONSUMER_WAIT_TIME)
        assert h is not None and h.count >= 4
        # the consumer genuinely waited on the starved queue
        assert h.sum > 0.04


# -- worker side: executor input wait -----------------------------------------


def _run_executor(trainer, batch, iter_fn, hooks=None, steps=12,
                  window=2):
    executor = TrainExecutor(
        trainer, train_iter_fn=iter_fn, hooks=hooks or [],
        conf=Configuration({
            "train_steps": steps, "log_every_steps": 0,
            "train_window": window, "preemption_grace": False,
        }),
    )
    return executor.train_and_evaluate()


class TestExecutorInputWait:
    def test_gauge_absent_until_measured_then_tracks_starvation(self):
        process_registry().reset()
        clear_ring()
        trainer, batch = _make_trainer()

        def starved():
            for _ in range(12):
                time.sleep(0.03)
                yield batch

        # absent BEFORE any run: a scrape must not read a fake 0
        assert process_registry().get(tm.INPUT_WAIT_FRAC) is None
        _run_executor(trainer, batch, starved)
        g = process_registry().get(tm.INPUT_WAIT_FRAC)
        assert g is not None and g.value > 0.5, g
        assert process_registry().get(tm.INPUT_WAIT_TIME).count >= 12
        # the drain's fetch-free tail windows must NOT zero the gauge
        # (asserted by the > 0.5 above: the last materializations are
        # back-to-back with no fetches between them)
        te = [r for r in recent_events()
              if r["kind"] == EventKind.TRAIN_END]
        assert te and te[-1]["input_wait_s"] > 0.2

        # a fast source drops the fraction back toward 0
        _run_executor(trainer, batch, lambda: iter([batch] * 12))
        assert process_registry().get(tm.INPUT_WAIT_FRAC).value < 0.3

    def test_runtime_report_carries_the_fraction(self):
        process_registry().reset()
        trainer, batch = _make_trainer()
        payloads = []

        class Client:
            node_id = 0

            def report_node_runtime(self, **kw):
                payloads.append(kw)

        hook = NodeRuntimeReportHook(Client(), every_steps=4,
                                     min_interval_s=0)
        _run_executor(trainer, batch, lambda: iter([batch] * 12),
                      hooks=[hook], steps=12)
        hook.end(None)
        assert payloads
        # the field exists and is a measured float (fast iterator: ~0)
        assert payloads[-1]["input_wait_frac"] is not None
        assert payloads[-1]["input_wait_frac"] < 0.5


# -- master side: shard-lifecycle accounting ----------------------------------


class TestMasterShardAccounting:
    def _manager(self, size=24, batch=4, epochs=1):
        t = TaskManager()
        t.new_dataset("acc-ds", size, batch, num_epochs=epochs,
                      num_minibatches_per_shard=2)
        return t

    def test_gauges_absent_before_dispatch_and_retract_on_completion(
            self):
        process_registry().reset()
        clear_ring()
        t = self._manager()
        labels = {"dataset": "acc-ds"}
        reg = process_registry()
        assert reg.get(tm.DATA_SHARDS_TODO, labels=labels) is None
        task = t.get_dataset_task(0, "acc-ds")
        assert reg.get(tm.DATA_SHARDS_TODO, labels=labels).value == 2
        assert reg.get(tm.DATA_SHARDS_DOING, labels=labels).value == 1
        # record credits complete the shard; per-node counters follow
        t.report_batch_done("acc-ds", 0, 8)
        assert reg.get(tm.DATA_SHARDS_DONE, labels=labels).value == 1
        assert reg.get(tm.DATA_NODE_SHARDS_COMPLETED,
                       labels={"node": "0"}).value == 1
        assert reg.get(tm.DATA_NODE_RECORDS_DONE,
                       labels={"node": "0"}).value == 8
        assert reg.get(tm.DATA_SHARD_LATENCY).count == 1
        assert reg.get(tm.DATA_EPOCH_PROGRESS,
                       labels=labels).value == pytest.approx(8 / 24)
        while True:
            task = t.get_dataset_task(1, "acc-ds")
            if task.task_id < 0:
                break
            t.report_batch_done("acc-ds", 1, 8)
        assert t.finished()
        # completion RETRACTS the lifecycle gauges (absent-not-zero)
        assert reg.get(tm.DATA_SHARDS_TODO, labels=labels) is None
        assert reg.get(tm.DATA_EPOCH_PROGRESS, labels=labels) is None
        ends = [r for r in recent_events()
                if r["kind"] == EventKind.DATA_EPOCH_END]
        assert ends and ends[-1]["shards_done"] == 3
        assert ends[-1]["records_done"] == 24 and ends[-1]["final"]

    def test_timeout_recovery_emits_event_and_counter(self):
        process_registry().reset()
        clear_ring()
        t = self._manager()
        t.get_dataset_task(5, "acc-ds")
        time.sleep(0.03)
        t.scan_timeout_tasks_once(timeout_secs=0.01)
        assert process_registry().get(
            tm.DATA_SHARDS_TIMEOUT_RECOVERED).value == 1
        ev = [r for r in recent_events()
              if r["kind"] == EventKind.DATA_SHARD_TIMEOUT]
        assert ev and ev[-1]["dataset"] == "acc-ds"
        assert ev[-1]["error_code"] == "DATA_SHARD_TIMEOUT"
        assert ev[-1]["count"] == 1
        # the recovered shard is dispatchable again
        assert t.get_dataset_task(6, "acc-ds").task_id >= 0

    def test_timeout_monitor_cadence_respects_test_speedups(
            self, monkeypatch):
        """The satellite: the monitor's scan cadence follows the
        configured timeout (re-read per cycle), so shrinking
        seconds_to_timeout_task under test no longer waits out a
        hardcoded 30 s sleep before the first scan."""
        process_registry().reset()
        monkeypatch.setattr(get_context(), "seconds_to_timeout_task",
                            0.05)
        t = self._manager()
        t.get_dataset_task(0, "acc-ds")
        t.start()
        try:
            deadline = time.time() + 5.0
            while time.time() < deadline:
                c = process_registry().get(
                    tm.DATA_SHARDS_TIMEOUT_RECOVERED)
                if c is not None and c.value >= 1:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("timeout monitor never scanned under a "
                            "sub-second seconds_to_timeout_task")
        finally:
            t.stop()

    def test_snapshot_rate_spans_the_union_of_node_windows(self):
        """ETA denominators: the aggregate rate must cover min(first)
        -> max(last) across nodes — a late-joining node's short span
        would overstate the rate and quote an ETA several times too
        short."""
        t = self._manager(size=48)  # 6 shards of 8
        t.get_dataset_task(0, "acc-ds")
        t.report_batch_done("acc-ds", 0, 8)
        t.get_dataset_task(1, "acc-ds")
        t.report_batch_done("acc-ds", 1, 8)
        d = t.get_dataset("acc-ds")
        # offset completion windows: node 0 over [100,110], node 1
        # (an elastic late joiner) over [160,170]
        d._node_first_ts.update({0: 100.0, 1: 160.0})
        d._node_last_ts.update({0: 110.0, 1: 170.0})
        snap = d.snapshot()
        union_rate = 16 / 70.0
        assert snap["eta_s"] == pytest.approx((48 - 16) / union_rate,
                                              rel=0.01)

    def test_overlapping_epochs_account_to_the_tasks_own_epoch(self):
        """Epochs overlap by design (get_task refills lazily while the
        previous epoch's last shards are still doing elsewhere): a late
        epoch-1 completion must close epoch 1 — not inflate epoch 2's
        progress or suppress its DATA_EPOCH_END forever."""
        process_registry().reset()
        clear_ring()
        t = TaskManager()
        t.new_dataset("epoch-ds", 16, 4, num_epochs=2,
                      num_minibatches_per_shard=2)  # 2 shards/epoch
        a = t.get_dataset_task(0, "epoch-ds")  # epoch 1
        b = t.get_dataset_task(1, "epoch-ds")  # epoch 1, todo empty
        assert a.epoch == b.epoch == 1
        t.report_batch_done("epoch-ds", 1, 8)  # B's shard completes
        # B moves on: the lazy refill rolls the splitter to epoch 2
        # while A's epoch-1 shard is STILL doing
        c = t.get_dataset_task(1, "epoch-ds")
        assert c.epoch == 2
        ends = [r for r in recent_events()
                if r["kind"] == EventKind.DATA_EPOCH_END]
        assert not ends  # epoch 1 not drained yet
        # A's late epoch-1 completion closes epoch 1, not epoch 2
        t.report_batch_done("epoch-ds", 0, 8)
        ends = [r for r in recent_events()
                if r["kind"] == EventKind.DATA_EPOCH_END]
        assert ends and ends[-1]["epoch"] == 1
        assert not ends[-1]["final"]
        # epoch 2's progress gauge saw none of epoch 1's records
        g = process_registry().get(tm.DATA_EPOCH_PROGRESS,
                                   labels={"dataset": "epoch-ds"})
        assert g is not None and g.value == 0.0

    def test_data_report_shape(self):
        t = self._manager()
        task = t.get_dataset_task(0, "acc-ds")
        t.report_batch_done("acc-ds", 0, 8)
        report = t.data_report()
        d = report["datasets"]["acc-ds"]
        assert d["shards_done"] == 1 and d["records_done"] == 8
        assert d["todo"] == 2 and d["doing"] == 0
        assert d["epoch_progress"] == pytest.approx(8 / 24, abs=1e-4)
        assert report["nodes"]["0"]["shards_completed"] == 1
        assert task.task_id >= 0


class TestShardCheckpointResumeGauges:
    def test_mid_epoch_resume_gauges_agree_with_remaining_records(self):
        """The satellite: restore from get_shard_checkpoint and the
        restored todo/doing/done + epoch-progress gauges must agree
        with the records ACTUALLY remaining."""
        process_registry().reset()
        t1 = TaskManager()
        t1.new_dataset("ckpt-ds", 40, 4, num_minibatches_per_shard=2)
        first = t1.get_dataset_task(0, "ckpt-ds")
        t1.report_batch_done("ckpt-ds", 0, 8)  # 1 shard done
        t1.get_dataset_task(0, "ckpt-ds")      # 1 doing at checkpoint
        ckpt = t1.get_shard_checkpoint("ckpt-ds")
        assert first.task_id >= 0

        process_registry().reset()  # the restarted master's registry
        t2 = TaskManager()
        t2.new_dataset("ckpt-ds", 40, 4, num_minibatches_per_shard=2)
        t2.restore_shard_checkpoint("ckpt-ds", ckpt)
        reg = process_registry()
        labels = {"dataset": "ckpt-ds"}
        # 5 shards total: 1 done, 1 doing + 3 todo -> 4 restored todo
        assert reg.get(tm.DATA_SHARDS_TODO, labels=labels).value == 4
        assert reg.get(tm.DATA_SHARDS_DOING, labels=labels).value == 0
        assert reg.get(tm.DATA_SHARDS_DONE, labels=labels).value == 1
        # 8 of 40 records consumed pre-restart
        assert reg.get(tm.DATA_EPOCH_PROGRESS, labels=labels).value \
            == pytest.approx(8 / 40)
        # and the remaining records really are 32
        remaining = sum(task.shard.size for task in t2.get_dataset(
            "ckpt-ds").todo)
        assert remaining == 32
        report = t2.data_report()["datasets"]["ckpt-ds"]
        assert report["records_done"] == 8
        assert report["shards_done"] == 1


# -- diagnosis: the input-bound bound label -----------------------------------


def _ingest(store, det, node, ms, steps_total, counts, ts,
            input_frac=None, comm_frac=None):
    store.ingest(comm.NodeRuntimeReport(
        node_id=node, timestamp=ts, step=int(steps_total),
        steps_total=float(steps_total), bounds=BOUNDS,
        step_time_counts=list(counts),
        input_wait_frac=input_frac, exposed_comm_frac=comm_frac,
    ), now=ts)
    det.observe(node, now=ts)


def _counts_at(ms, steps):
    import bisect

    counts = [0] * (len(BOUNDS) + 1)
    idx = bisect.bisect_left(BOUNDS, ms / 1000.0)
    counts[min(idx, len(BOUNDS))] += steps
    return counts


class _Feeder:
    """Cumulative per-node report feeder for synthetic windows."""

    def __init__(self, store, det):
        self.store, self.det = store, det
        self.cum = {}

    def feed(self, node, ms, ts, input_frac=None, comm_frac=None):
        s = self.cum.setdefault(node, {
            "c": [0] * (len(BOUNDS) + 1), "n": 0})
        s["c"] = [a + b for a, b in zip(s["c"], _counts_at(ms, 8))]
        s["n"] += 8
        _ingest(self.store, self.det, node, ms, s["n"], s["c"], ts,
                input_frac=input_frac, comm_frac=comm_frac)


class TestInputBoundVerdict:
    def _flag(self, slow_input, slow_comm, peer_input=0.02,
              peer_comm=0.1):
        store = NodeRuntimeStore()
        det = StragglerDetector(store, ratio=2.0, confirm_windows=3,
                                hang_secs=0)
        f = _Feeder(store, det)
        now = time.time()
        for w in range(3):
            f.feed(0, 5, now + w, input_frac=peer_input,
                   comm_frac=peer_comm)
            f.feed(1, 5, now + w, input_frac=peer_input,
                   comm_frac=peer_comm)
            f.feed(2, 50, now + w, input_frac=slow_input,
                   comm_frac=slow_comm)
        assert det.stragglers() == [2]
        return det.verdicts()[2]["evidence"]

    def test_starved_node_is_input_bound_with_peer_evidence(self):
        # a starved pipeline inflates the exposed-comm residual TOO —
        # without the input leg this node would read comm-bound
        ev = self._flag(slow_input=0.95, slow_comm=0.9)
        assert ev["bound"] == "input-bound"
        assert ev["input_wait_frac"] == pytest.approx(0.95)
        assert ev["peer_median_input_wait_frac"] == pytest.approx(0.02)

    def test_input_tracking_peers_falls_through_to_comm_bound(self):
        ev = self._flag(slow_input=0.05, slow_comm=0.9)
        assert ev["bound"] == "comm-bound"

    def test_everything_tracking_peers_is_compute_bound(self):
        ev = self._flag(slow_input=0.05, slow_comm=0.15)
        assert ev["bound"] == "compute-bound"


# -- control: the optimizer's input-bound replan gate -------------------------


def _running_report(**kw):
    kw.setdefault("node_id", 0)
    kw.setdefault("world", 8)
    kw.setdefault("mesh_shape", {"pipe": 1, "data": 8, "fsdp": 1,
                                 "seq": 1, "tensor": 1})
    kw.setdefault("train_window", 4)
    kw.setdefault("global_batch", 16)
    return comm.TrainerConfigReport(**kw)


def _starved_store(det=None):
    store = NodeRuntimeStore()
    det = det or StragglerDetector(store, ratio=2.0,
                                   confirm_windows=3, hang_secs=0)
    f = _Feeder(store, det)
    now = time.time()
    for w in range(3):
        f.feed(0, 5, now + w, input_frac=0.01)
        f.feed(1, 5, now + w, input_frac=0.02)
        f.feed(2, 50, now + w, input_frac=0.95)
    return store, det, f, now


def _optimizer(store):
    opt = RuntimeOptimizer(store, publish=lambda cfg: None)
    opt.update_model_info(comm.ModelInfo(
        num_params=10_000, hidden_size=32, num_layers=2, seq_len=16))
    opt.update_running_config(_running_report())
    return opt


class TestOptimizerInputBoundGate:
    def test_starved_job_rejects_program_replans_with_evidence(self):
        clear_ring()
        store, det, f, now = _starved_store()
        opt = _optimizer(store)
        d = opt.replan("straggler:2")
        assert d.outcome == "rejected"
        assert d.reason == "input_bound"
        assert d.input_bound["input_bound_node"] == 2
        assert (d.input_bound["input_wait_frac"]
                - d.input_bound["peer_median_input_wait_frac"]) >= 0.1
        rej = [r for r in recent_events()
               if r["kind"] == EventKind.OPTIMIZER_PLAN_REJECTED
               and r.get("reason") == "input_bound"]
        assert rej and rej[-1]["input_bound_node"] == 2

    def test_starvation_clearing_lets_replans_proceed(self):
        store, det, f, now = _starved_store()
        opt = _optimizer(store)
        assert opt.replan("straggler:2").reason == "input_bound"
        # the gate consumed NO cooldown: once the starvation clears
        # the next pass decides on the merits immediately
        for w in range(3, 5):
            f.feed(0, 5, now + w, input_frac=0.01)
            f.feed(1, 5, now + w, input_frac=0.02)
            f.feed(2, 5, now + w, input_frac=0.02)
        d = opt.replan("recovered:2")
        assert d.reason != "input_bound"

    def test_knob_disables_the_gate(self, monkeypatch):
        monkeypatch.setattr(get_context(), "replan_input_bound_gate",
                            False)
        store, det, f, now = _starved_store()
        opt = _optimizer(store)
        d = opt.replan("straggler:2")
        assert d.reason != "input_bound"

    def test_uniform_cluster_wide_starvation_still_gates(self):
        """The most common input-bound mode — every node starved by a
        shared slow source — shows NO peer excess; the absolute
        median backstop must still gate program replans."""
        store = NodeRuntimeStore()
        det = StragglerDetector(store, ratio=2.0, confirm_windows=3,
                                hang_secs=0)
        f = _Feeder(store, det)
        now = time.time()
        for w in range(3):
            for node in (0, 1, 2):
                f.feed(node, 50, now + w, input_frac=0.8)
        opt = _optimizer(store)
        d = opt.replan("tick")
        assert d.reason == "input_bound", (d.outcome, d.reason)
        assert d.input_bound["median_input_wait_frac"] >= 0.5

    def test_no_input_measurements_means_no_gate(self):
        store = NodeRuntimeStore()
        det = StragglerDetector(store, ratio=2.0, confirm_windows=3,
                                hang_secs=0)
        f = _Feeder(store, det)
        now = time.time()
        for w in range(3):
            f.feed(0, 5, now + w)
            f.feed(1, 50, now + w)
        opt = _optimizer(store)
        d = opt.replan("straggler:1")
        assert d is None or d.reason != "input_bound"


# -- goodput: the input-wait column -------------------------------------------


def _ev(kind, ts, pid=1, **kw):
    return {"kind": kind, "ts": ts, "mono": ts, "pid": pid,
            "node": "0", **kw}


class TestGoodputInputWaitColumn:
    def test_column_sums_train_end_fields(self):
        events = [
            _ev(EventKind.TRAIN_START, 0.0, pid=2),
            _ev(EventKind.TRAIN_END, 100.0, pid=2, input_wait_s=12.5),
            _ev(EventKind.TRAIN_START, 0.0, pid=3, node="1"),
            _ev(EventKind.TRAIN_END, 100.0, pid=3, node="1",
                input_wait_s=2.5),
        ]
        rep = derive_goodput(events)
        col = rep["detail"]["input_wait"]
        assert col["seconds"] == pytest.approx(15.0)
        assert col["workers"] == 2
        assert col["fraction_of_productive"] == pytest.approx(
            15.0 / 100.0, abs=0.01)

    def test_absent_without_measurements(self):
        events = [
            _ev(EventKind.TRAIN_START, 0.0, pid=2),
            _ev(EventKind.TRAIN_END, 10.0, pid=2),
        ]
        assert "input_wait" not in derive_goodput(events)["detail"]


# -- the tpurun data CLI gate (live + forensic agree) -------------------------


class TestDataCliGate:
    def test_live_and_forensic_agree_on_shard_counts(self, tmp_path,
                                                     monkeypatch):
        events_path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_path)
        process_registry().reset()
        master = start_local_master()
        try:
            client = MasterClient(master.addr, node_id=0)
            sc = ShardingClient(client, "cli-ds", batch_size=4,
                                dataset_size=24,
                                num_minibatches_per_shard=2)
            while sc.fetch_shard() is not None:
                sc.report_batch_done(2)  # 8 records completes a shard
            rc1, live = _run_json_cli(
                ["data", "--addr", master.addr, "--json"])
            rc2, forensic = _run_json_cli(
                ["data", "--events", events_path, "--json"])
            assert rc1 == 0 and rc2 == 0
            lv, fv = (live["datasets"]["cli-ds"],
                      forensic["datasets"]["cli-ds"])
            assert lv["shards_done"] == fv["shards_done"] == 3
            assert lv["records_done"] == fv["records_done"] == 24
            assert lv["completed"] and fv["completed"]
            # the text views render without error too
            from dlrover_tpu.trainer.run import main as tpurun

            assert tpurun(["data", "--addr", master.addr]) == 0
            assert tpurun(["data", "--events", events_path]) == 0
            client.close()
        finally:
            master.stop()


# -- overhead gate ------------------------------------------------------------


class _TimedRegion(TrainHook):
    def __init__(self, warmup):
        self.warmup = warmup
        self.t0 = None

    def before_step(self, step):
        if step == self.warmup + 1 and self.t0 is None:
            self.t0 = time.perf_counter()


class TestDataPlaneOverheadGate:
    def test_overhead_within_budget(self):
        """≤5% paired-median overhead for the data-plane hooks (the
        preloader instruments + the executor's input-wait clock), on
        vs off, with the PR 8 methodology hardened per the de-flake
        satellite: up to 3 attempts of 3 back-to-back pairs each,
        gating on the MINIMUM of the attempt medians — the true cost
        is a lower envelope, and one noisy attempt on a shared 1-core
        box must not fail a clean tree."""
        steps, warmup = 280, 8
        ctx = get_context()
        trainer, batch = _make_trainer()

        def run(telemetry):
            ctx.telemetry_enabled = telemetry
            timer = _TimedRegion(warmup)
            preloader = DevicePreloader(
                iter([batch] * (warmup + steps)), put_fn=lambda b: b)
            executor = TrainExecutor(
                trainer, train_iter_fn=lambda: iter(preloader),
                hooks=[timer],
                conf=Configuration({
                    "train_steps": warmup + steps,
                    "log_every_steps": 0, "train_window": 4,
                    "preemption_grace": False,
                }),
            )
            executor.train_and_evaluate()
            ctx.telemetry_enabled = True
            return time.perf_counter() - timer.t0

        def attempt():
            ratios = []
            for i in range(3):
                if i % 2 == 0:
                    dt_b = run(False)
                    dt_i = run(True)
                else:
                    dt_i = run(True)
                    dt_b = run(False)
                ratios.append(dt_i / dt_b)
            return sorted(ratios)[len(ratios) // 2]

        medians = []
        for _ in range(3):
            medians.append(attempt())
            if medians[-1] - 1.0 <= 0.05:
                break
        overhead = min(medians) - 1.0
        assert overhead <= 0.05, (
            f"data-plane overhead {overhead:.1%} above the 5% budget "
            f"(attempt medians {[round(m, 3) for m in medians]})"
        )


# -- the e2e input-bound wedge ------------------------------------------------


class _SlowBatches:
    """Wraps a loader: ~30 ms of host latency per batch — the injected
    input starvation (the dataloader is slow; the device step is not)."""

    def __init__(self, inner, seconds):
        self.inner = inner
        self.seconds = seconds

    def __iter__(self):
        for item in self.inner:
            time.sleep(self.seconds)
            yield item


def _wedge_dataset(batch, n_batches=40, batch_size=16):
    xs = np.asarray(batch["x"], np.float32)
    ys = np.asarray(batch["y"], np.float32)
    samples = []
    for i in range(n_batches * batch_size):
        samples.append({"x": xs[i % 16], "y": ys[i % 16]})
    return samples


def _run_wedge_node(trainer, batch, master, node_id, dataset_name,
                    slow_s=0.0):
    """One 'node' of the wedge: the FULL data path — IndexShardingClient
    pulling shards from the real master, ElasticDataLoader assembling
    batches, the shard-report hook crediting them back — under a real
    executor with the real runtime-report hook."""
    process_registry().reset()
    client = MasterClient(master.addr, node_id=node_id)
    batch_size, n_batches = 16, 40
    dataset = _wedge_dataset(batch, n_batches, batch_size)
    sharding = IndexShardingClient(
        client, dataset_name, batch_size=batch_size,
        dataset_size=len(dataset), num_minibatches_per_shard=2)
    loader = ElasticDataLoader(dataset, batch_size,
                               sharding_client=sharding)

    def iter_fn():
        return iter(_SlowBatches(loader, slow_s) if slow_s else loader)

    hooks = [
        ElasticDataShardReportHook(sharding, batch_size),
        NodeRuntimeReportHook(client, every_steps=6, min_interval_s=0),
    ]
    executor = TrainExecutor(
        trainer, train_iter_fn=iter_fn, hooks=hooks,
        conf=Configuration({
            "train_steps": 0,  # run the dataset to exhaustion
            "log_every_steps": 0, "train_window": 2,
            "preemption_grace": False,
        }),
    )
    out = executor.train_and_evaluate()
    client.close()
    return out


class TestInputBoundWedge:
    def test_starved_node_is_input_bound_and_gates_replans(
            self, tmp_path, monkeypatch):
        """The acceptance wedge: one node of three with a ~30 ms/batch
        slow dataloader on the CPU mesh → the diagnosis labels THAT
        node input-bound (with peer-median evidence, not
        comm/compute), the optimizer declines a program replan with
        PLAN_REJECTED reason=input_bound under the SAME incident trace
        id, removing the injection flips the label back and replans
        proceed — all visible in tpurun data / plan / trace."""
        events_path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_path)
        ctx = get_context()
        monkeypatch.setattr(ctx, "diagnosis_confirm_windows", 3)
        monkeypatch.setattr(ctx, "diagnosis_straggler_ratio", 2.0)
        master = start_local_master()
        try:
            trainer, batch = _make_trainer()
            # the optimizer needs the running config + model facts
            seed = MasterClient(master.addr, node_id=0)
            seed.report_trainer_config(
                world=1,
                mesh_shape={"pipe": 1, "data": 1, "fsdp": 1, "seq": 1,
                            "tensor": 1},
                train_window=2, global_batch=16)
            seed.report_model_info(comm.ModelInfo(
                num_params=10, hidden_size=4, num_layers=1,
                seq_len=16))
            seed.close()

            # fast peers anchor the medians, then the starved node
            _run_wedge_node(trainer, batch, master, 0, "wedge-0")
            _run_wedge_node(trainer, batch, master, 1, "wedge-1")
            _run_wedge_node(trainer, batch, master, 2, "wedge-2",
                            slow_s=0.03)

            det = master.servicer.straggler_detector
            assert det.stragglers() == [2], det.verdicts()
            verdict = det.verdicts()[2]
            ev = verdict["evidence"]
            assert ev["bound"] == "input-bound", ev
            assert ev["input_wait_frac"] \
                - ev["peer_median_input_wait_frac"] >= 0.1
            trace_id = verdict["trace_id"]

            # the verdict listener replanned; the gate declined the
            # program plan, and the decision joins the SAME incident
            decisions = master.servicer.runtime_optimizer.decisions()
            gated = [d for d in decisions
                     if d["reason"] == "input_bound"]
            assert gated, decisions
            assert gated[-1]["trace_id"] == trace_id
            assert gated[-1]["input_bound"]["input_bound_node"] == 2

            records = read_events(events_path)
            rejected = [
                r for r in records
                if r["kind"] == EventKind.OPTIMIZER_PLAN_REJECTED
                and r.get("reason") == "input_bound"
            ]
            assert rejected and rejected[-1]["trace_id"] == trace_id

            # remove the injection: the label clears and replans
            # proceed on the merits (no longer input_bound-gated)
            _run_wedge_node(trainer, batch, master, 2, "wedge-2b")
            assert det.stragglers() == [], det.verdicts()
            post = [
                d for d in
                master.servicer.runtime_optimizer.decisions()
                if d["trigger"] == "recovered:2"
            ]
            assert post, "recovery never triggered a replan"
            assert post[-1]["reason"] != "input_bound"

            # the shard ledger flowed end-to-end: live + forensic data
            # CLIs agree on the wedge datasets' shard counts
            rc_live, live = _run_json_cli(
                ["data", "--addr", master.addr, "--json"])
            rc_for, forensic = _run_json_cli(
                ["data", "--events", events_path, "--json"])
            assert rc_live == 0 and rc_for == 0
            for name in ("wedge-0", "wedge-1", "wedge-2"):
                assert live["datasets"][name]["shards_done"] \
                    == forensic["datasets"][name]["shards_done"] == 20
                assert live["datasets"][name]["completed"]

            # plan + trace views over the same incident render
            from dlrover_tpu.trainer.run import main as tpurun

            assert tpurun(["plan", "--events", events_path]) == 0
            trace_out = str(tmp_path / "trace.json")
            assert tpurun(["trace", "--events", events_path,
                           "--out", trace_out]) == 0
            assert json.load(open(trace_out))["traceEvents"]
        finally:
            master.stop()
