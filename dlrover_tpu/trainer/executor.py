"""High-level train_and_evaluate executor with hooks.

Role parity: ``dlrover/trainer/tensorflow/executor/
estimator_executor.py:52-287`` (estimator ``train_and_evaluate`` wrapper
with SessionRunHooks, checkpoint cadence, failover-driven session
restart) and the reporting hooks of ``dlrover/python/elastic_agent/
tensorflow/hooks.py:59-113``.

The TPU shape: the "session" is the compiled SPMD program owned by
``ElasticTrainer``; a restart is recompile+reshard, not process death.
Hooks observe the loop at the same points the TF SessionRunHooks did.
"""

from __future__ import annotations

import collections
import os
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional

from dlrover_tpu.common.config import get_context
from dlrover_tpu.common.constants import TrainingExceptionLevel
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.diagnosis.hang_detector import touch_heartbeat
from dlrover_tpu.telemetry import (
    EventKind,
    SpanName,
    emit_event,
    get_registry,
    names as tm,
    span,
)
from dlrover_tpu.telemetry.metrics import percentile_from_counts
from dlrover_tpu.utils.compile_cache import cache_traffic, compile_programs
from dlrover_tpu.trainer.conf import Configuration
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.failover import FailoverClient, TrainingFailover

logger = get_logger("trainer.executor")


def _fullest_device_memory() -> Optional[Dict[str, int]]:
    """``memory_stats()`` of the local device that holds the most
    (``bytes_in_use`` and the loaded programs' ``bytes_reserved``):
    those two, the allocator's peak and the limit. None where no local
    device keeps such statistics (the CPU)."""
    import jax

    keys = ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
            "bytes_limit")
    fullest = None
    for device in jax.local_devices():
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — a backend without the call
            logger.debug("device memory_stats unavailable", exc_info=True)
            stats = None
        if not stats:
            continue
        held = {k: int(stats.get(k, 0)) for k in keys}
        if fullest is None or (
                held["bytes_in_use"] + held["bytes_reserved"]
                > fullest["bytes_in_use"] + fullest["bytes_reserved"]):
            fullest = held
    return fullest


class NonFiniteLossError(RuntimeError):
    """Raised when the guardrail sees a NaN/Inf loss or gradient and the
    configured policy is \"halt\"."""


@dataclass
class _Inflight:
    """One dispatched-but-unmaterialized train step: step
    ``last_step``, metrics still on device."""

    last_step: int
    metrics: Dict[str, Any]


class TrainHook:
    """SessionRunHook parity: override any subset."""

    def begin(self, executor: "TrainExecutor"):
        ...

    def before_step(self, step: int):
        ...

    def after_step(self, step: int, metrics: Dict[str, Any]):
        ...

    def after_evaluate(self, step: int, metrics: Dict[str, Any]):
        ...

    def end(self, executor: "TrainExecutor"):
        ...


class ElasticDataShardReportHook(TrainHook):
    """Report consumed batches so the master completes shards
    (reference hooks.py:97 ``ElasticDataShardReportHook``).

    One BATCH credit per materialized step: ``report_batch_done``
    takes a batch COUNT and multiplies by the client's own batch size
    — passing ``batch_size`` as the count (the old behavior) credited
    ``batch_size²`` records per step, completing shards the worker had
    not actually read and desyncing the master's ledger from reality.
    ``batch_size`` stays accepted for call-site compatibility but the
    client owns the records conversion."""

    def __init__(self, sharding_client, batch_size: int = 0):
        self._client = sharding_client
        self._batch_size = batch_size  # informational only

    def after_step(self, step: int, metrics: Dict[str, Any]):
        try:
            self._client.report_batch_done(1)
        except Exception:  # noqa: BLE001 — reporting must not kill training
            logger.exception("shard report failed")


class ReportModelInfoHook(TrainHook):
    """Report model facts + step speed to the master (reference
    hooks.py:59 ``ReportModelMetricHook``)."""

    def __init__(self, master_client, param_count: int = 0,
                 flops_per_step: float = 0.0, every_steps: int = 20,
                 model_spec=None):
        self._client = master_client
        self._param_count = param_count
        self._flops = flops_per_step
        # optional planner ModelSpec: carries the shape facts (layers,
        # hidden, experts) the master's runtime optimizer needs to
        # price knob families — without them the calibrated spec is a
        # dense placeholder and e.g. dispatch_chunks never competes
        self._model_spec = model_spec
        self._every = max(every_steps, 1)
        reg = get_registry()
        self._c_reports = reg.counter(
            tm.MASTER_REPORTS, help="global-step/model reports sent")
        self._c_report_failures = reg.counter(
            tm.MASTER_REPORT_FAILURES,
            help="reports the master never acked (counted, never raised)")

    def begin(self, executor: "TrainExecutor"):
        if self._param_count <= 0:
            return
        try:
            from dlrover_tpu.common import comm

            spec = self._model_spec
            extra = {}
            if spec is not None:
                extra = dict(
                    hidden_size=int(getattr(spec, "hidden_size", 0)),
                    num_layers=int(getattr(spec, "num_layers", 0)),
                    seq_len=int(getattr(spec, "seq_len", 0)),
                    num_experts=int(getattr(spec, "num_experts", 0)),
                    moe_top_k=int(getattr(spec, "moe_top_k", 1)),
                    ffn_mult=float(getattr(spec, "ffn_mult", 0.0)),
                )
            self._client.report_model_info(comm.ModelInfo(
                num_params=self._param_count,
                flops_per_step=self._flops,
                **extra,
            ))
            self._c_reports.inc()
        except Exception:  # noqa: BLE001
            self._c_report_failures.inc()
            logger.exception("model info report failed")

    def after_step(self, step: int, metrics: Dict[str, Any]):
        # runs at MATERIALIZATION (the executor's lagged window), so the
        # reported step is never ahead of host-visible metrics
        if step % self._every:
            return
        try:
            self._client.report_global_step(step)
            self._c_reports.inc()
        except Exception:  # noqa: BLE001 — a dead master must not kill
            # training; the failure is counted so operators see the gap
            self._c_report_failures.inc()


class NodeRuntimeReportHook(TrainHook):
    """Push node-tagged snapshots of the PR 4 instruments to the master
    every ``runtime_report_steps`` materialized steps — the input of the
    cluster diagnosis plane (``master/monitor/node_series.py``).

    Snapshots are CUMULATIVE histogram bucket counts (the master diffs
    consecutive reports into per-window series), plus window occupancy,
    lagged-metric age, process RSS and accelerator ``bytes_in_use``
    where the backend exposes it.

    The step path only SNAPSHOTS (a few tuple copies) and enqueues; the
    RPC, the ``/proc`` RSS read, and the device memory query run on a
    background daemon sender thread. Backpressure drops the report (the
    next cadence supersedes it) — monitoring must never stall the loop,
    and a dead master is a counted gap, not a crash. The send rate is
    additionally floored by ``min_interval_s`` (default: the master's
    ``seconds_interval_to_report``), so a fast-stepping job cannot
    flood the master — or tax itself — with per-step-scale report
    traffic: reporting overhead scales with WALL time, not step count.
    """

    def __init__(self, master_client, every_steps: Optional[int] = None,
                 registry=None, min_interval_s: Optional[float] = None):
        import queue

        ctx = get_context()
        self._client = master_client
        self._every = int(
            every_steps if every_steps is not None
            else getattr(ctx, "runtime_report_steps", 32))
        self._min_interval = float(
            min_interval_s if min_interval_s is not None
            else getattr(ctx, "seconds_interval_to_report", 15))
        self._last_send = 0.0
        self._queue: "queue.Queue" = queue.Queue(maxsize=2)
        self._sender: Optional[threading.Thread] = None
        # the instruments this hook snapshots (same handles the
        # executor observes into); a test may pass a private registry
        # to simulate several nodes in one process
        reg = registry if registry is not None else get_registry()
        self._reg = reg
        self._h_step = reg.histogram(tm.STEP_TIME)
        self._h_dispatch = reg.histogram(tm.STEP_DISPATCH_TIME)
        self._h_sync = reg.histogram(tm.STEP_HOST_SYNC_TIME)
        self._g_window = reg.gauge(tm.DISPATCH_WINDOW_OCCUPANCY)
        self._g_lag = reg.gauge(tm.LAGGED_METRIC_AGE)
        self._c_steps = reg.counter(tm.TRAIN_STEPS)
        self._c_sent = get_registry().counter(
            tm.NODE_RUNTIME_REPORTS,
            help="node runtime snapshots pushed to the master")
        self._c_failed = get_registry().counter(
            tm.NODE_RUNTIME_REPORT_FAILURES,
            help="runtime snapshots the master never acked")
        self._devices = None

    def _rss_mb(self) -> float:
        try:
            import psutil

            return psutil.Process().memory_info().rss / (1024 * 1024)
        except Exception:  # noqa: BLE001 — psutil-less hosts
            logger.debug("psutil rss read failed; using getrusage",
                         exc_info=True)
            import resource

            # ru_maxrss is KB on Linux (peak, not current — good enough)
            return resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 1024.0

    def _device_memory_mb(self):
        """(bytes_in_use MB, headroom MB) summed over local devices —
        each ``None`` when NO backend device exposes the stat: a CPU
        mesh must report the gauge ABSENT, not a fake 0 an operator
        would read as an empty accelerator."""
        try:
            import jax

            if self._devices is None:
                self._devices = jax.local_devices()
            in_use = limit = None
            for d in self._devices:
                stats_fn = getattr(d, "memory_stats", None)
                stats = stats_fn() if stats_fn is not None else None
                if not stats:
                    continue
                if "bytes_in_use" in stats:
                    in_use = (in_use or 0) + int(stats["bytes_in_use"])
                if stats.get("bytes_limit"):
                    limit = (limit or 0) + int(stats["bytes_limit"])
            mb = 1024 * 1024
            headroom = (
                (limit - (in_use or 0)) / mb
                if limit is not None else None
            )
            return (in_use / mb if in_use is not None else None,
                    headroom)
        except Exception:  # noqa: BLE001 — CPU backends return nothing
            logger.debug("device memory_stats unavailable",
                         exc_info=True)
            return None, None

    def _gauge_value(self, name: str):
        """A gauge's value if it EXISTS in this hook's registry, else
        None — attribution gauges are created only once a record was
        captured, so absence genuinely means 'not measured'."""
        getter = getattr(self._reg, "get", None)
        metric = getter(name) if getter is not None else None
        return float(metric.value) if metric is not None else None

    def after_step(self, step: int, metrics: Dict[str, Any]):
        if self._every <= 0 or step % self._every:
            return
        now = time.monotonic()
        if now - self._last_send < self._min_interval:
            return
        self._last_send = now
        import queue

        bounds = getattr(self._h_step, "bounds", None)  # null when off
        counts = self._h_step.snapshot_counts()
        payload = dict(
            step=step,
            steps_total=float(self._c_steps.value),
            bounds=list(bounds) if bounds else None,
            step_time_counts=list(counts) if counts else None,
            dispatch_counts=(
                list(self._h_dispatch.snapshot_counts() or []) or None),
            host_sync_counts=(
                list(self._h_sync.snapshot_counts() or []) or None),
            window_occupancy=float(self._g_window.value),
            lagged_age=float(self._g_lag.value),
            # performance-attribution gauges (None until the executor
            # captured a record — the master exports them per node only
            # when they exist)
            mfu=self._gauge_value(tm.ATTR_MFU),
            exposed_comm_frac=self._gauge_value(
                tm.ATTR_EXPOSED_COMM_FRAC),
            flops_per_step=self._gauge_value(tm.ATTR_FLOPS_PER_STEP),
            peak_hbm_mb=self._gauge_value(tm.ATTR_PEAK_HBM_MB),
            # data plane: the executor's derived input-wait fraction
            # (absent until the first measured window, like the
            # attribution gauges — the master exports it per node only
            # when it exists)
            input_wait_frac=self._gauge_value(tm.INPUT_WAIT_FRAC),
        )
        if self._sender is None or not self._sender.is_alive():
            self._sender = threading.Thread(
                target=self._send_loop, name="node-runtime-report",
                daemon=True,
            )
            self._sender.start()
        try:
            self._queue.put_nowait(payload)
        except queue.Full:
            # sender is behind (slow/dead master): drop — the next
            # cadence's cumulative snapshot supersedes this one
            self._c_failed.inc()

    def _send_loop(self):
        while True:
            payload = self._queue.get()
            if payload is None:
                return
            try:
                payload["rss_mb"] = round(self._rss_mb(), 1)
                in_use_mb, headroom_mb = self._device_memory_mb()
                payload["device_mem_mb"] = (
                    round(in_use_mb, 1) if in_use_mb is not None
                    else None)
                payload["hbm_headroom_mb"] = (
                    round(headroom_mb, 1) if headroom_mb is not None
                    else None)
                if headroom_mb is not None:
                    # worker-local mirror (created only when the stat
                    # exists — absent on CPU, never 0)
                    self._reg.gauge(
                        tm.ATTR_HBM_HEADROOM_MB,
                        help="device HBM bytes_limit - bytes_in_use",
                    ).set(headroom_mb)
                self._client.report_node_runtime(**payload)
                self._c_sent.inc()
            except Exception:  # noqa: BLE001 — a dead master must not
                # kill reporting; the gap is counted for operators
                self._c_failed.inc()
                logger.debug("node runtime report failed",
                             exc_info=True)

    def end(self, executor: "TrainExecutor"):
        """Flush: stop the sender after the queued reports drain (join
        bounded — exit must not hang on a dead master)."""
        if self._sender is None or not self._sender.is_alive():
            return
        try:
            self._queue.put_nowait(None)
        except Exception:  # noqa: BLE001 — full queue: sender is wedged
            logger.debug("runtime report queue full at end", exc_info=True)
            return
        self._sender.join(timeout=5.0)


class SnapshotReplicaHook(TrainHook):
    """Push the node's host-snapshot regions to k master-assigned peers
    on a cadence — the peer-redundancy plane of checkpoint-free
    recovery (``checkpoint.replication``).

    The step path pays ONE ``device_get`` per cadence (the same sync a
    checkpoint save stages, floored by ``replica_min_interval_secs`` so
    a fast-stepping job cannot tax itself); slicing, checksummed
    framing and the per-peer RPC stream run on the replicator's
    background daemon thread, with drop-on-backpressure — replication
    is redundancy, never a stall."""

    def __init__(self, master_client, every_steps: Optional[int] = None,
                 min_interval_s: Optional[float] = None,
                 replicator=None):
        ctx = get_context()
        self._client = master_client
        self._every = int(
            every_steps if every_steps is not None
            else getattr(ctx, "replica_cadence_steps", 16))
        self._min_interval = float(
            min_interval_s if min_interval_s is not None
            else getattr(ctx, "replica_min_interval_secs", 15.0))
        self._last_send = 0.0
        self._executor: Optional["TrainExecutor"] = None
        self.replicator = replicator
        self._owns_replicator = replicator is None

    def begin(self, executor: "TrainExecutor"):
        self._executor = executor
        if self.replicator is not None:
            return
        from dlrover_tpu.checkpoint.replication import SnapshotReplicator

        try:
            self.replicator = SnapshotReplicator(
                self._client,
                node_id=int(getattr(self._client, "node_id", 0)),
            )
        except Exception:  # noqa: BLE001 — a port/bind failure loses
            # redundancy, not the job; the gap is visible in the logs
            logger.exception("snapshot replicator startup failed; "
                             "peer redundancy disabled for this run")

    def after_step(self, step: int, metrics: Dict[str, Any]):
        if self.replicator is None or self._executor is None:
            return
        # prefer the MASTER-computed cluster-wide cadence (one value
        # for every node): a per-node wall floor can drift nodes onto
        # disjoint push-step schedules — a jitter event puts node A on
        # {48, 80, ...} and node B on {64, 96, ...} with no resync —
        # and a rebuild needs ONE step with full owner coverage. The
        # local floor only paces the bootstrap cycles before the first
        # plan (and single-node runs, where alignment is moot).
        plan_cadence = int(getattr(
            self.replicator, "plan_cadence_steps", 0) or 0)
        every = plan_cadence if plan_cadence > 0 else self._every
        if every <= 0 or step % every:
            return
        now = time.monotonic()
        if plan_cadence <= 0 and now - self._last_send < \
                self._min_interval:
            return
        self._last_send = now
        try:
            snap = self._executor._trainer.snapshot(self._executor.state)
        except Exception:  # noqa: BLE001 — a failed snapshot loses one
            # cadence of redundancy, never the step loop
            logger.exception("replica snapshot failed at step %d", step)
            return
        self.replicator.submit(snap.tree, snap.meta, snap.step)

    def end(self, executor: "TrainExecutor"):
        if self.replicator is not None and self._owns_replicator:
            self.replicator.stop()


class OptimizerPlanHook(TrainHook):
    """Poll the master for a runtime-optimizer plan and apply it LIVE.

    The master's re-planner (``master/optimizer``) publishes chosen
    plans through the ``ParallelConfig`` broadcast (a non-empty
    ``plan_id`` marks one). A background daemon thread polls
    ``get_parallel_config`` on a WALL-TIME cadence — a dead master's
    RPC timeout must never park the step loop — and routes a fresh plan
    to ``executor.request_retune`` (live: drain → retune/reshard →
    resume) or ``request_restart`` when the master explicitly asked for
    one. Each plan id is applied at most once per process."""

    def __init__(self, master_client, poll_secs: Optional[float] = None):
        ctx = get_context()
        self._client = master_client
        self._poll = float(
            poll_secs if poll_secs is not None
            else getattr(ctx, "plan_poll_secs", 30.0))
        self._executor: Optional["TrainExecutor"] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._seen_plan = ""

    def begin(self, executor: "TrainExecutor"):
        self._executor = executor
        if self._poll <= 0:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._poll_loop, name="optimizer-plan-poll",
            daemon=True,
        )
        self._thread.start()

    def _poll_loop(self):
        while not self._stop.wait(self._poll):
            try:
                self.poll_once()
            except Exception as e:  # noqa: BLE001 — master briefly away
                logger.warning(
                    "optimizer plan poll failed, retrying next cadence "
                    "(%s: %s)", type(e).__name__, e)

    def poll_once(self):
        """One poll (also the test entry): fetch the broadcast config
        and hand any UNSEEN optimizer plan to the executor."""
        if self._executor is None:
            return
        cfg = self._client.get_parallel_config()
        plan_id = getattr(cfg, "plan_id", "") or ""
        if not plan_id or plan_id == self._seen_plan:
            return
        if (
            (getattr(cfg, "serve_slots", 0)
             or getattr(cfg, "serve_prefill_chunk", 0)
             or getattr(cfg, "serve_prefix_pool_pages", -1) >= 0)
            and not cfg.mesh_shape
            and cfg.train_window < 0
            and not getattr(cfg, "dispatch_chunks", 0)
            and not getattr(cfg, "moe_precision", "")
            and not getattr(cfg, "fsdp_precision", "")
            and not getattr(cfg, "restart", False)
        ):
            # a SERVE-ONLY plan (every training knob at its sentinel):
            # addressed to a serve worker sharing this master's
            # broadcast slot. Applying it here would be a no-op apply
            # that ACKS the plan — the master would mark it applied
            # and retract it before the serve worker ever polls it.
            # Mark seen and leave it alone.
            self._seen_plan = plan_id
            return
        self._seen_plan = plan_id
        if getattr(cfg, "restart", False):
            logger.info("optimizer plan %s requests a restart", plan_id)
            self._executor.request_restart()
            return
        import jax

        wants_program = (bool(cfg.mesh_shape)
                         or bool(getattr(cfg, "dispatch_chunks", 0))
                         or bool(getattr(cfg, "moe_precision", ""))
                         or bool(getattr(cfg, "fsdp_precision", "")))
        if wants_program and jax.process_count() > 1:
            # each process polls on its own clock: an in-place program
            # swap applied at different wall times would diverge the
            # collective schedule across hosts (host A on the new mesh
            # against host B's old program deadlocks). Until the apply
            # is barriered through a rendezvous, multi-host jobs take
            # only the host-local knob live.
            logger.warning(
                "optimizer plan %s changes the compiled program; "
                "in-place swaps are not synchronized across hosts yet "
                "— applying only train_window", plan_id)
            if cfg.train_window >= 0:
                # host-local knob only, WITHOUT the plan identity: an
                # ack would mark the full program plan applied on the
                # master (bogus ~1.0x realized + retraction) when its
                # program knobs never took effect
                self._executor.request_retune(
                    train_window=cfg.train_window,
                    trace_id=getattr(cfg, "trace_id", "") or "",
                )
            # negative-ack the program plan so the master blacklists
            # it instead of re-publishing every cooldown window
            self._executor._report_trainer_config(
                plan_id=plan_id, apply_failed=True)
            return
        if getattr(cfg, "moe_dispatch", ""):
            # a dispatch-mode change rebuilds the MODEL (the mode lives
            # in the model config, not a trainer knob) — not appliable
            # live yet, and silently acking it as applied would lie to
            # the decision trail
            logger.warning(
                "optimizer plan %s carries moe_dispatch=%s, which "
                "cannot be applied live yet; ignoring that knob",
                plan_id, cfg.moe_dispatch)
        self._executor.request_retune(
            train_window=(cfg.train_window
                          if cfg.train_window >= 0 else None),
            mesh_shape=(dict(cfg.mesh_shape) if cfg.mesh_shape
                        else None),
            dispatch_chunks=(
                getattr(cfg, "dispatch_chunks", 0) or None),
            moe_precision=(
                getattr(cfg, "moe_precision", "") or None),
            fsdp_precision=(
                getattr(cfg, "fsdp_precision", "") or None),
            plan_id=plan_id,
            trace_id=getattr(cfg, "trace_id", "") or "",
            predicted_speedup=float(
                getattr(cfg, "predicted_speedup", 0.0) or 0.0),
            prewarm=bool(getattr(cfg, "prewarm", True)),
        )

    def end(self, executor: "TrainExecutor"):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)


class TrainExecutor:
    """train_and_evaluate over an ElasticTrainer.

    Args:
      trainer: a prepared-or-not ElasticTrainer.
      train_iter_fn: () -> iterable of batches (re-invoked after restart,
        so elastic data sources re-attach at the current shard).
      eval_fn: optional (state) -> metrics dict.
      conf: Configuration with (all optional) ``train_steps``,
        ``eval_every_steps``, ``log_every_steps``.
    """

    def __init__(
        self,
        trainer: ElasticTrainer,
        train_iter_fn: Callable[[], Iterable],
        eval_fn: Optional[Callable[[Any], Dict]] = None,
        hooks: Optional[List[TrainHook]] = None,
        conf: Optional[Configuration] = None,
        master_client=None,
        failover_client: Optional[FailoverClient] = None,
        reshard_world_fn: Optional[Callable[[], Optional[List[Any]]]] = None,
    ):
        self._trainer = trainer
        self._train_iter_fn = train_iter_fn
        self._eval_fn = eval_fn
        self._hooks = list(hooks or [])
        conf = conf or Configuration()
        ctx = get_context()
        self._train_steps = int(conf.get("train_steps", 0))
        self._eval_every = int(conf.get("eval_every_steps", 0))
        self._log_every = int(conf.get("log_every_steps", 50))
        # NaN/overflow guardrail cadence + policy (reference: the error
        # monitor / report_failure path the torch agent takes on a
        # process error, training.py:426)
        self._check_finite_every = int(conf.get(
            "check_finite_every_steps", ctx.check_finite_every_steps
        ))
        # async dispatch pipeline: up to ``train_window`` step calls stay
        # in flight before the oldest call's metrics are materialized on
        # host; 0 = synchronous (materialize right after each dispatch).
        # Hooks, the finite check, speed logging and master reporting all
        # consume LAGGED host values, so the device queue never drains on
        # Python/RPC overhead — and non-finite detection can fire up to
        # train_window steps late (rollback unchanged).
        self._train_window = max(0, int(conf.get(
            "train_window", getattr(ctx, "train_window", 4)
        )))
        self._window: "collections.deque[_Inflight]" = collections.deque()
        # monotonic: the speed line must survive wall-clock jumps (NTP
        # slews on long jobs) and a drain/resume boundary
        self._last_log = time.monotonic()
        self._last_materialize = time.monotonic()
        # bucket-count snapshot at the previous speed-log line, so the
        # quoted p50/p95 cover just the last window
        self._log_counts_snapshot = None
        # telemetry handles (null objects when the knob is off — the
        # hot loop carries no branches either way)
        reg = get_registry()
        self._h_step_time = reg.histogram(
            tm.STEP_TIME, help="per-optimizer-step wall time, observed "
                               "at (lagged) materialization")
        self._h_dispatch = reg.histogram(
            tm.STEP_DISPATCH_TIME,
            help="host time dispatching one train-step call")
        self._h_host_sync = reg.histogram(
            tm.STEP_HOST_SYNC_TIME,
            help="host time blocked materializing the oldest in-flight "
                 "call (the pipeline's one device sync)")
        self._g_window = reg.gauge(
            tm.DISPATCH_WINDOW_OCCUPANCY,
            help="in-flight dispatches right after a dispatch")
        self._g_lag = reg.gauge(
            tm.LAGGED_METRIC_AGE,
            help="steps between the newest dispatch and the metrics "
                 "just materialized")
        self._c_steps = reg.counter(
            tm.TRAIN_STEPS, help="optimizer steps materialized")
        self._c_nonfinite = reg.counter(
            tm.NONFINITE_STEPS, help="non-finite steps detected")
        self._c_rollbacks = reg.counter(
            tm.NONFINITE_ROLLBACKS, help="checkpoint rollbacks taken")
        self._c_preempt = reg.counter(
            tm.PREEMPT_NOTICES, help="preemption notices received")
        self._h_eval = reg.histogram(
            tm.EVAL_TIME, help="eval_fn wall time")
        # data plane: host time blocked in next(data_iter) fetching the
        # batch for a dispatch. The derived INPUT_WAIT_FRAC gauge is
        # created lazily at the first MEASURED materialization window
        # (absent-not-zero, same discipline as ATTR_MFU) and rides
        # NodeRuntimeReport into the master's per-node series — the
        # third leg of the bound triad (input/comm/compute).
        self._h_input_wait = reg.histogram(
            tm.INPUT_WAIT_TIME,
            help="host time blocked waiting for the next host batch")
        self._g_input_wait: Optional[Any] = None
        self._input_wait_total = 0.0
        self._input_wait_count = 0
        self._input_wait_mark = 0.0
        self._input_wait_count_mark = 0
        self._input_wait_run_start = 0.0
        # newest dispatched (not yet necessarily materialized) step —
        # the minuend of the lagged-metric age
        self._dispatched_step = 0
        # on-demand device profiling: the profile_signal knob arms a
        # handler that opens a bounded jax.profiler window mid-run,
        # once for every signal
        self._profile_signal = str(conf.get(
            "profile_signal", getattr(ctx, "profile_signal", "")))
        self._profile_requested = False
        self._on_nonfinite = str(conf.get("on_nonfinite", ctx.on_nonfinite))
        self._max_rollbacks = int(conf.get("max_nonfinite_rollbacks", 3))
        # xprof trace capture (SURVEY §5 tracing): bounded windows of
        # trace_num_steps steps recorded into trace_dir, which
        # tensorboard/xprof can open. One is scheduled at
        # trace_start_step where trace_dir is set and the step is not
        # negative; each profile signal opens one more
        self._trace_dir = str(conf.get("trace_dir", ctx.trace_dir))
        self._trace_start = int(conf.get(
            "trace_start_step", ctx.trace_start_step))
        self._trace_steps = int(conf.get(
            "trace_num_steps", ctx.trace_num_steps))
        self._trace_scheduled = bool(self._trace_dir) \
            and self._trace_start >= 0
        # only a process that can open a window asks the attribution
        # pass for the step's scope table
        self._window_possible = bool(self._profile_signal) \
            or self._trace_scheduled
        # the open window: what the loop's counters read when it opened
        self._profile_open: Optional[Dict[str, Any]] = None
        self._profiler_warm = False
        self._rollbacks = 0
        self._last_metrics: Optional[Dict[str, Any]] = None
        self._master_client = master_client
        # cluster diagnosis: node-tagged runtime snapshots ride the
        # master connection automatically (runtime_report_steps=0 or an
        # explicit hook instance opts out)
        report_steps = int(conf.get(
            "runtime_report_steps",
            getattr(ctx, "runtime_report_steps", 32)))
        if master_client is not None and report_steps > 0 and not any(
            isinstance(h, NodeRuntimeReportHook) for h in self._hooks
        ):
            self._hooks.append(NodeRuntimeReportHook(
                master_client, every_steps=report_steps))
        # peer-redundant host snapshots: when the plane is on
        # (snapshot_replicas > 0) and a master connection exists, the
        # replica hook rides along automatically (an explicit hook
        # instance opts out of the auto-wire)
        replicas = int(conf.get(
            "snapshot_replicas",
            getattr(ctx, "snapshot_replicas", 0)))
        if (
            master_client is not None and replicas > 0
            and hasattr(master_client, "report_replica_endpoint")
            and not any(isinstance(h, SnapshotReplicaHook)
                        for h in self._hooks)
        ):
            self._hooks.append(SnapshotReplicaHook(master_client))
        # runtime-optimizer plan channel: poll the master for published
        # plans and apply them live (plan_poll_secs=0 or an explicit
        # hook instance opts out)
        plan_poll = float(conf.get(
            "plan_poll_secs", getattr(ctx, "plan_poll_secs", 30.0)))
        if (
            master_client is not None and plan_poll > 0
            and hasattr(master_client, "get_parallel_config")
            and not any(isinstance(h, OptimizerPlanHook)
                        for h in self._hooks)
        ):
            self._hooks.append(OptimizerPlanHook(
                master_client, poll_secs=plan_poll))
        # a pending optimizer plan (applied at the next loop boundary,
        # after the window drains) and the post-apply measurement window
        # feeding the OPTIMIZER_APPLIED predicted-vs-realized record
        self._retune_request: Optional[Dict[str, Any]] = None
        self._pending_applied: Optional[Dict[str, Any]] = None
        self._applied_probe_counts = None
        # rolling step-time snapshots (refreshed every plan_measure_steps
        # materialized steps): the pre-apply p50 is measured against the
        # most recent CLOSED window, not the whole-run cumulative
        # histogram — on a long job whose degradation started late, the
        # since-start p50 would be healthy-dominated and the realized
        # speedup meaningless
        self._recent_counts = None
        self._recent_counts_prev = None
        self._plan_measure_steps = max(1, int(conf.get(
            "plan_measure_steps",
            getattr(ctx, "plan_measure_steps", 16))))
        # performance attribution: the per-compiled-program record
        # (telemetry.attribution) fetched lazily at the first
        # materialization — its derived MFU / exposed-comm gauges are
        # created only once a record exists, so absence means
        # "not measured". A program change (retune/reshard) re-arms
        # the fetch.
        self._attr_enabled = bool(conf.get(
            "attribution_enabled",
            getattr(ctx, "attribution_enabled", True)))
        self._attr_record: Optional[Any] = None
        self._attr_pending = self._attr_enabled
        self._g_attr_mfu: Optional[Any] = None
        self._g_attr_exposed: Optional[Any] = None
        # precomputed per-step scalars (set at fetch): the hot-loop
        # derivation is two divisions and two gauge stores, nothing else
        self._attr_compute_s = 0.0
        self._attr_mfu_scale = 0.0
        # time-to-first-materialized-step after TRAIN_START: the
        # trace+compile(+restore) cost, the goodput compile bucket
        self._train_started_mono: Optional[float] = None
        # the attribution pass's part of that first step, and the
        # dispatch histogram's sum where the run began
        self._first_capture_seconds = 0.0
        self._dispatch_run_start = 0.0
        self._restart_requested = False
        # live recovery (the in-process scale path): a survivable
        # membership change drains the window, snapshots to host DRAM,
        # rebuilds the mesh and reshards — all without process death.
        # The knob gates whether the failover monitor may route
        # survivable changes here instead of request_restart.
        self._live_recovery = bool(conf.get(
            "live_recovery", getattr(ctx, "live_recovery", True)
        ))
        self._reshard_requested = False
        self._reshard_devices: Optional[List[Any]] = None
        # multi-host: called at reshard time to renegotiate membership
        # and return the survivor device list (e.g. re-join via
        # MasterRendezvousHandler.renegotiate + jax.distributed re-init,
        # then jax.devices()). None = single-host / tests, where the
        # requester passes the devices explicitly.
        self._reshard_world_fn = reshard_world_fn
        self._failover: Optional[TrainingFailover] = None
        if master_client is not None:
            if failover_client is not None:
                failover_client.init_version()
            self._failover = TrainingFailover(
                master_client, self.request_restart,
                failover_client=failover_client,
                on_reshard=(self.request_live_reshard
                            if self._live_recovery else None),
                mttr_table_fn=self._readiness_mttr_table,
            )
        self.state: Any = None
        self.eval_metrics: Dict[str, Any] = {}
        self._last_eval_step = -1
        # preemption grace (reference design goal: flash checkpoint,
        # docs/blogs/stabilize_llm_training_cn.md:215 — bound lost work
        # by an emergency save, not the periodic cadence)
        self._preempt_grace = bool(conf.get("preemption_grace", True))
        self._preempted: Optional[int] = None
        self._prev_handlers: Dict[int, Any] = {}

    # -- preemption grace ----------------------------------------------------

    def install_preemption_handler(self, signals=None):
        """SIGTERM = a preemption notice (the scheduler's grace window,
        and this framework's own agent stop path,
        ``agent/worker_group.py:186``): finish the in-flight step, flush
        an emergency host-staged checkpoint, then end the run cleanly —
        lost work <= 1 step instead of the periodic save cadence.

        Installed automatically by ``train_and_evaluate`` when the conf
        knob ``preemption_grace`` is true (default); a no-op off the
        main thread (signal handlers are main-thread-only in Python).

        One-shot: the first notice re-arms the previous disposition, so
        a SECOND SIGTERM (an impatient supervisor, or the loop blocked
        outside the step path, e.g. in a stalled data iterator) kills
        the process the ordinary way instead of being swallowed.
        """
        import signal as _signal

        if signals is None:
            signals = (_signal.SIGTERM,)

        def _handler(signum, _frame):
            # flag only — the save runs in the loop, after the jitted
            # step returns (handlers must not touch the device)
            self._preempted = signum
            self._restore_signal_dispositions()
            logger.warning(
                "preemption notice (signal %d): emergency checkpoint "
                "after the in-flight step", signum,
            )

        try:
            for s in signals:
                prev = _signal.signal(s, _handler)
                self._prev_handlers[s] = prev
        except ValueError:
            logger.warning(
                "preemption handler unavailable off the main thread"
            )

    def _restore_signal_dispositions(self):
        """Re-arm whatever handled the signals before install (default:
        terminate) — from the handler itself and from run teardown, so
        the process never ends up SIGTERM-proof."""
        import signal as _signal

        for s, prev in self._prev_handlers.items():
            try:
                _signal.signal(s, prev)
            except (ValueError, TypeError):
                pass
        self._prev_handlers = {}

    def _finish_preempted(self, step: int) -> Dict[str, Any]:
        """Emergency save + clean end. The grace window bounds us
        externally (SIGKILL follows); the save is host-DRAM staged, so
        the commit is a local write, not a slow remote upload."""
        logger.warning("preempted at step %d: flushing emergency "
                       "checkpoint", step)
        t0 = time.time()
        t0_mono = time.monotonic()
        try:
            # same guard as the periodic path (elastic.py step()): a
            # NaN-poisoned state must never become the newest restore
            # target — losing the window beats corrupting the chain
            if self._last_metrics is not None and not self._step_is_finite(
                self._last_metrics
            ):
                logger.error(
                    "skipping emergency checkpoint: non-finite state at "
                    "step %d (an older finite checkpoint remains the "
                    "restore target)", step,
                )
            else:
                self._trainer.save(self.state, force=True)
            saved = self._trainer.latest_checkpoint_step()  # flush
            logger.warning(
                "emergency checkpoint committed at step %s in %.1f s",
                saved, time.time() - t0,
            )
        except Exception:  # noqa: BLE001 — still exit cleanly in grace
            logger.exception("emergency checkpoint failed")
        mirror_timed_out = False
        try:
            # close the async manager even when the save above failed:
            # an earlier in-flight save must be waited on before exit
            mirror_timed_out = bool(self._trainer.finalize())
        except Exception:  # noqa: BLE001
            logger.exception("checkpoint finalize failed")
        if mirror_timed_out:
            logger.error(
                "[CKPT_MIRROR_TIMEOUT] preemption drain: the host-DRAM "
                "staging mirror never committed before exit; a storage-"
                "outage restore will fall back to an older staged step"
            )
        if self._master_client is not None:
            try:
                self._master_client.report_failure(
                    node_rank=getattr(self._master_client, "node_id", 0),
                    restart_count=0,
                    error_data=f"preempted at step {step}",
                    level=TrainingExceptionLevel.NODE_ERROR,
                )
            except Exception:  # noqa: BLE001
                pass
        emit_event(
            EventKind.PREEMPT_DRAIN_DONE,
            error_code="CKPT_MIRROR_TIMEOUT" if mirror_timed_out else "",
            step=step,
            drain_seconds=round(time.monotonic() - t0_mono, 3),
        )
        out = dict(self._last_metrics or {})
        out["preempted"] = True
        out["mirror_timed_out"] = mirror_timed_out
        out["step"] = step  # _finish() contract parity
        for hook in self._hooks:
            hook.end(self)
        return out

    # -- failover ------------------------------------------------------------

    def request_restart(self):
        """Membership changed: finish the current step, then rebuild."""
        self._restart_requested = True

    def _readiness_mttr_table(self) -> Dict[str, float]:
        """The master's predicted-MTTR ladder for THIS node (the
        readiness auditor's calibrated blast-radius pricing), consumed
        by the failover monitor so classify_recovery picks the priced
        rung. Empty dict = master without a readiness plane = unpriced."""
        if self._master_client is None or not hasattr(
            self._master_client, "get_readiness"
        ):
            return {}
        try:
            report = self._master_client.get_readiness(
                node_id=getattr(self._master_client, "node_id", -1))
        except Exception:  # noqa: BLE001 — unpriced beats blocked
            logger.warning("readiness fetch failed; recovery stays unpriced",
                           exc_info=True)
            return {}
        node = str(getattr(self._master_client, "node_id", -1))
        nodes = report.get("nodes") or {}
        per_node = nodes.get(node) or {}
        table = per_node.get("predicted_mttr")
        if not table:
            # never swept under this id: any swept node's ladder is a
            # better price than none (pricer state is cluster-wide)
            for detail in nodes.values():
                if detail.get("predicted_mttr"):
                    table = detail["predicted_mttr"]
                    break
        if not isinstance(table, dict):
            return {}
        return {str(k): float(v) for k, v in table.items()}

    def request_live_reshard(self, devices=None):
        """A SURVIVABLE world change (peer lost with a viable survivor
        world, a scale plan, another node's preemption): drain the
        in-flight window at the next loop boundary, then snapshot →
        reshard → resume inside this process. ``devices``: the survivor
        device subset (None = the full post-change world)."""
        self._reshard_devices = list(devices) if devices is not None else None
        self._reshard_requested = True

    def request_retune(self, train_window: Optional[int] = None,
                       mesh_shape: Optional[Dict[str, int]] = None,
                       dispatch_chunks: Optional[int] = None,
                       moe_precision: Optional[str] = None,
                       fsdp_precision: Optional[str] = None,
                       plan_id: str = "", trace_id: str = "",
                       predicted_speedup: float = 0.0,
                       prewarm: bool = True):
        """A runtime-optimizer plan arrived (``OptimizerPlanHook``):
        apply it at the next loop boundary — drain the window, then
        retune the host knob (``train_window``) in place and swap the
        compiled program (``dispatch_chunks`` / ``moe_precision`` /
        ``fsdp_precision`` / mesh override) through the program cache.
        No process restart."""
        self._retune_request = {
            "train_window": train_window,
            "mesh_shape": dict(mesh_shape) if mesh_shape else None,
            "dispatch_chunks": dispatch_chunks,
            "moe_precision": moe_precision,
            "fsdp_precision": fsdp_precision,
            "plan_id": plan_id,
            "trace_id": trace_id,
            "predicted_speedup": float(predicted_speedup or 0.0),
            "prewarm": bool(prewarm),
        }

    def _maybe_restart(self):
        if self._reshard_requested:
            self._reshard_requested = False
            devices = self._reshard_devices
            self._reshard_devices = None
            if devices is None and self._reshard_world_fn is not None:
                # multi-host: renegotiate membership first — the new
                # world's devices are only visible after the re-join
                devices = self._reshard_world_fn()
            if devices is None and not self._world_actually_changed():
                # the failover monitor re-fires while nodes sit at the
                # rendezvous, but without new coordinates (no explicit
                # devices, no reshard_world_fn, ambient world unchanged)
                # a reshard would be a snapshot + device_put onto the
                # IDENTICAL topology — churn, not recovery. Skip; the
                # agent's grace-window fallback restart handles a change
                # this process cannot absorb.
                logger.info(
                    "live reshard requested but the visible world is "
                    "unchanged; skipping (no renegotiated coordinates)"
                )
                return
            # the drain already ran at the loop boundary, so the
            # snapshot inside live_reshard covers the last completed
            # optimizer step — nothing is skipped or replayed
            self.state = self._trainer.live_reshard(
                self.state, devices=devices, reason="executor"
            )
            # a reshard may have swapped the compiled program: the old
            # attribution record no longer describes it
            self._refresh_attribution()
            # the resumed step may be behind the max() the master saw
            # (the snapshot covers the last DRAINED step): reset the
            # speed monitor so its gauge/series track the truth
            self._report_step_reset()
            # the master's optimizer re-plans on world changes: tell it
            # what this worker now actually runs
            self._report_trainer_config()
            return
        if self._retune_request is not None:
            req = self._retune_request
            self._retune_request = None
            self._apply_plan(req)
            return
        if not self._restart_requested:
            return
        self._restart_requested = False
        logger.info("rebuilding training session (membership change)")
        self.state = self._trainer.on_world_change(self.state)
        self._refresh_attribution()

    # -- optimizer plan application ------------------------------------------

    def _window_p50(self, counts, baseline) -> Optional[float]:
        """Step-time p50 over the histogram DELTA between two snapshots
        (baseline None = since the start of the run)."""
        if counts is None:
            return None
        window = (
            [c - b for c, b in zip(counts, baseline)]
            if baseline is not None else list(counts)
        )
        bounds = getattr(self._h_step_time, "bounds", None)
        if not bounds:
            return None
        return percentile_from_counts(bounds, window, 0.50)

    def _mesh_override_from(self, mesh_shape) -> Optional[Any]:
        """The MeshPlan override a plan's mesh_shape asks for — None
        when it matches what the trainer already runs (an identical
        override would only churn the program-cache key)."""
        if not mesh_shape:
            return None
        from dlrover_tpu.parallel.mesh import MESH_AXES, MeshPlan

        wanted = {a: int(mesh_shape.get(a, 1)) for a in MESH_AXES}
        try:
            current = self._trainer.accelerated.strategy.mesh.axis_sizes()
        except (RuntimeError, AttributeError):
            current = None
        if current is not None and {
            a: int(v) for a, v in current.items()
        } == wanted:
            return None
        return MeshPlan(**wanted)

    def _apply_plan(self, req: Dict[str, Any]):
        """Apply one optimizer plan at a drained boundary: host knobs
        retune in place, program knobs swap through the trainer's
        program cache (prewarmed first so the swap itself pays zero
        recompiles). Failure keeps the previous config running — a bad
        plan must never take the job down."""
        from dlrover_tpu.telemetry.trace_context import trace_scope

        plan_id = req.get("plan_id", "")
        with trace_scope(req.get("trace_id") or None):
            self._apply_plan_scoped(req, plan_id)

    def _apply_plan_scoped(self, req: Dict[str, Any], plan_id: str):
        w = req.get("train_window")
        ch = req.get("dispatch_chunks")
        mp = req.get("moe_precision")
        mesh = self._mesh_override_from(req.get("mesh_shape"))
        cur_c = max(1, int(getattr(
            self._trainer, "dispatch_chunks", 1)))
        if ch is not None and int(ch) == cur_c:
            ch = None
        cur_p = str(getattr(
            self._trainer, "moe_precision", "bf16") or "bf16")
        if mp is not None:
            eff = mp
            normalize = getattr(self._trainer, "_effective_precision",
                                None)
            if normalize is not None:
                eff = normalize(mp)
            if eff != mp:
                # the backend cannot honor the requested wire (fp8
                # probe failed): applying would silently run bf16
                # while acking fp8 — the master would mark the plan
                # applied and re-choose it after every trigger, each
                # cycle paying a futile drain. Negative-ack instead so
                # the knob tuple is blacklisted (the multi-host
                # program-plan precedent).
                logger.warning(
                    "optimizer plan %s wants moe_precision=%s but the "
                    "backend runs %s (fp8 probe failed); negative-"
                    "acking so the master blacklists it", plan_id, mp,
                    eff,
                )
                self._report_trainer_config(plan_id=plan_id,
                                            apply_failed=True)
                return
            if mp == cur_p:
                mp = None
        fp = req.get("fsdp_precision")
        cur_fp = str(getattr(
            self._trainer, "fsdp_precision", "bf16") or "bf16")
        if fp is not None:
            eff_fp = fp
            normalize = getattr(self._trainer, "_effective_precision",
                                None)
            if normalize is not None:
                eff_fp = normalize(fp)
            if eff_fp != fp:
                # same phantom-apply hazard as the MoE wire: a backend
                # failing the fp8 probe would run (and the trainer
                # report) bf16 while the master marks fp8 applied —
                # negative-ack so the knob tuple is blacklisted
                logger.warning(
                    "optimizer plan %s wants fsdp_precision=%s but the "
                    "backend runs %s (fp8 probe failed); negative-"
                    "acking so the master blacklists it", plan_id, fp,
                    eff_fp,
                )
                self._report_trainer_config(plan_id=plan_id,
                                            apply_failed=True)
                return
            if fp == cur_fp:
                fp = None
        needs_program = (mesh is not None
                         or ch is not None or mp is not None
                         or fp is not None)
        emit_event(
            EventKind.OPTIMIZER_APPLY_BEGIN, plan_id=plan_id,
            train_window=w, dispatch_chunks=ch,
            moe_precision=mp, fsdp_precision=fp,
            mesh=req.get("mesh_shape") if mesh is not None else None,
            step=int(getattr(self.state, "step", 0)),
        )
        t0 = time.monotonic()
        pre_counts = self._h_step_time.snapshot_counts()
        # baseline: the start of the last CLOSED rolling window (falls
        # back to the since-start histogram early in a short run)
        baseline = (self._recent_counts_prev
                    if self._recent_counts_prev is not None
                    else self._recent_counts)
        if baseline is None:
            baseline = self._applied_probe_counts
        pre_p50 = self._window_p50(pre_counts, baseline)
        recompiled = 0
        prewarmed = False
        try:
            if needs_program:
                if req.get("prewarm", True):
                    prewarmed = self._trainer.prewarm(
                        devices=getattr(self._trainer, "devices", None),
                        mesh=mesh, dispatch_chunks=ch,
                        moe_precision=mp, fsdp_precision=fp,
                    )
                compiles_before = self._trainer.compile_count
                self.state = self._trainer.retune(
                    self.state, mesh=mesh,
                    dispatch_chunks=ch, moe_precision=mp,
                    fsdp_precision=fp,
                )
                recompiled = (
                    self._trainer.compile_count - compiles_before
                )
                self._refresh_attribution()
                self._report_step_reset()
            if w is not None:
                self._train_window = max(0, int(w))
        except Exception:  # noqa: BLE001 — a bad plan must not kill the job
            logger.exception(
                "optimizer plan %s failed to apply; continuing with "
                "the previous config", plan_id,
            )
            emit_event(
                EventKind.OPTIMIZER_APPLY_DONE, error_code="APPLY_FAILED",
                plan_id=plan_id,
                seconds=round(time.monotonic() - t0, 3),
            )
            # negative ack: without it the master re-chooses the same
            # deterministically-failing plan after every cooldown
            # window, stalling the job with a drain + failed rebuild
            # each cycle
            self._report_trainer_config(plan_id=plan_id,
                                        apply_failed=True)
            return
        seconds = time.monotonic() - t0
        # the apply stall (prewarm compile, snapshot/reshard) must not
        # bleed into the FIRST post-apply step's measured wall time —
        # it would poison the realized-speedup window
        self._last_materialize = time.monotonic()
        reg = get_registry()
        reg.counter(
            tm.OPTIMIZER_PLANS_APPLIED,
            help="optimizer plans applied live (no restart)").inc()
        reg.histogram(
            tm.OPTIMIZER_APPLY_TIME,
            help="wall seconds of one live plan application",
        ).observe(seconds)
        emit_event(
            EventKind.OPTIMIZER_APPLY_DONE, plan_id=plan_id,
            seconds=round(seconds, 3), recompiled=recompiled,
            prewarmed=prewarmed, train_window=self._train_window,
            dispatch_chunks=int(getattr(
                self._trainer, "dispatch_chunks", 1)),
            moe_precision=str(getattr(
                self._trainer, "moe_precision", "bf16")),
            fsdp_precision=str(getattr(
                self._trainer, "fsdp_precision", "bf16")),
        )
        logger.info(
            "optimizer plan %s applied in %.2fs (recompiled=%d, "
            "prewarmed=%s)", plan_id, seconds, recompiled, prewarmed,
        )
        counts_after = self._h_step_time.snapshot_counts()
        if counts_after is not None:
            self._pending_applied = {
                "plan_id": plan_id,
                "trace_id": req.get("trace_id", ""),
                "predicted_speedup": req.get("predicted_speedup", 0.0),
                "pre_p50": pre_p50,
                "counts_at_apply": counts_after,
                "target_steps": (
                    self._c_steps.value + self._plan_measure_steps),
            }
        # ack the APPLY immediately (so the master marks the decision
        # applied and retracts the broadcast even if the job ends — or
        # telemetry is off — before the measurement window closes); the
        # realized-speedup measurement follows as a best-effort second
        # report from _finish_applied
        self._report_trainer_config(
            plan_id=plan_id,
            predicted_speedup=req.get("predicted_speedup", 0.0),
        )

    def _finish_applied(self, step: int):
        """The post-apply measurement window closed: emit the
        predicted-vs-realized OPTIMIZER_APPLIED record and ack the plan
        to the master."""
        pa = self._pending_applied
        self._pending_applied = None
        if pa is None:
            return
        cur = self._h_step_time.snapshot_counts()
        post_p50 = self._window_p50(cur, pa["counts_at_apply"])
        realized = None
        if pa["pre_p50"] and post_p50:
            realized = round(pa["pre_p50"] / post_p50, 3)
        from dlrover_tpu.telemetry.trace_context import trace_scope

        # re-enter the plan's incident scope: the measurement window
        # closes steps after the apply, but the APPLIED record must
        # join the same decision trail
        with trace_scope(pa.get("trace_id") or None):
            emit_event(
                EventKind.OPTIMIZER_APPLIED, plan_id=pa["plan_id"],
                predicted_speedup=round(pa["predicted_speedup"], 3),
                realized_speedup=realized,
                pre_step_p50_s=pa["pre_p50"], post_step_p50_s=post_p50,
                step=step,
            )
        self._applied_probe_counts = cur
        self._report_trainer_config(
            plan_id=pa["plan_id"],
            predicted_speedup=pa["predicted_speedup"],
            realized_speedup=realized or 0.0,
        )

    def _report_trainer_config(self, plan_id: str = "",
                               predicted_speedup: float = 0.0,
                               realized_speedup: float = 0.0,
                               apply_failed: bool = False):
        """Tell the master what this worker ACTUALLY runs (the runtime
        optimizer's running-config input and plan-apply ack)."""
        if self._master_client is None or not hasattr(
            self._master_client, "report_trainer_config"
        ):
            return
        try:
            result = self._trainer.accelerated
            mesh_shape = {
                a: int(v)
                for a, v in result.strategy.mesh.axis_sizes().items()
            }
            # the MoE dispatch mode lives in the MODEL config; the
            # trainer sees it only through its planner ModelSpec —
            # report it when known so the optimizer's dispatch_chunks
            # family unlocks (it gates on moe_dispatch=="grouped_ep")
            spec = getattr(self._trainer, "_model_spec", None)
            self._master_client.report_trainer_config(
                world=int(result.mesh.devices.size),
                mesh_shape=mesh_shape,
                train_window=int(self._train_window),
                dispatch_chunks=int(getattr(
                    self._trainer, "dispatch_chunks", 1)),
                moe_precision=(
                    str(getattr(self._trainer, "moe_precision",
                                "bf16"))
                    if getattr(spec, "num_experts", 0) else ""),
                moe_dispatch=(
                    getattr(spec, "moe_dispatch", "")
                    if getattr(spec, "num_experts", 0) else ""),
                # the dense-wire knobs are reported only when the
                # trainer carries a planner ModelSpec (the llama-family
                # path that actually implements the wire): an
                # unconditional "bf16" would unpark the optimizer's
                # fsdp_precision family for models whose loss_fn never
                # resolves the knob — a plan the worker acks but the
                # program ignores (the moe_dispatch precedent above)
                fsdp_precision=(
                    str(getattr(self._trainer, "fsdp_precision",
                                "bf16") or "bf16")
                    if spec is not None else ""),
                grad_precision=(
                    str(getattr(self._trainer, "grad_precision",
                                "bf16") or "bf16")
                    if spec is not None else ""),
                global_batch=int(
                    result.strategy.global_batch_size or 0),
                plan_id=plan_id,
                predicted_speedup=float(predicted_speedup or 0.0),
                realized_speedup=float(realized_speedup or 0.0),
                apply_failed=bool(apply_failed),
            )
        except Exception:  # noqa: BLE001 — a dead master must not block
            # training; the optimizer just runs on a staler config view
            logger.debug("trainer config report failed", exc_info=True)

    # -- performance attribution ---------------------------------------------

    def _refresh_attribution(self):
        """The compiled program changed (retune / live reshard /
        restart rebuild): drop the record and re-arm the lazy fetch."""
        self._attr_record = None
        self._attr_pending = self._attr_enabled

    def _fetch_attribution(self):
        """Fetch the trainer's per-program attribution record (once
        per program — the trainer caches it by the program-cache key)
        and export the static gauges. Gauges are CREATED here, not in
        __init__, so a job that never captured a record never exports
        a misleading 0."""
        attribution = getattr(self._trainer, "attribution", None)
        if attribution is None:
            return
        try:
            record = (attribution(step_scopes=True)
                      if self._window_possible else attribution())
        except Exception:  # noqa: BLE001 — observation-only: a capture
            # failure must never take the step loop down
            logger.warning("attribution fetch failed", exc_info=True)
            record = None
        if record is None:
            return
        self._attr_record = record
        # mfu = flops / (step_s * peak) = (flops / peak) / step_s — the
        # same derived_mfu formula, folded to one multiply per step
        self._attr_mfu_scale = (
            record.flops_per_step / record.peak_flops_per_s
            if record.peak_flops_per_s > 0 else 0.0
        )
        self._attr_compute_s = record.predicted_compute_s
        # NB: the DERIVED gauges (mfu, exposed-comm) are created in
        # _observe_attribution at the first MEASURED step — creating
        # them here would export a fake 0.0 for the whole first
        # trace+compile window (minutes at scale), exactly the
        # absent-never-0 invariant the node series depends on
        reg = get_registry()
        reg.gauge(
            tm.ATTR_FLOPS_PER_STEP,
            help="compiled per-device FLOPs per optimizer step",
        ).set(record.flops_per_step)
        reg.gauge(
            tm.ATTR_ARITH_INTENSITY,
            help="compiled FLOPs / bytes-accessed (HBM-bound when low)",
        ).set(record.arithmetic_intensity)
        reg.gauge(
            tm.ATTR_PEAK_HBM_MB,
            help="compiled per-device peak HBM residency (MB)",
        ).set(record.peak_hbm_bytes / (1024 * 1024))
        reg.gauge(
            tm.ATTR_COMM_PREDICTED_S,
            help="predicted per-step collective seconds (all families)",
        ).set(record.predicted_comm_total_s)
        # the capture's AOT compile is a one-off stall: it must not
        # bleed into the NEXT step's measured wall time (same guard as
        # the optimizer-plan apply)
        self._last_materialize = time.monotonic()

    def _observe_attribution(self, per_step: float):
        """Fuse one measured per-step time with the record into the
        derived gauges — two divisions and two gauge stores, the only
        per-step cost the attribution plane carries (the ≤5% paired
        overhead gate in tests/test_attribution.py pins it)."""
        if self._attr_pending:
            self._attr_pending = False
            self._fetch_attribution()
        if self._attr_record is None or per_step <= 0:
            return
        if self._attr_record.peak_flops_per_s <= 0:
            return  # unknown device kind: no peak, no utilization
        if self._g_attr_mfu is None:
            reg = get_registry()
            self._g_attr_mfu = reg.gauge(
                tm.ATTR_MFU,
                help="live model-FLOPs utilization (compiled FLOPs/"
                     "step over measured step time x device peak)")
            self._g_attr_exposed = reg.gauge(
                tm.ATTR_EXPOSED_COMM_FRAC,
                help="upper bound on the un-overlapped comm share of "
                     "the step (1 - ideal compute s / measured step s)")
        # .set(), not raw attribute stores: if telemetry was toggled
        # off between fetch and here, the lazy creation above handed
        # back the SHARED null-metric singleton — set() is a no-op on
        # it, a direct .value write would poison every null consumer
        inv = 1.0 / per_step
        self._g_attr_mfu.set(self._attr_mfu_scale * inv)
        frac = 1.0 - self._attr_compute_s * inv
        self._g_attr_exposed.set(
            0.0 if frac < 0.0 else (1.0 if frac > 1.0 else frac)
        )

    def _observe_input_wait(self, window_s: float):
        """Derive the input-wait fraction of the just-closed
        materialization window: batch-fetch seconds accumulated since
        the previous materialization over the window's wall time. With
        a deep dispatch window the fetches belong to NEWER steps than
        the one materializing — the fraction is a windowed average that
        converges over a report window, which is exactly the
        granularity the node series diffs at. Cost: one subtraction,
        one division, one gauge store."""
        waited = self._input_wait_total - self._input_wait_mark
        fetches = self._input_wait_count - self._input_wait_count_mark
        self._input_wait_mark = self._input_wait_total
        self._input_wait_count_mark = self._input_wait_count
        if window_s <= 0 or self._input_wait_count == 0:
            # nothing measured yet: the gauge must stay ABSENT — a
            # scrape must never read a fake 0 for an unmeasured window
            return
        if fetches == 0:
            # a window with NO batch fetch (the drain's tail: queued
            # dispatches materialize back-to-back) says nothing about
            # the input pipeline — overwriting the gauge with its 0/0
            # would erase the measurement the last real window made
            return
        if self._g_input_wait is None:
            self._g_input_wait = get_registry().gauge(
                tm.INPUT_WAIT_FRAC,
                help="fraction of the last materialization window the "
                     "host spent blocked waiting for the next batch")
        frac = waited / window_s
        # .set(), never a raw .value store: a telemetry toggle between
        # construction and here lands the lazy creation on the shared
        # null-metric singleton (same invariant as the attribution
        # gauges)
        self._g_input_wait.set(
            0.0 if frac < 0.0 else (1.0 if frac > 1.0 else frac)
        )

    def _report_step_reset(self):
        """Tell the master the true global step REWOUND (rollback / live
        reshard) so ``SpeedMonitor.reset_step`` unpins the monotone
        max() gauge and restarts the speed window."""
        if self._master_client is None:
            return
        try:
            self._master_client.report_global_step(
                int(self.state.step), reset=True)
        except Exception:  # noqa: BLE001 — a dead master must not block
            # the recovery path; the gap only stales the speed gauge
            logger.debug("step reset report failed", exc_info=True)

    def _world_actually_changed(self) -> bool:
        """Whether the ambient device world differs from the mesh the
        trainer is currently compiled for (set-compare on device ids —
        ``mesh_utils`` is free to reorder within a topology)."""
        import jax

        try:
            result = self._trainer.accelerated
        except (RuntimeError, AttributeError):
            return True  # nothing compiled yet: let the rebuild decide
        mesh_devices = result.mesh.devices.flatten().tolist()
        ambient = jax.devices()
        return (
            len(mesh_devices) != len(ambient)
            or {getattr(d, "id", None) for d in mesh_devices}
            != {getattr(d, "id", None) for d in ambient}
        )

    # -- NaN/overflow guardrail ----------------------------------------------

    @staticmethod
    def _step_is_finite(metrics: Dict[str, Any]) -> bool:
        import math

        if "finite" in metrics:
            return bool(metrics["finite"])
        try:
            return math.isfinite(float(metrics.get("loss", 0.0)))
        except (TypeError, ValueError):
            return True

    def _report_nonfinite(self, step: int, metrics: Dict[str, Any]) -> str:
        """Log + report the non-finite step to the master; returns the
        serialized detail for the exception message."""
        import json as _json

        detail = _json.dumps({
            "step": step,
            "loss": repr(metrics.get("loss")),
            "grad_norm": repr(metrics.get("grad_norm")),
            "reason": "non-finite loss/gradients",
        })
        logger.error("non-finite training step: %s", detail)
        self._c_nonfinite.inc()
        emit_event(EventKind.NONFINITE_STEP, error_code="NONFINITE",
                   step=step, policy=self._on_nonfinite)
        if self._master_client is not None:
            try:
                self._master_client.report_failure(
                    node_rank=getattr(self._master_client, "node_id", 0),
                    restart_count=0,
                    error_data=detail,
                    level=TrainingExceptionLevel.PROCESS_ERROR,
                )
            except Exception:  # noqa: BLE001 — never mask the real error
                logger.exception("failed to report non-finite step")
        return detail

    def _handle_nonfinite(self, step: int, metrics: Dict[str, Any]) -> bool:
        """Report the failure and apply the policy. Returns True when the
        loop must re-enter (rollback restored an older state). The whole
        failure → recovery edge runs under one freshly minted incident
        trace id, so the NONFINITE_STEP / ROLLBACK_RESTORED events and
        the master's ingress-side records correlate."""
        from dlrover_tpu.telemetry.trace_context import trace_scope

        with trace_scope():
            return self._handle_nonfinite_scoped(step, metrics)

    def _handle_nonfinite_scoped(self, step: int,
                                 metrics: Dict[str, Any]) -> bool:
        detail = self._report_nonfinite(step, metrics)
        if self._on_nonfinite == "rollback":
            latest = getattr(
                self._trainer, "latest_checkpoint_step", lambda: None
            )()
            if latest is None:
                # no checkpoint manager OR nothing saved yet: "rollback"
                # would silently restart from a fresh random init —
                # escalate instead of losing all progress
                raise NonFiniteLossError(
                    "on_nonfinite=rollback but no checkpoint exists to "
                    f"restore; halting. {detail}"
                )
            self._rollbacks += 1
            if self._rollbacks > self._max_rollbacks:
                raise NonFiniteLossError(
                    f"non-finite step persisted through {self._max_rollbacks}"
                    f" rollbacks; halting. {detail}"
                )
            logger.warning(
                "rolling back to the last checkpoint after non-finite step "
                "(%d/%d)", self._rollbacks, self._max_rollbacks,
            )
            # same world: restore onto the existing compiled program;
            # prepare(None) would recompile the whole step for nothing
            restore = getattr(self._trainer, "restore_state", None)
            restored = restore() if restore is not None else None
            self.state = (restored if restored is not None
                          else self._trainer.prepare(None))
            self._c_rollbacks.inc()
            emit_event(EventKind.ROLLBACK_RESTORED, step=step,
                       restored_step=int(self.state.step),
                       rollback=self._rollbacks)
            self._report_step_reset()
            return True
        if self._on_nonfinite == "ignore":
            return False
        raise NonFiniteLossError(detail)

    # -- loop ----------------------------------------------------------------

    def _take_batch(self, data_iter: Iterator) -> Optional[Any]:
        """The next batch, or None when the data source is exhausted."""
        t0 = time.monotonic()
        try:
            with span(SpanName.INPUT_WAIT):
                batch = next(data_iter)
        except StopIteration:
            return None
        # the input-wait clock: with the dispatch window keeping
        # the device busy, host time spent here is the data
        # pipeline failing to stay ahead of the accelerator
        waited = time.monotonic() - t0
        self._input_wait_total += waited
        self._input_wait_count += 1
        self._h_input_wait.observe(waited)
        return batch

    def _dispatch(self, batch, step: int):
        """One call of the trainer's ``step`` under its span. The
        dispatch histogram gets the seconds of the call less what the
        trainer's save branch took inside it: a save step waits for the
        steps in flight and copies the state, which is no dispatch and
        has its own span and count."""
        saved = getattr(self._trainer, "save_seconds", 0.0)
        t0 = time.monotonic()
        with span(SpanName.STEP_DISPATCH, step=step):
            out = self._trainer.step(self.state, batch)
        took = time.monotonic() - t0
        self._h_dispatch.observe(
            took - (getattr(self._trainer, "save_seconds", 0.0) - saved))
        return out

    def _emit_first_step(self, step: int, now: float, sync_seconds: float):
        """The run's first materialization: its latency is dominated by
        trace+compile (+restore) and is the goodput ledger's compile
        bucket, which reads ``seconds`` from this event. Its parts, all
        up to this moment: the attribution pass, the waits for batches,
        the dispatches (the first holds the step's tracing, lowering
        and compile or cache read), the wait for the device, and the
        rest (the trainer-config report, the hooks' before_step); the
        compile ledger's totals and its dearest programs since process
        start."""
        seconds = now - self._train_started_mono
        self._train_started_mono = None
        parts = {
            "capture_seconds": self._first_capture_seconds,
            "input_wait_seconds": (self._input_wait_total
                                   - self._input_wait_run_start),
            "dispatch_seconds": (self._h_dispatch.sum
                                 - self._dispatch_run_start),
            "sync_seconds": sync_seconds,
        }
        emit_event(
            EventKind.COMPILE_FIRST_STEP, step=step,
            seconds=round(seconds, 3),
            rest_seconds=round(seconds - sum(parts.values()), 6),
            compile=cache_traffic(), programs=compile_programs(),
            **{k: round(v, 6) for k, v in parts.items()})

    def _materialize_oldest(self, handle_nonfinite: bool = True) -> bool:
        """Pop the oldest in-flight step, pull its metrics to host (the
        ONE device sync of the pipeline — it waits only on work that is
        already ``train_window`` steps old), and run the lagged per-step
        consumers: after-step hooks, the finite check, speed logging.
        Returns True when a non-finite step triggered a rollback (the
        remaining in-flight steps descend from the poisoned state, so
        the window is discarded wholesale)."""
        import jax

        entry = self._window.popleft()
        t_sync = time.monotonic()
        with span(SpanName.HOST_SYNC, step=entry.last_step):
            host = jax.device_get(entry.metrics)
        now = time.monotonic()
        self._h_host_sync.observe(now - t_sync)
        self._count_into_profile_window(entry.last_step, host)
        self._close_profile_window_if_ran()
        if self._train_started_mono is not None:
            self._emit_first_step(entry.last_step, now, now - t_sync)
            # an incident trace id inherited from the agent's
            # environment covers the RECOVERY (startup → first step),
            # not the rest of this worker's life: consume it here so
            # hours-later routine events don't mis-correlate to a
            # closed incident
            from dlrover_tpu.telemetry.trace_context import TRACE_ID_ENV

            os.environ.pop(TRACE_ID_ENV, None)
        # per-step wall time: the interval since the previous
        # materialization
        per_step = now - self._last_materialize
        self._last_materialize = now
        self._g_lag.set(self._dispatched_step - entry.last_step)
        self._observe_attribution(per_step)
        self._observe_input_wait(per_step)
        touch_heartbeat()
        s = entry.last_step
        self._last_metrics = host
        self._h_step_time.observe(per_step)
        self._c_steps.inc()
        if self._c_steps.value % self._plan_measure_steps == 0:
            self._recent_counts_prev = self._recent_counts
            self._recent_counts = self._h_step_time.snapshot_counts()
        if (
            self._pending_applied is not None
            and self._c_steps.value
            >= self._pending_applied["target_steps"]
        ):
            self._finish_applied(s)
        for hook in self._hooks:
            hook.after_step(s, host)
        if (
            handle_nonfinite
            and self._check_finite_every
            and s % self._check_finite_every == 0
            and not self._step_is_finite(host)
        ):
            if self._handle_nonfinite(s, host):
                self._window.clear()
                return True
        if self._log_every and s % self._log_every == 0:
            # monotonic, and quantiles from the step-time histogram
            # DELTA since the previous log line: a log_every/dt
            # average under-reports jitter and reads garbage across
            # a drain/resume boundary, and lifetime-cumulative
            # quantiles would stop tracking a late regression once
            # old observations dominate
            dt = time.monotonic() - self._last_log
            self._last_log = time.monotonic()
            quantiles = ""
            cur = self._h_step_time.snapshot_counts()
            if cur is not None:
                prev = self._log_counts_snapshot
                self._log_counts_snapshot = cur
                window_counts = (
                    [c - p for c, p in zip(cur, prev)]
                    if prev is not None else cur
                )
                bounds = self._h_step_time.bounds
                p50 = percentile_from_counts(
                    bounds, window_counts, 0.50)
                p95 = percentile_from_counts(
                    bounds, window_counts, 0.95)
                if p50 is not None and p95 is not None:
                    quantiles = (" p50=%.1fms p95=%.1fms"
                                 % (p50 * 1e3, p95 * 1e3))
            logger.info(
                "step %d loss=%.4f (%.2f steps/s%s)", s,
                float(host.get("loss", float("nan"))),
                self._log_every / max(dt, 1e-9), quantiles,
            )
        return False

    def _trim_window(self, limit: int, handle_nonfinite: bool = True) -> bool:
        while len(self._window) > limit:
            if self._materialize_oldest(handle_nonfinite):
                return True
        return False

    def _drain_window(self, handle_nonfinite: bool = True) -> bool:
        """Materialize every in-flight step (eval/exit/preemption/restart
        boundaries). Returns True when the drain hit a rollback."""
        return self._trim_window(0, handle_nonfinite)

    def train_and_evaluate(self) -> Dict[str, Any]:
        # NB: no heartbeat before the first step — the agent's
        # hang_first_beat_grace covers setup + first-step compile, and an
        # early beat would forfeit it (beaten=True drops the allowance to
        # the bare timeout while the compile is still running)
        if self._preempt_grace:
            self.install_preemption_handler()
        self._install_profile_signal_handler()
        self.state = self._trainer.prepare(self.state)
        # re-arm per run: prepare() may have (re)built the program, and
        # a second run must re-read the trainer's cached record
        self._refresh_attribution()
        t_hooks = time.monotonic()
        for hook in self._hooks:
            hook.begin(self)
        hooks_begin_seconds = time.monotonic() - t_hooks
        if self._failover is not None:
            self._failover.start()

        step = int(self.state.step)
        self._last_log = time.monotonic()
        self._last_materialize = time.monotonic()
        self._log_counts_snapshot = None
        self._applied_probe_counts = None
        self._recent_counts = None
        self._recent_counts_prev = None
        self._last_eval_step = -1
        self._dispatched_step = step
        self._window.clear()
        self._input_wait_mark = self._input_wait_total
        self._input_wait_count_mark = self._input_wait_count
        self._input_wait_run_start = self._input_wait_total
        self._train_started_mono = time.monotonic()
        self._dispatch_run_start = self._h_dispatch.sum
        emit_event(EventKind.TRAIN_START, step=step,
                   train_window=self._train_window,
                   hooks_begin_seconds=round(hooks_begin_seconds, 6),
                   compile=cache_traffic())
        self._report_trainer_config()
        # capture the attribution record NOW, before the first dispatch:
        # its AOT compile is compile-side cost (the persistent cache
        # then serves the first step's compile warm) and it lands inside
        # the COMPILE_FIRST_STEP window — never in a steady-state timed
        # region (deep windows materialize their first step long after
        # warmup, where a 0.2s capture would poison throughput gates)
        self._first_capture_seconds = 0.0
        if self._attr_pending:
            self._attr_pending = False
            t_capture = time.monotonic()
            self._fetch_attribution()
            self._first_capture_seconds = time.monotonic() - t_capture
        try:
            while True:
                # re-read per iterator epoch: a live retune (optimizer
                # plan) changes it between boundary re-entries
                window = self._train_window
                data_iter = iter(self._train_iter_fn())
                restarted = False
                while True:
                    if self._train_steps and step >= self._train_steps:
                        break  # resumed at or past the last step
                    batch = self._take_batch(data_iter)
                    if batch is None:
                        break  # data source exhausted
                    for hook in self._hooks:
                        hook.before_step(step + 1)
                    self.state, metrics = self._dispatch(batch, step + 1)
                    step += 1
                    self._window.append(_Inflight(step, metrics))
                    self._dispatched_step = step
                    touch_heartbeat()  # hang-relaunch liveness beacon
                    self._update_trace(step)

                    if self._trim_window(window):
                        step = int(self.state.step)
                        restarted = True
                        break  # rollback: fresh iterator + old state
                    # steady-state occupancy (post-trim): 0..train_window
                    self._g_window.set(len(self._window))

                    if self._preempted is not None:
                        self._c_preempt.inc()
                        emit_event(EventKind.PREEMPT_NOTICE,
                                   error_code="PREEMPTED", step=step,
                                   signum=int(self._preempted))
                        # drain first: the emergency save must cover the
                        # last MATERIALIZED (completed-on-device) step,
                        # and the finite guard in _finish_preempted needs
                        # real host metrics to judge
                        self._drain_window(handle_nonfinite=False)
                        return self._finish_preempted(step)

                    if self._eval_every and step % self._eval_every == 0:
                        if self._drain_window():
                            step = int(self.state.step)
                            restarted = True
                            break
                        self._evaluate(step)
                    if self._train_steps and step >= self._train_steps:
                        if self._drain_window():
                            step = int(self.state.step)
                            restarted = True
                            break
                        return self._finish(step)
                    if (self._restart_requested or self._reshard_requested
                            or self._retune_request is not None):
                        if self._drain_window():
                            step = int(self.state.step)
                            restarted = True
                            break
                        self._maybe_restart()
                        restarted = True
                        break  # re-enter with a fresh data iterator
                if not restarted:
                    # data source exhausted: drain, then finish (a drain
                    # that rolled back re-enters with a fresh iterator)
                    if self._drain_window():
                        step = int(self.state.step)
                        continue
                    return self._finish(step)
        except BaseException:
            # a save begun before the failure is still with the saver
            # thread: it is committed before the error leaves the run
            self._flush_saves()
            raise
        finally:
            self._close_profile_window(step)
            self._restore_signal_dispositions()
            if self._failover is not None:
                self._failover.stop()

    def _flush_saves(self):
        """Commit what the checkpoint manager has begun; never raises
        (the run's own error is the one to come out)."""
        try:
            getattr(self._trainer, "latest_checkpoint_step", lambda: None)()
        except Exception:  # noqa: BLE001
            logger.exception("flushing the saves of a failed run failed")

    def _install_profile_signal_handler(self):
        """Arm the on-demand device-profile window: every delivery of
        the configured signal (conf/Context ``profile_signal``, e.g.
        "USR2") requests one bounded ``jax.profiler`` capture starting
        at the next step — so a production job can be profiled without
        a restart (``kill -USR2 <worker pid>``), and the
        ``profile_window`` event on the timeline says where the dump
        went. Main-thread-only, like the preemption handler; a no-op
        when the knob is empty."""
        if not self._profile_signal:
            return
        import signal as _signal

        name = self._profile_signal.upper().removeprefix("SIG")
        signum = getattr(_signal, f"SIG{name}", None)
        if signum is None:
            logger.warning("unknown profile_signal %r",
                           self._profile_signal)
            return

        def _handler(_signum, _frame):
            # flag only: start_trace must run from the loop, not a
            # signal frame racing the dispatch path
            self._profile_requested = True

        try:
            self._prev_handlers[signum] = _signal.signal(signum, _handler)
        except ValueError:
            logger.warning(
                "profile_signal handler unavailable off the main thread"
            )

    def _loop_counters(self) -> Dict[str, float]:
        """What the loop has counted so far; a profiling window reports
        the difference between its two ends."""
        return {
            "dispatch_seconds": self._h_dispatch.sum,
            "host_sync_seconds": self._h_host_sync.sum,
            "input_wait_seconds": self._h_input_wait.sum,
            "save_seconds": getattr(self._trainer, "save_seconds", 0.0),
            "saves_begun": getattr(self._trainer, "saves_begun", 0),
        }

    def _count_into_profile_window(self, step: int, host):
        """Add a materialized step's counters (those of
        ``StepCounter`` that its loss function returned) to the open
        profiling window, if the step is one of the window's."""
        opened = self._profile_open
        if opened is None or not (
                opened["first_step"] <= step <= opened["stop_at"]):
            return
        for name in tm.StepCounter.ALL:
            if name in host:
                sums = opened.setdefault("step_counters", {})
                sums[name] = sums.get(name, 0.0) + float(host[name])

    def _update_trace(self, step: int):
        """Open and close the bounded profiling windows around the step
        counter. The scheduled window opens after ``trace_start_step``
        completed steps (past compile + warmup), a requested one at
        once; each spans ``trace_num_steps`` steps, from the dispatch of
        the first to the completion of the last on the device."""
        if self._profile_open is not None:
            if ("last" not in self._profile_open
                    and step >= self._profile_open["stop_at"]):
                self._end_profile_window(step)
            self._close_profile_window_if_ran()
        elif self._profile_requested:
            self._profile_requested = False
            self._open_profile_window(step)
        elif self._trace_scheduled and step >= self._trace_start:
            # ">=", not "==": a checkpoint-resumed run enters with the
            # restored global step already past trace_start_step, and
            # profiling a restored production job is a primary use
            self._trace_scheduled = False
            self._open_profile_window(step)

    def _open_profile_window(self, step: int):
        import jax

        target = self._trace_dir or os.path.join(
            tempfile.gettempdir(), f"dlrover_tpu_xprof_{os.getpid()}"
        )
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the spans, not every call
        options.host_tracer_level = 2
        t0 = time.time()
        if not self._profiler_warm:
            # the profiler's first start in a process sets up its
            # tracers; paid here, into a directory that is thrown
            # away, it stalls no step of the capture
            self._profiler_warm = True
            with tempfile.TemporaryDirectory() as scratch:
                jax.profiler.start_trace(scratch, profiler_options=options)
                jax.profiler.stop_trace()
        jax.profiler.start_trace(target, profiler_options=options)
        now = time.time()
        self._profile_open = {
            "dir": target, "first_step": step + 1,
            "stop_at": step + self._trace_steps,
            "start_ts": now, "start_seconds": now - t0,
            **self._loop_counters(),
        }
        logger.info("xprof trace started at step %d -> %s", step, target)

    def _end_profile_window(self, step: int):
        """The window's last step has been dispatched: the counters of
        its event are read here. The trace goes on until that step has
        run (``_close_profile_window_if_ran``)."""
        self._profile_open.update(
            last=self._window[-1] if self._window else None,
            last_step=step, end_ts=time.time(),
            after=self._loop_counters(),
        )

    def _close_profile_window_if_ran(self):
        """Stop the trace once the window's last step has completed on
        the device, which the loop knows when it has materialized that
        step's entry. The window's steps have been dispatched, not run:
        with the train window just drained (a save, an eval, a restart)
        they were dispatched in milliseconds and the chip has started
        none of them, so a trace stopped at the dispatch of the last
        holds nothing. Called where an entry has left the train window
        and after every dispatch: no entry is materialized for it and
        the loop waits for nothing it would not wait for anyway, so the
        steps in flight behind the window cover ``stop_trace``."""
        opened = self._profile_open
        if opened is None or "last" not in opened:
            return
        if any(entry is opened["last"] for entry in self._window):
            return
        self._close_profile_window(opened["last_step"])

    def _close_profile_window(self, step: int):
        """xprof only flushes on stop_trace — also called from the run's
        finally so a window open at exit isn't lost: there it waits for
        the window's last step itself, if that is still in flight."""
        if self._profile_open is None:
            return
        import jax

        if "last" not in self._profile_open:  # short of its steps
            self._end_profile_window(step)
        opened, self._profile_open = self._profile_open, None
        if any(entry is opened["last"] for entry in self._window):
            try:
                jax.block_until_ready(opened["last"].metrics)
            except Exception:  # noqa: BLE001 — the run's own error stands
                logger.warning("profile window: its last step did not "
                               "complete; closing the trace as it is")
        t_stop = time.time()
        jax.profiler.stop_trace()
        stop_seconds = time.time() - t_stop
        self._emit_step_scopes()
        emit_event(
            EventKind.PROFILE_WINDOW, dir=opened["dir"],
            first_step=opened["first_step"], last_step=opened["last_step"],
            steps=opened["last_step"] - opened["first_step"] + 1,
            start_ts=opened["start_ts"], end_ts=opened["end_ts"],
            start_seconds=round(opened["start_seconds"], 6),
            stop_seconds=round(stop_seconds, 6),
            **{k: round(v - opened[k], 6)
               for k, v in opened["after"].items()},
            # a model that counts (``StepCounter``): sums over the
            # window's steps
            **({"step_counters": opened["step_counters"]}
               if "step_counters" in opened else {}),
            # what the fullest chip holds after the window's steps;
            # absent where the backend keeps no such statistics
            memory=_fullest_device_memory(),
        )
        logger.info("xprof trace stopped after step %d",
                    opened["last_step"])

    def _emit_step_scopes(self):
        """Before a window's ``profile_window``, once a program: which
        phase and scope each instruction of the step that ran it
        belongs to (the attribution record's ``step_scopes``, there
        because this process asked for it, and handed over here: the
        trainer keeps the record a program, so a later window of the
        same program finds nothing to say again)."""
        record = self._attr_record
        table = getattr(record, "step_scopes", None)
        if not table:
            return
        record.step_scopes = None
        emit_event(EventKind.STEP_SCOPES, program=record.program_key,
                   **table)

    def _evaluate(self, step: int):
        if self._eval_fn is None or step == self._last_eval_step:
            return
        self._last_eval_step = step
        # reset the hang clock at eval ENTRY so the allowance covers the
        # eval from its start (a beat after it would land too late)
        touch_heartbeat()
        t0 = time.monotonic()
        with span(SpanName.EVALUATE, step=step):
            self.eval_metrics = self._eval_fn(self.state)
        self._h_eval.observe(time.monotonic() - t0)
        touch_heartbeat()
        logger.info("eval @%d: %s", step, {
            # vector metrics (e.g. moe_expert_load [E]) log as lists;
            # only 0-d values convert to float
            k: (float(v) if getattr(v, "ndim", 0) == 0
                else [round(float(x), 4) for x in v])
            for k, v in self.eval_metrics.items()
        })
        for hook in self._hooks:
            hook.after_evaluate(step, self.eval_metrics)

    def _finish(self, step: int) -> Dict[str, Any]:
        if self._eval_fn is not None:
            self._evaluate(step)
        if self._last_metrics is None or self._step_is_finite(
            self._last_metrics
        ):
            self._trainer.save(self.state, force=True)
        else:
            # the final state is NaN-poisoned (the NaN landed between
            # check cadences, or the policy is "ignore"/"rollback"): a
            # force-save here would make it the newest restore target.
            # Report it, and under "halt" fail the run — a NaN final step
            # must not exit 0 as a success.
            detail = self._report_nonfinite(step, self._last_metrics)
            logger.warning(
                "skipping final checkpoint: last step was non-finite"
            )
            if self._on_nonfinite == "halt":
                raise NonFiniteLossError(f"final step non-finite: {detail}")
        self._trainer.finalize()
        # the run's total input-wait seconds ride the TRAIN_END record:
        # the goodput ledger's input-wait column sums these per worker
        # (a column, not a wall bucket — the wait overlaps train spans)
        emit_event(EventKind.TRAIN_END, step=step,
                   input_wait_s=round(
                       self._input_wait_total
                       - self._input_wait_run_start, 3))
        for hook in self._hooks:
            hook.end(self)
        return {"step": step, **self.eval_metrics}
