"""ElasticTrainer: fixed global batch under a changing world.

Role parity: ``dlrover/trainer/torch/elastic.py:214-407``
(``ElasticTrainer``) — the reference keeps the *global* batch size fixed
under elasticity by setting ``gradient_accumulation_steps =
max_workers / cur_world`` and skipping gradient sync on accumulation
steps.

TPU-first: there is no per-step sync to skip — the train step is one
compiled SPMD program. Elasticity instead means: when the world changes,
re-derive the strategy for the new device count (same global batch, the
``data`` axis shrinks, ``grad_accum_steps`` grows to compensate) and
re-``accelerate``. Checkpoint/restore across the transition is GSPMD-
native (``dlrover_tpu.checkpoint``). The per-step hot loop stays pure.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from dlrover_tpu.checkpoint import (
    CheckpointInterval,
    ElasticCheckpointManager,
    HostSnapshot,
    abstract_like,
)
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.accelerate import AccelerateResult, accelerate
from dlrover_tpu.parallel.mesh import topology_key
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry import (
    EventKind,
    SpanName,
    emit_event,
    get_registry,
    names as tm,
    span,
)
from dlrover_tpu.trainer import bootstrap

logger = get_logger("trainer.elastic")


class ElasticTrainer:
    """Owns the (strategy, compiled step, state) triple across world changes.

    Usage::

        trainer = ElasticTrainer(init_fn, loss_fn, optimizer, example_batch,
                                 strategy, ckpt_dir="/ckpt")
        state = trainer.prepare()          # restores if a checkpoint exists
        for batch in loader:
            state, metrics = trainer.step(state, batch)
        # agent signals a membership change:
        state = trainer.on_world_change(state)   # recompile + reshard
    """

    def __init__(
        self,
        init_fn: Callable,
        loss_fn: Callable,
        optimizer,
        example_batch: Any,
        strategy: Optional[Strategy] = None,
        ckpt_dir: str = "",
        ckpt_interval: Optional[CheckpointInterval] = None,
        master_client=None,
        report_every_steps: int = 10,
        devices=None,
        model_spec=None,
        dispatch_chunks: Optional[int] = None,
        moe_precision: Optional[str] = None,
        fsdp_precision: Optional[str] = None,
        grad_precision: Optional[str] = None,
    ):
        # what the script did between init_worker's return and here
        # (building its job) is a phase of the boot: ``prepare`` puts
        # it on the timeline. None where no init_worker ran
        ready = bootstrap.worker_ready_mono
        self._script_seconds = (
            None if ready is None else round(time.monotonic() - ready, 6))
        self._init_fn = init_fn
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._example_batch = example_batch
        # optional planner ModelSpec: when known, the attribution
        # record's per-collective comm seconds come from the planner's
        # predicted_collective_bytes formula instead of the compiled
        # HLO's own byte parse (telemetry.attribution)
        self._model_spec = model_spec
        self._base_strategy = strategy or Strategy()
        self._master_client = master_client
        self._report_every = max(report_every_steps, 1)
        # grouped_ep chunked-dispatch degree: a COMPILED-program knob
        # (the program-cache key carries it, and retune/prewarm swap
        # it live). The model reads it from the Context at trace time
        # (ops.moe.resolve_dispatch_chunks), so _build pins the Context
        # knob to this trainer's value before any build — and the lazy
        # jit trace that follows — runs.
        if dispatch_chunks is None:
            from dlrover_tpu.common.config import get_context

            dispatch_chunks = int(getattr(
                get_context(), "dispatch_chunks", 1))
        self.dispatch_chunks = max(1, int(dispatch_chunks))
        # MoE wire precision: the same COMPILED-program trace-time knob
        # contract as dispatch_chunks (the program-cache key carries
        # it, _build pins the Context knob, retune/prewarm swap it
        # live through the cache)
        if moe_precision is None:
            from dlrover_tpu.common.config import get_context

            moe_precision = str(getattr(
                get_context(), "moe_precision", "bf16") or "bf16")
        self.moe_precision = self._effective_precision(moe_precision)
        # dense FSDP wire precision: the same trace-time program-cache
        # contract as moe_precision (the key carries |fp=, _build pins
        # the Context knob, prewarm/retune swap it live) — normalized
        # through the SAME capability probe so key/report/pricing agree
        # with the traced program
        if fsdp_precision is None:
            from dlrover_tpu.common.config import get_context

            fsdp_precision = str(getattr(
                get_context(), "fsdp_precision", "bf16") or "bf16")
        self.fsdp_precision = self._effective_precision(fsdp_precision)
        # gradient-path precision (error-feedback residual): a BUILD-
        # time knob — it changes the TrainState STRUCTURE, so it is
        # pinned at construction and never enumerated for live retunes
        # (a plan carrying a different value is negative-acked by the
        # executor). The program-cache key still carries |gp= so
        # distinct builds never collide.
        from dlrover_tpu.parallel.accelerate import resolve_grad_precision

        self.grad_precision = resolve_grad_precision(grad_precision)
        # explicit device set (default: the whole jax.devices() world);
        # the agent hands the post-change survivor subset to
        # on_world_change, and dryruns carve sub-worlds out of one host
        self._devices = list(devices) if devices is not None else None
        # runtime-optimizer mesh override: a specific factorization for
        # the CURRENT world (e.g. trade data for fsdp) chosen by the
        # master's re-planner, applied via retune(). None = the base
        # strategy's adjust_to_world derivation.
        self._mesh_override = None

        self._result: Optional[AccelerateResult] = None
        # Compiled-program cache, keyed by (mesh topology, mesh
        # override, program knobs): a live reshard BACK to a program this
        # trainer already compiled for (scale down on a failure, scale
        # up when the node returns, a retune back to earlier knobs)
        # reuses the whole AccelerateResult — jitted step, shardings,
        # mesh — with ZERO recompiles. Bounded: each entry pins its
        # compiled executables in host memory, and elastic jobs
        # oscillate between a handful of worlds, not dozens.
        self._programs: "collections.OrderedDict[str, AccelerateResult]" = (
            collections.OrderedDict()
        )
        self._program_cache_cap = 4
        # per-compiled-program attribution records, keyed by the SAME
        # program-cache key (captured lazily on first request, evicted
        # with the program). A failed capture caches False so a broken
        # backend is probed once per program, not once per step.
        self._attr_records: Dict[str, Any] = {}
        self._current_program_key: Optional[str] = None
        # accelerate() invocations that actually compiled (cache misses)
        self.compile_count = 0
        # Device count the base strategy was written for; grad-accum scales
        # relative to this (the reference's max_workers anchor).
        self._initial_devices: Optional[int] = None
        # Host-side mirror of state.step: reading the device scalar every
        # step would force a host-device sync in the hot loop.
        self._host_step = 0
        self._rng = jax.random.PRNGKey(0)
        reg = get_registry()
        self._c_reports = reg.counter(
            tm.MASTER_REPORTS, help="global-step/model reports sent")
        self._c_report_failures = reg.counter(
            tm.MASTER_REPORT_FAILURES,
            help="reports the master never acked (counted, never raised)")
        self._ckpt: Optional[ElasticCheckpointManager] = None
        t0 = time.monotonic()
        if ckpt_dir:
            self._ckpt = ElasticCheckpointManager(
                ckpt_dir, save_interval=ckpt_interval or CheckpointInterval()
            )
        # the first manager of a process imports Orbax: seconds of boot
        # that ``prepare`` puts on the timeline
        self._ckpt_manager_seconds = time.monotonic() - t0
        # what the save branch of ``step`` has taken so far, for the
        # executor, which keeps it apart from dispatch
        self.save_seconds = 0.0
        self.saves_begun = 0

    @staticmethod
    def _effective_precision(precision: Optional[str]) -> str:
        """The wire precision the traced program will ACTUALLY run:
        the probe fallback applied HERE, not just inside ops.moe — so
        the program-cache key, the Context pin, the worker's
        TrainerConfigReport and the planner spec all agree with the
        compiled program. Without this, a backend that fails the fp8
        probe would run the bf16 wire while the trainer reports (and
        the optimizer prices, applies and 'realizes') a phantom fp8."""
        p = (precision or "bf16").strip() or "bf16"
        if p != "bf16":
            from dlrover_tpu.ops.shard_compat import fp8_wire_supported

            if not fp8_wire_supported():
                logger.warning(
                    "moe precision %r requested but the backend fails "
                    "the fp8 probe; the trainer runs (and reports) "
                    "the bf16 wire", p,
                )
                return "bf16"
        return p

    # -- build / rebuild -----------------------------------------------------

    @property
    def accelerated(self) -> AccelerateResult:
        if self._result is None:
            raise RuntimeError("call prepare() first")
        return self._result

    @property
    def devices(self) -> Optional[list]:
        """The explicit device subset this trainer runs on (None = the
        whole ambient world) — what a same-world prewarm must target."""
        return list(self._devices) if self._devices is not None else None

    def _resolved_strategy(self, num_devices: int):
        """The strategy a build for ``num_devices`` will actually
        compile: the base strategy's world derivation, with the
        optimizer's mesh override (when set and it fits) replacing the
        derived factorization."""
        strategy = self._base_strategy.adjust_to_world(
            num_devices, prev_num_devices=self._initial_devices
        )
        if self._mesh_override is not None:
            try:
                strategy = dataclasses.replace(
                    strategy,
                    mesh=self._mesh_override.resolve(num_devices),
                )
            except ValueError:
                # the override was chosen for a different world size:
                # fall back to the derived mesh rather than fail the
                # rebuild (the optimizer re-plans for the new world)
                logger.warning(
                    "mesh override %s does not fit %d devices; using "
                    "the derived mesh", self._mesh_override, num_devices,
                )
        return strategy

    def _program_key(self, devices: list, strategy) -> str:
        """Program-cache identity: device topology x the knobs that
        change the compiled program (RESOLVED mesh factorization,
        chunk degree, wire precisions). Keyed on what the build will
        actually compile — not on how the knobs were requested — so a
        retune back to the startup config hits the program the trainer
        began with."""
        from dlrover_tpu.parallel.mesh import mesh_axes_key

        return (
            topology_key(devices)
            + f"|mesh={mesh_axes_key(strategy.mesh)}"
            + f"|c={self.dispatch_chunks}"
            + f"|p={self.moe_precision}"
            + f"|fp={self.fsdp_precision}"
            + f"|gp={self.grad_precision}"
        )

    def _build(self, devices: Optional[list]) -> AccelerateResult:
        """Compile (or fetch from the program cache) for ``devices``
        (None = the whole ``jax.devices()`` world)."""
        actual = list(devices) if devices else jax.devices()
        num_devices = len(actual)
        if self._initial_devices is None:
            self._initial_devices = num_devices
        # pin the trace-time knob BEFORE anything compiles (jit is
        # lazy: the trace may land on the first post-build step, so the
        # Context value must persist — on a prewarm/failed retune the
        # caller restores it alongside self.dispatch_chunks)
        from dlrover_tpu.common.config import get_context

        get_context().dispatch_chunks = self.dispatch_chunks
        get_context().moe_precision = self.moe_precision
        get_context().fsdp_precision = self.fsdp_precision
        strategy = self._resolved_strategy(num_devices)
        key = self._program_key(actual, strategy)
        self._current_program_key = key
        reg = get_registry()
        cached = self._programs.get(key)
        if cached is not None:
            # LRU touch: the topology we are running on must be the
            # last evicted when the cap trims standby entries
            self._programs.move_to_end(key)
            reg.counter(
                tm.PROGRAM_CACHE_HITS,
                help="rebuilds served from the compiled-program cache "
                     "(zero recompiles)").inc()
            logger.info("program cache hit for %d devices (zero "
                        "recompiles)", num_devices)
            return cached
        reg.counter(
            tm.PROGRAM_CACHE_MISSES,
            help="rebuilds that had to compile").inc()
        result = accelerate(
            self._init_fn,
            self._loss_fn,
            self._optimizer,
            self._example_batch,
            strategy=strategy,
            rng=self._rng,
            devices=devices,
            grad_precision=self.grad_precision,
        )
        self.compile_count += 1
        self._programs[key] = result
        while len(self._programs) > self._program_cache_cap:
            evicted, _ = self._programs.popitem(last=False)
            self._attr_records.pop(evicted, None)
            logger.info("program cache evicted topology %.40s...", evicted)
        return result

    def attribution(self, step_scopes: bool = False):
        """The performance-attribution record for the CURRENT compiled
        program (``telemetry.attribution.AttributionRecord``), captured
        lazily through the AOT path and cached by the program-cache key
        — a retune back to a seen knob set reuses the record like it
        reuses the program. None when attribution/telemetry is off, no
        program is built yet, or the capture failed (probed once).
        ``step_scopes``: the capture also keeps which phase and scope
        each instruction of the step belongs to (the executor asks
        where it can open a profiling window); ``program_key`` on the
        record is the cache key it was captured under."""
        from dlrover_tpu.telemetry import attribution as attr_mod

        if self._result is None or not attr_mod.attribution_enabled():
            return None
        key = self._current_program_key or ""
        cached = self._attr_records.get(key)
        if cached is not None:
            return cached or None  # False = a probed, failed capture
        try:
            record = attr_mod.capture_attribution(
                self._result,
                example_batch=self._example_batch,
                model_spec=self._model_spec,
                mesh_plan=getattr(self._result.strategy, "mesh", None),
                step_scopes=step_scopes,
            )
        except Exception:  # noqa: BLE001 — attribution is observation-
            # only: a backend without AOT analysis must not kill the job
            logger.warning("attribution capture failed for this "
                           "program", exc_info=True)
            record = None
        if record is not None:
            record.program_key = key
        self._attr_records[key] = record if record is not None else False
        return record

    def prepare(self, state: Any = None) -> Any:
        """Compile for the current world; restore or init state.

        Restore ladder (docs/elasticity.md): peer rebuild first — the
        checkpoint-free path that streams state out of surviving peers'
        DRAM (``checkpoint.replication``), taken when replicas are
        configured and at least as fresh as the newest checkpoint —
        then the Orbax/host-mirror restore, then a fresh init."""
        from dlrover_tpu.utils.compile_cache import cache_traffic

        t0 = time.monotonic()
        self._result = self._build(self._devices)
        t1 = time.monotonic()
        state = self._initial_state(state)
        # layers by kind, where the model's init_fn says (a model of
        # several kinds of layer), and the passes a step over them (a
        # looped model): a trace reads without the config
        kinds = getattr(self._init_fn, "layer_kinds", None)
        passes = getattr(self._init_fn, "passes", None)
        emit_event(
            EventKind.TRAINER_READY, step=self._host_step,
            script_seconds=self._script_seconds,
            build_seconds=round(t1 - t0, 6),
            ckpt_manager_seconds=round(self._ckpt_manager_seconds, 6),
            # restored, rebuilt from peers or initialised (dispatched:
            # a fresh init completes behind the first step)
            state_seconds=round(time.monotonic() - t1, 6),
            compile=cache_traffic(),
            **({"layer_kinds": kinds} if kinds else {}),
            **({"passes": passes} if passes else {}),
        )
        return state

    def _initial_state(self, state: Any) -> Any:
        if state is not None:
            self._host_step = int(state.step)
            return state
        restored = self._try_peer_restore()
        if restored is not None:
            return restored
        if self._ckpt is not None:
            restored = self._try_restore()
            if restored is not None:
                return restored
        self._host_step = 0
        return self._result.init_fn(self._rng)

    def _try_restore(self) -> Optional[Any]:
        abstract = jax.eval_shape(
            lambda r: self._result.init_fn(r), self._rng
        )
        target = abstract_like(abstract, self._result.state_sharding)
        out = self._ckpt.restore(target)
        if out is None:
            return None
        if out["shard_checkpoint"] and self._master_client is not None:
            # Hand the data-shard state back to the master so the epoch
            # resumes where it left off.
            try:
                from dlrover_tpu.common import comm

                self._master_client.report(
                    comm.ShardCheckpoint(content=out["shard_checkpoint"])
                )
            except Exception:  # noqa: BLE001
                logger.exception("restoring shard checkpoint failed")
        logger.info("resumed from step %d", out["step"])
        self._host_step = int(out["state"].step)
        # the cadence counts from the restored step, not from 0: a
        # restarted worker must not stall on a save one step after the
        # restore (seen on the chip: 2.4 s of the kill-to-step window)
        self._ckpt.interval.mark_saved(self._host_step)
        return out["state"]

    def _try_peer_restore(self) -> Optional[Any]:
        """The checkpoint-free recovery path: ask the master which live
        peers hold replicated snapshot regions, stream them (chunked,
        checksummed, holder-fallback), and ``device_put`` the rebuilt
        host tree against THIS mesh's shardings — the same
        sharding-agnostic landing an Orbax reshard-on-load performs,
        minus the storage round-trip. Returns the rebuilt state, or
        None to degrade to the storage path (no replicas configured,
        none reachable, structure mismatch, or the peers' snapshot is
        STALER than the newest committed checkpoint)."""
        from dlrover_tpu.common.config import get_context

        ctx = get_context()
        if (
            self._master_client is None
            or not getattr(ctx, "peer_restore", True)
            or int(getattr(ctx, "snapshot_replicas", 0)) <= 0
            or not hasattr(self._master_client, "get_recovery_plan")
        ):
            return None
        from dlrover_tpu.checkpoint import replication as repl
        from dlrover_tpu.diagnosis.hang_detector import announce_long_phase

        try:
            plan = self._master_client.get_recovery_plan()
        except Exception as e:  # noqa: BLE001 — no master, no peers:
            # the storage ladder below still recovers the job
            logger.warning("recovery plan fetch failed (%s: %s); taking "
                           "the storage path", type(e).__name__, e)
            return None
        owners = {
            int(k): list(v or [])
            for k, v in (plan.get("owners") or {}).items()
        }
        if not owners or not any(owners.values()):
            return None
        # the master's priced recovery ladder (readiness auditor): the
        # predicted MTTR of each rung, calibrated from realized
        # incidents and push-cycle bandwidth. Absent on old masters —
        # every priced decision below degrades to the ladder order.
        mttr_table: Dict[str, float] = {}
        for rung, secs in (plan.get("predicted_mttr") or {}).items():
            try:
                mttr_table[str(rung)] = float(secs)
            except (TypeError, ValueError):
                continue
        predicted_s = mttr_table.get("peer_rebuild")
        announce_long_phase(600.0)  # rebuild window: not a hang
        abstract = jax.eval_shape(
            lambda r: self._result.init_fn(r), self._rng
        )
        flat, treedef = jax.tree_util.tree_flatten(abstract)
        # the plane's ONE fast-fail channel policy (a dead holder must
        # fall through to the next replica quickly, not burn the
        # patient master backoff ladder)
        channel_factory, close_channels = repl.replica_channel_factory()
        t0 = time.monotonic()
        try:
            # cheap inventory sweep first: the candidate step is known
            # BEFORE any chunk moves, so the staleness gate below can
            # veto the transfer without paying for it
            all_endpoints = [ep for eps in owners.values() for ep in eps]
            inventories = repl._collect_inventories(
                all_endpoints, channel_factory)
            found = repl.best_common_step(inventories)
            if found is None:
                raise repl.PeerRestoreError(
                    "no step with full owner coverage on any "
                    "reachable holder")
            peek_step = found[0]
            # staleness gate: a frozen replicator (expired cadence)
            # must not roll the job back past a newer committed
            # checkpoint — the one storage touch here is a step
            # LISTING, not a state transfer
            if self._ckpt is not None:
                try:
                    ckpt_step = self._ckpt.latest_step()
                except Exception:  # noqa: BLE001 — unreachable storage
                    # cannot veto the in-DRAM copy on offer
                    logger.warning("checkpoint step listing failed "
                                   "during peer restore", exc_info=True)
                    ckpt_step = None
                if ckpt_step is not None and int(ckpt_step) > peek_step:
                    emit_event(EventKind.PEER_REBUILD_FALLBACK,
                               error_code="REPLICA_STALE",
                               replica_step=int(peek_step),
                               checkpoint_step=int(ckpt_step))
                    logger.warning(
                        "peer snapshot step %d is staler than "
                        "checkpoint step %d; restoring from storage",
                        peek_step, ckpt_step)
                    return None
                # priced-rung gate: when an equally fresh checkpoint
                # exists AND the master's calibrated ladder prices the
                # storage restore cheaper than the peer fetch (e.g. a
                # local NVMe cache vs a congested link), take the
                # cheaper rung — the ladder order is a prior, the
                # price is evidence
                storage_pred = mttr_table.get("storage_restore")
                if (ckpt_step is not None
                        and int(ckpt_step) >= peek_step
                        and predicted_s is not None
                        and storage_pred is not None
                        and storage_pred < predicted_s):
                    emit_event(EventKind.PEER_REBUILD_FALLBACK,
                               error_code="MTTR_PRICED_OUT",
                               rung="storage_restore",
                               predicted_mttr_s=round(storage_pred, 3),
                               peer_predicted_mttr_s=round(
                                   predicted_s, 3))
                    logger.info(
                        "storage restore priced at %.2fs beats peer "
                        "rebuild at %.2fs for step %d; taking the "
                        "storage rung", storage_pred, predicted_s,
                        peek_step)
                    return None
            # the failure edge opens only once the gates passed and a
            # transfer actually begins: a by-design degradation (stale
            # replica, nothing reachable) must not strand an unpaired
            # PEER_REBUILD_BEGIN that the MTTR derivation would report
            # as an unrecovered incident
            begin_fields: Dict[str, Any] = {}
            if predicted_s is not None:
                begin_fields["predicted_mttr_s"] = round(predicted_s, 3)
                begin_fields["rung"] = "peer_rebuild"
            emit_event(EventKind.PEER_REBUILD_BEGIN,
                       step=int(peek_step), owners=sorted(owners),
                       holders=sum(len(v) for v in owners.values()),
                       **begin_fields)
            leaves, meta, step, wire_bytes = repl.fetch_tree(
                flat, owners, channel_factory,
                inventories=inventories)
        except repl.PeerRestoreError as e:
            emit_event(EventKind.PEER_REBUILD_FALLBACK,
                       error_code="PEER_RESTORE_UNAVAILABLE",
                       detail=str(e)[:300])
            logger.warning("peer rebuild unavailable (%s); degrading to "
                           "the storage restore path", e)
            return None
        finally:
            close_channels()
        fetch_s = time.monotonic() - t0
        t1 = time.monotonic()
        from dlrover_tpu.checkpoint.manager import _rematerialize

        tree = jax.tree_util.tree_unflatten(treedef, leaves)
        state = jax.device_put(tree, self._result.state_sharding)
        # donation safety: on CPU, device_put can zero-copy ALIAS the
        # fetched numpy buffers — the first donated step would scribble
        # host memory XLA does not own (the Orbax adjacency lesson)
        state = _rematerialize(state)
        jax.block_until_ready(state)
        put_s = time.monotonic() - t1
        self._host_step = int(meta.get("host_step", step))
        rng = meta.get("rng")
        if rng:
            import numpy as np

            self._rng = jax.numpy.asarray(
                np.asarray(rng, dtype=np.uint32))
        reg = get_registry()
        reg.histogram(
            tm.PEER_REBUILD_TIME,
            help="checkpoint-free rebuild: peer fetch + device_put "
                 "wall seconds").observe(fetch_s + put_s)
        reg.counter(
            tm.PEER_REBUILD_BYTES,
            help="bytes streamed out of peer DRAM during rebuilds",
        ).inc(wire_bytes)
        # predicted-vs-realized stamped on the recovery event itself:
        # the readiness plane EMA-corrects its pricer against exactly
        # this pair, and `tpurun mttr --predict` reports the ratio
        done_fields: Dict[str, Any] = {
            "realized_mttr_s": round(fetch_s + put_s, 3),
            "rung": "peer_rebuild",
        }
        if predicted_s is not None:
            done_fields["predicted_mttr_s"] = round(predicted_s, 3)
        emit_event(EventKind.PEER_REBUILD_DONE, step=int(step),
                   fetch_seconds=round(fetch_s, 3),
                   put_seconds=round(put_s, 3),
                   bytes_from_peers=int(wire_bytes), storage_bytes=0,
                   owners=sorted(owners), **done_fields)
        logger.info(
            "peer rebuild: restored step %d from surviving peers' DRAM "
            "(%.1f MB over the wire in %.2fs, device_put %.2fs, zero "
            "storage reads)", step, wire_bytes / 1e6, fetch_s, put_s)
        return state

    def restore_state(self) -> Optional[Any]:
        """Restore the latest checkpoint onto the EXISTING compiled
        program — the rollback path. The world hasn't changed, so the
        jitted step and shardings stay valid; rebuilding via
        ``prepare(None)`` would pay a full re-accelerate + retrace for
        nothing (minutes at scale, and a silent no-heartbeat window the
        hang detector could misread)."""
        if self._result is None or self._ckpt is None:
            return None
        from dlrover_tpu.diagnosis.hang_detector import announce_long_phase

        announce_long_phase(600.0)  # restore window: not a hang
        return self._try_restore()

    def snapshot(self, state: Any) -> HostSnapshot:
        """Host-DRAM copy of the live state (one ``device_get``). The
        reshard source of ``live_reshard``, a rollback anchor that
        survives the loss of any peer's devices, and — with the rng
        stream and host step in its meta — a complete resume point the
        peer-replication plane can rebuild a DIFFERENT process from
        bitwise (the replayed trainer must continue the same rng
        stream the lost one would have)."""
        import numpy as np

        return HostSnapshot.take(
            state, strategy=self._result.strategy.to_json()
            if self._result else "",
            rng=[int(x) for x in np.asarray(self._rng).reshape(-1)],
            host_step=int(self._host_step),
        )

    def live_reshard(self, state: Any, devices=None,
                     snapshot: Optional[HostSnapshot] = None,
                     reason: str = "", emit_events: bool = True) -> Any:
        """The live recovery fast path: absorb a world change WITHOUT
        leaving the process.

        snapshot (host DRAM) → rebuild (program cache, often zero
        recompiles) → reshard (``device_put`` against the new
        shardings) → resume. Callers (the executor) drain their
        in-flight window first so the snapshot covers the last
        completed optimizer step. ``devices``: the surviving device
        subset (default: the full post-change ``jax.devices()`` world —
        an explicit construction-time subset is dropped, because after
        a membership change those handles may be stale/dead).
        ``snapshot``: a pre-taken HostSnapshot (e.g. from a caller that
        snapshotted before re-rendezvous); default is to take one now.

        The global batch stays fixed: ``Strategy.adjust_to_world``
        shrinks the data axis and grows grad accumulation to compensate
        — the reference's ``_set_gradient_accumulation_steps``
        semantics.
        """
        from dlrover_tpu.diagnosis.hang_detector import announce_long_phase

        announce_long_phase(900.0)  # rebuild window: not a hang
        old_result = self._result
        old_n = (
            old_result.mesh.devices.size if old_result is not None else 0
        )
        t0 = time.monotonic()
        if emit_events:
            emit_event(EventKind.LIVE_RESHARD_BEGIN, world_from=old_n,
                       reason=reason, step=int(self._host_step))
        with span(SpanName.LIVE_RESHARD, world_from=old_n):
            if snapshot is None:
                snapshot = self.snapshot(state)
            self._devices = list(devices) if devices is not None else None
            n = len(self._devices) if self._devices else len(jax.devices())
            compiles_before = self.compile_count
            self._result = self._build(self._devices)
            state = snapshot.restore(self._result.state_sharding)
            # the reshard program must have RUN before we claim
            # recovered (and before the timing below means anything)
            jax.block_until_ready(state)
        reshard_s = time.monotonic() - t0
        reg = get_registry()
        reg.counter(
            tm.LIVE_RESHARDS,
            help="world changes absorbed in-process (no restart)").inc()
        reg.histogram(
            tm.LIVE_RESHARD_TIME,
            help="snapshot -> rebuild -> reshard wall seconds",
        ).observe(reshard_s)
        recompiled = self.compile_count - compiles_before
        old_accum = (
            old_result.strategy.grad_accum_steps if old_result else 1
        )
        logger.info(
            "live reshard: %d -> %d devices in %.2fs (grad_accum "
            "%d -> %d, %s)", old_n, n, reshard_s, old_accum,
            self._result.strategy.grad_accum_steps,
            "program cache hit" if not recompiled else "recompiled",
        )
        if emit_events:
            emit_event(EventKind.LIVE_RESHARD_DONE, world_from=old_n,
                       world_to=n, reshard_seconds=round(reshard_s, 3),
                       recompiled=recompiled, step=snapshot.step)
        return state

    def prewarm(self, devices=None, execute: bool = True,
                mesh=None, dispatch_chunks: Optional[int] = None,
                moe_precision: Optional[str] = None,
                fsdp_precision: Optional[str] = None) -> bool:
        """Standby-compile the program for a topology OR knob set we may
        swap to — the (N - node_unit)-device survivor world before a
        failure, or an optimizer-chosen knob set (mesh override, chunk
        degree, wire precisions) before the retune that applies it — so
        the live reshard/retune that follows hits the program cache and
        pays zero recompiles. Returns True when a compile happened,
        False on a cache hit. Does NOT switch the trainer's active
        program, device set, or knobs (the temporary knob swap is
        restored).

        ``execute`` (default): run one throwaway step on the standby
        program — jit is lazy, so merely building the program object
        would still leave trace + XLA compile to the first post-swap
        step. The dummy step costs a transient extra copy of the state
        on the standby submesh; pass ``execute=False`` on models too
        large to double-book (the swap then pays the compile, but
        still skips the strategy/mesh rebuild)."""
        from dlrover_tpu.common.config import get_context

        prev_mesh = self._mesh_override
        prev_c = self.dispatch_chunks
        prev_p = self.moe_precision
        prev_fp = self.fsdp_precision
        prev_key = self._current_program_key
        if mesh is not None:
            self._mesh_override = mesh
        if dispatch_chunks is not None:
            self.dispatch_chunks = max(1, int(dispatch_chunks))
        if moe_precision is not None:
            self.moe_precision = self._effective_precision(moe_precision)
        if fsdp_precision is not None:
            self.fsdp_precision = self._effective_precision(fsdp_precision)
        try:
            before = self.compile_count
            result = self._build(
                list(devices) if devices is not None else None)
            compiled = self.compile_count > before
            if execute and compiled:
                # the dummy step also forces the standby TRACE, which
                # is when ops.moe / models.llama read the chunk and
                # precision knobs off the Context
                self._execute_dummy_step(result)
        finally:
            self._mesh_override = prev_mesh
            self.dispatch_chunks = prev_c
            self.moe_precision = prev_p
            self.fsdp_precision = prev_fp
            # the ACTIVE program keeps its trace-time knobs (and its
            # attribution identity — not re-pointed at the standby key)
            get_context().dispatch_chunks = prev_c
            get_context().moe_precision = prev_p
            get_context().fsdp_precision = prev_fp
            self._current_program_key = prev_key
        return compiled

    def _execute_dummy_step(self, result: AccelerateResult) -> None:
        """Force the lazy jit through trace + XLA compile by running one
        throwaway step on the standby program."""
        from dlrover_tpu.diagnosis.hang_detector import (
            announce_long_phase,
        )

        announce_long_phase(900.0)  # standby compile: not a hang
        rng = jax.random.PRNGKey(0)
        dummy = result.init_fn(rng)
        sharded = result.shard_batch(self._example_batch)
        dummy, _unused = result.train_step(dummy, sharded, rng)
        jax.block_until_ready(dummy)
        logger.info(
            "prewarmed standby program (%d devices): one dummy "
            "step executed", result.mesh.devices.size,
        )

    def retune(self, state: Any,
               mesh=None, dispatch_chunks: Optional[int] = None,
               moe_precision: Optional[str] = None,
               fsdp_precision: Optional[str] = None,
               reason: str = "optimizer") -> Any:
        """Apply optimizer-chosen PROGRAM knobs on the current world
        without a restart: ``dispatch_chunks`` / ``moe_precision`` /
        ``fsdp_precision`` (the grouped_ep chunked-dispatch degree and
        the MoE / dense-FSDP wire precisions — trace-time knobs the
        program-cache key carries) and/or a mesh override (a different
        factorization of the same devices). Same mechanics as
        ``live_reshard`` — the caller drains its window first;
        snapshot → rebuild → reshard — but against the unchanged
        device set, and through the program cache keyed on these very
        knobs, so a prewarmed knob set swaps with ZERO recompiles.
        (``grad_precision`` is deliberately absent: the error-feedback
        residual is part of TrainState, so that knob cannot flip under
        a live state.) On failure the previous knobs (and the
        previously compiled program) are restored and the error
        propagates — the job keeps running the old config."""
        prev_mesh = self._mesh_override
        prev_c = self.dispatch_chunks
        prev_p = self.moe_precision
        prev_fp = self.fsdp_precision
        if mesh is not None:
            self._mesh_override = mesh
        if dispatch_chunks is not None:
            self.dispatch_chunks = max(1, int(dispatch_chunks))
        if moe_precision is not None:
            self.moe_precision = self._effective_precision(moe_precision)
        if fsdp_precision is not None:
            self.fsdp_precision = self._effective_precision(fsdp_precision)
        try:
            return self.live_reshard(
                state, devices=self._devices, reason=reason,
                emit_events=False,
            )
        except Exception:
            self._mesh_override = prev_mesh
            self.dispatch_chunks = prev_c
            self.moe_precision = prev_p
            self.fsdp_precision = prev_fp
            # re-point at the old program (cache hit, and the Context
            # chunk knob re-pinned by _build) so the trainer stays
            # runnable with the pre-retune config
            self._result = self._build(self._devices)
            raise

    def on_world_change(self, state: Any, devices=None) -> Any:
        """The process-restart rebuild entrypoint (agent/bootstrap,
        after ``jax.distributed`` re-init; also the executor's classic
        ``request_restart`` path). Same mechanics as ``live_reshard``
        but WITHOUT the live-reshard timeline events: a restart-path
        rebuild must pair with the restart scenarios in the MTTR
        derivation, not inflate the ``live_reshard`` one."""
        return self.live_reshard(state, devices=devices,
                                 reason="on_world_change",
                                 emit_events=False)

    # -- hot loop ------------------------------------------------------------

    def step(self, state: Any, batch: Any) -> Tuple[Any, Dict]:
        self._rng, step_rng = jax.random.split(self._rng)
        sharded = self._result.shard_batch(batch)
        state, metrics = self._result.train_step(state, sharded, step_rng)
        self._host_step += 1
        step = self._host_step
        if self._master_client is not None and step % self._report_every == 0:
            try:
                from dlrover_tpu.common import comm

                self._master_client.report(
                    comm.GlobalStep(step=step, timestamp=time.time())
                )
                self._c_reports.inc()
            except Exception:  # noqa: BLE001 - reporting must never kill training
                self._c_report_failures.inc()
        if self._ckpt is not None and self._ckpt.interval.should_save(step):
            self._save_if_finite(state, metrics, step)
        return state, metrics

    def _save_if_finite(self, state: Any, metrics: Dict, step: int):
        """The save branch of a save step, with a span and a count of
        its own. Never checkpoint a NaN-poisoned state: it would
        corrupt the rollback/restore target. The step's flag goes to
        the checkpoint manager as the device value it is: this thread
        reads nothing from the device, so the steps in flight stay in
        flight, unless the manager has no room for a snapshot and
        stages the live state here (``ElasticCheckpointManager.save``).
        ``save_seconds`` is what the branch held this thread."""
        t0 = time.monotonic()
        with span(SpanName.CKPT_SAVE, step=step):
            if self.save(state, finite=metrics.get("finite"), step=step):
                self.saves_begun += 1
        self.save_seconds += time.monotonic() - t0

    # -- checkpoint ----------------------------------------------------------

    def latest_checkpoint_step(self) -> Optional[int]:
        """Newest restorable step, flushing any in-flight async save
        first; None when no checkpointing is configured or nothing has
        been committed yet (the executor's rollback precondition)."""
        if self._ckpt is None:
            return None
        try:
            self._ckpt.wait()
        except Exception:  # noqa: BLE001
            logger.exception("flushing async checkpoint failed")
        return self._ckpt.latest_step()

    def save(self, state: Any, force: bool = True,
             finite: Any = None, step: Optional[int] = None) -> bool:
        """Checkpoint ``state`` as step ``step``. The train loop gives
        the step it has just dispatched, from the host's mirror;
        without it the step is read from ``state.step``, a device value
        (the read waits for every step in flight). The data position is
        asked of the master here, on the step it belongs to. Returns
        whether a save was begun."""
        if self._ckpt is None:
            return False
        if step is None:
            step = int(state.step)
        shard_ckpt = ""
        if self._master_client is not None:
            try:
                from dlrover_tpu.common import comm

                resp = self._master_client.get(
                    comm.ShardCheckpointRequest(dataset_name="")
                )
                shard_ckpt = getattr(resp, "content", "") or ""
            except Exception:  # noqa: BLE001
                pass
        return self._ckpt.save(
            step,
            state,
            metadata={"strategy": self._result.strategy.to_json()},
            shard_checkpoint=shard_ckpt,
            force=force,
            finite=finite,
        )

    def finalize(self) -> bool:
        """Flush + close checkpointing. Returns True when a staging
        mirror timed out (``ElasticCheckpointManager.wait``) — surfaced
        so exit paths (preemption drain) can report that the host-DRAM
        mirror never committed."""
        timed_out = False
        if self._ckpt is not None:
            timed_out = bool(self._ckpt.wait())
            self._ckpt.close()
        return timed_out
