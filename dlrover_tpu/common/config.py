"""Global tunables singleton.

Role parity: ``dlrover/python/common/global_context.py`` — one process-wide
``Context`` with named knobs (timeouts, thresholds, feature gates), each
overridable from the environment (``DLROVER_TPU_<UPPER_NAME>``) or at runtime
(e.g. by a cluster-level optimizer service).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict


class Context:
    _instance = None
    _lock = threading.Lock()

    def __init__(self):
        # control-loop cadences (seconds)
        self.master_service_timeout = 600
        self.seconds_to_wait_failed_ps = 600
        self.train_speed_record_num = 50
        self.seconds_for_stable_worker_count = 60
        self.seconds_interval_to_optimize = 30
        self.seconds_interval_to_report = 15
        self.seconds_to_start_autoscale_worker = 90
        self.step_to_adjust_worker = 200
        self.seconds_to_timeout_task = 1800
        self.hang_cpu_usage_percentage = 0.05
        self.hang_detection_secs = 1800
        self.heartbeat_timeout_secs = 300
        self.seconds_to_wait_pending_pod = 900
        # rendezvous
        self.rdzv_timeout_secs = 600
        self.rdzv_round_wait_secs = 3
        self.network_check_timeout_secs = 300
        # relaunch policy
        self.relaunch_on_worker_failure = 3
        self.max_relaunch_count = 5
        self.relaunch_always = False
        # elasticity
        self.auto_scale_enabled = True
        self.dynamic_sharding_enabled = True
        # cooldown between executed scale plans: a scale-up implies a new
        # rendezvous + recompile, and the stats window needs to refill
        # with post-scale samples before the optimizer can judge again
        self.seconds_between_scale_plans = 60
        # optimizer
        self.oom_memory_factor = 2.0
        self.optimize_worker_cpu_threshold = 0.8
        # checkpoint
        self.ckpt_async = True
        self.ckpt_host_staging = True
        # numerics debugging: opt-in jax_debug_nans (traps the first NaN
        # inside jit with a traceback; expensive — debug runs only)
        self.jax_debug_nans = False
        # guardrail: steps between non-finite loss/grad checks (0 = off);
        # each check reads one device scalar, so keep it off the per-step
        # hot path
        self.check_finite_every_steps = 10
        # async dispatch pipeline: how many train-step dispatches may be
        # in flight before the oldest one's metrics are materialized
        # (hooks/logging/finite-check consume LAGGED host values; 0 =
        # fully synchronous — materialize right after each dispatch)
        self.train_window = 4
        # live elastic recovery: survivable membership changes (peer
        # lost, scale plan, another node preempted) are absorbed
        # IN-PROCESS — drain the dispatch window, snapshot TrainState to
        # host DRAM, rebuild the mesh for the survivor world, reshard
        # via device_put — instead of restarting the worker process
        # (docs/operations.md decision tree). Off = every change takes
        # the process-restart path.
        self.live_recovery = True
        # peer-redundant host snapshots (checkpoint-free pod-scale
        # recovery, docs/elasticity.md recovery ladder): how many PEER
        # DRAM replicas of each node's snapshot regions the master
        # should assign (0 = plane off). The budget admission can
        # degrade below this — fewer replicas, never a worker OOM.
        self.snapshot_replicas = 0
        # replication cadence: materialized steps between snapshot
        # pushes, floored by a wall-time interval so a fast-stepping
        # job cannot tax itself with per-step-scale replication
        self.replica_cadence_steps = 16
        self.replica_min_interval_secs = 15.0
        # host-DRAM budget (MB) this node grants to PEER replicas —
        # the admission input the master prices plans against (capped
        # at a quarter of the host's available memory at registration).
        # 0 = uncapped; NEGATIVE = lend nothing (the node is never a
        # peer-replica holder, while its OWN regions — budget-exempt
        # on its store — still replicate out to peers)
        self.replica_budget_mb = 512.0
        # chunk size of the replica wire stream (KB): each chunk is
        # length-prefixed + crc32-checksummed and retried individually
        self.replica_chunk_kb = 256
        # port the worker's replica store serves on (0 = ephemeral)
        self.replica_port = 0
        # recovering workers try the peer-rebuild path before the
        # Orbax/mirror restore (only meaningful with replicas > 0);
        # a stale peer snapshot older than the newest checkpoint
        # falls back to storage
        self.peer_restore = True
        # recovery-readiness plane (master/monitor/readiness.py,
        # docs/operations.md "Reading a readiness report"): wall
        # seconds between durability-audit sweeps of the replica
        # directory against the stores' live inventories (0 = the
        # continuous audit is off; forced sweeps — the RPC's refresh,
        # tests — still run)
        self.readiness_sweep_secs = 30.0
        # staleness allowance: a replica group whose committed step
        # trails the owner's reported step by more than this factor
        # times the master-computed cadence is STALE (coverage a
        # rebuild would roll the job back past one cadence is not
        # durability)
        self.readiness_stale_factor = 2.0
        # what to do on a non-finite step after reporting the failure:
        # "halt" | "rollback" (restore last checkpoint) | "ignore"
        self.on_nonfinite = "halt"
        # xprof trace capture ("" = off): the executor records
        # trace_num_steps steps starting at trace_start_step into
        # trace_dir (open with tensorboard/xprof). A negative
        # trace_start_step schedules nothing: trace_dir is then only
        # where the windows asked for by profile_signal go. Env:
        # DLROVER_TPU_TRACE_DIR etc.
        self.trace_dir = ""
        self.trace_start_step = 5
        self.trace_num_steps = 3
        # telemetry (dlrover_tpu.telemetry / docs/observability.md):
        # master switch for the metrics registry, event timeline, and
        # host-span tracing (each instrument site holds handles fetched
        # through get_registry(), which goes null when this is off)
        self.telemetry_enabled = True
        # append-only JSONL event-timeline sink ("" = in-memory ring
        # only); DLROVER_TPU_EVENTS_FILE overrides per process and is
        # what the agent hands its workers so one file holds the job
        self.telemetry_events_file = ""
        # Prometheus exposition port on the agent/master (0 = off)
        self.telemetry_metrics_port = 0
        # event-timeline rotation cap in MB (0 = never rotate): past
        # this size the file rotates to <path>.1 and a fresh file opens;
        # read_events / mttr / goodput read the rotated pair
        self.telemetry_events_max_mb = 64
        # cluster diagnosis plane (master-side, docs/observability.md):
        # cadence of the workers' NodeRuntimeReport pushes (optimizer
        # steps between reports; 0 disables the hook)
        self.runtime_report_steps = 32
        # straggler verdict: a node is flagged when its windowed
        # step-time p50 exceeds the median of its peers by this ratio...
        self.diagnosis_straggler_ratio = 2.0
        # ...for this many CONSECUTIVE report windows (rides out the
        # one-off box-noise spikes a single window would flag)
        self.diagnosis_confirm_windows = 3
        # a node whose last runtime report is older than this while a
        # peer is still reporting is diagnosed hung (0 = off)
        self.diagnosis_hang_secs = 120.0
        # signal name ("" = off, e.g. "USR2") that opens an on-demand
        # bounded jax.profiler trace window in the executor, one for
        # every delivery; each closes with a profile_window event
        self.profile_signal = ""
        # runtime optimization loop (master/optimizer; the telemetry ->
        # planner -> live-reshard control loop, docs/operations.md
        # "Self-tuning"): master switch for re-planning on diagnosis
        # verdicts / world changes
        self.runtime_optimizer_enabled = True
        # hysteresis: a candidate plan must predict at least this
        # speedup over the calibrated estimate of the CURRENT config to
        # be published (1.2 = 20% — below that the drain + swap churn
        # outweighs the win)
        self.replan_min_speedup = 1.2
        # cooldown/dedup window: the identical plan proposed twice
        # within this many seconds is suppressed (flapping triggers
        # cannot thrash the job through the same plan)
        self.replan_cooldown_secs = 60.0
        # input-bound replan gate (docs/operations.md "Self-tuning"):
        # when a node's input_wait_fraction sits >= 0.1 above the peer
        # median, the job is data-starved and a program (mesh)
        # replan cannot help — the optimizer rejects program plans with
        # reason=input_bound instead of paying a futile drain. Host
        # knobs (train_window) still apply.
        self.replan_input_bound_gate = True
        # worker-side: wall seconds between get_parallel_config polls
        # for a master-published plan (0 = the OptimizerPlanHook is off)
        self.plan_poll_secs = 30.0
        # worker-side: materialized steps after a live plan apply
        # before the realized speedup is measured and OPTIMIZER_APPLIED
        # is emitted (the post-convergence window)
        self.plan_measure_steps = 16
        # performance-attribution plane (telemetry.attribution,
        # docs/observability.md): capture a per-compiled-program
        # attribution record (exact FLOPs, bytes-accessed, per-
        # collective bytes, compiled peak HBM) once per program and
        # derive live MFU / exposed-comm-fraction gauges from it.
        # Requires telemetry_enabled; off = no capture, gauges absent.
        self.attribution_enabled = True
        # hardware peak FLOPs/s per device for the MFU denominator
        # (0 = sniff the device kind against the planner's TPU_SPECS;
        # CPU meshes fall back to the v5e datasheet so the gauge stays
        # defined — set this explicitly for meaningful CPU numbers)
        self.device_peak_flops = 0.0
        # per-device HBM budget in BYTES for the G107 graph lint and
        # the optimizer's memory-feasibility gate (0 = the device
        # spec's capacity, with the planner's 0.8 fit headroom where it
        # applies)
        self.device_hbm_budget_bytes = 0.0
        # comm/compute overlap (docs/parallelism.md "Hiding the
        # network"): chunked expert dispatch — how many static chunks
        # the grouped_ep MoE row exchange splits into (1 = the serial
        # one-shot all_to_all). Resolved at TRACE time by ops.moe, so
        # ElasticTrainer.retune can re-chunk a running job; the runtime
        # optimizer enumerates {1, 2, 4, 8} as a knob family.
        self.dispatch_chunks = 1
        # FSDP layer prefetch: gather layer l+1's params while layer l
        # computes (a double-buffered carry through the scan-over-
        # layers; same math, float-roundoff-level schedule differences
        # vs the plain scan). Resolved at trace time by models that
        # support it (llama). Off by default: with heavy tensor
        # sharding the replicate-gather it issues can cost more than
        # it hides.
        self.fsdp_prefetch = False
        # low-precision MoE wire (docs/parallelism.md "Low-precision"):
        # the grouped_ep row exchanges' wire format — "bf16" (the
        # compute dtype, no quantization), "fp8" (block-scaled e4m3
        # values + f32 per-block scales, ~0.56x the bytes; G109 lints
        # the numerics drift, G106 audits the bytes), or "fp8_qdq"
        # (the bitwise reference oracle / debug mode). Resolved at
        # TRACE time by ops.moe, so ElasticTrainer.retune can swap a
        # running job's wire precision through the program cache; the
        # runtime optimizer enumerates {bf16, fp8} as a knob family.
        self.moe_precision = "bf16"
        # low-precision DENSE wire (docs/parallelism.md "Low-precision
        # / The dense wire"): what the per-layer FSDP param gathers of
        # the scan-over-layers ship — "bf16" (the param dtype, no
        # quantization), "fp8" (block-scaled e4m3 + f32 scales, ~1/4
        # of an f32 gather; dequant-exact at consumption, gradients
        # straight-through) or "fp8_qdq" (the bitwise reference
        # oracle). Resolved at TRACE time by models that support it
        # (llama), so ElasticTrainer.retune can swap a running job's
        # dense wire through the program cache; the runtime optimizer
        # enumerates {bf16, fp8} as a knob family.
        self.fsdp_precision = "bf16"
        # low-precision GRADIENT path: "bf16" (exact, today's math) or
        # "fp8" — the per-shard gradient tree is quantized with an
        # ERROR-FEEDBACK residual (decompression error carried in
        # TrainState alongside optimizer state, added back before the
        # next quantize so the error telescopes instead of
        # accumulating). Unlike the dense gathers this changes
        # training numerics (bounded; G109 ratchets the drift) and the
        # residual is part of the training state, so it is a BUILD-time
        # knob of accelerate/ElasticTrainer, not a live-retune family.
        # ("fp8_nofb" quantizes WITHOUT feedback — the degradation
        # control the telescoping tests compare against; never use it
        # to train.)
        self.grad_precision = "bf16"
        # -- serving tier (dlrover_tpu.serving, docs/serving.md) -----
        # fixed slot-batch width of the continuous-batching decode
        # loop (the compiled batch dimension; the runtime optimizer
        # retunes it live through the serve program cache)
        self.serve_slots = 8
        # prompt tokens prefilled per chunk, interleaved into the
        # decode stream so long prompts cannot stall the batch (also
        # optimizer-retunable)
        self.serve_prefill_chunk = 32
        # KV-page storage precision: "f32" | "bf16" | "int8" (int8 =
        # values + f32 per-block scales, ~1/4 of f32 residency; probe
        # fallback to f32; the G109 "kv" family ratchets the drift)
        self.serve_kv_precision = "f32"
        # in-flight decode dispatches before the oldest one's tokens
        # materialize on host (the PR 3 async window, re-aimed at
        # decode; 0 = synchronous)
        self.serve_window = 2
        # shared prefix pool, in pages (0 = off): device-resident
        # refcounted KV pages beside the slot pool, radix-indexed
        # host-side; admission COPIES matched pages into the slot
        # (copy-on-admit) and prefills only the unmatched tail. Pool
        # bytes ride the same HBM feasibility gate the slot pool does;
        # the runtime optimizer retunes this live (docs/serving.md
        # "Prefix reuse").
        self.serve_prefix_pool_pages = 0
        # router-side soft session affinity: lease same-prefix
        # requests to the worker whose pool already holds the pages
        # (correctness never depends on it — a worker without the
        # pages just misses and prefills)
        self.serve_prefix_affinity = True
        # planner prior for the expected prefix hit rate before any
        # worker has observed one (0 = price prefill undiscounted, so
        # the optimizer only spends pool HBM once traffic proves
        # prefix sharing — or an operator declares it)
        self.serve_prefix_expected_hit_rate = 0.0
        # speculative decode (self-drafting: host n-gram prompt-lookup
        # proposer + one batched multi-token verify step; bitwise
        # identical to plain greedy at every acceptance pattern —
        # docs/serving.md "Speculative decoding"). Master switch: when
        # False the draft length is pinned to 0 everywhere and the
        # optimizer refuses to enumerate K.
        self.serve_spec_enabled = True
        # draft tokens verified per slot per step (K; 0 = off). K is
        # static per compiled program — the optimizer retunes it live
        # from the OBSERVED acceptance rate through the program cache
        # (a pure program swap: zero recompiles once prewarmed).
        self.serve_spec_draft_len = 0
        # master-side: a leased request whose worker has not touched
        # the router for this long is re-leased to a live worker
        # (the shard-timeout machinery re-pointed at requests)
        self.serve_lease_timeout_secs = 120.0
        # -- serving SLO plane (master/monitor/serve_slo.py;
        # docs/operations.md "Reading an SLO violation") --------------
        # declared SLO targets, evaluated over rolling windows with
        # multi-window burn-rate confirmation. 0 = target OFF (both
        # off = the SLO engine never evaluates — the default: SLOs are
        # a deployment declaration, not a framework guess)
        self.serve_slo_ttft_p95_secs = 0.0
        self.serve_slo_queue_depth = 0.0
        # rolling evaluation window, and how many consecutive
        # over-budget (or, for recovery, under-budget) windows confirm
        # (0 = follow diagnosis_confirm_windows)
        self.serve_slo_window_secs = 30.0
        self.serve_slo_confirm_windows = 0
        # SLO-driven serving scale policy: per-direction proposal
        # cooldown (a flapping SLO cannot thrash the serving world),
        # and how many consecutive all-idle ticks propose a scale-in
        # (0 = scale-in off)
        self.serve_scale_cooldown_secs = 120.0
        self.serve_scale_idle_windows = 0
        self._apply_env_overrides()

    def _apply_env_overrides(self):
        for name, val in vars(self).items():
            if name.startswith("_"):
                continue
            env = os.environ.get("DLROVER_TPU_" + name.upper())
            if env is None:
                continue
            try:
                if isinstance(val, bool):
                    setattr(self, name, env.lower() in ("1", "true", "yes"))
                elif isinstance(val, int):
                    setattr(self, name, int(env))
                elif isinstance(val, float):
                    setattr(self, name, float(env))
                else:
                    setattr(self, name, env)
            except ValueError:
                import logging

                logging.getLogger("dlrover_tpu").warning(
                    "ignoring malformed env override DLROVER_TPU_%s=%r",
                    name.upper(), env,
                )

    def set_params(self, params: Dict[str, Any]):
        """Runtime override (the reference's ``set_params_from_brain``)."""
        for k, v in params.items():
            if hasattr(self, k) and not k.startswith("_"):
                setattr(self, k, v)

    @classmethod
    def singleton_instance(cls) -> "Context":
        if cls._instance is None:
            with cls._lock:
                if cls._instance is None:
                    cls._instance = cls()
        return cls._instance


def get_context() -> Context:
    return Context.singleton_instance()
