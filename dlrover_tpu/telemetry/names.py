"""The metric/event/span name registry — the ONE place observability
names live.

Every metric name handed to the registry (``counter()``, ``gauge()``,
``histogram()``) and every event kind handed to ``emit_event()`` must be
a constant from this module: the AST lint rule DLR007 rejects string
literals at telemetry call sites anywhere else in the package, so the
name table in ``docs/observability.md`` can never silently drift from
the code, and two subsystems can never claim the same series with
slightly different spellings.

Prometheus conventions: ``_total`` counters, ``_seconds`` durations,
unitless gauges named for what they measure.
"""

from __future__ import annotations

# -- worker / executor --------------------------------------------------------

# per-optimizer-step wall time, observed at materialization (the lagged
# window means one observation per step, dt shared across a drained group)
STEP_TIME = "dlrover_step_time_seconds"
# host time spent DISPATCHING one train-step call (tracing + enqueue,
# never device compute): the async-pipeline "is Python the bottleneck?"
# series PR 3 made invisible
STEP_DISPATCH_TIME = "dlrover_step_dispatch_seconds"
# host time blocked in device_get materializing the oldest in-flight
# call — the ONE device sync of the pipeline (≈ device-bound time)
STEP_HOST_SYNC_TIME = "dlrover_step_host_sync_seconds"
# in-flight dispatch window occupancy right after a dispatch
DISPATCH_WINDOW_OCCUPANCY = "dlrover_dispatch_window_occupancy"
# how many steps behind the newest dispatch the just-materialized
# metrics are (the "lagged-metric age" of the PR 3 ring)
LAGGED_METRIC_AGE = "dlrover_lagged_metric_age_steps"
TRAIN_STEPS = "dlrover_train_steps_total"
NONFINITE_STEPS = "dlrover_nonfinite_steps_total"
NONFINITE_ROLLBACKS = "dlrover_nonfinite_rollbacks_total"
PREEMPT_NOTICES = "dlrover_preemption_notices_total"
EVAL_TIME = "dlrover_eval_seconds"

# -- live elastic recovery ----------------------------------------------------

# in-process scale events absorbed without a process restart
LIVE_RESHARDS = "dlrover_live_reshards_total"
# drain -> snapshot -> rebuild -> reshard -> ready, wall seconds
LIVE_RESHARD_TIME = "dlrover_live_reshard_seconds"
# host-DRAM TrainState snapshot (device_get) wall seconds
SNAPSHOT_TIME = "dlrover_state_snapshot_seconds"
# in-process compiled-program cache of ElasticTrainer: a same-topology
# resume that hits it pays ZERO recompiles
PROGRAM_CACHE_HITS = "dlrover_program_cache_hits_total"
PROGRAM_CACHE_MISSES = "dlrover_program_cache_misses_total"

# -- peer-redundant host snapshots (checkpoint-free recovery) -----------------
# Worker side: the SnapshotReplicator's push cycles and its in-DRAM
# ReplicaStore; fetch side: the peer-rebuild stream a recovering worker
# runs instead of an Orbax restore.

REPLICA_PUSHES = "dlrover_replica_pushes_total"
REPLICA_PUSH_FAILURES = "dlrover_replica_push_failures_total"
REPLICA_PUSH_TIME = "dlrover_replica_push_seconds"
REPLICA_BYTES_PUSHED = "dlrover_replica_bytes_pushed_total"
# peer-replica bytes resident in this worker's DRAM (budget-bounded:
# admission degrades the plan before this can OOM a worker)
REPLICA_STORE_BYTES = "dlrover_replica_store_bytes"
# chunk frames rejected by the length-prefix/crc32 checks (holder-side
# on put, fetcher-side on read — silent bitrot becomes a counted fault)
REPLICA_CHUNK_CORRUPTIONS = "dlrover_replica_chunk_corruptions_total"
# chunk fetches retried or failed over to the next replica holder
REPLICA_FETCH_RETRIES = "dlrover_replica_fetch_retries_total"
# the checkpoint-free rebuild itself: peer-fetch + device_put wall
# seconds, and the bytes streamed out of peer DRAM (vs storage: 0)
PEER_REBUILD_TIME = "dlrover_peer_rebuild_seconds"
PEER_REBUILD_BYTES = "dlrover_peer_rebuild_bytes_fetched_total"

# -- recovery readiness (continuous durability audit) --------------------------
# Master-side auditor (master/monitor/readiness.py): the
# ReplicaDirectory's assignments swept against live store inventory()
# facts. Per-node gauges are {node=}-labeled, absent-not-zero, and
# retracted when the node leaves the directory.

# 1 = every owner region of this node is held by >= k live, fresh,
# crc-committed holders; 0 = at risk (the DIAG_DURABILITY verdict
# carries the evidence). Absent until the first sweep sees the node.
READINESS_COVERAGE = "dlrover_readiness_owner_coverage"
# how many steps the node's newest fully-held replica group trails its
# reported step (fresh means <= stale_factor x the master cadence)
READINESS_STALENESS = "dlrover_readiness_staleness_steps"
# the priced recovery ladder: predicted MTTR of rung {rung=} for node
# {node=}, seconds (calibrated decomposition, EMA-corrected against
# realized incidents)
READINESS_PREDICTED_MTTR = "dlrover_readiness_predicted_mttr_seconds"
# best survivable rung index for the node (0=live_reshard,
# 1=peer_rebuild, 2=storage_restore, 3=init)
READINESS_BEST_RUNG = "dlrover_readiness_best_rung"
# audit sweeps completed, and the wall seconds one sweep costs
READINESS_SWEEPS = "dlrover_readiness_sweeps_total"
READINESS_SWEEP_TIME = "dlrover_readiness_sweep_seconds"
# durability verdicts flagged by the auditor (clears ride the shared
# DIAG_RECOVERIES counter like every other diagnosis verdict)
DIAG_DURABILITY_FLAGS = "dlrover_diagnosis_durability_total"

# ReplicaDirectory admission facts as labeled gauges (previously
# event-only): per-holder assigned replica load and remaining budget
# headroom in MB ({node=}; headroom absent when the holder is
# uncapped), plus the plan-wide admitted k and how far below the
# requested k the budget degraded it
REPLICA_HOLDER_LOAD_MB = "dlrover_replica_holder_load_mb"
REPLICA_HOLDER_HEADROOM_MB = "dlrover_replica_holder_headroom_mb"
REPLICA_ASSIGNED_K = "dlrover_replica_assigned_k"
REPLICA_DEGRADED_K = "dlrover_replica_degraded_k"

# -- rpc client ---------------------------------------------------------------

# transient-RPC retries taken by the client channel (the retry budget
# spent): a synchronized burst after a master blip shows here first
RPC_RETRIES = "dlrover_rpc_retries_total"

# -- persistent XLA compile cache ---------------------------------------------

COMPILE_CACHE_HITS = "dlrover_compile_cache_hits_total"
COMPILE_CACHE_MISSES = "dlrover_compile_cache_misses_total"
COMPILE_CACHE_ENTRIES = "dlrover_compile_cache_entries"
# the compile ledger's four totals, one family: {phase=trace|lower|
# backend|cache_read} (utils/compile_cache.py)
COMPILE_SECONDS = "dlrover_compile_seconds_total"

# -- master reporting from the worker ----------------------------------------

MASTER_REPORTS = "dlrover_master_reports_total"
MASTER_REPORT_FAILURES = "dlrover_master_report_failures_total"

# -- checkpoint ---------------------------------------------------------------

CKPT_SAVES = "dlrover_checkpoint_saves_total"
CKPT_SNAPSHOT_SAVES = "dlrover_checkpoint_snapshot_saves_total"
CKPT_BLOCKING_SAVES = "dlrover_checkpoint_blocking_saves_total"
CKPT_DROPPED_SAVES = "dlrover_checkpoint_dropped_saves_total"
CKPT_SAVE_TIME = "dlrover_checkpoint_save_stage_seconds"
CKPT_MIRROR_TIME = "dlrover_checkpoint_mirror_seconds"
CKPT_MIRROR_TIMEOUTS = "dlrover_checkpoint_mirror_timeouts_total"
CKPT_RESTORE_TIME = "dlrover_checkpoint_restore_seconds"
CKPT_RESTORES = "dlrover_checkpoint_restores_total"

# -- agent --------------------------------------------------------------------

AGENT_WORKER_RESTARTS = "dlrover_agent_worker_restarts_total"
AGENT_HANG_DETECTIONS = "dlrover_agent_hang_detections_total"
AGENT_WORKER_FAILURES = "dlrover_agent_worker_failures_total"
RDZV_ROUNDS = "dlrover_rendezvous_rounds_total"
RDZV_TIME = "dlrover_rendezvous_seconds"

# -- master -------------------------------------------------------------------

MASTER_GLOBAL_STEP = "dlrover_master_global_step"
MASTER_TRAIN_SPEED = "dlrover_master_train_speed_steps_per_second"
MASTER_FAILURE_REPORTS = "dlrover_master_failure_reports_total"
MASTER_RUNTIME_SAMPLES = "dlrover_master_runtime_samples_total"

# -- diagnosis ----------------------------------------------------------------

ERROR_REPORTS = "dlrover_error_reports_total"
ERRORS_DEDUPED = "dlrover_error_reports_deduped_total"

# -- cluster diagnosis plane (per-node runtime series on the master) ----------

# worker-side: NodeRuntimeReport pushes sent / lost (the hook never
# raises into the train loop)
NODE_RUNTIME_REPORTS = "dlrover_node_runtime_reports_total"
NODE_RUNTIME_REPORT_FAILURES = "dlrover_node_runtime_report_failures_total"
# master-side per-node gauges (labeled {node="<id>"}), refreshed on
# every ingested report — the /metrics view of the node series
NODE_STEP_P50 = "dlrover_node_step_time_p50_seconds"
NODE_STEP_P95 = "dlrover_node_step_time_p95_seconds"
NODE_DISPATCH_P50 = "dlrover_node_dispatch_p50_seconds"
NODE_HOST_SYNC_P50 = "dlrover_node_host_sync_p50_seconds"
NODE_WINDOW_OCCUPANCY = "dlrover_node_dispatch_window_occupancy"
NODE_RSS_MB = "dlrover_node_rss_mb"
NODE_DEVICE_MEM_MB = "dlrover_node_device_mem_mb"
NODE_STEPS_TOTAL = "dlrover_node_steps_total"
NODE_REPORT_AGE = "dlrover_node_report_age_seconds"
# master-side ingest counter + verdict counters
NODE_REPORTS_INGESTED = "dlrover_master_node_reports_total"
DIAG_STRAGGLERS = "dlrover_diagnosis_stragglers_total"
DIAG_NODE_HANGS = "dlrover_diagnosis_node_hangs_total"
DIAG_RECOVERIES = "dlrover_diagnosis_recoveries_total"

# -- runtime optimizer (the telemetry -> planner -> live-reshard loop) --------

# re-plan passes run by the master-side optimizer (one per trigger that
# survived the cooldown gate: straggler verdict, recovery, world change)
OPTIMIZER_REPLANS = "dlrover_optimizer_replans_total"
# plans published to workers / suppressed by hysteresis-cooldown-dedup
OPTIMIZER_PLANS_CHOSEN = "dlrover_optimizer_plans_chosen_total"
OPTIMIZER_PLANS_REJECTED = "dlrover_optimizer_plans_rejected_total"
# calibration passes fitting the planner's cost terms to measured series
OPTIMIZER_CALIBRATIONS = "dlrover_optimizer_calibrations_total"
# worker-side: live plan applications (drain -> retune/reshard -> resume)
OPTIMIZER_PLANS_APPLIED = "dlrover_optimizer_plans_applied_total"
# wall seconds of one live plan application on the worker
OPTIMIZER_APPLY_TIME = "dlrover_optimizer_apply_seconds"
# candidate plans the memory-feasibility gate rejected BEFORE pricing
# (compiled/predicted peak HBM above the device budget)
OPTIMIZER_PLANS_MEMORY_REJECTED = (
    "dlrover_optimizer_plans_memory_rejected_total"
)

# -- performance attribution (device-time & HBM accounting) -------------------
# Derived from the per-compiled-program attribution record
# (telemetry.attribution: exact FLOPs / bytes-accessed / peak HBM read
# at compile time) fused with measured step times at materialization.
# Gauges are created ONLY once a record was captured — absent means
# "not measured", never 0.

# live model-FLOPs utilization: compiled per-device FLOPs/step over
# (measured step seconds x device peak) — utils/prof.derived_mfu
ATTR_MFU = "dlrover_attribution_mfu"
# compiled FLOPs / bytes-accessed: low values = HBM-bound on TPU
ATTR_ARITH_INTENSITY = "dlrover_attribution_arithmetic_intensity"
# clamped (1 - ideal compute seconds / measured step seconds): an
# UPPER bound on the un-overlapped communication share of the step
ATTR_EXPOSED_COMM_FRAC = "dlrover_attribution_exposed_comm_fraction"
# the static record, exported for scrape-side math
ATTR_FLOPS_PER_STEP = "dlrover_attribution_flops_per_step"
ATTR_PEAK_HBM_MB = "dlrover_attribution_compiled_peak_hbm_mb"
ATTR_COMM_PREDICTED_S = "dlrover_attribution_predicted_comm_seconds"
# device HBM headroom: bytes_limit - bytes_in_use where the backend
# exposes memory stats (absent on CPU — never a fake 0)
ATTR_HBM_HEADROOM_MB = "dlrover_attribution_hbm_headroom_mb"

# master-side per-node mirrors (labeled {node="<id>"}), fed by the
# NodeRuntimeReport push — the cluster view of the same quantities
NODE_MFU = "dlrover_node_mfu"
NODE_EXPOSED_COMM_FRAC = "dlrover_node_exposed_comm_fraction"
NODE_FLOPS_PER_STEP = "dlrover_node_flops_per_step"
NODE_PEAK_HBM_MB = "dlrover_node_compiled_peak_hbm_mb"
NODE_HBM_HEADROOM_MB = "dlrover_node_hbm_headroom_mb"

# -- data plane (shard dispatch & input pipeline) -----------------------------
# Worker side instruments the path batch data takes to the device
# (sharding client RPCs, the H2D prefetcher, the executor's wait for
# the next host batch); master side accounts the shard queues. The
# derived INPUT_WAIT_FRAC / NODE_INPUT_WAIT_FRAC gauges follow the
# absent-not-zero discipline of ATTR_MFU: no gauge exists before the
# first measured window, and per-dataset shard gauges exist only
# between the first dispatched shard and dataset completion.

# worker-side: ShardingClient (the master's todo/doing window)
DATA_SHARD_FETCH_TIME = "dlrover_data_shard_fetch_seconds"
DATA_SHARDS_FETCHED = "dlrover_data_shards_fetched_total"
DATA_SHARDS_COMPLETED = "dlrover_data_shards_completed_total"
# batch-done credits whose RPC failed and were re-queued for the next
# report (the credit is restored, never silently dropped)
DATA_BATCH_REPORT_RETRIES = "dlrover_data_batch_report_retries_total"
# worker-side: the H2D prefetcher (DevicePreloader / DevicePrefetcher)
DATA_PREFETCH_QUEUE_DEPTH = "dlrover_data_prefetch_queue_depth"
# producer wait: the pump blocked handing a ready batch to a full
# queue (consumer-slow — the healthy direction)
DATA_PRODUCER_WAIT_TIME = "dlrover_data_producer_wait_seconds"
# consumer wait: the train loop blocked on an empty prefetch queue
# (producer-slow — the input-bound direction)
DATA_CONSUMER_WAIT_TIME = "dlrover_data_consumer_wait_seconds"
# worker-side: executor host time blocked fetching the next batch
INPUT_WAIT_TIME = "dlrover_input_wait_seconds"
# fraction of the last materialization window spent waiting on input
# (absent until the first measured window — never a fake 0)
INPUT_WAIT_FRAC = "dlrover_input_wait_fraction"

# master-side shard lifecycle, labeled {dataset="<name>"} — created at
# the first dispatched shard, retracted when the dataset completes
DATA_SHARDS_TODO = "dlrover_data_shards_todo"
DATA_SHARDS_DOING = "dlrover_data_shards_doing"
DATA_SHARDS_DONE = "dlrover_data_shards_done"
DATA_EPOCH = "dlrover_data_epoch"
DATA_EPOCH_PROGRESS = "dlrover_data_epoch_progress"
# dispatch -> completion wall seconds of one shard
DATA_SHARD_LATENCY = "dlrover_data_shard_latency_seconds"
# shards requeued by the timeout monitor (straggler mitigation — each
# recovery risks duplicate data, so it is counted and evented)
DATA_SHARDS_TIMEOUT_RECOVERED = (
    "dlrover_data_shards_timeout_recovered_total"
)
# master-side per-node consumption, labeled {node="<id>"}
DATA_NODE_SHARDS_COMPLETED = "dlrover_data_node_shards_completed_total"
DATA_NODE_RECORDS_DONE = "dlrover_data_node_records_done_total"
# master-side per-node mirror of the worker's input-wait fraction
# (rides NodeRuntimeReport like NODE_MFU; absent until measured)
NODE_INPUT_WAIT_FRAC = "dlrover_node_input_wait_fraction"

# -- serving tier (dlrover_tpu.serving) ---------------------------------------
# Worker side: the continuous-batching decode loop; master side: the
# request router's ledger (the PR 9 shard ledger generalized).

# worker-side decode loop
SERVE_DECODE_STEPS = "dlrover_serve_decode_steps_total"
SERVE_TOKENS = "dlrover_serve_tokens_total"
SERVE_PREFILL_CHUNKS = "dlrover_serve_prefill_chunks_total"
SERVE_ADMISSIONS = "dlrover_serve_admissions_total"
SERVE_SLOT_OCCUPANCY = "dlrover_serve_slot_occupancy"
SERVE_STEP_TIME = "dlrover_serve_decode_step_seconds"
# worker-side elasticity: live serving-world resizes (requests held,
# never dropped)
SERVE_RESIZES = "dlrover_serve_resizes_total"
SERVE_RESIZE_TIME = "dlrover_serve_resize_seconds"
# master-side router ledger (requests, not shards)
SERVE_REQUESTS_SUBMITTED = "dlrover_serve_requests_submitted_total"
SERVE_REQUESTS_COMPLETED = "dlrover_serve_requests_completed_total"
SERVE_REQUESTS_QUEUED = "dlrover_serve_requests_queued"
SERVE_REQUESTS_LEASED = "dlrover_serve_requests_leased"
# requests DROPPED (lost without completion or re-lease): the resize
# wedge pins this at exactly zero
SERVE_REQUESTS_DROPPED = "dlrover_serve_requests_dropped_total"
# leases that expired and were re-queued to a live worker (the shard
# re-dispatch machinery re-pointed at requests — duplicate decode
# work, so counted and evented like DATA_SHARDS_TIMEOUT_RECOVERED)
SERVE_LEASES_EXPIRED = "dlrover_serve_leases_expired_total"
# per-request latency accounting on the master. The full SLO
# decomposition: queue-wait (enqueue -> lease), TTFT (admit -> first
# token), TPOT (inter-token: (e2e - ttft) / (tokens - 1)), e2e — all
# on the serving LATENCY_BUCKETS (sub-ms resolution; the seconds-scale
# DURATION_BUCKETS would flatten a decode-step-scale latency into its
# first bucket). SERVE_PREFILL_TIME is worker-side (admit -> prompt
# fully prefilled).
SERVE_TTFT_TIME = "dlrover_serve_ttft_seconds"
SERVE_E2E_TIME = "dlrover_serve_e2e_seconds"
SERVE_QUEUE_WAIT_TIME = "dlrover_serve_queue_wait_seconds"
SERVE_TPOT_TIME = "dlrover_serve_tpot_seconds"
SERVE_PREFILL_TIME = "dlrover_serve_prefill_seconds"
# tokens generated per completed request: a COUNT, not a duration —
# it takes explicit count-scale buckets (metrics.COUNT_BUCKETS); the
# registry refuses duration buckets on a non-``_seconds`` histogram
SERVE_TOKENS_PER_REQUEST = "dlrover_serve_tokens_per_request"
# worker-side shared prefix pool (radix-indexed KV reuse, copy-on-
# admit): hit/miss on admission, pages LRU-evicted from the pool,
# prefill tokens NOT recomputed because their pages were copied from
# the pool, and the pool occupancy gauges the HBM gate prices
SERVE_PREFIX_HITS = "dlrover_serve_prefix_hits_total"
SERVE_PREFIX_MISSES = "dlrover_serve_prefix_misses_total"
SERVE_PREFIX_EVICTIONS = "dlrover_serve_prefix_evictions_total"
SERVE_PREFIX_SAVED_TOKENS = "dlrover_serve_prefix_saved_prefill_tokens_total"
SERVE_PREFIX_POOL_USED_PAGES = "dlrover_serve_prefix_pool_used_pages"
SERVE_PREFIX_POOL_BYTES = "dlrover_serve_prefix_pool_bytes"
# master-side router: requests leased to the worker whose pool already
# holds their prefix pages (soft session affinity)
SERVE_PREFIX_AFFINITY_ROUTED = "dlrover_serve_prefix_affinity_routed_total"
# speculative decode (n-gram draft + batched multi-token verify):
# drafted = accepted + wasted at every grain — the conservation the
# router ledger checks. The accept-rate gauge is -1 until the first
# draft (no-evidence sentinel, mirrors the prefix hit-rate prior).
SERVE_SPEC_VERIFY_STEPS = "dlrover_serve_spec_verify_steps_total"
SERVE_SPEC_DRAFTED = "dlrover_serve_spec_drafted_tokens_total"
SERVE_SPEC_ACCEPTED = "dlrover_serve_spec_accepted_tokens_total"
SERVE_SPEC_WASTED = "dlrover_serve_spec_wasted_tokens_total"
SERVE_SPEC_ACCEPT_RATE = "dlrover_serve_spec_accept_rate"

# -- serving SLO plane (dlrover_tpu/serving/slo.py + master/monitor/
# serve_slo.py) ---------------------------------------------------------------
# master-side per-serve-node gauges (labeled {node="<id>"}), fed by
# the ServeRuntimeReportHook push through the NodeRuntimeReport path —
# the serving twin of the NODE_* training series
NODE_SERVE_DECODE_P50 = "dlrover_node_serve_decode_p50_seconds"
NODE_SERVE_DECODE_P95 = "dlrover_node_serve_decode_p95_seconds"
NODE_SERVE_TOKENS_PER_S = "dlrover_node_serve_tokens_per_second"
NODE_SERVE_SLOT_OCCUPANCY = "dlrover_node_serve_slot_occupancy"
NODE_SERVE_QUEUE_LEN = "dlrover_node_serve_queue_len"
NODE_SERVE_SLOTS = "dlrover_node_serve_slots"
NODE_SERVE_STEPS_TOTAL = "dlrover_node_serve_decode_steps_total"
NODE_SERVE_SPEC_ACCEPT_RATE = "dlrover_node_serve_spec_accept_rate"
# master-side SLO verdict engine: violations flagged / recovered after
# multi-window burn-rate confirmation, plus the current burn rate per
# declared target (labeled {slo="<target>"}; burn > 1 = out of SLO)
SERVE_SLO_VIOLATIONS = "dlrover_serve_slo_violations_total"
SERVE_SLO_RECOVERIES = "dlrover_serve_slo_recoveries_total"
SERVE_SLO_BURN_RATE = "dlrover_serve_slo_burn_rate"
# SLO/idle-driven serving scale proposals handed to the auto-scaler
SERVE_SCALE_PROPOSALS = "dlrover_serve_scale_proposals_total"


class EventKind:
    """Event-timeline record kinds (``telemetry.events``). Failure-edge
    kinds pair with recovery-edge kinds in the MTTR derivation
    (``telemetry.mttr``)."""

    # rendezvous lifecycle
    RDZV_JOIN = "rdzv_join"
    RDZV_COMPLETE = "rdzv_complete"
    RDZV_TIMEOUT = "rdzv_timeout"
    # scaling
    SCALE_PLAN_APPLIED = "scale_plan_applied"
    # live in-process recovery (failure edge -> recovery edge): the
    # world changed under a surviving process; drain + snapshot +
    # rebuild + reshard happen without a restart
    LIVE_RESHARD_BEGIN = "live_reshard_begin"
    LIVE_RESHARD_DONE = "live_reshard_done"
    # host-DRAM TrainState snapshot taken (the reshard/rollback source)
    STATE_SNAPSHOT = "state_snapshot"
    # agent chose to delegate a survivable membership change to the
    # workers' in-process reshard instead of restarting them
    LIVE_RESHARD_DELEGATED = "live_reshard_delegated"
    # peer-redundant host snapshots. PUSHED records a completed
    # replication cycle (step, peers, bytes); the failure-class edges
    # (DLR008: all carry error codes) mark a peer push that could not
    # land (dead peer / budget refusal), a budget-degraded plan, and a
    # holder dying mid-fetch (the fallback-to-next-replica edge).
    # PEER_REBUILD_BEGIN -> PEER_REBUILD_DONE bracket the checkpoint-
    # free recovery (the mttr "peer_rebuild" scenario);
    # PEER_REBUILD_FALLBACK is the terminal degradation to the
    # Orbax/mirror storage path.
    REPLICA_PUSHED = "replica_pushed"
    REPLICA_PUSH_FAILED = "replica_push_failed"
    REPLICA_PLAN_DEGRADED = "replica_plan_degraded"
    REPLICA_HOLDER_LOST = "replica_holder_lost"
    PEER_REBUILD_BEGIN = "peer_rebuild_begin"
    PEER_REBUILD_DONE = "peer_rebuild_done"
    PEER_REBUILD_FALLBACK = "peer_rebuild_fallback"
    # preemption (failure edge -> recovery edge)
    PREEMPT_NOTICE = "preempt_notice"
    PREEMPT_DRAIN_DONE = "preempt_drain_done"
    # checkpoint
    CKPT_SAVE = "ckpt_save"
    # the staging of a save (Orbax's device-to-host copy) returned, on
    # the saver thread or, for a blocking save, on the caller's
    CKPT_SAVE_STAGED = "ckpt_save_staged"
    # a snapshot save that ckpt_save announced wrote nothing: the saver
    # thread found its state not finite (ckpt_save is emitted before
    # any device value is read); no ckpt_save_staged follows
    CKPT_SAVE_DROPPED = "ckpt_save_dropped"
    CKPT_MIRROR = "ckpt_mirror"
    CKPT_MIRROR_TIMEOUT = "ckpt_mirror_timeout"
    CKPT_RESTORE = "ckpt_restore"
    # numerics (failure edge -> recovery edge)
    NONFINITE_STEP = "nonfinite_step"
    ROLLBACK_RESTORED = "rollback_restored"
    # agent lifecycle (failure edges -> WORKERS_STARTED recovery edge)
    HANG_DETECTED = "hang_detected"
    WORKER_FAILED = "worker_failed"
    AGENT_RESTART = "agent_restart"
    WORKERS_STARTED = "workers_started"
    # a worker's boot, each part where it happens: WORKER_BOOT from
    # init_worker (the process's start, the seconds of interpreter and
    # imports up to init_worker, of jax.distributed.initialize, of the
    # first jax.devices()), TRAINER_READY from ElasticTrainer.prepare
    # (the seconds of the user's script between the two, of
    # constructing the checkpoint manager, of building the program, of
    # restoring or initialising the state); CKPT_RESTORE, TRAIN_START
    # (the hooks' begin) and COMPILE_FIRST_STEP (the first step and its
    # parts) hold the rest. Each of the four carries the compile
    # ledger's totals so far under ``compile``: a phase's share is the
    # difference of two neighbours (docs/observability.md)
    WORKER_BOOT = "worker_boot"
    TRAINER_READY = "trainer_ready"
    # one profiling window of the executor closed: where the dump is,
    # its steps and wall-clock ends, the seconds start_trace and
    # stop_trace took, and the deltas of the loop's own counters
    # (dispatch, host sync, input wait, save) over exactly those steps
    PROFILE_WINDOW = "profile_window"
    # once a program and process, before the first ``profile_window``
    # of a window that program ran: which phase (forward, replay,
    # backward, optimizer) and which ``DeviceScope`` path each
    # instruction of the compiled step belongs to, so that a device
    # trace's seconds by instruction name become seconds by phase and
    # by scope (``telemetry.attribution.step_scope_table``)
    STEP_SCOPES = "step_scopes"
    # run lifecycle
    TRAIN_START = "train_start"
    TRAIN_END = "train_end"
    # first materialized step after TRAIN_START: its latency is the
    # trace+compile(+restore) cost — the goodput ledger's compile bucket
    COMPILE_FIRST_STEP = "compile_first_step"
    # diagnosis
    ERROR_REPORT = "error_report"
    # cluster diagnosis verdicts (master-side detector, evidence
    # attached: node p50/p95, peer median, ratio, confirm windows)
    DIAG_STRAGGLER = "diag_straggler"
    DIAG_NODE_HANG = "diag_node_hang"
    DIAG_RECOVERED = "diag_recovered"
    # recovery-readiness plane (master/monitor/readiness.py).
    # DIAG_DURABILITY (failure-class, DLR008) flags ONE node whose
    # owner regions fail the durability audit — coverage lost,
    # replicas stale past the cadence allowance, or budget-degraded k
    # — with the sweep's evidence attached; cleared by DIAG_RECOVERED
    # (was=durability) once a later sweep passes.
    # READINESS_DEGRADED -> READINESS_RESTORED bracket the CLUSTER
    # posture edge (any node at risk -> none), the mttr
    # "durability_at_risk" scenario. READINESS_SWEEP summarizes a
    # sweep's verdict table, emitted only when the posture changes.
    DIAG_DURABILITY = "diag_durability"
    READINESS_DEGRADED = "readiness_degraded"
    READINESS_RESTORED = "readiness_restored"
    READINESS_SWEEP = "readiness_sweep"
    # runtime optimization loop. Master side: one REPLAN per evaluated
    # trigger (candidate table attached), then CHOSEN (plan published to
    # workers) or REJECTED (hysteresis / cooldown-dedup / already
    # optimal); CALIBRATED records the predicted-vs-observed correction
    # factors each pass fits. Worker side: APPLY_BEGIN -> APPLY_DONE
    # bracket the live drain -> retune/reshard -> resume (the mttr
    # "replan" scenario pairs them), and APPLIED lands once the
    # post-plan window measured the realized speedup against the
    # decision's prediction.
    OPTIMIZER_REPLAN = "optimizer_replan"
    OPTIMIZER_CALIBRATED = "optimizer_calibrated"
    OPTIMIZER_PLAN_CHOSEN = "optimizer_plan_chosen"
    OPTIMIZER_PLAN_REJECTED = "optimizer_plan_rejected"
    OPTIMIZER_APPLY_BEGIN = "optimizer_apply_begin"
    OPTIMIZER_APPLY_DONE = "optimizer_apply_done"
    OPTIMIZER_APPLIED = "optimizer_applied"
    # performance attribution: one record per compiled program (exact
    # FLOPs, bytes-accessed, per-collective bytes, compiled peak HBM)
    # captured through the AOT path and keyed by the program cache —
    # the forensic source of `tpurun attribution --events`
    ATTRIBUTION_CAPTURED = "attribution_captured"
    # data plane: the master's timeout monitor requeued doing shards
    # of a slow/dead worker (failure-class: the shard will be re-read
    # — duplicate data risk — so the edge carries an error code), and
    # a dataset's epoch drained (todo and doing both empty; carries
    # the cumulative shard/record accounting — the forensic source of
    # `tpurun data --events`)
    DATA_SHARD_TIMEOUT = "data_shard_timeout"
    DATA_EPOCH_END = "data_epoch_end"
    # serving tier: run lifecycle, the live serving-world resize
    # (failure edge -> recovery edge for the serving_resize MTTR
    # scenario), and the failure-class request edges (eviction when a
    # request cannot fit the pool; a lease expiring on a dead worker
    # and re-queueing — both carry error codes, DLR008)
    SERVE_START = "serve_start"
    SERVE_END = "serve_end"
    SERVE_RESIZE_BEGIN = "serve_resize_begin"
    SERVE_RESIZE_DONE = "serve_resize_done"
    SERVE_REQUEST_EVICTED = "serve_request_evicted"
    SERVE_LEASE_EXPIRED = "serve_lease_expired"
    # per-request lifecycle (every record carries the request's trace
    # id, minted at Router.submit, so `tpurun trace --events` renders
    # one lane per request with flow arrows across the router and
    # worker pids): submitted/leased/completed on the router,
    # prefill-chunk/first-token/done on the worker
    SERVE_REQUEST_SUBMITTED = "serve_request_submitted"
    SERVE_REQUEST_LEASED = "serve_request_leased"
    SERVE_REQUEST_COMPLETED = "serve_request_completed"
    SERVE_PREFILL_CHUNK = "serve_prefill_chunk"
    SERVE_FIRST_TOKEN = "serve_first_token"
    SERVE_REQUEST_DONE = "serve_request_done"
    # shared prefix pool: a request admitted with matched pages copied
    # from the pool (carries hit_tokens — the prefill it skipped), and
    # a page LRU-evicted to make room for a publish. Both are INFO
    # edges of normal operation (a full pool degrades to miss-and-
    # prefill, never an error), so neither is DLR008 error-coded.
    SERVE_PREFIX_HIT = "serve_prefix_hit"
    SERVE_PREFIX_EVICTED = "serve_prefix_evicted"
    # serving SLO plane: a declared SLO target violated for the
    # confirmation windows (failure-class — carries an error code and
    # the burn-rate evidence; DLR008), its recovery, and the scale
    # proposal the policy loop hands the auto-scaler. VIOLATION ->
    # RECOVERED pairs into the mttr/goodput `serving_scale` scenario.
    SERVE_SLO_VIOLATION = "serve_slo_violation"
    SERVE_SLO_RECOVERED = "serve_slo_recovered"
    SERVE_SCALE_PROPOSED = "serve_scale_proposed"


class SpanName:
    """Names of the host spans (``telemetry.tracing``): each reads
    ``dlrover:<name>`` in a profiler trace."""

    STEP_DISPATCH = "step_dispatch"
    HOST_SYNC = "host_sync"
    # the train loop blocked in next() on its batch iterator
    INPUT_WAIT = "input_wait"
    # the trainer's save branch on a save step: the shard-checkpoint
    # RPC and the manager's save call (a snapshot save: the device copy
    # enqueued; a blocking one: the wait for the steps in flight and
    # ckpt_save_stage nested in it)
    CKPT_SAVE = "ckpt_save"
    LIVE_RESHARD = "live_reshard"
    STATE_SNAPSHOT = "state_snapshot"
    # Orbax's device-to-host copy of a save, on the thread that stages
    # (the saver thread for a snapshot save)
    CKPT_SAVE_STAGE = "ckpt_save_stage"
    CKPT_MIRROR = "ckpt_mirror"
    CKPT_RESTORE = "ckpt_restore"
    RENDEZVOUS = "rendezvous"
    EVALUATE = "evaluate"
    RPC = "rpc"  # prefix; full name is "rpc.<MessageType>"
    # serving: host spans on the worker (decode dispatch, prefill
    # chunk) and router (lease/complete handling) pids
    SERVE_DECODE = "serve_decode_step"
    SERVE_PREFILL = "serve_prefill_chunk"
    SERVE_LEASE = "serve_lease"
    SERVE_COMPLETE = "serve_complete"


class DeviceScope:
    """Names of the ``jax.named_scope``s a model puts around its parts:
    a device trace shows every operation of a part under its name, and
    ``telemetry.attribution.step_scope_table`` reads them off the
    compiled step's ``op_name``s (``ALL`` is the one list it knows)."""

    # a dense rotary decoder's attention sublayer (``models/llama.py``:
    # projections, rotary and the flash kernels), and, in
    # ``models/sambay.py``, the attention of a self-decoder period by
    # its kind (over a window, or the boundary pair's over everything)
    # and of a cross-decoder period (over the boundary pair's keys)
    ATTENTION = "attention"
    ATTENTION_WINDOW = "attention_window"
    ATTENTION_FULL = "attention_full"
    ATTENTION_CROSS = "attention_cross"
    # the same model's state-space mixer (projections, convolution and
    # the ``ssm_scan_*`` kernels) and the gated memory unit that reads
    # its memory in the cross-decoder
    SSM = "ssm"
    GMU = "gmu"
    # multi-head latent attention: projections, norms, rotary and the
    # ``flash_mla_*`` kernels
    MLA = "mla"
    # the same attention's output gate (a sigmoid of the layer's normed
    # input a head and value column, times the attention's output before
    # ``W_o``), and a gated norm's low-rank sigmoid gate on the normed
    # vector (``models/mla_moe.py``: ``attn_output_gate``,
    # ``gated_norm_rank``)
    ATTN_GATE = "attn_gate"
    GATED_NORM = "gated_norm"
    # grouped differential latent attention (``models/mla_moe.py``
    # ``num_noise_heads``): lambda's projection and sigmoid, a group's
    # noise head times lambda taken from its signal heads, and their
    # transposes
    ATTN_DIFF = "attn_diff"
    # PolyNorm, the activation of the same model's FFNs
    # (``ffn_activation`` ``poly_norm``): the three normalised powers of
    # a gate row and their weighted sum, forward and backward, in the
    # dense FFN, the shared expert and the held experts' gate stage
    POLYNORM = "polynorm"
    # the router's selection bias moved by the step from the experts'
    # load (``ops.moe.selection_bias_update``; ``router_bias_rate``)
    ROUTER_BIAS = "router_bias"
    # grouped-query attention of a model whose layers are of two kinds
    # (``models/gqa_moe.py``), by the layer's kind: projections, rotary
    # where the layer has it, and the ``flash_*`` kernels of a full
    # layer or the ``flash_win_*`` kernels of a window layer
    ATTN_FULL = "attn_full"
    ATTN_WINDOW = "attn_window"
    # the same model's third kind of layer, attention over the keys a
    # learned indexer selects for each query
    # (``ops/sparse_attention.py``): the main projections, norms and
    # rotary and the ``dsa_attn_*`` kernels; and the indexer beside it:
    # its projections and rotary, the selection and the indexer's loss
    # (the ``dsa_index_*`` kernels)
    ATTN_SPARSE = "attn_sparse"
    DSA_INDEX = "dsa_index"
    # a gated-delta-rule linear-attention layer's mixer
    # (``models/delta_hybrid.py``): projections, convolutions, norms,
    # gates and the ``gdn_*`` kernels; and inside it what XLA does of
    # the rule in ``ops/gated_delta.py``'s two steps (the chunk-local
    # preparation and its backward: nothing in a model's program since
    # the ``gdn_rule_*`` kernels prepare a chunk in VMEM)
    GDN = "gdn"
    GDN_CHUNK = "gdn_chunk"
    # a Mamba-2 mixer (``models/ssd_hybrid.py``): ``W_in``, the
    # convolution and SiLU, ``dt``, the ``ssd_*`` kernels, the gated
    # norm and ``W_out``; and inside it what XLA still does of the
    # recurrence around the kernels (``ops/ssd.py``: ``dt A`` and its
    # cumulative sums, the row forms, the partial sums' addition)
    SSD = "ssd"
    SSD_CHUNK = "ssd_chunk"
    # a Kimi-delta-attention mixer (``models/kda_mla_moe.py``):
    # projections, convolutions, the bounded per-channel gate, the
    # ``kda_*`` kernels, the gated norm a head and ``W_o``; and inside
    # it what XLA still does of the rule (``ops/kda.py``: the
    # chunk-local preparation under the diagonal decay and its backward)
    KDA = "kda"
    KDA_CHUNK = "kda_chunk"
    # an expert layer's router (scores, top-k, balance loss), its
    # shared expert, and its routed experts (gather, ``gmm`` kernels,
    # combine)
    MOE_ROUTER = "moe_router"
    MOE_SHARED = "moe_shared"
    MOE_EXPERTS = "moe_experts"
    # inside the router, what a group-limited selection adds: the
    # groups' marks and the choice of groups (``ops.moe.top_groups``)
    MOE_GROUPS = "moe_groups"
    FFN = "ffn"
    # hyper-connections (``ops/hyper_connections.py``): the three
    # mappings of a sublayer (norm over the streams, projection,
    # Sinkhorn iteration), and its two stream mixes
    HC_MAP = "hc_map"
    HC_MIX = "hc_mix"
    # a multi-token-prediction module: its projection, its layer and
    # its pass of the head and loss
    MTP = "mtp"
    # a looped model's exit gate (``models/looped.py``): the gate's
    # projection of every pass's normed state, the exit distribution
    # over the passes, its entropy and the means the counters carry
    # (the distribution is the head's weights)
    EXIT_GATE = "exit_gate"
    # the head and its loss (``models/losses.py``): the chunked
    # projection to the vocabulary and the cross entropy, in
    # ``chunked_lm_head_loss`` with their own checkpoint's replay, in
    # ``one_pass_lm_head_loss`` with the two gradients' products made
    # in the forward rule and their scaling in the backward rule, in
    # ``weighted_lm_head_loss`` the same over every pass of a looped
    # model under the exit distribution's weights
    HEAD_LOSS = "head_loss"

    ALL = (ATTENTION, ATTENTION_WINDOW, ATTENTION_FULL, ATTENTION_CROSS,
           SSM, GMU, MLA, ATTN_GATE, GATED_NORM, ATTN_DIFF, POLYNORM,
           ROUTER_BIAS, ATTN_FULL, ATTN_WINDOW,
           ATTN_SPARSE, DSA_INDEX, GDN, GDN_CHUNK, SSD, SSD_CHUNK, KDA,
           KDA_CHUNK, MOE_ROUTER,
           MOE_SHARED, MOE_EXPERTS, MOE_GROUPS, FFN, HC_MAP, HC_MIX, MTP,
           EXIT_GATE, HEAD_LOSS)


class StepCounter:
    """Counters a loss function returns in its aux, a value a step: the
    executor sums the ones named here over the steps of a profiling
    window into the ``profile_window`` event's ``step_counters``."""

    # expert layers that hold a set of the routed experts
    # (``models/mla_moe.py``, ``models/gqa_moe.py``), summed over the
    # layers: assignments
    # routed to held experts, the fullest held expert's, those that
    # fell past the static row bound, and the rows of the buffer the
    # layer computed on (the rung of ``ops.moe.held_row_ladder`` it
    # ran: over layers x steps the rung taken, and ``moe_rows_held``
    # over it the share of computed rows that are real)
    MOE_ROWS_HELD = "moe_rows_held"
    MOE_ROWS_MAX = "moe_rows_max"
    MOE_ROWS_DROPPED = "moe_rows_dropped"
    MOE_ROWS_BUFFERED = "moe_rows_buffered"
    # a router that limits a token to some groups of experts
    # (``ops.moe.group_limited_routing``), summed over the expert
    # layers: the tokens whose kept groups hold a group of an expert
    # held here (only those can send this chip a row), and the tokens
    # counted; their ratio is ``topk_group / n_group`` where the groups
    # are chosen evenly and a chip's experts lie in one group
    MOE_GROUP_REACH = "moe_group_reach"
    MOE_GROUP_TOKENS = "moe_group_tokens"
    # a model whose residual is hyper-connected streams: a step's mean,
    # over tokens and sublayers, of the largest ``|row or column sum -
    # 1|`` of ``H_res`` (what the Sinkhorn iterations left)
    HC_RES_DEFECT = "hc_res_defect"
    # the same model: the hyper-connected sublayers of a step that the
    # ``hc_enter_*`` / ``hc_leave_*`` kernels ran, counted where the
    # path is chosen (``ops.hyper_connections.connect``): every one
    # where the streams' width is whole lanes and a token tile fits
    # VMEM, 0 where the ``jax.numpy`` functions ran
    HC_KERNEL_PASSES = "hc_kernel_passes"
    # a model with a multi-token-prediction module: that module's
    # loss before its weight
    MTP_LOSS = "mtp_loss"
    # a model with grouped differential attention: a step's mean lambda
    # over tokens, signal heads and layers
    DIFF_LAMBDA_MEAN = "diff_lambda_mean"
    # a model whose step moves its router's selection bias: the mean
    # ``|bias|`` over experts and expert layers after the step's update
    # (it grows by at most the rate a step from its start at 0)
    ROUTER_BIAS_ABS = "router_bias_abs"
    # a model with window attention layers on the flash kernels
    # (``models/gqa_moe.py``, ``models/sambay.py``, and the latent
    # band of ``models/mla_moe.py``): tiles of the band
    # the window forward visits, over batch, heads and window layers,
    # and those of them that ran the body without a mask
    # (``ops.flash_attention.band_walk``); constants of the shapes
    ATTN_BAND_TILES = "attn_band_tiles"
    ATTN_BAND_TILES_UNMASKED = "attn_band_tiles_unmasked"
    # a model with gated-delta-rule layers (``models/delta_hybrid.py``):
    # a step's mean over linear layers, tokens and heads of ``beta >
    # 1``, the share of updates whose transition has a negative
    # eigenvalue; 0 exactly where ``linear_allow_neg_eigval`` is off
    GDN_NEG_EIG = "gdn_neg_eig"
    # a model with Mamba-2 layers (``models/ssd_hybrid.py``): a step's
    # mean of ``dt`` over Mamba layers, tokens and heads; ``softplus(0)``
    # = 0.693 where ``dt_bias`` is left out at small weights, a few
    # hundredths where the published parametrisation ran at its
    # initialisation
    SSD_DT_MEAN = "ssd_dt_mean"
    # a model with Kimi-delta-attention layers
    # (``models/kda_mla_moe.py``): a step's mean of the log decay ``g``
    # over KDA layers, tokens, heads and key channels; inside
    # ``(kda_lower_bound, 0)`` where the bounded per-channel gate ran
    KDA_LOG_DECAY_MEAN = "kda_log_decay_mean"
    # a model whose stack runs several times a step with an exit after
    # each pass (``models/looped.py``), a step's means over the unmasked
    # tokens: the entropy of the exit distribution over the passes (0
    # to ``ln T``), the expected exit pass ``sum_t t p_t`` (1 to ``T``),
    # and the first and the last pass's own cross entropy before their
    # weights (their difference is what the later passes gain)
    LOOP_EXIT_ENTROPY = "loop_exit_entropy"
    LOOP_EXIT_MEAN_PASS = "loop_exit_mean_pass"
    LOOP_LOSS_FIRST = "loop_loss_first"
    LOOP_LOSS_LAST = "loop_loss_last"
    # a model with learned sparse attention layers
    # (``models/gqa_moe.py``, ``ops/sparse_attention.py``), summed over
    # those layers: the (query, key) pairs the indexer selected and the
    # causal pairs they were chosen from (a query's, not a head's); the
    # tiles the forward kernel visited and the causal tiles it skipped
    # because no pair of them was selected, over batch and heads; and
    # the indexer's loss, nats a query, summed over the layers; and the
    # bytes of the selected attention's output and logsumexp that the
    # sparse layers' checkpoints keep so that the replay leaves
    # ``dsa_attn_fwd`` out, counted where the path is chosen
    # (``gqa_moe.apply_hidden``): shape arithmetic, 0 with no remat;
    # and, counted beside it, the bytes of the three gradients of the
    # indexer's loss (to its queries, its key head and its weights) that
    # the same checkpoints keep from the one ``dsa_index_kl`` call of
    # the forward pass, so that neither the replay nor the backward
    # pass runs that kernel
    DSA_PAIRS_SELECTED = "dsa_pairs_selected"
    DSA_PAIRS_CAUSAL = "dsa_pairs_causal"
    DSA_TILES_VISITED = "dsa_tiles_visited"
    DSA_TILES_SKIPPED = "dsa_tiles_skipped"
    DSA_INDEX_KL = "dsa_index_kl"
    DSA_ATTN_KEPT_BYTES = "dsa_attn_kept_bytes"
    DSA_INDEX_KEPT_BYTES = "dsa_index_kept_bytes"
    # a model with full, window or latent layers under their own
    # checkpoints (``models/gqa_moe.py``; ``models/mla_moe.py`` with no
    # indexer, its prediction modules' layers too): the bytes of the
    # flash kernels' output and logsumexp
    # (``ops.flash_attention.KEPT_NAMES``) that those checkpoints keep
    # so that the replay leaves ``flash_fwd``, ``flash_win_fwd``,
    # ``flash_mla_fwd`` and ``flash_mla_win_fwd`` out, counted where the
    # path is chosen (``gqa_moe.apply_hidden``, ``mla_moe``'s loss):
    # shape arithmetic, 0 with no remat and with XLA's dense forms
    ATTN_KEPT_BYTES = "attn_kept_bytes"

    ALL = (MOE_ROWS_HELD, MOE_ROWS_MAX, MOE_ROWS_DROPPED,
           MOE_ROWS_BUFFERED, MOE_GROUP_REACH, MOE_GROUP_TOKENS,
           HC_RES_DEFECT, HC_KERNEL_PASSES, MTP_LOSS, DIFF_LAMBDA_MEAN,
           ROUTER_BIAS_ABS, ATTN_BAND_TILES, ATTN_BAND_TILES_UNMASKED, GDN_NEG_EIG,
           SSD_DT_MEAN, KDA_LOG_DECAY_MEAN, LOOP_EXIT_ENTROPY,
           LOOP_EXIT_MEAN_PASS, LOOP_LOSS_FIRST, LOOP_LOSS_LAST,
           DSA_PAIRS_SELECTED, DSA_PAIRS_CAUSAL, DSA_TILES_VISITED,
           DSA_TILES_SKIPPED, DSA_INDEX_KL, DSA_ATTN_KEPT_BYTES,
           DSA_INDEX_KEPT_BYTES, ATTN_KEPT_BYTES)
