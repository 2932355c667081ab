"""Wire messages between agents and the master.

Role parity: ``dlrover/proto/elastic_training.proto`` (~30 rpcs). Every
message here is a registered dataclass (see ``serialize.message``); the
master exposes exactly two unary rpcs — ``get`` (query) and ``report``
(fire-and-forget-ish state push) — and dispatches on message type, which is
the shape the reference's servicer converges to as well.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from dlrover_tpu.common.serialize import message

# --------------------------------------------------------------------------
# envelope
# --------------------------------------------------------------------------


@message
class BaseRequest:
    node_id: int = -1
    node_type: str = ""


@message
class Response:
    success: bool = True
    reason: str = ""
    data: Optional[object] = None


# --------------------------------------------------------------------------
# data sharding
# --------------------------------------------------------------------------


@message
class DatasetShardParams:
    """Registers a dataset with the master's task manager."""

    dataset_name: str = ""
    dataset_size: int = 0
    batch_size: int = 0
    num_epochs: int = 1
    shuffle: bool = False
    num_minibatches_per_shard: int = 2
    storage_type: str = "table"  # table | text | stream
    task_type: str = "training"  # training | evaluation


@message
class TaskRequest:
    dataset_name: str = ""
    node_id: int = -1


@message
class Shard:
    name: str = ""
    start: int = 0
    end: int = 0
    record_indices: Optional[List[int]] = None


@message
class Task:
    task_id: int = -1
    task_type: str = ""
    shard: Optional[Shard] = None
    epoch: int = 0

    @property
    def exists(self) -> bool:
        return self.task_id >= 0


@message
class TaskResult:
    dataset_name: str = ""
    task_id: int = -1
    err_message: str = ""
    node_id: int = -1


@message
class BatchDoneReport:
    dataset_name: str = ""
    node_id: int = -1
    record_count: int = 0


@message
class ShardCheckpointRequest:
    dataset_name: str = ""


@message
class ShardCheckpoint:
    dataset_name: str = ""
    content: str = ""  # JSON blob owned by the dataset manager


# --------------------------------------------------------------------------
# rendezvous
# --------------------------------------------------------------------------


@message
class RendezvousParams:
    """Pushed once by node rank 0 before joining."""

    min_nodes: int = 1
    max_nodes: int = 1
    waiting_timeout: float = 30.0
    node_unit: int = 1  # world size must be a multiple (TPU slice hosts)
    rdzv_name: str = ""


@message
class JoinRendezvousRequest:
    node_rank: int = -1
    local_world_size: int = 1
    rdzv_name: str = ""
    node_id: int = -1
    slice_index: int = 0
    addr: str = ""  # host addr usable as jax.distributed coordinator


@message
class CommWorldRequest:
    rdzv_name: str = ""
    node_rank: int = -1


@message
class CommWorld:
    """The agreed world for one rendezvous round.

    ``world`` maps node_rank -> local_world_size (number of JAX processes the
    host will start). ``coordinator_addr`` is the jax.distributed coordinator
    (host of the smallest participating node rank) — the TPU analogue of the
    reference handing out the c10d store address.
    """

    rdzv_name: str = ""
    round: int = 0
    group: int = 0
    world: Optional[Dict[int, int]] = None
    coordinator_addr: str = ""


@message
class WaitingNodeNumRequest:
    rdzv_name: str = ""


@message
class NetworkReadyRequest:
    pass


@message
class NetworkCheckResult:
    node_rank: int = -1
    normal: bool = True
    elapsed_time: float = 0.0


@message
class StragglerExistRequest:
    pass


@message
class AbnormalNodesRequest:
    pass


@message
class NodeRankList:
    ranks: Optional[List[int]] = None
    # master-clock timestamp of the response: pollers reuse it as the
    # next window start so cross-host clock skew can't drop records
    server_time: float = 0.0


@message
class RendezvousState:
    round: int = 0
    waiting_num: int = 0


# --------------------------------------------------------------------------
# kv store / sync
# --------------------------------------------------------------------------


@message
class KVStoreSetRequest:
    key: str = ""
    value: str = ""  # base64 when binary


@message
class KVStoreGetRequest:
    key: str = ""


@message
class KVStoreValue:
    key: str = ""
    value: str = ""
    found: bool = False


@message
class KVStoreAddRequest:
    key: str = ""
    amount: int = 0


@message
class SyncJoinRequest:
    sync_name: str = ""
    node_rank: int = -1


@message
class SyncFinishRequest:
    sync_name: str = ""


@message
class BarrierRequest:
    barrier_name: str = ""
    notify: bool = False


# --------------------------------------------------------------------------
# failures / monitoring
# --------------------------------------------------------------------------


@message
class FailedNodesRequest:
    """Query node ids with hard failures since a timestamp (the engine's
    dead-rank watcher polls this instead of waiting out task timeouts)."""

    since_timestamp: float = 0.0


@message
class NodeFailure:
    node_id: int = -1
    node_rank: int = -1
    restart_count: int = 0
    error_data: str = ""
    level: str = "process"  # TrainingExceptionLevel


@message
class ResourceStats:
    node_id: int = -1
    node_type: str = ""
    cpu_percent: float = 0.0
    memory_mb: int = 0
    chips: int = 0
    duty_cycle: float = 0.0  # accelerator busy fraction, if known


@message
class GlobalStep:
    step: int = 0
    timestamp: float = 0.0
    elapsed_time_per_step: float = 0.0
    # True when the reported step REWINDS the truth (non-finite
    # rollback, live reshard resuming from a snapshot): the master's
    # monotone max() gauge and speed window must reset, not ignore it
    reset: bool = False


@message
class NodeRuntimeReport:
    """Node-tagged snapshot of the worker's runtime instruments
    (cumulative histogram bucket counts — the master diffs consecutive
    reports into per-window series; see master/monitor/node_series.py).
    """

    node_id: int = -1
    node_type: str = "worker"
    timestamp: float = 0.0
    step: int = 0
    steps_total: float = 0.0
    # shared bucket bounds (+Inf bucket is the extra last count)
    bounds: Optional[List[float]] = None
    step_time_counts: Optional[List[int]] = None
    dispatch_counts: Optional[List[int]] = None
    host_sync_counts: Optional[List[int]] = None
    window_occupancy: float = 0.0
    lagged_age: float = 0.0
    rss_mb: float = 0.0
    # None = the backend exposes no memory stats (CPU): the master must
    # report the gauge ABSENT, never a fake 0
    device_mem_mb: Optional[float] = None
    hbm_headroom_mb: Optional[float] = None
    # performance-attribution derived gauges (None until the worker
    # captured a per-program attribution record)
    mfu: Optional[float] = None
    exposed_comm_frac: Optional[float] = None
    flops_per_step: Optional[float] = None
    peak_hbm_mb: Optional[float] = None
    # data plane: fraction of the worker's last materialization window
    # spent blocked waiting for the next host batch (None until the
    # executor measured a window — absent, never a fake 0)
    input_wait_frac: Optional[float] = None
    # serving tier (reports with node_type="serve", pushed by
    # ServeRuntimeReportHook): ``step_time_counts`` carries the
    # cumulative DECODE-step histogram and ``steps_total`` the decode
    # steps; these fields carry the serving-only facts. None on
    # training reports — the master exports the serve gauges only for
    # serve nodes.
    serve_tokens_total: Optional[float] = None
    serve_queue_len: Optional[float] = None
    serve_slot_occupancy: Optional[float] = None
    serve_slots: Optional[float] = None
    # speculative decode: cumulative drafted/accepted totals — the
    # master diffs consecutive reports into a windowed acceptance-rate
    # gauge (None while K=0 or on training reports)
    serve_spec_drafted_total: Optional[float] = None
    serve_spec_accepted_total: Optional[float] = None


@message
class AttributionRequest:
    """Query the master's performance-attribution view: per-node
    derived MFU / exposed-comm / HBM gauges from the node series plus
    the optimizer's memory-feasibility rejections (the ``tpurun
    attribution --addr`` view). Answered with a DiagnosisReport-style
    JSON blob."""

    node_id: int = -1
    limit: int = 0  # 0 = every retained memory rejection


@message
class DataShardRequest:
    """Query the master's shard-dispatch ledger: per-dataset
    todo/doing/done queues, epoch progress + ETA, timeout recoveries
    and per-node consumption rates (the ``tpurun data --addr`` view).
    Answered with a DiagnosisReport-style JSON blob."""

    dataset_name: str = ""  # "" = every registered dataset


@message
class DiagnosisRequest:
    """Query the master's cluster diagnosis: node series summaries plus
    straggler/hang verdicts (node_id -1 = whole cluster)."""

    node_id: int = -1


@message
class DiagnosisReport:
    # JSON blob (nodes, verdicts, stragglers, hung) — the diagnosis
    # schema is owned by master/monitor, not the wire layer
    report_json: str = ""


@message
class NodeHeartbeat:
    node_id: int = -1
    timestamp: float = 0.0


@message
class NodeStatusReport:
    node_id: int = -1
    node_type: str = ""
    status: str = ""


@message
class DatasetMetric:
    dataset_name: str = ""
    dataset_size: int = 0
    storage_type: str = ""


@message
class ModelInfo:
    num_params: int = 0
    flops_per_step: float = 0.0
    hidden_size: int = 0
    num_layers: int = 0
    seq_len: int = 0
    # MoE shape: lets the runtime optimizer's calibrated ModelSpec
    # price the dispatch-comm terms (and enumerate dispatch_chunks)
    # instead of seeing a dense model
    num_experts: int = 0
    moe_top_k: int = 1
    ffn_mult: float = 0.0  # intermediate/hidden (0 = spec default)


@message
class ParallelConfig:
    """Mesh/partition decisions the master can push to agents at runtime.

    The runtime optimizer (``master/optimizer``) publishes its chosen
    plans through this message: a non-empty ``plan_id`` marks an
    optimizer plan, and workers polling ``get_parallel_config``
    (``OptimizerPlanHook``) apply it LIVE — ``restart=False`` means
    drain the window and retune/reshard in place; sentinel values
    (``train_window=-1``, ``dispatch_chunks=0``) leave a knob unchanged.
    """

    mesh_shape: Optional[Dict[str, int]] = None
    remat_policy: str = ""
    grad_accum_steps: int = 1
    restart: bool = False
    # -1 / 0 / "" = leave the knob as the worker currently runs it
    train_window: int = -1
    moe_dispatch: str = ""
    # grouped_ep chunked dispatch degree (0 = leave unchanged): a
    # COMPILED-program knob, applied through the same prewarmed
    # program-cache swap as mesh overrides
    dispatch_chunks: int = 0
    # grouped_ep wire precision ("" = leave unchanged; "bf16"/"fp8"):
    # the same prewarmed program-cache swap contract as dispatch_chunks
    moe_precision: str = ""
    # dense FSDP gather wire precision ("" = leave unchanged;
    # "bf16"/"fp8"): the same prewarmed program-cache swap contract —
    # a backend whose fp8 probe fails negative-acks the plan
    fsdp_precision: str = ""
    # serving-tier knobs (0 = leave unchanged): the continuous-batching
    # slot width and the prefill chunk, applied by serve workers through
    # the SAME prewarmed program-cache swap as the training knobs
    serve_slots: int = 0
    serve_prefill_chunk: int = 0
    # shared prefix pool pages. 0 is a REAL value here (pool off), so
    # the leave-unchanged sentinel is -1, unlike its 0-sentinel siblings
    serve_prefix_pool_pages: int = -1
    # speculative draft length K. 0 is a REAL value (spec off), so the
    # leave-unchanged sentinel is -1 like the pool knob
    serve_spec_draft_len: int = -1
    # optimizer decision identity: the worker echoes plan_id back in its
    # TrainerConfigReport ack, and every OPTIMIZER_* event on both sides
    # carries trace_id so the decision trail merges per incident
    plan_id: str = ""
    trace_id: str = ""
    predicted_speedup: float = 0.0
    # standby-compile the candidate program before swapping, so the swap
    # itself pays zero recompiles (ElasticTrainer.prewarm)
    prewarm: bool = True


@message
class ParallelConfigRequest:
    node_id: int = -1


@message
class TrainerConfigReport:
    """Worker -> master: the config the trainer is ACTUALLY running —
    the runtime optimizer's running-config input (sent at train start
    and after every live reshard/retune). A non-empty ``plan_id`` acks
    an applied optimizer plan, carrying the realized speedup the
    post-apply window measured."""

    node_id: int = -1
    world: int = 0  # devices in the active mesh
    mesh_shape: Optional[Dict[str, int]] = None
    train_window: int = 0
    moe_dispatch: str = ""
    # the grouped_ep chunk degree this worker actually runs (0 = not
    # reported / not applicable)
    dispatch_chunks: int = 0
    # the grouped_ep wire precision this worker actually runs ("" =
    # not reported / not applicable)
    moe_precision: str = ""
    # the dense FSDP gather wire precision this worker actually runs
    # ("" = not reported): what unlocks the optimizer's fsdp_precision
    # knob family — always known for a trainer-managed job
    fsdp_precision: str = ""
    # the gradient-path precision (error-feedback residual) this worker
    # was BUILT with — reported for observability; never enumerated by
    # the optimizer (the residual is TrainState structure)
    grad_precision: str = ""
    global_batch: int = 0
    plan_id: str = ""
    predicted_speedup: float = 0.0
    realized_speedup: float = 0.0
    # negative ack: the plan could not be applied (rebuild failed, or
    # the knobs are unsupported on this deployment) — the optimizer
    # blacklists the knob tuple instead of re-proposing it forever
    apply_failed: bool = False


# --------------------------------------------------------------------------
# peer-redundant host snapshots (checkpoint-free pod-scale recovery)
# --------------------------------------------------------------------------


@message
class ReplicaEndpointReport:
    """Worker -> master: this node serves a replica store at ``addr``.

    Re-reported on every push cycle so the master's ReplicaDirectory
    tracks liveness and snapshot freshness without a second heartbeat
    channel. ``budget_mb`` is the host-DRAM budget this node grants to
    PEER replicas (the admission input of the replica plan);
    ``snapshot_mb`` the size of one full snapshot on this node (the
    numerator of the per-owner share the plan prices)."""

    node_id: int = -1
    addr: str = ""
    budget_mb: float = 0.0
    snapshot_mb: float = 0.0
    step: int = -1  # newest replicated (committed) step, -1 = none yet
    timestamp: float = 0.0
    # last completed push cycle's wall seconds / bytes shipped: the
    # readiness auditor's continuous link-bandwidth calibration (a push
    # streams exactly the bytes a rebuild fetches back, over the same
    # RPC path). 0 = no completed cycle yet.
    push_seconds: float = 0.0
    push_bytes: float = 0.0


@message
class ReplicaPlanRequest:
    """Worker -> master: which peers should hold my snapshot regions?"""

    node_id: int = -1


@message
class ReplicaPlan:
    """The master-chosen, rendezvous-stable peer assignment for one
    owner. ``replicas`` may be below the configured k when the budget
    pricing degraded the plan (``degraded``/``reason`` say why) — an
    infeasible plan ships fewer replicas, never an OOM."""

    owner: int = -1
    peers: Optional[List[Dict]] = None  # [{"node_id": int, "addr": str}]
    replicas: int = 0
    requested: int = 0
    # the FULL live owner group the byte partition is computed over —
    # every owner must slice against the same group or the per-owner
    # regions cannot reassemble (k < n-1 means peers ⊂ group)
    group: Optional[List[int]] = None
    # MASTER-computed effective cadence in steps (0 = master has no
    # step-time series yet; workers fall back to their local knob +
    # wall floor). One value for the whole cluster: per-node wall
    # floors drift nodes onto disjoint push-step schedules, and a
    # rebuild needs ONE step with full owner coverage.
    cadence_steps: int = 0
    degraded: bool = False
    reason: str = ""


@message
class RecoveryPlanRequest:
    """Rebuilding worker -> master: map every owner's snapshot regions
    to live replica holders (answered with a DiagnosisReport JSON
    blob: {"owners": {owner: [endpoints...]}, "replicas": k,
    "predicted_mttr": {rung: seconds} — the priced recovery ladder
    the worker's rung choice consults)."""

    node_id: int = -1


@message
class ReadinessRequest:
    """Operator/CLI -> master: the recovery-readiness report — the
    durability audit's posture, per-node blast-radius verdicts and
    predicted-MTTR-per-rung table, and the pricer's calibration state
    (answered with a DiagnosisReport JSON blob; `tpurun readiness`'s
    live view)."""

    node_id: int = -1


@message
class ReplicaPut:
    """One length-prefixed, checksummed snapshot chunk (or the commit
    manifest that seals a step) pushed peer-to-peer into a holder's
    ReplicaStore. ``frame`` is the base64 chunk frame
    (``checkpoint.replication.encode_chunk``)."""

    node_id: int = -1  # the PUSHING node (the region owner)
    frame: str = ""


@message
class ReplicaFetchRequest:
    """Fetch one stored chunk of a committed snapshot from a holder."""

    owner: int = -1
    step: int = -1
    leaf: int = -1
    seq: int = 0


@message
class ReplicaFrame:
    frame: str = ""  # base64 chunk frame; "" when not held
    found: bool = False


@message
class ReplicaInfoRequest:
    """Holder inventory: which (owner, step) snapshots are committed
    here, with per-leaf coverage. Answered with a DiagnosisReport
    JSON blob."""

    owner: int = -1  # -1 = every owner this store holds


# --------------------------------------------------------------------------
# serving (request router + serve workers)
# --------------------------------------------------------------------------


@message
class ServeSubmit:
    """Enqueue one inference request on the master's request router."""

    request_id: str = ""  # "" = router-assigned
    prompt: Optional[List[int]] = None
    max_new_tokens: int = 16
    eos_id: int = -1


@message
class ServeLeaseRequest:
    """Worker -> master: lease up to ``max_requests`` queued requests
    (the serving twin of TaskRequest)."""

    node_id: int = -1
    max_requests: int = 1


@message
class ServeLeases:
    # list of ServeRequest wire dicts (request_id/prompt/
    # max_new_tokens/eos_id) — the router owns the schema
    requests: Optional[List[Dict]] = None


@message
class ServeResult:
    """Worker -> master: one request finished (tokens + the latency
    facts the router's histograms account)."""

    node_id: int = -1
    request_id: str = ""
    tokens: Optional[List[int]] = None
    ttft_s: Optional[float] = None
    e2e_s: Optional[float] = None
    error_code: str = ""
    # prompt tokens whose KV pages were COPIED from the worker's
    # shared prefix pool instead of prefilled (0 = miss or pool off) —
    # the router's saved-token ledger input
    prefix_hit_tokens: int = 0
    # speculative decode: draft tokens this request proposed into
    # verify steps and the subset accepted (drafted - accepted =
    # wasted) — the router's conservation-checked spec ledger input
    spec_drafted_tokens: int = 0
    spec_accepted_tokens: int = 0


@message
class ServeTouch:
    """Worker liveness for the lease-expiry scan (rate-limited by the
    worker; absence past ``serve_lease_timeout_secs`` re-leases its
    requests)."""

    node_id: int = -1


@message
class ServeReportRequest:
    """Query the router ledger (``tpurun requests --addr``): queue /
    lease / completion counts, latency percentiles, per-node rows.
    Answered with a DiagnosisReport-style JSON blob."""

    pass


@message
class ServeConfigReport:
    """Serve worker -> master: the serving config actually running —
    the runtime optimizer's serve-knob input and plan-apply ack (the
    TrainerConfigReport pattern for the serving workload)."""

    node_id: int = -1
    world: int = 0
    serve_slots: int = 0
    prefill_chunk: int = 0
    kv_precision: str = ""
    max_seq: int = 0
    # the REAL pool geometry (the worker's KVCacheSpec): without it
    # the optimizer's HBM gate would price a GQA model's pool at the
    # full query-head count — up to heads/kv_heads too large — and
    # memory-reject slot widths that actually fit
    num_layers: int = 0
    kv_heads: int = 0
    head_dim: int = 0
    # shared prefix pool actually running (pages; 0 = off), its page
    # grain, and the hit rate this worker has OBSERVED — the
    # optimizer's pricing input for the prefill discount (observation
    # beats the serve_prefix_expected_hit_rate prior)
    prefix_pool_pages: int = 0
    page_size: int = 0
    prefix_hit_rate: float = -1.0
    # speculative decode actually running (draft length K; 0 = off)
    # and the acceptance rate this worker has OBSERVED (-1 = no draft
    # yet): the optimizer prices K ONLY from evidence — zero evidence
    # prices every K>0 at exactly 1.0x (no assumed speedup)
    spec_draft_len: int = 0
    spec_accept_rate: float = -1.0
    plan_id: str = ""
    apply_failed: bool = False


@message
class ServeSLORequest:
    """Query the master's serving SLO plane (``tpurun serve slo
    --addr``): declared targets, current burn rates, active violation
    verdicts and the scale proposals the policy loop issued. Answered
    with a DiagnosisReport-style JSON blob."""

    pass


@message
class PlanRequest:
    """Query the master's runtime optimizer: running config, calibration
    factors, candidate tables and the decision trail (the ``tpurun plan
    --addr`` view). Answered with a DiagnosisReport-style JSON blob."""

    limit: int = 0  # 0 = the full retained decision trail


# --------------------------------------------------------------------------
# PS-strategy parity (elastic PS cluster versioning)
# --------------------------------------------------------------------------


@message
class ClusterVersionRequest:
    task_type: str = ""
    task_id: int = 0
    version_type: str = "global"  # global | local | restored


@message
class ClusterVersion:
    version: int = 0


@message
class ClusterVersionUpdate:
    task_type: str = ""
    task_id: int = 0
    version_type: str = "global"
    version: int = 0
    # Compare-and-set guard: apply only while the current value equals
    # `expected` (-1 = unconditional). Makes concurrent global-version
    # bumps race-free server-side.
    expected: int = -1


@message
class QueryPsNodesRequest:
    pass


@message
class PsNodes:
    addrs: Optional[List[str]] = None
    ready: bool = False
    new_ps_ready: bool = False


# --------------------------------------------------------------------------
# job control
# --------------------------------------------------------------------------


@message
class JobExitRequest:
    node_id: int = -1
    success: bool = True
    reason: str = ""


@message
class ScaleRequest:
    """Manual scaling hook (the reference's user-submitted ScalePlan CR)."""

    worker_num: int = 0


def is_message(obj) -> bool:
    return dataclasses.is_dataclass(obj) and not isinstance(obj, type)
