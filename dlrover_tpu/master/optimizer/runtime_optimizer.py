"""Master-side runtime optimizer: the closed control loop.

Triggers (straggler/hang verdicts from ``master/monitor/straggler.py``,
``DIAG_RECOVERED``, world changes reported by resharded workers) run a
re-plan pass: calibrate the planner's cost model against the measured
node series (``calibration``), enumerate candidate configs — mesh shape
for the current world, ``train_window``, MoE dispatch mode — price
every one through the calibrated estimate, and publish the winner as a
``ParallelConfig`` plan the workers apply LIVE
(``OptimizerPlanHook`` → executor retune → program cache / live
reshard; no process restart).

Guard rails so the loop cannot oscillate:

  hysteresis   a plan must predict ≥ ``replan_min_speedup`` over the
               calibrated estimate of the CURRENT config;
  cooldown     the identical candidate proposed twice within
               ``replan_cooldown_secs`` is suppressed
               (``parallel.search.ProposalCooldown``);
  tie-break    equal-price candidates sort by distance from the current
               knobs, so "no change" always beats gratuitous churn.

Every decision (candidates priced, plan chosen/rejected, calibration
factors, predicted-vs-realized speedup) lands in the event timeline as
``OPTIMIZER_*`` records under one incident trace id; ``tpurun plan``
renders the live table (``PlanRequest`` RPC) and the forensic trail
(``decision_trail_from_events``).
"""

from __future__ import annotations

import collections
import statistics
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from dlrover_tpu.common import comm
from dlrover_tpu.common.config import get_context
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.master.monitor.straggler import BOUND_PEER_DELTA
from dlrover_tpu.master.optimizer.calibration import (
    CostCalibrator,
    MemoryInfeasibleError,
)
from dlrover_tpu.parallel.mesh import (
    MeshPlan,
    candidate_plans,
    mesh_axes_key,
)
from dlrover_tpu.parallel.planner import DeviceSpec, ModelSpec
from dlrover_tpu.parallel.search import ProposalCooldown
from dlrover_tpu.telemetry import (
    EventKind,
    emit_event,
    get_registry,
    names as tm,
)
from dlrover_tpu.telemetry.trace_context import (
    current_trace_id,
    trace_scope,
)

logger = get_logger("master.optimizer")

# how many top-priced candidates ride along in events / the plan report
_TABLE_ROWS = 8
# bound on the retained decision trail
_MAX_DECISIONS = 64
# a node's latest sample older than this does not anchor calibration
_CALIBRATION_FRESHNESS_S = 600.0
# the input-bound replan gate's absolute backstop: uniform cluster-wide
# starvation (a shared slow filesystem — the most common input-bound
# mode) has no peer excess to show, so a MEDIAN input-wait fraction at
# an absolute majority of the window also marks the job data-starved.
# The peer-relative leg shares BOUND_PEER_DELTA with the straggler
# verdict's bound label (one constant, never desynchronized).
_INPUT_BOUND_ABS = 0.5

# grouped_ep chunked-dispatch degrees the optimizer prices (the
# comm/compute-overlap knob, ops.moe dispatch_chunks). Enumerated only
# when the worker REPORTS it runs moe_dispatch="grouped_ep" — on any
# other dispatch the knob is inert and would only widen the candidate
# product. Applied live through the same prewarmed program-cache swap
# as a mesh override (ElasticTrainer.retune(dispatch_chunks=...)).
DISPATCH_CHUNKS_OPTIONS = (1, 2, 4, 8)
# grouped_ep wire precisions the optimizer prices (ops.moe precision /
# ops.quantize): the fp8 wire halves the dispatch-comm bytes the
# planner prices, so on a comm-bound MoE job the family wins honestly.
# Enumerated under the same parked-knob discipline as dispatch_chunks
# (only when the worker REPORTS moe_dispatch="grouped_ep" — on any
# other dispatch the knob is inert and would only widen the candidate
# product), and applied live through the same prewarmed program-cache
# swap (ElasticTrainer.retune(moe_precision=...)). "fp8_qdq" (the
# reference oracle) is deliberately absent: it prices as bf16 and
# exists to test against, never to run.
MOE_PRECISION_OPTIONS = ("bf16", "fp8")
# dense FSDP gather wire precisions the optimizer prices (models/llama
# fsdp_precision / ops.quantize): the fp8 wire cuts the per-layer param
# gather bytes the planner's fsdp_gather term prices (~0.28x of an f32
# gather), so on a gather-bound dense job the family wins honestly.
# Enumerated under the parked-knob discipline: only when the worker
# REPORTS a dense-wire precision (TrainerConfigReport.fsdp_precision —
# a trainer-managed llama job always does) AND the running mesh
# actually has an fsdp axis > 1; otherwise the knob is inert and would
# only widen the candidate product. Applied live through the same
# prewarmed program-cache swap (ElasticTrainer.retune(fsdp_precision=))
# with the probe-failure negative-ack contract. "fp8_qdq" (the
# dequant-exact oracle) is deliberately absent: it prices at the full-
# precision wire and exists to test against, never to run. The
# GRADIENT-path precision (grad_precision) is NOT a family at all: its
# error-feedback residual is TrainState structure, which no live
# retune can swap.
FSDP_PRECISION_OPTIONS = ("bf16", "fp8")
# priced by the cost model, but NOT yet live-appliable: a dispatch-mode
# change rebuilds the model, and enumeration is gated on the calibrator
# seeing num_experts > 0 — which comm.ModelInfo does not carry yet, so
# today every candidate keeps the running mode. Wire ModelInfo experts
# + a model-rebuild apply path before enabling this knob for real.
MOE_DISPATCH_OPTIONS = ("gather", "einsum", "grouped", "grouped_ep")


def _mesh_dict(mesh: MeshPlan) -> Dict[str, int]:
    return {k: int(v) for k, v in mesh.axis_sizes().items()}


@dataclass
class RunningConfig:
    """What the workers report they are actually running."""

    mesh: MeshPlan
    world: int
    train_window: int = 4
    moe_dispatch: str = ""
    dispatch_chunks: int = 1
    moe_precision: str = "bf16"
    # "" = the worker did not report a dense-wire precision (the
    # family stays parked); a trainer-managed job reports "bf16"/"fp8"
    fsdp_precision: str = ""
    global_batch: int = 0

    @classmethod
    def from_report(cls, report: comm.TrainerConfigReport
                    ) -> "RunningConfig":
        shape = dict(report.mesh_shape or {})
        mesh = MeshPlan(**{
            k: int(v) for k, v in shape.items()
            if k in ("pipe", "data", "fsdp", "seq", "tensor")
        }) if shape else MeshPlan(data=max(1, report.world))
        return cls(
            mesh=mesh,
            world=int(report.world or 0),
            train_window=int(report.train_window),
            moe_dispatch=report.moe_dispatch or "",
            dispatch_chunks=max(
                1, int(getattr(report, "dispatch_chunks", 0) or 1)),
            moe_precision=str(
                getattr(report, "moe_precision", "") or "bf16"),
            fsdp_precision=str(
                getattr(report, "fsdp_precision", "") or ""),
            global_batch=int(report.global_batch or 0),
        )

    def to_dict(self) -> Dict:
        return {
            "mesh": _mesh_dict(self.mesh),
            "world": self.world,
            "train_window": self.train_window,
            "moe_dispatch": self.moe_dispatch,
            "dispatch_chunks": self.dispatch_chunks,
            "moe_precision": self.moe_precision,
            "fsdp_precision": self.fsdp_precision,
            "global_batch": self.global_batch,
        }


@dataclass
class CandidateScore:
    """One priced candidate config."""

    mesh: MeshPlan
    train_window: int
    moe_dispatch: str
    dispatch_chunks: int = 1
    moe_precision: str = "bf16"
    fsdp_precision: str = "bf16"
    predicted_step_s: float = 0.0
    speedup: float = 0.0  # current predicted / this predicted

    @property
    def key(self) -> str:
        return (
            f"mesh={mesh_axes_key(self.mesh)}"
            f"|w={self.train_window}"
            f"|moe={self.moe_dispatch}|c={self.dispatch_chunks}"
            f"|p={self.moe_precision}|fp={self.fsdp_precision}"
        )

    def to_dict(self) -> Dict:
        return {
            "mesh": _mesh_dict(self.mesh),
            "train_window": self.train_window,
            "moe_dispatch": self.moe_dispatch,
            "dispatch_chunks": self.dispatch_chunks,
            "moe_precision": self.moe_precision,
            "fsdp_precision": self.fsdp_precision,
            "predicted_step_s": round(self.predicted_step_s, 6),
            "speedup": round(self.speedup, 3),
        }


@dataclass
class Decision:
    """One re-plan pass: what was priced, what was decided, and — once
    the worker's post-apply window lands — what it actually bought."""

    trigger: str
    trace_id: str
    ts: float
    outcome: str = "rejected"  # "chosen" | "rejected"
    reason: str = ""
    plan_id: str = ""
    current: Dict = field(default_factory=dict)
    current_predicted_s: float = 0.0
    candidates: List[Dict] = field(default_factory=list)
    chosen: Optional[Dict] = None
    predicted_speedup: float = 0.0
    corrections: Dict = field(default_factory=dict)
    applied: bool = False
    apply_failed: bool = False
    realized_speedup: Optional[float] = None
    # candidate meshes the MEMORY-FEASIBILITY gate rejected BEFORE
    # pricing (predicted peak HBM above the device budget) — the
    # evidence `tpurun plan` / `tpurun attribution` surface
    memory_rejected: List[Dict] = field(default_factory=list)
    # the INPUT-BOUND gate's evidence when it rejected this pass's
    # program plan (which node is starved, by how much over peers)
    input_bound: Optional[Dict] = None
    # the readiness auditor's verdict evidence when a ``durability:``
    # trigger fired this pass (which owner is at risk, which coverage /
    # staleness / budget dimension failed) — `tpurun plan` shows WHY a
    # placement replan was asked for, not just that one was
    durability: Optional[Dict] = None
    # the chosen candidate's knob-tuple key (blacklist identity on a
    # failed apply); not part of the reported dict
    chosen_key: str = ""

    def to_dict(self) -> Dict:
        return {
            "trigger": self.trigger,
            "trace_id": self.trace_id,
            "ts": self.ts,
            "outcome": self.outcome,
            "reason": self.reason,
            "plan_id": self.plan_id,
            "current": dict(self.current),
            "current_predicted_s": round(self.current_predicted_s, 6),
            "candidates": list(self.candidates),
            "chosen": dict(self.chosen) if self.chosen else None,
            "predicted_speedup": round(self.predicted_speedup, 3),
            "corrections": dict(self.corrections),
            "applied": self.applied,
            "apply_failed": self.apply_failed,
            "realized_speedup": self.realized_speedup,
            "memory_rejected": list(self.memory_rejected),
            "input_bound": (dict(self.input_bound)
                            if self.input_bound else None),
            "durability": (dict(self.durability)
                           if self.durability else None),
        }


class RuntimeOptimizer:
    """The loop brain. Thread-safe: triggers arrive from RPC handler
    threads and the master's periodic stats loop."""

    def __init__(
        self,
        store,
        publish: Optional[Callable[[comm.ParallelConfig], None]] = None,
        retract: Optional[Callable[[str], None]] = None,
        device: Optional[DeviceSpec] = None,
        min_speedup: Optional[float] = None,
        cooldown_secs: Optional[float] = None,
        enabled: Optional[bool] = None,
        mesh_candidates: bool = True,
    ):
        ctx = get_context()
        self._store = store
        self._publish = publish
        self._retract = retract
        self._device = device or DeviceSpec()
        self._min_speedup = float(
            min_speedup if min_speedup is not None
            else getattr(ctx, "replan_min_speedup", 1.2))
        self._cooldown = ProposalCooldown(float(
            cooldown_secs if cooldown_secs is not None
            else getattr(ctx, "replan_cooldown_secs", 60.0)))
        self._enabled = bool(
            enabled if enabled is not None
            else getattr(ctx, "runtime_optimizer_enabled", True))
        # the input-bound gate (mirror of PR 8's memory gate): a
        # data-starved job must not pay a drain for a program replan
        # that cannot feed it — docs/operations.md names the knob
        self._input_bound_gate = bool(
            getattr(ctx, "replan_input_bound_gate", True))
        self._mesh_candidates = mesh_candidates
        # supplies the readiness auditor's verdict evidence for a node
        # (wired by the servicer) so durability-triggered decisions
        # carry WHY placement must change, not just the trigger string
        self._durability_evidence_fn: Optional[
            Callable[[int], Optional[Dict]]] = None
        self._lock = threading.RLock()
        self._running: Optional[RunningConfig] = None
        # last reported world PER NODE (the world-change trigger input)
        self._node_worlds: Dict[int, int] = {}
        # knob tuples a worker negative-acked (rebuild failed /
        # unsupported): excluded from the candidate ranking for this
        # optimizer's lifetime — the model priced them feasible once
        # and reality disagreed, so re-proposing every cooldown window
        # would stall the job with a failed rebuild each cycle
        self._failed_keys: set = set()
        self._model_info: Optional[comm.ModelInfo] = None
        # the serving workload's running view (ServeConfigReport) —
        # the serve-knob family's input, None until a serve worker
        # reports; worlds tracked PER NODE so a laggard's stale report
        # cannot rewind the view (the _node_worlds discipline)
        self._serving: Optional[Dict] = None
        self._serve_node_worlds: Dict[int, int] = {}
        self._calibrator: Optional[CostCalibrator] = None
        self._decisions: "collections.deque[Decision]" = (
            collections.deque(maxlen=_MAX_DECISIONS)
        )
        self._pending: Optional[comm.ParallelConfig] = None
        self._plan_seq = 0
        reg = get_registry()
        self._c_replans = reg.counter(
            tm.OPTIMIZER_REPLANS, help="re-plan passes evaluated")
        self._c_chosen = reg.counter(
            tm.OPTIMIZER_PLANS_CHOSEN, help="plans published to workers")
        self._c_rejected = reg.counter(
            tm.OPTIMIZER_PLANS_REJECTED,
            help="plans suppressed (hysteresis / cooldown / optimal)")
        self._c_calibrations = reg.counter(
            tm.OPTIMIZER_CALIBRATIONS,
            help="cost-model calibration passes")
        self._c_memory_rejected = reg.counter(
            tm.OPTIMIZER_PLANS_MEMORY_REJECTED,
            help="candidate plans rejected by the memory-feasibility "
                 "gate before pricing")

    # -- inputs --------------------------------------------------------------

    def update_model_info(self, info: comm.ModelInfo) -> None:
        with self._lock:
            self._model_info = info
            self._calibrator = None  # respec; corrections re-fit fast

    def update_running_config(self, report: comm.TrainerConfigReport
                              ) -> None:
        """A worker reported the config it actually runs (train start,
        post-reshard, post-retune, or a plan-apply ack)."""
        with self._lock:
            cfg = RunningConfig.from_report(report)
            # the world-change trigger compares a node against ITS OWN
            # previous report: during a reshard the survivors re-report
            # at different times, and judging consecutive reports from
            # DIFFERENT nodes against one global slot would fire
            # spurious 8->4->8->4 replans off a laggard's stale view
            nid = int(report.node_id)
            prev_world = self._node_worlds.get(nid)
            self._node_worlds[nid] = cfg.world
            world_changed = (
                prev_world is not None and prev_world != cfg.world
                and cfg.world > 0
            )
            # adopt the report as the running view unless it is a
            # laggard's STALE minority world: after an 8->4 shrink a
            # queued pre-shrink report (world=8, no per-node change)
            # must not rewind _running — the next replan would price
            # and publish candidates for a world that no longer exists
            if (
                self._running is None or world_changed
                or cfg.world == self._running.world
            ):
                self._running = cfg
            if report.plan_id:
                self._record_applied(report)
        if world_changed:
            # ScalePlan / live-reshard world change: the knobs tuned for
            # the old world may be wrong for the survivor one
            self.replan(f"world_change:{prev_world}->{cfg.world}")

    def _record_applied(self, report: comm.TrainerConfigReport) -> None:
        failed = bool(getattr(report, "apply_failed", False))
        for d in reversed(self._decisions):
            if d.plan_id == report.plan_id:
                if failed:
                    d.apply_failed = True
                    if d.chosen_key:
                        self._failed_keys.add(d.chosen_key)
                        logger.warning(
                            "plan %s (%s) failed to apply on node %d; "
                            "knob tuple blacklisted",
                            report.plan_id, d.chosen_key, report.node_id,
                        )
                else:
                    d.applied = True
                    realized = getattr(report, "realized_speedup", 0.0)
                    if realized:
                        d.realized_speedup = round(float(realized), 3)
                break
        # a consumed plan is RETRACTED from the broadcast slot: a worker
        # restarted later (fresh _seen_plan) must not replay a plan the
        # running job already absorbed — it would retune the job off a
        # judgment the optimizer no longer stands behind and corrupt
        # the decision trail with a second apply/measurement cycle
        if (
            self._pending is not None
            and self._pending.plan_id == report.plan_id
        ):
            self._pending = None
            if self._retract is not None:
                try:
                    self._retract(report.plan_id)
                except Exception:  # noqa: BLE001 — ack path must not die
                    logger.exception("failed to retract consumed plan")

    def update_serving_config(self, report: comm.ServeConfigReport
                              ) -> None:
        """A SERVE worker reported its running config (serve start,
        post-resize, or a serve-plan ack) — the serving twin of
        ``update_running_config``. A config change (fresh worker,
        resized world) triggers a serve-knob re-plan."""
        with self._lock:
            cfg = {
                "node_id": int(report.node_id),
                "world": int(report.world),
                "serve_slots": int(report.serve_slots),
                "prefill_chunk": int(report.prefill_chunk),
                "kv_precision": report.kv_precision or "f32",
                "max_seq": int(report.max_seq),
                "num_layers": int(getattr(report, "num_layers", 0)),
                "kv_heads": int(getattr(report, "kv_heads", 0)),
                "head_dim": int(getattr(report, "head_dim", 0)),
                "prefix_pool_pages": int(getattr(
                    report, "prefix_pool_pages", 0)),
                "page_size": int(getattr(report, "page_size", 0)),
                # observed hit rate rides the report but is NOT a
                # replan trigger (it drifts every request) — it only
                # feeds the pricing when >= 0
                "prefix_hit_rate": float(getattr(
                    report, "prefix_hit_rate", -1.0)),
                "spec_draft_len": int(getattr(
                    report, "spec_draft_len", 0) or 0),
                # the observed acceptance rate: pricing evidence only
                # (like the hit rate, it drifts — never a trigger)
                "spec_accept_rate": float(getattr(
                    report, "spec_accept_rate", -1.0)),
            }
            if report.plan_id:
                self._record_applied(report)
            # per-node world tracking + stale-minority rejection, the
            # update_running_config discipline: around a resize, a
            # laggard peer's queued pre-resize report must neither
            # rewind the serving view to a dead world nor fire a
            # replan priced for it
            nid = int(report.node_id)
            prev_world = self._serve_node_worlds.get(nid)
            self._serve_node_worlds[nid] = cfg["world"]
            world_changed = (prev_world is not None
                             and prev_world != cfg["world"]
                             and cfg["world"] > 0)
            prev = self._serving
            adopted = (prev is None or world_changed
                       or cfg["world"] == prev.get("world"))
            if adopted:
                self._serving = cfg
            changed = adopted and (prev is None or any(
                prev.get(k) != cfg[k]
                for k in ("world", "serve_slots", "prefill_chunk",
                          "kv_precision", "prefix_pool_pages",
                          "spec_draft_len")))
        if changed and not report.plan_id:
            # an ack's config echo is the plan we just published —
            # re-planning on it would chase our own tail
            self.replan_serving("serve_config")

    # -- the serving knob family ---------------------------------------------

    def serving_config(self) -> Optional[Dict]:
        with self._lock:
            cfg = getattr(self, "_serving", None)
            return dict(cfg) if cfg else None

    def _serve_candidates(self, cfg: Dict) -> List[Dict]:
        slots = max(1, cfg["serve_slots"])
        chunk = max(1, cfg["prefill_chunk"])
        max_seq = max(1, cfg["max_seq"])
        slot_opts = sorted({
            s for s in (slots // 2, slots, slots * 2, slots * 4)
            if 1 <= s <= 256})
        # only chunks the worker can honor EXACTLY: the reported
        # max_seq is the page-aligned pool depth, and the engine fits
        # chunks to its divisors (a non-divisor plan would be
        # negative-acked — don't enumerate guaranteed nacks)
        chunk_opts = sorted({
            c for c in (chunk // 2, chunk, chunk * 2)
            if 1 <= c <= max_seq and max_seq % c == 0})
        if not chunk_opts:
            chunk_opts = [chunk]
        # prefix-pool widths: 0 (off), current, and pool depths sized
        # to hold whole prompts (max_seq / page_size pages each). Only
        # enumerable when the worker reported its page geometry — an
        # old worker without page_size keeps its pool untouched.
        ppp = max(0, int(cfg.get("prefix_pool_pages", 0) or 0))
        pg = int(cfg.get("page_size", 0) or 0)
        if pg > 0:
            per_prompt = max(1, max_seq // pg)
            pool_opts = sorted({
                p for p in (0, ppp, per_prompt * 4, per_prompt * 8)
                if 0 <= p <= 4096})
        else:
            pool_opts = [ppp]
        # speculative draft lengths: 0 (off), current, and the small
        # powers of two the verify step's compute trade favors — but
        # ONLY under the serve_spec_enabled master switch (disabled =
        # the current K alone, so a hand-set K is left untouched but
        # never enumerated away from)
        sk = max(0, int(cfg.get("spec_draft_len", 0) or 0))
        if bool(getattr(get_context(), "serve_spec_enabled", True)):
            spec_opts = sorted({0, sk, 2, 4, 8})
        else:
            spec_opts = [sk]
        return [{"serve_slots": s, "prefill_chunk": c,
                 "prefix_pool_pages": p, "spec_draft_len": k}
                for s in slot_opts for c in chunk_opts
                for p in pool_opts for k in spec_opts]

    def _serve_spec(self, cfg: Optional[Dict] = None):
        """A ModelSpec for the decode pricing. The KV-pool geometry
        (layers, kv heads, head_dim) comes from the SERVE WORKER's
        report when it carries it — the worker knows its KVCacheSpec
        exactly, and guessing heads from hidden_size would price a
        GQA model's pool up to heads/kv_heads too large and memory-
        reject slot widths that actually fit. ModelInfo fills the
        param count (the weight-read term); a placeholder otherwise
        (the RANKING is shape-driven either way)."""
        from dlrover_tpu.parallel.planner import ModelSpec

        cfg = cfg or getattr(self, "_serving", None) or {}
        info = self._model_info
        kv_heads = int(cfg.get("kv_heads") or 0)
        head_dim = int(cfg.get("head_dim") or 0)
        layers = int(cfg.get("num_layers") or 0)
        if kv_heads and head_dim:
            # encode the reported geometry exactly: hidden/heads is
            # how the planner re-derives head_dim, so set heads such
            # that hidden_size // heads == head_dim
            hidden = (int(info.hidden_size) if info is not None
                      and info.hidden_size else kv_heads * head_dim)
            heads = max(1, hidden // head_dim)
            return ModelSpec(
                param_count=int(info.num_params) if info is not None
                and info.num_params > 0 else 1e6,
                num_layers=max(1, layers or (
                    int(info.num_layers) if info is not None else 1)),
                hidden_size=hidden,
                seq_len=max(1, int(getattr(info, "seq_len", 0) or 128)
                            if info is not None else 128),
                global_batch=1,
                num_heads=heads, kv_heads=kv_heads,
            )
        if info is not None and info.num_params > 0:
            heads = max(1, (info.hidden_size or 64) // 64)
            return ModelSpec(
                param_count=int(info.num_params),
                num_layers=max(1, int(info.num_layers or 1)),
                hidden_size=max(1, int(info.hidden_size or 64)),
                seq_len=max(1, int(info.seq_len or 128)),
                global_batch=1,
                num_heads=heads, kv_heads=heads,
            )
        return ModelSpec(param_count=1e6, num_layers=2, hidden_size=64,
                         seq_len=128, global_batch=1, num_heads=4,
                         kv_heads=2)

    def _serve_budget_bytes(self) -> float:
        budget = float(getattr(
            get_context(), "device_hbm_budget_bytes", 0.0) or 0.0)
        if budget > 0:
            return budget
        return float(self._device.hbm_bytes) * 0.8

    def replan_serving(self, trigger: str) -> Optional[Decision]:
        """Enumerate and price ``serve_slots`` / ``prefill_chunk``
        under live traffic — the serving mirror of ``replan``: the
        planner's decode term (KV-read bytes, the memory-bound regime)
        prices candidates, the HBM feasibility gate (PR 8) refuses
        pools that cannot fit, hysteresis/cooldown/blacklist guard the
        churn, and winners publish through the SAME ParallelConfig
        broadcast the training knobs ride."""
        if not self._enabled:
            return None
        from dlrover_tpu.parallel.planner import (
            estimate_decode,
            serve_cache_bytes,
            serve_prefix_pool_bytes,
        )

        with self._lock:
            cfg = getattr(self, "_serving", None)
            if cfg is None:
                return None
            with trace_scope(current_trace_id() or None) as tid:
                self._c_replans.inc()
                spec = self._serve_spec(cfg)
                world = max(1, cfg["world"])
                kvp = cfg["kv_precision"]
                max_seq = max(1, cfg["max_seq"])
                budget = self._serve_budget_bytes()
                page_size = int(cfg.get("page_size", 0) or 0)
                # the hit-rate driving the prefill discount: observed
                # (from the worker's ledger) once traffic has spoken,
                # else the operator's prior — 0 without either, which
                # prices every pool width as pure cost and keeps the
                # knob off until there is evidence it pays
                observed_hr = float(cfg.get("prefix_hit_rate", -1.0))
                hit_rate = (observed_hr if observed_hr >= 0.0
                            else float(getattr(
                                get_context(),
                                "serve_prefix_expected_hit_rate",
                                0.0) or 0.0))
                # the acceptance rate has NO prior knob: with no
                # observation every K>0 prices at exactly 1.0x inside
                # estimate_decode, so spec stays off until traffic
                # proves drafts land — evidence-only, stricter than
                # the prefix discount (a wrong prior here would cost
                # real compute every step, not just idle pool HBM)
                accept_rate = float(cfg.get("spec_accept_rate", -1.0))
                current = estimate_decode(
                    spec, world, cfg["serve_slots"],
                    cfg["prefill_chunk"], max_seq, kvp,
                    device=self._device,
                    prefix_pool_pages=max(
                        0, cfg.get("prefix_pool_pages", 0)),
                    page_size=page_size or 16,
                    prefix_hit_rate=hit_rate,
                    spec_draft_len=max(
                        0, cfg.get("spec_draft_len", 0)),
                    spec_accept_rate=accept_rate)
                priced, memory_rejected = [], []
                for cand in self._serve_candidates(cfg):
                    pool = serve_cache_bytes(
                        spec, cand["serve_slots"], max_seq, kvp)
                    # the prefix pool is sharded only on heads and
                    # charged UNDIVIDED per device (conservative: the
                    # page dim is replicated) on top of this node's
                    # slot-pool share
                    prefix_bytes = serve_prefix_pool_bytes(
                        spec, cand["prefix_pool_pages"],
                        page_size or 16, kvp)
                    per_device = pool / world + prefix_bytes
                    if per_device > budget:
                        memory_rejected.append({
                            "serve_slots": cand["serve_slots"],
                            "prefix_pool_pages":
                                cand["prefix_pool_pages"],
                            "predicted_hbm_bytes": per_device,
                            "budget_bytes": budget,
                        })
                        self._c_memory_rejected.inc()
                        continue
                    est = estimate_decode(
                        spec, world, cand["serve_slots"],
                        cand["prefill_chunk"], max_seq, kvp,
                        device=self._device,
                        prefix_pool_pages=cand["prefix_pool_pages"],
                        page_size=page_size or 16,
                        prefix_hit_rate=hit_rate,
                        spec_draft_len=cand["spec_draft_len"],
                        spec_accept_rate=accept_rate)
                    key = (f"serve|slots={cand['serve_slots']}"
                           f"|pc={cand['prefill_chunk']}"
                           f"|ppp={cand['prefix_pool_pages']}"
                           f"|spec={cand['spec_draft_len']}")
                    if key in self._failed_keys:
                        continue
                    priced.append({
                        **cand, "key": key,
                        "tokens_per_s": est["tokens_per_s"],
                        "step_s": est["step_s"],
                        "speedup": (est["tokens_per_s"]
                                    / max(current["tokens_per_s"],
                                          1e-12)),
                    })
                memory_rejected.sort(
                    key=lambda r: -r["predicted_hbm_bytes"])
                decision = Decision(
                    trigger=f"serve:{trigger}", trace_id=tid,
                    ts=time.time(), current=dict(cfg),
                    current_predicted_s=current["step_s"],
                    memory_rejected=memory_rejected[:8],
                )
                if not priced:
                    self._reject(decision, "serve:no_feasible_candidate")
                    self._decisions.append(decision)
                    return decision
                def churn(c):
                    # equal throughput prefers the fewest knob flips
                    # (the training ranking's churn tie-break): a tied
                    # prefill_chunk change must not ride along free
                    return ((c["serve_slots"] != cfg["serve_slots"])
                            + (c["prefill_chunk"]
                               != cfg["prefill_chunk"])
                            + (c["prefix_pool_pages"]
                               != cfg.get("prefix_pool_pages", 0))
                            + (c["spec_draft_len"]
                               != cfg.get("spec_draft_len", 0)))

                priced.sort(key=lambda c: (-c["tokens_per_s"],
                                           churn(c), c["serve_slots"]))
                decision.candidates = [
                    {k: (round(v, 6) if isinstance(v, float) else v)
                     for k, v in c.items()} for c in priced[:8]]
                best = priced[0]
                decision.predicted_speedup = round(best["speedup"], 3)
                unchanged = (
                    best["serve_slots"] == cfg["serve_slots"]
                    and best["prefill_chunk"] == cfg["prefill_chunk"]
                    and best["prefix_pool_pages"]
                    == cfg.get("prefix_pool_pages", 0)
                    and best["spec_draft_len"]
                    == cfg.get("spec_draft_len", 0))
                pending_training = (
                    self._pending is not None
                    and not getattr(self._pending, "serve_slots", 0)
                    and not getattr(self._pending,
                                    "serve_prefill_chunk", 0)
                    and getattr(self._pending,
                                "serve_prefix_pool_pages", -1) < 0
                    and getattr(self._pending,
                                "serve_spec_draft_len", -1) < 0)
                if unchanged:
                    self._reject(decision, "already_optimal")
                elif pending_training:
                    # ONE broadcast slot serves both planes today (the
                    # colocation split is ROADMAP item 3): publishing
                    # now would silently clobber an unconsumed TRAINING
                    # plan. Defer — the next serve-config report
                    # re-triggers this pass. (The trainer's plan hook
                    # symmetrically ignores serve-only plans, so the
                    # reverse clobber is an overwrite, not a bad ack.)
                    self._reject(decision, "pending_training_plan")
                elif best["speedup"] < self._min_speedup:
                    self._reject(
                        decision,
                        f"hysteresis:{best['speedup']:.2f}"
                        f"<{self._min_speedup:.2f}")
                elif not self._cooldown.check(best["key"]):
                    self._reject(
                        decision, "cooldown:%.0fs"
                        % self._cooldown.seconds_remaining(best["key"]))
                else:
                    self._choose_serving(decision, best, cfg)
                self._decisions.append(decision)
                return decision

    def _choose_serving(self, decision: Decision, best: Dict,
                        cfg: Dict) -> None:
        self._plan_seq += 1
        plan_id = f"plan-{self._plan_seq}"
        decision.outcome = "chosen"
        decision.plan_id = plan_id
        decision.chosen = dict(best)
        decision.chosen_key = best["key"]
        self._c_chosen.inc()
        published = comm.ParallelConfig(
            serve_slots=(best["serve_slots"]
                         if best["serve_slots"] != cfg["serve_slots"]
                         else 0),
            serve_prefill_chunk=(
                best["prefill_chunk"]
                if best["prefill_chunk"] != cfg["prefill_chunk"]
                else 0),
            serve_prefix_pool_pages=(
                best["prefix_pool_pages"]
                if best["prefix_pool_pages"]
                != cfg.get("prefix_pool_pages", 0)
                else -1),
            serve_spec_draft_len=(
                best["spec_draft_len"]
                if best["spec_draft_len"]
                != cfg.get("spec_draft_len", 0)
                else -1),
            plan_id=plan_id,
            trace_id=decision.trace_id,
            predicted_speedup=round(best["speedup"], 3),
            prewarm=True,
        )
        self._pending = published
        emit_event(
            EventKind.OPTIMIZER_PLAN_CHOSEN,
            plan_id=plan_id, trigger=decision.trigger,
            predicted_speedup=round(best["speedup"], 3),
            knob_serve_slots=best["serve_slots"],
            knob_serve_prefill_chunk=best["prefill_chunk"],
            knob_serve_prefix_pool_pages=best["prefix_pool_pages"],
            knob_serve_spec_draft_len=best["spec_draft_len"],
        )
        logger.info("replan(%s): chose %s (predicted %.2fx tokens/s, "
                    "plan %s)", decision.trigger, best["key"],
                    best["speedup"], plan_id)
        if self._publish is not None:
            self._publish(published)

    def on_verdict(self, node_id: int, verdict: str) -> None:
        """Straggler-detector listener: a flagged verdict (and its
        recovery) is a re-plan trigger. Recovery replans IMMEDIATELY —
        the degraded-config workaround should not outlive the incident
        by a scaler period (ISSUE 7 satellite; the auto-scaler gets the
        same kick through its own listener)."""
        if verdict == "healthy":
            self.replan(f"recovered:{node_id}")
        else:
            self.replan(f"{verdict}:{node_id}")

    def set_durability_evidence_fn(
            self, fn: Callable[[int], Optional[Dict]]) -> None:
        """Wire the readiness auditor's per-node verdict lookup in."""
        self._durability_evidence_fn = fn

    def _durability_evidence(self, trigger: str) -> Optional[Dict]:
        """The at-risk owner's audit evidence for a ``durability:N``
        trigger (None for every other trigger, or when the verdict
        already cleared by the time the pass runs)."""
        if (self._durability_evidence_fn is None
                or not trigger.startswith("durability:")):
            return None
        try:
            node_id = int(trigger.split(":", 1)[1])
        except (TypeError, ValueError):
            return None
        try:
            return self._durability_evidence_fn(node_id)
        except Exception:  # noqa: BLE001 — evidence is garnish, the
            # replan itself must still run
            logger.exception("durability evidence lookup failed")
            return None

    # -- calibration ---------------------------------------------------------

    def _ensure_calibrator(self) -> Optional[CostCalibrator]:
        if self._running is None:
            return None
        if self._calibrator is not None:
            return self._calibrator
        info = self._model_info
        batch = self._running.global_batch or 8
        if info is not None and info.num_params > 0:
            moe_kwargs = {}
            if int(getattr(info, "num_experts", 0) or 0) > 0:
                # the worker runs an MoE model: the spec must carry the
                # expert shape (and the RUNNING dispatch mode) or the
                # dispatch-comm terms price as zero and the
                # dispatch_chunks family collapses into ties
                moe_kwargs = dict(
                    num_experts=int(info.num_experts),
                    moe_top_k=max(1, int(
                        getattr(info, "moe_top_k", 1) or 1)),
                    moe_dispatch=(self._running.moe_dispatch
                                  or "grouped_ep"),
                    moe_dispatch_chunks=max(
                        1, self._running.dispatch_chunks),
                    moe_precision=(self._running.moe_precision
                                   or "bf16"),
                )
                if float(getattr(info, "ffn_mult", 0.0) or 0.0) > 0:
                    moe_kwargs["ffn_mult"] = float(info.ffn_mult)
            spec = ModelSpec(
                param_count=int(info.num_params),
                num_layers=max(1, int(info.num_layers or 2)),
                hidden_size=max(8, int(info.hidden_size or 256)),
                seq_len=max(1, int(info.seq_len or 128)),
                global_batch=batch,
                fsdp_precision=(self._running.fsdp_precision or "bf16"),
                **moe_kwargs,
            )
        else:
            # no ModelInfo reported: a minimal placeholder spec — the
            # corrections anchor absolute scale, the analytic model only
            # contributes relative structure across knobs
            spec = ModelSpec(
                param_count=1_000_000, num_layers=2, hidden_size=256,
                seq_len=128, global_batch=batch,
            )
        ctx = get_context()
        self._calibrator = CostCalibrator(
            model=spec, device=self._device,
            # operator HBM budget for the memory-feasibility gate
            # (0 = the device spec's capacity under the fit headroom)
            hbm_budget_bytes=float(
                getattr(ctx, "device_hbm_budget_bytes", 0.0)),
        )
        return self._calibrator

    def _measured_anchor(self) -> Dict[str, Optional[float]]:
        """The step/dispatch p50 the JOB actually paces at: the MAX
        over fresh nodes. A synchronous SPMD job runs at its slowest
        member, so a degraded-but-alive straggler IS the job's step
        time — the HSDP-at-100k position (PAPERS.md 2602.00277): treat
        it as a config-search input, not just a restart trigger. Each
        node's windowed p50 already rides out single-sample noise."""
        now = time.time()
        steps: List[float] = []
        dispatches: List[float] = []
        for nid in self._store.node_ids():
            s = self._store.latest(nid)
            if s is None or now - s.ts > _CALIBRATION_FRESHNESS_S:
                continue
            if s.step_p50 is not None:
                steps.append(s.step_p50)
            if s.dispatch_p50 is not None:
                dispatches.append(s.dispatch_p50)
        return {
            "step_p50": max(steps) if steps else None,
            "dispatch_p50": max(dispatches) if dispatches else None,
        }

    def calibrate(self) -> Optional[Dict]:
        """One predicted-vs-observed fit for the current config;
        returns the correction factors (None without a running config
        or any fresh measurement)."""
        with self._lock:
            cal = self._ensure_calibrator()
            if cal is None:
                return None
            measured = self._measured_anchor()
            if (measured["step_p50"] is None
                    and measured["dispatch_p50"] is None):
                return None
            run = self._running
            corr = cal.observe(
                run.mesh,
                measured_step_p50=measured["step_p50"],
                measured_dispatch_p50=measured["dispatch_p50"],
            )
            self._c_calibrations.inc()
            out = corr.to_dict()
            emit_event(
                EventKind.OPTIMIZER_CALIBRATED,
                measured_step_p50_s=measured["step_p50"],
                measured_dispatch_p50_s=measured["dispatch_p50"],
                **{f"factor_{k}": v for k, v in out.items()
                   if k in ("compute", "comm", "dispatch")},
            )
            return out

    # -- candidate enumeration / pricing -------------------------------------

    def _knob_options(self, run: RunningConfig):
        meshes: List[MeshPlan] = [run.mesh]
        if self._mesh_candidates and run.world > 1:
            seen = {mesh_axes_key(run.mesh)}
            for m in candidate_plans(run.world):
                k = mesh_axes_key(m)
                if k not in seen:
                    seen.add(k)
                    meshes.append(m)
        windows = [run.train_window]
        if run.train_window == 0:
            windows.append(4)  # enable dispatch/compute overlap
        cal = self._ensure_calibrator()
        # the moe-dispatch family stays PARKED at the running mode even
        # now that ModelInfo carries num_experts: a dispatch-mode
        # change rebuilds the MODEL, and the worker's plan hook ignores
        # the knob while acking the rest of the plan — enumerating it
        # would let a fiction win the ranking and mark itself applied.
        # (MOE_DISPATCH_OPTIONS waits on a model-rebuild apply path.)
        moes = [run.moe_dispatch]
        # the chunked-dispatch family: only live-appliable on the
        # dispatch the worker reports running (grouped_ep) — on every
        # other mode the knob is a no-op the worker would ack but the
        # program would ignore
        chunk_opts = [max(1, run.dispatch_chunks)]
        # the wire-precision family rides the same gate: a precision
        # the running dispatch would silently ignore must not compete
        precision_opts = [run.moe_precision or "bf16"]
        if (cal is not None and cal.model.num_experts > 0
                and run.moe_dispatch == "grouped_ep"):
            chunk_opts = sorted(
                {max(1, run.dispatch_chunks), *DISPATCH_CHUNKS_OPTIONS})
            precision_opts = sorted(
                {run.moe_precision or "bf16", *MOE_PRECISION_OPTIONS})
        # the dense-wire family: parked unless the worker REPORTS a
        # dense-wire precision (i.e. the trainer manages the knob and a
        # live apply exists); per-MESH gating — only factorizations
        # that actually pay fsdp gathers differentiate the options —
        # happens in _price_candidates, the chunks_for_moe pattern
        fsdp_opts = [run.fsdp_precision or "bf16"]
        if run.fsdp_precision:
            fsdp_opts = sorted(
                {run.fsdp_precision or "bf16", *FSDP_PRECISION_OPTIONS})
        return (meshes, windows, moes, chunk_opts, precision_opts,
                fsdp_opts)

    def _price_candidates(self, run: RunningConfig
                          ) -> Tuple[List[CandidateScore], List[Dict]]:
        """Price every knob combination; returns (priced candidates,
        memory-rejected evidence). The memory-feasibility gate fires
        BEFORE pricing: a plan whose predicted peak HBM exceeds the
        device budget is recorded (once per mesh — the memory estimate
        is knob-invariant) instead of silently skipped, so the
        decision trail shows WHY a cheap-looking mesh never competed."""
        cal = self._ensure_calibrator()
        if cal is None:
            return [], []
        (meshes, windows, moes, chunk_opts,
         precision_opts, fsdp_opts) = self._knob_options(run)
        out: List[CandidateScore] = []
        memory_rejected: List[Dict] = []
        mem_seen: set = set()
        for mesh in meshes:
            # the dense-wire family only differentiates meshes that pay
            # fsdp gathers; elsewhere it would add identical-priced rows
            fsdp_for_mesh = (
                fsdp_opts
                if max(1, mesh.axis_sizes().get("fsdp", 1)) > 1
                else [run.fsdp_precision or "bf16"]
            )
            for w in windows:
                for moe in moes:
                    # the chunk family only differentiates the
                    # grouped_ep dispatch; pricing other modes at
                    # every C would add identical-priced rows
                    chunks_for_moe = (
                        chunk_opts if moe == "grouped_ep"
                        else [max(1, run.dispatch_chunks)]
                    )
                    precisions_for_moe = (
                        precision_opts if moe == "grouped_ep"
                        else [run.moe_precision or "bf16"]
                    )
                    combos = [
                        (ch, prec, fp)
                        for ch in chunks_for_moe
                        for prec in precisions_for_moe
                        for fp in fsdp_for_mesh
                    ]
                    for ch, prec, fp in combos:
                        try:
                            s = cal.price(
                                mesh, train_window=w,
                                moe_dispatch=moe,
                                dispatch_chunks=ch,
                                moe_precision=prec,
                                fsdp_precision=fp)
                        except MemoryInfeasibleError as e:
                            mkey = mesh_axes_key(mesh)
                            if mkey not in mem_seen:
                                mem_seen.add(mkey)
                                self._c_memory_rejected.inc()
                                memory_rejected.append({
                                    "mesh": _mesh_dict(mesh),
                                    "predicted_hbm_bytes":
                                        round(e.memory_bytes),
                                    "budget_bytes": round(
                                        e.budget_bytes),
                                })
                            break
                        except (ValueError, KeyError) as e:
                            logger.debug(
                                "candidate %s unpriceable: %s",
                                mesh, e)
                            break
                        out.append(CandidateScore(
                            mesh=mesh, train_window=w, moe_dispatch=moe,
                            dispatch_chunks=ch,
                            moe_precision=prec,
                            fsdp_precision=fp,
                            predicted_step_s=s,
                        ))
        # worst offender first: the trimmed decision evidence and the
        # PLAN_REJECTED event must name the true worst, not whichever
        # mesh enumeration happened to visit early
        memory_rejected.sort(key=lambda m: -m["predicted_hbm_bytes"])
        return out, memory_rejected

    def _input_bound_evidence(self) -> Optional[Dict]:
        """The input-bound judgement over the fresh node samples, on
        two legs: (a) peer-relative — the worst node's
        ``input_wait_frac`` at least ``BOUND_PEER_DELTA`` above the
        peer median (the straggler verdict's bound-label pattern,
        catching ONE starved node); (b) absolute — the cluster MEDIAN
        fraction at ``_INPUT_BOUND_ABS`` or above (uniform starvation
        from a shared slow source shows no peer excess at all).
        Returns the evidence dict when the job is input-bound, else
        None. A mesh replan reshapes device work; it
        cannot make the host produce batches faster, so a program plan
        chosen while this holds is rejected as ``input_bound``."""
        if not self._input_bound_gate:
            return None
        now = time.time()
        fracs: Dict[int, float] = {}
        for nid in self._store.node_ids():
            s = self._store.latest(nid)
            if s is None or now - getattr(s, "ts", now) > \
                    _CALIBRATION_FRESHNESS_S:
                continue
            frac = getattr(s, "input_wait_frac", None)
            if frac is not None:
                fracs[int(nid)] = float(frac)
        if not fracs:
            return None
        worst = max(fracs, key=fracs.get)
        peers = [f for n, f in fracs.items() if n != worst]
        # "input_bound_node", not "node": the evidence rides emit_event
        # kwargs, where a "node" field would clobber the record's own
        # node-identity stamp
        if peers:
            peer_median = statistics.median(peers)
            if fracs[worst] - peer_median >= BOUND_PEER_DELTA:
                return {
                    "input_bound_node": worst,
                    "input_wait_frac": round(fracs[worst], 4),
                    "peer_median_input_wait_frac": round(peer_median, 4),
                }
        median = statistics.median(fracs.values())
        if median >= _INPUT_BOUND_ABS:
            return {
                "input_bound_node": worst,
                "input_wait_frac": round(fracs[worst], 4),
                "median_input_wait_frac": round(median, 4),
            }
        return None

    @staticmethod
    def _wants_program(c: CandidateScore, run: RunningConfig) -> bool:
        """Whether the candidate changes the COMPILED program (mesh,
        dispatch chunking, or a wire precision) — the knobs whose
        apply pays a drain. A host-knob-only plan (train_window) stays
        appliable even on a data-starved job."""
        return (
            _mesh_dict(c.mesh) != _mesh_dict(run.mesh)
            or max(1, c.dispatch_chunks) != max(1, run.dispatch_chunks)
            or (c.moe_precision or "bf16")
            != (run.moe_precision or "bf16")
            or (c.fsdp_precision or "bf16")
            != (run.fsdp_precision or "bf16")
        )

    @staticmethod
    def _churn(c: CandidateScore, run: RunningConfig) -> int:
        """Tie-break distance from the current knobs: equal-price plans
        must prefer NOT changing anything."""
        cur = _mesh_dict(run.mesh)
        cand = _mesh_dict(c.mesh)
        return (
            int(cand != cur)
            + int(c.train_window != run.train_window)
            + int((c.moe_dispatch or "") != (run.moe_dispatch or ""))
            + int(max(1, c.dispatch_chunks)
                  != max(1, run.dispatch_chunks))
            + int((c.moe_precision or "bf16")
                  != (run.moe_precision or "bf16"))
            + int((c.fsdp_precision or "bf16")
                  != (run.fsdp_precision or "bf16"))
        )

    # -- the re-plan pass ----------------------------------------------------

    def replan(self, trigger: str) -> Optional[Decision]:
        """Calibrate, enumerate, price, decide, publish. Returns the
        recorded Decision (None when disabled or nothing is known yet
        about the running job)."""
        if not self._enabled:
            return None
        with self._lock:
            run = self._running
            if run is None:
                logger.info("replan(%s) skipped: no running config "
                            "reported yet", trigger)
                return None
            # adopt the ambient incident id when one is open (the
            # verdict listener fires inside the verdict's trace scope,
            # an RPC-triggered replan inside the caller's) so the
            # DIAG_* verdict and the OPTIMIZER_* decision trail merge
            # into ONE incident in `tpurun trace`
            with trace_scope(current_trace_id() or None) as tid:
                return self._replan_locked(trigger, run, tid)

    def _replan_locked(self, trigger: str, run: RunningConfig,
                       tid: str) -> Optional[Decision]:
        self._c_replans.inc()
        durability_ev = self._durability_evidence(trigger)
        corrections = self.calibrate() or (
            self._calibrator.corrections.to_dict()
            if self._calibrator is not None else {}
        )
        cal = self._ensure_calibrator()
        if cal is None:
            return None
        # require_fit=False: the current config is OBSERVABLY running,
        # whatever the analytic memory model thinks of it
        current_s = cal.price(
            run.mesh, train_window=run.train_window,
            moe_dispatch=run.moe_dispatch,
            dispatch_chunks=run.dispatch_chunks,
            moe_precision=run.moe_precision,
            fsdp_precision=run.fsdp_precision, require_fit=False,
        )
        priced, memory_rejected = self._price_candidates(run)
        candidates = [c for c in priced
                      if c.key not in self._failed_keys]
        if memory_rejected:
            # the memory-feasibility gate fired: one PLAN_REJECTED
            # record per pass carrying the evidence (which meshes, how
            # far over budget) — visible in `tpurun plan` and
            # `tpurun attribution`. Decision evidence keeps the 8
            # worst; the event carries the full count.
            worst = memory_rejected[0]
            total_rejected = len(memory_rejected)
            memory_rejected = memory_rejected[:8]
            emit_event(
                EventKind.OPTIMIZER_PLAN_REJECTED,
                trigger=trigger,
                reason="memory_infeasible",
                rejected_meshes=total_rejected,
                mesh=worst["mesh"],
                predicted_hbm_mb=round(
                    worst["predicted_hbm_bytes"] / 1e6, 1),
                budget_mb=round(worst["budget_bytes"] / 1e6, 1),
            )
            logger.info(
                "replan(%s): %d candidate mesh(es) memory-infeasible "
                "(worst %s needs %.1f MB > %.1f MB budget)",
                trigger, total_rejected, worst["mesh"],
                worst["predicted_hbm_bytes"] / 1e6,
                worst["budget_bytes"] / 1e6,
            )
        if not candidates:
            if memory_rejected:
                # every candidate died at the gate: the pass itself is
                # a recorded rejection, not a silent no-op
                decision = Decision(
                    trigger=trigger, trace_id=tid, ts=time.time(),
                    current=run.to_dict(),
                    current_predicted_s=current_s,
                    corrections=corrections,
                    memory_rejected=memory_rejected,
                    durability=durability_ev,
                )
                self._reject(decision, "memory_infeasible:all")
                self._decisions.append(decision)
                return decision
            return None
        for c in candidates:
            c.speedup = current_s / max(c.predicted_step_s, 1e-12)
        candidates.sort(
            key=lambda c: (c.predicted_step_s, self._churn(c, run)))
        table = [c.to_dict() for c in candidates[:_TABLE_ROWS]]
        decision = Decision(
            trigger=trigger, trace_id=tid, ts=time.time(),
            current=run.to_dict(), current_predicted_s=current_s,
            candidates=table, corrections=corrections,
            memory_rejected=memory_rejected,
            durability=durability_ev,
        )
        best = candidates[0]
        decision.predicted_speedup = best.speedup
        emit_event(
            EventKind.OPTIMIZER_REPLAN, trigger=trigger,
            candidates_priced=len(candidates),
            current_predicted_s=round(current_s, 6),
            best_predicted_s=round(best.predicted_step_s, 6),
            best_speedup=round(best.speedup, 3),
        )
        input_ev = self._input_bound_evidence()
        if input_ev is not None and (
            self._churn(best, run) == 0
            or self._wants_program(best, run)
        ):
            # the INPUT-BOUND gate, checked before every other verdict
            # on the pass: a starved input pipeline poisons the
            # calibration in BOTH directions (the anchor p50 includes
            # host wait the cost model books as device work), so
            # "already optimal" and "8x from a new mesh" are equally
            # fictional — and a program drain cannot make the host
            # produce batches faster. The pass is rejected with the
            # starvation evidence instead; only a host-knob-only plan
            # (train_window) passes through. The gate does not consume
            # the cooldown, so the same plan is immediately proposable
            # once the starvation clears.
            decision.input_bound = dict(input_ev)
            self._reject(decision, "input_bound", **input_ev)
        elif self._churn(best, run) == 0:
            self._reject(decision, "already_optimal")
        elif best.speedup < self._min_speedup:
            self._reject(
                decision,
                f"hysteresis:{best.speedup:.2f}<{self._min_speedup:.2f}",
            )
        elif not self._cooldown.check(best.key):
            self._reject(
                decision,
                "cooldown:%.0fs" % self._cooldown.seconds_remaining(
                    best.key),
            )
        else:
            self._choose(decision, best)
        self._decisions.append(decision)
        return decision

    def _reject(self, decision: Decision, reason: str,
                **evidence) -> None:
        decision.outcome = "rejected"
        decision.reason = reason
        self._c_rejected.inc()
        emit_event(
            EventKind.OPTIMIZER_PLAN_REJECTED,
            trigger=decision.trigger, reason=reason,
            predicted_speedup=round(decision.predicted_speedup, 3),
            **evidence,
        )
        logger.info("replan(%s): no plan published (%s)",
                    decision.trigger, reason)

    def _choose(self, decision: Decision, best: CandidateScore) -> None:
        self._plan_seq += 1
        plan_id = f"plan-{self._plan_seq}"
        decision.outcome = "chosen"
        decision.plan_id = plan_id
        decision.chosen = best.to_dict()
        decision.chosen_key = best.key
        self._c_chosen.inc()
        # UNCHANGED knobs are published as their "leave it alone"
        # sentinels (None / -1 / 0 / ""), so the worker can tell a
        # host-knob-only plan from a compiled-program change (the
        # multi-host guard keys off exactly that)
        cur = decision.current
        mesh_changed = _mesh_dict(best.mesh) != cur.get("mesh")
        cfg = comm.ParallelConfig(
            mesh_shape=_mesh_dict(best.mesh) if mesh_changed else None,
            train_window=(best.train_window
                          if best.train_window != cur.get("train_window")
                          else -1),
            moe_dispatch=(best.moe_dispatch
                          if (best.moe_dispatch or "")
                          != (cur.get("moe_dispatch") or "") else ""),
            dispatch_chunks=(
                best.dispatch_chunks
                if max(1, best.dispatch_chunks)
                != max(1, cur.get("dispatch_chunks") or 1) else 0),
            moe_precision=(
                best.moe_precision
                if (best.moe_precision or "bf16")
                != (cur.get("moe_precision") or "bf16") else ""),
            fsdp_precision=(
                best.fsdp_precision
                if (best.fsdp_precision or "bf16")
                != (cur.get("fsdp_precision") or "bf16") else ""),
            plan_id=plan_id,
            trace_id=decision.trace_id,
            predicted_speedup=round(best.speedup, 3),
            prewarm=True,
        )
        self._pending = cfg
        emit_event(
            EventKind.OPTIMIZER_PLAN_CHOSEN,
            plan_id=plan_id, trigger=decision.trigger,
            predicted_speedup=round(best.speedup, 3),
            predicted_step_s=round(best.predicted_step_s, 6),
            **{f"knob_{k}": v for k, v in best.to_dict().items()
               if k in ("train_window", "moe_dispatch", "dispatch_chunks",
                        "moe_precision", "fsdp_precision")},
            mesh=_mesh_dict(best.mesh),
        )
        logger.info(
            "replan(%s): chose %s (predicted %.2fx, plan %s)",
            decision.trigger, best.key, best.speedup, plan_id,
        )
        if self._publish is not None:
            self._publish(cfg)

    # -- queries -------------------------------------------------------------

    def exposed_comm_view(self) -> Optional[Dict]:
        """Predicted vs measured exposed-comm fraction for the RUNNING
        config, side by side — the operator's check that the overlap
        the planner paid for actually materialized. Predicted comes
        from the overlap-aware ``estimate`` breakdown at the running
        knobs; measured is the median of the fresh nodes'
        ``exposed_comm_frac`` gauges (PR 8's attribution plane — an
        UPPER bound, so measured modestly above predicted is healthy;
        measured near the C=1 serial prediction means the overlap never
        happened). None when nothing is running yet."""
        import dataclasses as _dc

        from dlrover_tpu.parallel.planner import estimate

        with self._lock:
            cal = self._ensure_calibrator()
            run = self._running
            if cal is None or run is None:
                return None
            model = cal.model
            if run.moe_dispatch and run.moe_dispatch != model.moe_dispatch:
                model = _dc.replace(model, moe_dispatch=run.moe_dispatch)
            if max(1, run.dispatch_chunks) != model.moe_dispatch_chunks:
                model = _dc.replace(
                    model,
                    moe_dispatch_chunks=max(1, run.dispatch_chunks))
            if (run.moe_precision or "bf16") != model.moe_precision:
                model = _dc.replace(
                    model, moe_precision=run.moe_precision or "bf16")
            if (run.fsdp_precision or "bf16") != model.fsdp_precision:
                model = _dc.replace(
                    model, fsdp_precision=run.fsdp_precision or "bf16")
            score = estimate(run.mesh, model, self._device)
            predicted = score.breakdown.get("exposed_comm_frac")
            now = time.time()
            fracs: List[float] = []
            for nid in self._store.node_ids():
                s = self._store.latest(nid)
                if s is None or now - getattr(s, "ts", now) > \
                        _CALIBRATION_FRESHNESS_S:
                    continue
                f = getattr(s, "exposed_comm_frac", None)
                if f is not None:
                    fracs.append(float(f))
        return {
            "predicted": (round(float(predicted), 4)
                          if predicted is not None else None),
            "measured": (round(statistics.median(fracs), 4)
                         if fracs else None),
            "nodes_measured": len(fracs),
            "dispatch_chunks": max(1, run.dispatch_chunks),
        }

    def pending_plan(self) -> Optional[comm.ParallelConfig]:
        with self._lock:
            return self._pending

    def decisions(self, limit: int = 0) -> List[Dict]:
        with self._lock:
            out = [d.to_dict() for d in self._decisions]
        return out[-limit:] if limit else out

    def memory_rejections(self, limit: int = 0) -> List[Dict]:
        """Every memory-feasibility rejection in the retained decision
        trail, newest last — the ``tpurun attribution`` evidence of
        which candidate plans the devices could not hold."""
        with self._lock:
            out = [
                {"ts": d.ts, "trigger": d.trigger,
                 "trace_id": d.trace_id, **m}
                for d in self._decisions for m in d.memory_rejected
            ]
        return out[-limit:] if limit else out

    def to_report(self, limit: int = 0) -> Dict:
        """The ``tpurun plan --addr`` payload."""
        with self._lock:
            running = self._running.to_dict() if self._running else None
            serving = dict(self._serving) if self._serving else None
            corr = (self._calibrator.corrections.to_dict()
                    if self._calibrator is not None else None)
            pending = self._pending
        return {
            "enabled": self._enabled,
            "running": running,
            "serving": serving,
            "corrections": corr,
            "min_speedup": self._min_speedup,
            "cooldown_secs": self._cooldown.cooldown_secs,
            "exposed_comm": self.exposed_comm_view(),
            "pending_plan": {
                "plan_id": pending.plan_id,
                "mesh": dict(pending.mesh_shape or {}),
                "train_window": pending.train_window,
                "moe_dispatch": pending.moe_dispatch,
                "dispatch_chunks": getattr(
                    pending, "dispatch_chunks", 0),
                "moe_precision": getattr(
                    pending, "moe_precision", ""),
                "predicted_speedup": pending.predicted_speedup,
                "trace_id": pending.trace_id,
            } if pending is not None else None,
            "decisions": self.decisions(limit),
        }


# -- forensic decision trail (tpurun plan --events) ---------------------------

_OPTIMIZER_KINDS = (
    EventKind.OPTIMIZER_REPLAN,
    EventKind.OPTIMIZER_CALIBRATED,
    EventKind.OPTIMIZER_PLAN_CHOSEN,
    EventKind.OPTIMIZER_PLAN_REJECTED,
    EventKind.OPTIMIZER_APPLY_BEGIN,
    EventKind.OPTIMIZER_APPLY_DONE,
    EventKind.OPTIMIZER_APPLIED,
)


def decision_trail_from_events(records: List[Dict]) -> Dict:
    """Reconstruct the decision trail from a (merged, multi-process)
    event timeline: master-side decisions joined to worker-side applies
    by plan id / trace id — the forensic ``tpurun plan --events`` view.
    """
    trail = [r for r in records if r.get("kind") in _OPTIMIZER_KINDS]
    plans: Dict[str, Dict] = {}
    for rec in trail:
        kind = rec.get("kind")
        pid = rec.get("plan_id", "")
        if not pid:
            continue
        p = plans.setdefault(pid, {"plan_id": pid})
        if kind == EventKind.OPTIMIZER_PLAN_CHOSEN:
            p.update(
                chosen_ts=rec.get("ts"),
                trigger=rec.get("trigger", ""),
                trace_id=rec.get("trace_id", ""),
                predicted_speedup=rec.get("predicted_speedup"),
                mesh=rec.get("mesh"),
                train_window=rec.get("knob_train_window"),
            )
        elif kind == EventKind.OPTIMIZER_APPLY_BEGIN:
            p["apply_begin_ts"] = rec.get("ts")
        elif kind == EventKind.OPTIMIZER_APPLY_DONE:
            p["apply_done_ts"] = rec.get("ts")
            p["apply_seconds"] = rec.get("seconds")
            p["recompiled"] = rec.get("recompiled")
            if rec.get("error_code"):
                p["apply_error"] = rec.get("error_code")
        elif kind == EventKind.OPTIMIZER_APPLIED:
            p["realized_speedup"] = rec.get("realized_speedup")
            p["applied_predicted_speedup"] = rec.get("predicted_speedup")
    return {
        "events": len(trail),
        "plans": [plans[k] for k in sorted(
            plans, key=lambda k: plans[k].get("chosen_ts") or 0.0)],
        "trail": trail,
    }
