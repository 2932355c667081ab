"""Master-side continuous durability audit: the recovery-readiness
plane.

PR 15's replication plane is only observable AFTER a failure — nothing
answers "if node N dies right now, do we survive, via which rung, and
how long does it take?". This auditor sweeps the ``ReplicaDirectory``'s
admitted assignments against the stores' live ``inventory()`` facts on
the master's stats tick and keeps three judgements current:

* **coverage** — every owner's regions committed on at least the
  admitted k live peer holders (a holder counts only with a committed,
  crc-checked manifest — the store refuses anything else);
* **staleness** — the newest fully-held replica step may trail the
  owner's reported step by at most ``readiness_stale_factor`` × the
  master-computed cadence;
* **budget** — the admitted k reached the requested k, and no holder
  sits over its declared DRAM budget.

A node whose owner regions fail any dimension gets a ``DIAG_DURABILITY``
verdict (failure-class, error-coded, evidence attached, fresh incident
trace id) delivered through the same listener machinery as the
straggler detector — so the RuntimeOptimizer's ``on_verdict`` fires a
``durability:<node>`` re-plan under the verdict's trace scope, and the
whole verdict → replan → clear arc shares one incident id. The cluster
posture edge (any node at risk ⇄ none) emits ``READINESS_DEGRADED`` /
``READINESS_RESTORED`` — the mttr ``durability_at_risk`` scenario.

Each sweep also prices the **blast radius** of every node: the best
survivable rung of the recovery ladder (live_reshard / peer_rebuild /
storage_restore / init) with a predicted MTTR from the calibrated
``RungPricer`` (drain + fetch-bytes/link-bw + device_put — the
round-14 CPU run's decomposition, EMA-corrected against every realized
incident). The table feeds the ``{node=,rung=}`` gauges, the
``ReadinessRequest`` RPC behind ``tpurun readiness``, and — attached to
recovery plans — the worker's priced rung choice in
``trainer/failover`` / ``ElasticTrainer.prepare``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from dlrover_tpu.common.config import get_context
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.master.monitor.straggler import (
    VERDICT_HEALTHY,
    NodeVerdict,
)
from dlrover_tpu.telemetry import (
    EventKind,
    emit_event,
    get_registry,
    names as tm,
)
from dlrover_tpu.telemetry.events import default_events_path, read_events
from dlrover_tpu.telemetry.mttr import derive_incidents
from dlrover_tpu.telemetry.readiness import (
    RUNG_INDEX,
    RUNG_INIT,
    RUNG_LADDER,
    RUNG_LIVE_RESHARD,
    RUNG_PEER_REBUILD,
    RUNG_STORAGE_RESTORE,
    RungPricer,
    cheapest_viable_rung,
)
from dlrover_tpu.telemetry.trace_context import new_trace_id, trace_scope

logger = get_logger("master.readiness")

VERDICT_DURABILITY = "durability"


def _default_inventory_fn(endpoints: List[Dict[str, Any]]
                          ) -> Dict[str, Dict[str, Any]]:
    """The live sweep: one ReplicaInfoRequest per reachable store,
    over the same cached retrying channels the fetch side uses."""
    from dlrover_tpu.checkpoint.replication import (
        _collect_inventories,
        replica_channel_factory,
    )

    factory, close = replica_channel_factory()
    try:
        return _collect_inventories(endpoints, factory)
    finally:
        close()


class ReadinessAuditor:
    """Continuous durability audit + per-node blast-radius pricing.

    Ticked from the master's stats loop (``sweep()`` self-paces by
    ``readiness_sweep_secs``); ``sweep(force=True)`` runs regardless —
    the RPC handler's refresh path and tests. Verdict listeners follow
    the StragglerDetector contract exactly: ``fn(node_id, verdict)``
    called OUTSIDE the auditor lock, under the verdict's trace scope.
    """

    def __init__(
        self,
        directory,
        cadence_fn: Callable[[], int],
        replicas_fn: Callable[[], int],
        inventory_fn: Optional[Callable] = None,
        sweep_secs: Optional[float] = None,
        stale_factor: Optional[float] = None,
    ):
        ctx = get_context()
        self._directory = directory
        self._cadence_fn = cadence_fn
        self._replicas_fn = replicas_fn
        self._inventory_fn = inventory_fn or _default_inventory_fn
        self._sweep_secs = float(
            sweep_secs if sweep_secs is not None
            else getattr(ctx, "readiness_sweep_secs", 30.0))
        self._stale_factor = float(
            stale_factor if stale_factor is not None
            else getattr(ctx, "readiness_stale_factor", 2.0))
        self.pricer = RungPricer()
        self._lock = threading.Lock()
        self._last_sweep = 0.0
        self._sweeps = 0
        self._verdicts: Dict[int, NodeVerdict] = {}
        self._listeners: List = []
        self._pending_notices: List[Tuple[int, str, str]] = []
        # cluster posture: the trace id of the open READINESS_DEGRADED
        # edge (None = ready)
        self._degraded_tid: Optional[str] = None
        # per-node snapshot of the last sweep (report() serves it)
        self._nodes: Dict[int, Dict[str, Any]] = {}
        self._admitted: Dict[str, Any] = {}
        # calibration bookkeeping: push cycles already folded in
        # (node -> registration ts) and incidents already EMA'd
        self._seen_push: Dict[int, float] = {}
        self._seen_incidents: Set[Tuple[str, float]] = set()
        self._events_mtime = 0.0
        # gauge label sets currently exported, for retraction
        self._exported: Dict[str, Set[Tuple[Tuple[str, str], ...]]] = {}
        reg = get_registry()
        self._c_sweeps = reg.counter(
            tm.READINESS_SWEEPS, help="durability audit sweeps completed")
        self._h_sweep = reg.histogram(
            tm.READINESS_SWEEP_TIME, help="wall seconds of one sweep")
        self._c_flags = reg.counter(
            tm.DIAG_DURABILITY_FLAGS,
            help="durability verdicts confirmed by the audit")
        self._c_recoveries = reg.counter(
            tm.DIAG_RECOVERIES, help="verdicts cleared by recovery")

    # -- listener machinery (the StragglerDetector contract) -----------------

    def add_verdict_listener(self, fn) -> None:
        self._listeners.append(fn)

    def _notify(self, node_id: int, verdict: str, trace_id: str) -> None:
        self._pending_notices.append((node_id, verdict, trace_id))

    def _drain_notices(self) -> None:
        with self._lock:
            pending, self._pending_notices = self._pending_notices, []
        for node_id, verdict, tid in pending:
            with trace_scope(tid or None):
                for fn in self._listeners:
                    try:
                        fn(node_id, verdict)
                    except Exception:  # noqa: BLE001 — a listener must
                        # not kill the audit tick
                        logger.exception(
                            "readiness verdict listener failed for node "
                            "%d (%s)", node_id, verdict)

    # -- calibration feeds ---------------------------------------------------

    def _calibrate_from_directory(self, nodes: Dict[str, Dict]) -> None:
        """Fold each node's newest push-cycle stats in exactly once
        (keyed by registration ts — re-reading the same cycle would
        over-weight it in the EMA)."""
        for key, info in nodes.items():
            try:
                node_id = int(key)
            except (TypeError, ValueError):
                continue
            ts = float(info.get("ts", 0.0))
            if ts <= self._seen_push.get(node_id, 0.0):
                continue
            pb = float(info.get("push_bytes", 0.0) or 0.0)
            ps = float(info.get("push_seconds", 0.0) or 0.0)
            if pb > 0 and ps > 0:
                self.pricer.observe_push(pb, ps)
                self._seen_push[node_id] = ts

    def _calibrate_from_events(self) -> None:
        """EMA-correct rung prices against every newly CLOSED incident
        in the shared timeline, and feed the device_put leg from
        stamped rebuild events. Gated on the file's mtime so a quiet
        timeline costs one stat call per sweep."""
        path = default_events_path()
        if not path or not os.path.exists(path):
            return
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            return
        if mtime <= self._events_mtime:
            return
        self._events_mtime = mtime
        try:
            events = read_events(path)
        except Exception:  # noqa: BLE001 — a torn timeline read only
            # delays calibration to the next sweep
            logger.exception("readiness calibration read failed")
            return
        from dlrover_tpu.telemetry.readiness import SCENARIO_RUNG

        for inc in derive_incidents(events):
            realized = inc.get("recovery_seconds")
            rung = SCENARIO_RUNG.get(inc.get("scenario", ""))
            started = inc.get("started_ts")
            if realized is None or rung is None or started is None:
                continue
            key = (inc["scenario"], round(float(started), 6))
            if key in self._seen_incidents:
                continue
            self._seen_incidents.add(key)
            self.pricer.observe_realized(rung, float(realized))
        for rec in events:
            if rec.get("kind") != EventKind.PEER_REBUILD_DONE:
                continue
            try:
                put_s = float(rec.get("put_seconds", 0.0) or 0.0)
                put_b = float(rec.get("bytes_from_peers", 0.0) or 0.0)
                pred = float(rec.get("predicted_mttr_s", 0.0) or 0.0)
                realz = float(rec.get("realized_mttr_s", 0.0) or 0.0)
            except (TypeError, ValueError):
                continue
            key = ("put", round(float(rec.get("ts", 0.0)), 6))
            if key in self._seen_incidents:
                continue
            self._seen_incidents.add(key)
            if put_s > 0 and put_b > 0:
                self.pricer.observe_put(put_b, put_s)
            # the worker stamped its own predicted-vs-realized pair:
            # the exact signal the multiplicative correction EMA wants
            if pred > 0 and realz > 0:
                self.pricer.observe_realized(
                    RUNG_PEER_REBUILD, realz, predicted_s=pred)

    # -- gauge export (absent-not-zero + retract) ----------------------------

    def _export(self, reg, name: str, help_: str,
                values: Dict[Tuple[Tuple[str, str], ...], float]) -> None:
        """Set one gauge family's series to exactly ``values`` —
        departed label sets are RETRACTED, never left at a stale
        number."""
        prev = self._exported.get(name, set())
        for labels in prev - set(values):
            reg.remove(name, labels=dict(labels))
        for labels, value in values.items():
            reg.gauge(name, help=help_, labels=dict(labels)).set(value)
        self._exported[name] = set(values)

    def _export_gauges(self, reg, admitted: Dict,
                       per_node: Dict[int, Dict]) -> None:
        def node_label(n) -> Tuple[Tuple[str, str], ...]:
            return (("node", str(n)),)

        self._export(
            reg, tm.REPLICA_HOLDER_LOAD_MB,
            "assigned peer-replica load per holder (MB)",
            {node_label(n): round(v, 3)
             for n, v in (admitted.get("load") or {}).items()})
        self._export(
            reg, tm.REPLICA_HOLDER_HEADROOM_MB,
            "holder DRAM budget minus assigned load (MB; absent when "
            "the holder is uncapped)",
            {node_label(n): round(v, 3)
             for n, v in (admitted.get("headroom_mb") or {}).items()})
        # plan-wide scalars: exported only while the plane is on
        # (requested > 0) — absent-not-zero
        scalars: Dict[Tuple[Tuple[str, str], ...], float] = {}
        if int(admitted.get("requested", 0)) > 0:
            scalars[()] = float(admitted.get("replicas", 0))
        self._export(reg, tm.REPLICA_ASSIGNED_K,
                     "admitted replica count k", scalars)
        degraded: Dict[Tuple[Tuple[str, str], ...], float] = {}
        if int(admitted.get("requested", 0)) > 0:
            degraded[()] = float(
                int(admitted["requested"]) - int(admitted["replicas"]))
        self._export(reg, tm.REPLICA_DEGRADED_K,
                     "requested minus admitted replica count", degraded)
        self._export(
            reg, tm.READINESS_COVERAGE,
            "1 = owner regions on >= k live committed holders",
            {node_label(n): 1.0 if d["coverage_ok"] else 0.0
             for n, d in per_node.items() if d.get("owner")})
        self._export(
            reg, tm.READINESS_STALENESS,
            "steps the newest fully-held replica group trails the owner",
            {node_label(n): float(d["staleness_steps"])
             for n, d in per_node.items()
             if d.get("owner") and d.get("staleness_steps") is not None})
        self._export(
            reg, tm.READINESS_BEST_RUNG,
            "best survivable rung index (0=live_reshard..3=init)",
            {node_label(n): float(RUNG_INDEX[d["best_rung"]])
             for n, d in per_node.items() if d.get("best_rung")})
        self._export(
            reg, tm.READINESS_PREDICTED_MTTR,
            "predicted MTTR of rung {rung=} for node {node=} (seconds)",
            {(("node", str(n)), ("rung", rung)): s
             for n, d in per_node.items()
             for rung, s in (d.get("predicted_mttr") or {}).items()})

    # -- the sweep -----------------------------------------------------------

    def sweep(self, now: Optional[float] = None,
              force: bool = False) -> Optional[Dict[str, Any]]:
        """One audit pass. Self-paced unless forced; returns the sweep
        summary, or None when the interval gate skipped it."""
        now = time.time() if now is None else now
        with self._lock:
            if not force and (
                self._sweep_secs <= 0
                or now - self._last_sweep < self._sweep_secs
            ):
                return None
            self._last_sweep = now
        t0 = time.monotonic()
        requested = int(self._replicas_fn())
        report = self._directory.to_report()
        nodes = report.get("nodes", {})
        failed = set(int(f) for f in report.get("failed", []))
        self._calibrate_from_directory(nodes)
        self._calibrate_from_events()
        admitted = self._directory.admitted_replicas(requested)
        k = int(admitted.get("replicas", 0))
        cadence = int(self._cadence_fn() or 0)
        allowed_steps = (
            int(self._stale_factor * cadence) if cadence > 0 else None)

        # live inventory sweep over every registered endpoint that is
        # not known-failed (a dead store simply doesn't answer — its
        # holdings drop out of coverage, which IS the detection)
        endpoints = [
            {"addr": info.get("addr", ""), "node_id": key}
            for key, info in nodes.items()
            if int(key) not in failed
        ]
        inventories = self._inventory_fn(endpoints) if endpoints else {}
        addr_to_node = {
            info.get("addr", ""): int(key) for key, info in nodes.items()
        }
        # owner -> {holder node -> newest committed step for that owner}
        held: Dict[int, Dict[int, int]] = {}
        for addr, inv in inventories.items():
            holder = addr_to_node.get(addr)
            if holder is None:
                continue
            for owner_key, entry in (inv or {}).items():
                try:
                    owner = int(owner_key)
                    steps = entry.get("steps") or {
                        str(entry["step"]): entry.get("manifest", {})}
                    newest = max(int(s) for s in steps)
                except (TypeError, ValueError, KeyError):
                    continue
                cur = held.setdefault(owner, {})
                cur[holder] = max(cur.get(holder, -1), newest)

        per_node: Dict[int, Dict[str, Any]] = {}
        at_risk: Dict[int, Tuple[str, Dict[str, Any]]] = {}
        for key, info in nodes.items():
            node_id = int(key)
            owner = float(info.get("snapshot_mb", 0.0)) > 0
            lender = float(info.get("budget_mb", 0.0)) >= 0
            region_bytes = float(info.get("snapshot_mb", 0.0)) * 1024 * 1024
            owner_step = int(info.get("step", -1))
            holders = dict(held.get(node_id, {}))
            peer_holders = {
                h: s for h, s in holders.items()
                if h != node_id and h not in failed
            }
            detail: Dict[str, Any] = {
                "owner": owner,
                "lender": lender,
                "failed": node_id in failed,
                "regions_mb": round(float(info.get("snapshot_mb", 0.0)), 3),
                "holders": sorted(peer_holders),
                "coverage_ok": True,
                "staleness_steps": None,
            }
            verdict: Optional[Tuple[str, Dict[str, Any]]] = None
            if owner and requested > 0 and node_id not in failed:
                required = max(1, k)
                # the newest step held by >= required peer holders: the
                # step a rebuild of THIS node would actually come back at
                steps_held = sorted(peer_holders.values(), reverse=True)
                covered_step = (
                    steps_held[required - 1]
                    if len(steps_held) >= required else None)
                if k == 0:
                    detail["coverage_ok"] = False
                    verdict = ("REPLICA_BUDGET", {
                        "requested": requested, "admitted": k,
                        "reason": admitted.get("reason", ""),
                    })
                elif covered_step is None:
                    detail["coverage_ok"] = False
                    verdict = ("DURABILITY_COVERAGE", {
                        "required": required,
                        "held": len(peer_holders),
                        "holders": sorted(peer_holders),
                        "requested": requested, "admitted": k,
                    })
                else:
                    staleness = max(0, owner_step - covered_step) \
                        if owner_step >= 0 else 0
                    detail["staleness_steps"] = staleness
                    detail["covered_step"] = covered_step
                    if (allowed_steps is not None
                            and staleness > allowed_steps):
                        verdict = ("REPLICA_STALE", {
                            "staleness_steps": staleness,
                            "allowed_steps": allowed_steps,
                            "owner_step": owner_step,
                            "covered_step": covered_step,
                        })
            # blast radius: the ladder this node's death is survivable
            # through, priced with drain=0 (a dead node drains nothing)
            viable = {
                # nothing of this node's training state is lost when it
                # owns no regions: the survivors absorb the membership
                # change in-process
                RUNG_LIVE_RESHARD: not owner,
                RUNG_PEER_REBUILD: owner and detail["coverage_ok"]
                and verdict is None and requested > 0,
                RUNG_STORAGE_RESTORE: True,
                RUNG_INIT: True,
            }
            table = self.pricer.table(region_bytes, drain_s=0.0)
            detail["predicted_mttr"] = table
            detail["best_rung"] = cheapest_viable_rung(table, viable)
            per_node[node_id] = detail
            if verdict is not None:
                at_risk[node_id] = verdict

        self._flag_and_clear(at_risk, per_node, now)
        reg = get_registry()
        self._export_gauges(reg, admitted, per_node)
        sweep_s = time.monotonic() - t0
        self._c_sweeps.inc()
        self._h_sweep.observe(sweep_s)
        with self._lock:
            self._sweeps += 1
            self._nodes = per_node
            self._admitted = {
                kk: vv for kk, vv in admitted.items()
                if kk != "assignments"
            }
            summary = self._report_locked(now)
        self._drain_notices()
        return summary

    def _flag_and_clear(self, at_risk: Dict[int, Tuple[str, Dict]],
                        per_node: Dict[int, Dict],
                        now: float) -> None:
        with self._lock:
            for node_id, (code, evidence) in at_risk.items():
                cur = self._verdicts.get(node_id)
                if cur is not None:
                    # refresh evidence; the incident stays open under
                    # its original trace id
                    cur.evidence = dict(evidence)
                    continue
                tid = new_trace_id()
                self._verdicts[node_id] = NodeVerdict(
                    node_id=node_id, verdict=VERDICT_DURABILITY,
                    since_ts=now, trace_id=tid, evidence=dict(evidence),
                )
                self._c_flags.inc()
                emit_event(EventKind.DIAG_DURABILITY, error_code=code,
                           trace_id=tid, diag_node=node_id, **evidence)
                logger.warning(
                    "node %d durability at risk [%s] %s: %s",
                    node_id, tid, code, evidence)
                self._notify(node_id, VERDICT_DURABILITY, tid)
            for node_id in [n for n in self._verdicts if n not in at_risk]:
                cur = self._verdicts.pop(node_id)
                self._c_recoveries.inc()
                emit_event(
                    EventKind.DIAG_RECOVERED, trace_id=cur.trace_id,
                    diag_node=node_id, was=VERDICT_DURABILITY,
                    flagged_seconds=round(now - cur.since_ts, 1))
                logger.info(
                    "node %d durability restored", node_id)
                self._notify(node_id, VERDICT_HEALTHY, cur.trace_id)
            # the cluster posture edge (the mttr durability_at_risk
            # scenario): first node at risk opens it, last clear
            # closes it under the SAME trace id
            if self._verdicts and self._degraded_tid is None:
                first = min(
                    self._verdicts.values(), key=lambda v: v.since_ts)
                self._degraded_tid = first.trace_id
                code = next(iter(at_risk.values()))[0] if at_risk \
                    else "DURABILITY_COVERAGE"
                emit_event(
                    EventKind.READINESS_DEGRADED, error_code=code,
                    trace_id=self._degraded_tid,
                    nodes=sorted(self._verdicts))
            elif not self._verdicts and self._degraded_tid is not None:
                emit_event(EventKind.READINESS_RESTORED,
                           trace_id=self._degraded_tid)
                self._degraded_tid = None
                emit_event(
                    EventKind.READINESS_SWEEP, posture="ready",
                    at_risk=0, nodes=len(per_node))
            if self._verdicts and self._degraded_tid is not None \
                    and at_risk:
                # posture-change summary (only while something changed
                # this sweep — a steady degraded state does not spam
                # the timeline)
                new_flags = [
                    n for n in at_risk
                    if self._verdicts.get(n) is not None
                    and self._verdicts[n].since_ts == now
                ]
                if new_flags:
                    emit_event(
                        EventKind.READINESS_SWEEP, posture="degraded",
                        at_risk=len(self._verdicts),
                        nodes=len(per_node))

    # -- views ---------------------------------------------------------------

    def _report_locked(self, now: float) -> Dict[str, Any]:
        return {
            "posture": ("degraded" if self._verdicts else "ready"),
            "at_risk": {
                str(n): v.to_dict() for n, v in self._verdicts.items()
            },
            "at_risk_nodes": sorted(str(n) for n in self._verdicts),
            "nodes": {
                str(n): dict(d) for n, d in self._nodes.items()
            },
            "admitted": dict(self._admitted),
            "calibration": self.pricer.to_dict(),
            "ladder": list(RUNG_LADDER),
            "sweeps": self._sweeps,
            "swept_ts": self._last_sweep,
            "ts": now,
        }

    def report(self) -> Dict[str, Any]:
        """The ReadinessRequest RPC payload (and `tpurun readiness
        --addr`'s live view)."""
        with self._lock:
            return self._report_locked(time.time())

    def verdicts(self) -> Dict[int, NodeVerdict]:
        with self._lock:
            return dict(self._verdicts)

    def predicted_mttr_table(self, node_id: int = -1) -> Dict[str, float]:
        """The per-rung predicted-MTTR table for ``node_id`` — what
        recovery plans attach so the worker's rung choice is the priced
        one. Calibration is refreshed from the directory's push stats
        and the event timeline first (both local reads, no RPC): a plan
        requested before the first periodic sweep still gets real
        prices, not priors."""
        try:
            nodes = self._directory.to_report().get("nodes", {})
        except Exception:  # noqa: BLE001 — price from current state
            logger.warning("directory report failed; pricing without node facts",
                           exc_info=True)
            nodes = {}
        self._calibrate_from_directory(nodes)
        self._calibrate_from_events()
        info = nodes.get(str(node_id)) or {}
        region_bytes = float(info.get("snapshot_mb", 0.0)) * 1024 * 1024
        return self.pricer.table(region_bytes, drain_s=0.0)
