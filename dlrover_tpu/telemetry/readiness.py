"""Priced recovery ladder: rung constants, the calibrated MTTR pricer,
and the pure event-timeline derivations behind ``tpurun readiness
--events`` and ``tpurun mttr --predict``.

The recovery ladder (docs/elasticity.md) has four rungs a failing node
can come back through — live_reshard, peer_rebuild, storage_restore,
init — and until now the framework always walked them top-down by
availability. ElasWave's rung-pricing contract (PAPERS.md, 2510.00606)
makes the rung a PRICED decision instead: each rung carries a predicted
MTTR from calibrated observations, and every realized recovery feeds an
EMA correction back into the price, so the prediction converges on this
cluster's actual behavior instead of a datasheet guess.

The peer_rebuild price is round 14's decomposition (a CPU run; git history):

    drain + fetch_bytes / link_bw + device_put(bytes)

where ``link_bw`` is calibrated from the replicator's OWN push cycles —
a push frames and streams exactly the bytes a rebuild fetches back,
over the same RPC path between the same hosts, so the replication plane
continuously measures the recovery plane's transfer term without ever
injecting a failure. The observation-only rungs (live_reshard,
storage_restore, init) are priced from the EMA of realized incidents of
their scenario, falling back to a stated prior before the first one.

Everything in this module is master-state-free: the ``RungPricer`` is a
plain calibration object the master's ReadinessAuditor owns, and the
``predict_report`` / ``readiness_view`` derivations read only the event
timeline, so the CLI works forensically on a dead job's JSONL.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from dlrover_tpu.telemetry.mttr import derive_incidents
from dlrover_tpu.telemetry.names import EventKind

# the recovery ladder, cheapest rung first; the gauge encodes a rung as
# its index here (0=live_reshard .. 3=init)
RUNG_LIVE_RESHARD = "live_reshard"
RUNG_PEER_REBUILD = "peer_rebuild"
RUNG_STORAGE_RESTORE = "storage_restore"
RUNG_INIT = "init"
RUNG_LADDER = (
    RUNG_LIVE_RESHARD,
    RUNG_PEER_REBUILD,
    RUNG_STORAGE_RESTORE,
    RUNG_INIT,
)
RUNG_INDEX = {r: i for i, r in enumerate(RUNG_LADDER)}

# which mttr scenario realizes which rung, for the EMA correction: a
# closed live-reshard incident prices the live_reshard rung, a closed
# peer rebuild the peer_rebuild rung, and a worker-failure incident
# (process relaunch + storage/mirror restore) the storage_restore rung.
# Nothing realizes init — a from-scratch start is not an incident — so
# its price stays the prior.
SCENARIO_RUNG = {
    "live_reshard": RUNG_LIVE_RESHARD,
    "peer_rebuild": RUNG_PEER_REBUILD,
    "worker_failure": RUNG_STORAGE_RESTORE,
}

# priors (seconds) quoted before the first calibrating observation:
# deliberately pessimistic so an uncalibrated ladder never talks the
# planner OUT of a cheaper rung it has no evidence for
_RUNG_PRIORS = {
    RUNG_LIVE_RESHARD: 1.0,
    RUNG_PEER_REBUILD: 5.0,
    RUNG_STORAGE_RESTORE: 30.0,
    RUNG_INIT: 120.0,
}

# device_put prior before the first observed rebuild measures it
# (host-DRAM -> device transfer; conservative for PCIe-class paths)
_PUT_BW_PRIOR = 2.0e9  # bytes/s


def _ema(prev: Optional[float], obs: float, alpha: float) -> float:
    return obs if prev is None else prev + alpha * (obs - prev)


@dataclass
class RungPricer:
    """Calibration state + the pricing function for the four rungs.

    Thread-compat note: callers (the ReadinessAuditor) serialize access
    under their own lock; the pricer itself holds none.
    """

    alpha: float = 0.3
    # transfer-path calibration, EMA'd over replicator push cycles:
    # effective bytes/s of slice+frame+stream for ONE peer's worth of
    # region bytes (fixed per-cycle overhead included, which is what
    # makes small-state predictions honest)
    link_bw: Optional[float] = None
    # device_put bytes/s, EMA'd over realized rebuild put legs
    put_bw: Optional[float] = None
    # drain seconds a live rung pays before state moves (EMA over
    # realized live reshards' total is folded into ema_realized; this
    # term is the drain a peer_rebuild of a LIVE node would add — a
    # DEAD node has nothing left to drain, so blast-radius pricing
    # passes drain_s=0)
    drain_s: float = 0.0
    # absolute realized-MTTR EMA per rung (observation-priced rungs)
    ema_realized: Dict[str, float] = field(default_factory=dict)
    # multiplicative correction per rung: EMA of realized/predicted
    # whenever a recovery event carries both stamps
    corr: Dict[str, float] = field(default_factory=dict)
    # bookkeeping: how many observations each calibration term has seen
    observations: Dict[str, int] = field(default_factory=dict)

    def _count(self, term: str) -> None:
        self.observations[term] = self.observations.get(term, 0) + 1

    # -- calibration feeds ---------------------------------------------------

    def observe_push(self, push_bytes: float, push_seconds: float) -> None:
        """One replicator push cycle: the continuous, failure-free
        measurement of the rebuild transfer path."""
        if push_bytes <= 0 or push_seconds <= 0:
            return
        self.link_bw = _ema(
            self.link_bw, push_bytes / push_seconds, self.alpha)
        self._count("push")

    def observe_put(self, put_bytes: float, put_seconds: float) -> None:
        if put_bytes <= 0 or put_seconds <= 0:
            return
        self.put_bw = _ema(
            self.put_bw, put_bytes / put_seconds, self.alpha)
        self._count("put")

    def observe_realized(self, rung: str, realized_s: float,
                         predicted_s: Optional[float] = None) -> None:
        """A closed incident's realized MTTR for ``rung``. When the
        recovery event also carried the prediction made BEFORE the
        recovery ran, the ratio feeds the rung's multiplicative
        correction; the absolute EMA updates either way."""
        if rung not in RUNG_INDEX or realized_s < 0:
            return
        self.ema_realized[rung] = _ema(
            self.ema_realized.get(rung), realized_s, self.alpha)
        if predicted_s is not None and predicted_s > 0:
            ratio = min(10.0, max(0.1, realized_s / predicted_s))
            self.corr[rung] = _ema(
                self.corr.get(rung), ratio, self.alpha)
        self._count(rung)

    def update_from_incidents(self, incidents: List[Dict]) -> None:
        """Fold a batch of closed mttr incidents in (the "every time
        ``tpurun mttr`` closes an incident" contract — the auditor calls
        this over the tail of the shared events file)."""
        for inc in incidents:
            rung = SCENARIO_RUNG.get(inc.get("scenario", ""))
            realized = inc.get("recovery_seconds")
            if rung is None or realized is None:
                continue
            self.observe_realized(rung, float(realized))

    # -- pricing -------------------------------------------------------------

    def predict(self, rung: str, region_bytes: float = 0.0,
                drain_s: Optional[float] = None) -> float:
        """Predicted MTTR (seconds) of ``rung`` for a node whose owner
        regions total ``region_bytes``. ``drain_s`` defaults to the
        calibrated drain for live rungs; blast-radius pricing (the node
        is DEAD) passes 0 — there is nothing left to drain."""
        if rung == RUNG_PEER_REBUILD:
            drain = self.drain_s if drain_s is None else drain_s
            link = self.link_bw
            fetch = (region_bytes / link) if (link and link > 0) else None
            put = region_bytes / (self.put_bw or _PUT_BW_PRIOR)
            if fetch is None:
                base = self.ema_realized.get(
                    rung, _RUNG_PRIORS[rung])
            else:
                base = drain + fetch + put
            return max(0.0, base * self.corr.get(rung, 1.0))
        if rung not in RUNG_INDEX:
            raise ValueError(f"unknown recovery rung: {rung!r}")
        base = self.ema_realized.get(rung, _RUNG_PRIORS[rung])
        return max(0.0, base * self.corr.get(rung, 1.0))

    def table(self, region_bytes: float = 0.0,
              drain_s: Optional[float] = None) -> Dict[str, float]:
        """The per-rung predicted-MTTR table, cheapest-ladder order."""
        return {
            rung: round(self.predict(rung, region_bytes, drain_s), 6)
            for rung in RUNG_LADDER
        }

    def to_dict(self) -> Dict:
        """Calibration snapshot for the readiness report."""
        return {
            "link_bw_bytes_per_s": (
                round(self.link_bw, 1) if self.link_bw else None),
            "put_bw_bytes_per_s": (
                round(self.put_bw, 1) if self.put_bw else None),
            "drain_s": round(self.drain_s, 6),
            "ema_realized_s": {
                k: round(v, 6) for k, v in self.ema_realized.items()},
            "corrections": {
                k: round(v, 4) for k, v in self.corr.items()},
            "observations": dict(self.observations),
        }


def cheapest_viable_rung(table: Dict[str, float],
                         viable: Dict[str, bool]) -> Optional[str]:
    """The priced choice: among the rungs marked viable, the one with
    the lowest predicted MTTR — ties break toward the ladder's
    traditional (cheapest-first) order because ``table`` iterates in
    RUNG_LADDER order. None when nothing is viable."""
    best: Optional[str] = None
    for rung in RUNG_LADDER:
        if not viable.get(rung):
            continue
        if best is None or table.get(rung, float("inf")) < table.get(
                best, float("inf")):
            best = rung
    return best


# -- forensic derivations (pure functions over the event timeline) ------------


def predict_report(events: List[Dict]) -> Dict:
    """``tpurun mttr --predict``: per-incident predicted-vs-realized
    columns, derived purely from the timeline. An incident gains the
    prediction columns only when its recovery event was stamped with
    ``predicted_mttr_s`` (the priced-ladder paths stamp both predicted
    and realized); unstamped incidents keep ``predicted_s: None`` —
    absent means "this recovery was not priced", never 0."""
    ordered = sorted(events, key=lambda r: r.get("ts", 0.0))
    stamped: Dict = {}
    for rec in ordered:
        if rec.get("predicted_mttr_s") is None:
            continue
        key = (rec.get("kind", ""), round(rec.get("ts", 0.0), 6))
        stamped[key] = rec
    rows: List[Dict] = []
    priced = 0
    within_2x = 0
    for inc in derive_incidents(ordered):
        row = {
            "scenario": inc["scenario"],
            "node": inc.get("node", ""),
            "started_ts": inc["started_ts"],
            "realized_s": inc["recovery_seconds"],
            "predicted_s": None,
            "rung": None,
            "ratio": None,
        }
        rec = stamped.get((
            inc.get("recovery_kind") or "",
            round(inc["recovered_ts"] or -1.0, 6),
        ))
        if rec is not None:
            try:
                predicted = float(rec["predicted_mttr_s"])
            except (TypeError, ValueError):
                predicted = None
            if predicted is not None:
                realized = rec.get(
                    "realized_mttr_s", inc["recovery_seconds"])
                row["predicted_s"] = round(predicted, 6)
                row["rung"] = rec.get("rung")
                if realized is not None:
                    realized = float(realized)
                    row["realized_s"] = round(realized, 6)
                    if realized > 0:
                        row["ratio"] = round(predicted / realized, 3)
                priced += 1
                if (realized is not None and
                        predicted <= 2.0 * realized + 0.05 and
                        realized <= 2.0 * predicted + 0.05):
                    within_2x += 1
        rows.append(row)
    return {
        "metric": "recovery_mttr_predicted_vs_realized",
        "incidents": rows,
        "priced": priced,
        "within_2x": within_2x,
        "source": "event_timeline",
    }


def readiness_view(events: List[Dict]) -> Dict:
    """The forensic readiness report: replay the durability verdict
    edges (DIAG_DURABILITY flags, DIAG_RECOVERED ``was=durability``
    clears) and the posture edges to the state the auditor held at the
    timeline's end — what ``tpurun readiness --events`` shows, and what
    the live/forensic agreement gate pins against the RPC view."""
    at_risk: Dict[str, Dict] = {}
    posture = "ready"
    last_sweep: Optional[Dict] = None
    sweeps = 0
    for rec in sorted(events, key=lambda r: r.get("ts", 0.0)):
        kind = rec.get("kind", "")
        if kind == EventKind.DIAG_DURABILITY:
            node = str(rec.get("diag_node", ""))
            at_risk[node] = {
                "node_id": rec.get("diag_node"),
                "error_code": rec.get("error_code", ""),
                "since_ts": rec.get("ts"),
                "trace_id": rec.get("trace_id", ""),
                "evidence": {
                    k: v for k, v in rec.items()
                    if k in ("missing_regions", "held", "required",
                             "staleness_steps", "allowed_steps",
                             "degraded", "requested", "admitted",
                             "owner_step", "holders")
                },
            }
        elif (kind == EventKind.DIAG_RECOVERED
              and rec.get("was") == "durability"):
            at_risk.pop(str(rec.get("diag_node", "")), None)
        elif kind == EventKind.READINESS_DEGRADED:
            posture = "degraded"
        elif kind == EventKind.READINESS_RESTORED:
            posture = "ready"
        elif kind == EventKind.READINESS_SWEEP:
            sweeps += 1
            last_sweep = {
                k: rec.get(k)
                for k in ("ts", "at_risk", "nodes", "owners",
                          "posture", "sweep_seconds")
                if rec.get(k) is not None
            }
    if at_risk and posture == "ready":
        # a flag without its posture edge (rotated-away file): the
        # verdict table wins — degraded is the honest summary
        posture = "degraded"
    return {
        "posture": posture,
        "at_risk": at_risk,
        "at_risk_nodes": sorted(at_risk),
        "last_sweep": last_sweep,
        "sweep_events": sweeps,
        "source": "event_timeline",
    }
