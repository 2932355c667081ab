"""MTTR + recovery-count reports derived from the event timeline.

Replaces hand-assembled artifacts: instead of a bench script timing one
staged kill, recovery time is *derived* from the same JSONL the
production components emit at every lifecycle edge. Each failure-edge
event is paired with the first later recovery-edge event of a
compatible kind:

  failure edge            recovery edge            scenario
  ---------------------   ----------------------   ----------------------
  worker_failed           workers_started          crash/SIGKILL relaunch
  hang_detected           workers_started          hang relaunch
  nonfinite_step          rollback_restored        NaN rollback
  preempt_notice          preempt_drain_done       preemption drain
  live_reshard_begin      live_reshard_done        in-process reshard
  optimizer_apply_begin   optimizer_apply_done     live re-plan apply

Durations use the monotonic clock when both events came from the same
process (exact), else wall clocks (cross-process, e.g. agent-side
relaunch edges vs worker-side failure edges). Multiple failure edges
before one recovery edge collapse into ONE incident (a burst of
per-rank failure reports is one recovery), anchored at the first edge.

Workers running again is not training again. A ``worker_failure`` or
``hang`` incident is followed further, to the first
``compile_first_step`` of a worker of a later restart round: the
incident's ``first_step_seconds`` runs from the failure edge to that
first trained step, and ``phases`` splits it by the restarted worker's
own boot events (``boot_phases``). That is the recovery the report's
headline and ``--target`` judge; ``recovery_seconds`` keeps the seconds
to ``workers_started``. Where no such step follows (a job that was
stopped, a worker that never trains) the incident has neither key.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from dlrover_tpu.telemetry.names import EventKind

# failure kind -> {recovery kinds}, with a scenario label for the report
_PAIRINGS = {
    EventKind.WORKER_FAILED: (
        {EventKind.WORKERS_STARTED}, "worker_failure"),
    EventKind.HANG_DETECTED: (
        {EventKind.WORKERS_STARTED}, "hang"),
    EventKind.NONFINITE_STEP: (
        {EventKind.ROLLBACK_RESTORED}, "nonfinite_rollback"),
    EventKind.PREEMPT_NOTICE: (
        {EventKind.PREEMPT_DRAIN_DONE}, "preemption_drain"),
    EventKind.LIVE_RESHARD_BEGIN: (
        {EventKind.LIVE_RESHARD_DONE}, "live_reshard"),
    # checkpoint-free recovery: a rebuilding worker streaming its state
    # out of surviving peers' DRAM instead of an Orbax restore (the
    # recovery-ladder rung between live reshard and storage restore).
    # FALLBACK also closes the incident: a mid-transfer terminal
    # failure degrades to the storage rung — the rebuild attempt is
    # over either way, and an open incident would wrongly flag a
    # by-design degradation as unrecovered.
    EventKind.PEER_REBUILD_BEGIN: (
        {EventKind.PEER_REBUILD_DONE, EventKind.PEER_REBUILD_FALLBACK},
        "peer_rebuild"),
    # a runtime-optimizer plan applying live (drain -> retune/reshard ->
    # resume): not a failure, but downtime the loop chose to spend — the
    # ledger and the recovery report must both see it
    EventKind.OPTIMIZER_APPLY_BEGIN: (
        {EventKind.OPTIMIZER_APPLY_DONE}, "replan"),
    # the serving world resizing live (drain decode window -> snapshot
    # params+KV pages -> reshard): requests are HELD across it, so
    # this interval is exactly the per-request latency bump a resize
    # costs — the serving tier's recovery scenario
    EventKind.SERVE_RESIZE_BEGIN: (
        {EventKind.SERVE_RESIZE_DONE}, "serving_resize"),
    # a confirmed serving SLO violation -> its recovery: the interval
    # the SLO-driven scale policy is judged on (detection latency +
    # proposal + resize + burn-down), distinct from the resize pause
    # itself (serving_resize) which it usually contains
    EventKind.SERVE_SLO_VIOLATION: (
        {EventKind.SERVE_SLO_RECOVERED}, "serving_scale"),
    # the durability audit's cluster posture edge: some node's owner
    # regions at risk (coverage / staleness / budget) -> all clear.
    # Degraded-but-alive like serving_scale — training continues, so
    # goodput surfaces it as an overlap COLUMN, never a wall bucket —
    # but the interval is exactly the exposure window an operator is
    # judged on, so the recovery report prices it like any incident.
    EventKind.READINESS_DEGRADED: (
        {EventKind.READINESS_RESTORED}, "durability_at_risk"),
}


def _delta_seconds(failure: Dict, recovery: Dict) -> float:
    if (
        failure.get("pid") == recovery.get("pid")
        and "mono" in failure and "mono" in recovery
    ):
        return max(0.0, recovery["mono"] - failure["mono"])
    return max(0.0, recovery.get("ts", 0.0) - failure.get("ts", 0.0))


# the phases of a worker's boot, in order, from process start to the
# first trained step: (name, event kind, the event's fields that sum to
# it). ``ckpt_restore`` is nested in ``trainer_ready.state_seconds``:
# ``restore`` is that part of the state phase and ``state`` the rest
BOOT_PHASES = (
    ("import", EventKind.WORKER_BOOT, ("import_seconds",)),
    ("backend", EventKind.WORKER_BOOT,
     ("distributed_seconds", "backend_seconds")),
    ("script", EventKind.TRAINER_READY, ("script_seconds",)),
    ("ckpt_manager", EventKind.TRAINER_READY, ("ckpt_manager_seconds",)),
    ("build", EventKind.TRAINER_READY, ("build_seconds",)),
    ("restore", EventKind.CKPT_RESTORE, ("restore_seconds",)),
    ("state", EventKind.TRAINER_READY, ("state_seconds",)),
    ("hooks", EventKind.TRAIN_START, ("hooks_begin_seconds",)),
    ("first_step", EventKind.COMPILE_FIRST_STEP, ("seconds",)),
)


def boot_phases(events: List[Dict], pid: int,
                node: Optional[str] = None) -> Optional[Dict]:
    """One worker's boot from its own events: ``total_seconds`` from
    the process's start to its first trained step
    (``compile_first_step.ts - worker_boot.process_start_ts``), the
    seconds of each phase of ``BOOT_PHASES``, and ``remainder``: what
    no phase names (the executor's construction, ``prepare``'s own
    bookkeeping), reported and never folded into a neighbour. Only
    what the worker wrote up to that first step is its boot: a restore
    or a rebuilt program later in its life (live recovery) is not. None
    where the worker did not get as far as a first step."""
    mine = [rec for rec in events if rec.get("pid") == pid and (
        node is None or rec.get("node") == node)]
    step = next((rec for rec in mine
                 if rec.get("kind") == EventKind.COMPILE_FIRST_STEP), None)
    if step is None:
        return None
    first: Dict[str, Dict] = {}
    for rec in mine:
        if rec.get("ts", 0.0) <= step["ts"]:
            first.setdefault(rec.get("kind", ""), rec)
    boot = first.get(EventKind.WORKER_BOOT)
    if boot is None or "process_start_ts" not in boot:
        return None
    phases = {
        name: sum(first.get(kind, {}).get(f) or 0.0 for f in fields)
        for name, kind, fields in BOOT_PHASES
    }
    phases["state"] = max(0.0, phases["state"] - phases["restore"])
    total = step["ts"] - boot["process_start_ts"]
    phases["remainder"] = total - sum(phases.values())
    return {"total_seconds": round(total, 3),
            "process_start_ts": boot["process_start_ts"],
            "first_step_ts": step["ts"],
            "phases": {k: round(v, 3) for k, v in phases.items()}}


def _follow_to_first_step(incident: Dict, failure: Dict,
                          ordered: List[Dict]) -> None:
    """Give a worker-failure incident the restarted worker's first
    trained step: the first ``compile_first_step`` after the failure
    edge from a worker whose ``worker_boot.restart_round`` is higher
    than the failed one's."""
    t_failed = failure.get("ts", 0.0)
    boots = [r for r in ordered if r.get("kind") == EventKind.WORKER_BOOT]
    failed_round = failure.get("restart_round")
    if failed_round is None:  # a hang names no round: the newest booted
        failed_round = max((b.get("restart_round", 0) for b in boots
                            if b.get("ts", 0.0) <= t_failed), default=-1)
    later = {(b.get("node"), b.get("pid")) for b in boots
             if b.get("restart_round", 0) > failed_round}
    step = next((r for r in ordered
                 if r.get("kind") == EventKind.COMPILE_FIRST_STEP
                 and r.get("ts", 0.0) >= t_failed
                 and (r.get("node"), r.get("pid")) in later), None)
    if step is None:
        return
    boot = boot_phases(ordered, step["pid"], step.get("node"))
    incident["first_step_ts"] = step["ts"]
    incident["first_step_seconds"] = round(step["ts"] - t_failed, 3)
    if boot is not None:
        # failure edge to the new process's start: the agent's report,
        # the new rendezvous round, the fork
        respawn = boot["process_start_ts"] - t_failed
        incident["phases"] = {"respawn": round(respawn, 3),
                              **boot["phases"]}


def derive_incidents(events: List[Dict]) -> List[Dict]:
    """Pair failure edges with recovery edges into incident records."""
    ordered = sorted(events, key=lambda r: r.get("ts", 0.0))
    incidents: List[Dict] = []
    open_incident: Dict[str, Optional[Dict]] = {
        scenario: None for _, (_r, scenario) in _PAIRINGS.items()
    }
    for rec in ordered:
        kind = rec.get("kind", "")
        pairing = _PAIRINGS.get(kind)
        if pairing is not None:
            _, scenario = pairing
            # a burst of failure edges before recovery = ONE incident,
            # anchored at the FIRST edge (that is when downtime began)
            if open_incident.get(scenario) is None:
                open_incident[scenario] = rec
            continue
        for scenario, failure in list(open_incident.items()):
            if failure is None:
                continue
            recovery_kinds = next(
                rk for fk, (rk, sc) in _PAIRINGS.items() if sc == scenario
            )
            if kind in recovery_kinds:
                incidents.append({
                    "scenario": scenario,
                    "failure_kind": failure.get("kind"),
                    "recovery_kind": kind,
                    "error_code": failure.get("error_code", ""),
                    "node": failure.get("node", ""),
                    "started_ts": failure.get("ts"),
                    "recovered_ts": rec.get("ts"),
                    "recovery_seconds": round(
                        _delta_seconds(failure, rec), 3),
                })
                if scenario in ("worker_failure", "hang"):
                    _follow_to_first_step(incidents[-1], failure, ordered)
                open_incident[scenario] = None
    # unrecovered failures are reported too — a dashboard that hides
    # the incident still in progress is worse than none
    for scenario, failure in open_incident.items():
        if failure is not None:
            incidents.append({
                "scenario": scenario,
                "failure_kind": failure.get("kind"),
                "recovery_kind": None,
                "error_code": failure.get("error_code", ""),
                "node": failure.get("node", ""),
                "started_ts": failure.get("ts"),
                "recovered_ts": None,
                "recovery_seconds": None,
            })
    incidents.sort(key=lambda i: i.get("started_ts") or 0.0)
    return incidents


def mttr_report(events: List[Dict], target_s: float = 90.0) -> Dict:
    """The machine-verifiable recovery artifact, derived."""
    incidents = derive_incidents(events)
    recovered = [
        i for i in incidents if i["recovery_seconds"] is not None
    ]
    # an incident followed to the restarted worker's first trained step
    # counts to there: workers running again is not training again
    durations = [i.get("first_step_seconds", i["recovery_seconds"])
                 for i in recovered]
    by_scenario: Dict[str, Dict] = {}
    for inc, seconds in zip(recovered, durations):
        s = by_scenario.setdefault(
            inc["scenario"], {"count": 0, "total_s": 0.0, "max_s": 0.0}
        )
        s["count"] += 1
        s["total_s"] += seconds
        s["max_s"] = max(s["max_s"], seconds)
    for s in by_scenario.values():
        s["mean_s"] = round(s["total_s"] / s["count"], 3)
        s["total_s"] = round(s["total_s"], 3)
    value = (
        round(sum(durations) / len(durations), 3) if durations else 0.0
    )
    report = {
        "metric": "recovery_mttr_s",
        "value": value,
        "unit": "s",
        "vs_baseline": round(value / target_s, 3) if durations else 0.0,
        "detail": {
            "incidents": len(incidents),
            "recovered": len(recovered),
            "unrecovered": len(incidents) - len(recovered),
            "max_s": round(max(durations), 3) if durations else 0.0,
            "by_scenario": by_scenario,
            "source": "event_timeline",
            # the followed incidents, each with its phases
            "to_first_step": [
                {k: i[k] for k in ("scenario", "started_ts",
                                   "recovery_seconds",
                                   "first_step_seconds", "phases")
                 if k in i}
                for i in recovered if "first_step_seconds" in i],
        },
    }
    if len(incidents) > len(recovered):
        report["error"] = (
            f"{len(incidents) - len(recovered)} incident(s) without a "
            f"recovery edge in the timeline"
        )
    return report
