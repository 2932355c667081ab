"""Cross-process trace correlation: merge an event timeline into one
Perfetto view.

A profiler trace (host spans beside the device's lines) covers ONE
process. An incident, though, threads through three: the agent detects
the failure, the master ingests the report, the relaunched worker
recovers — each appending to the shared JSONL timeline with its own
``pid`` and (when an incident trace id was ambient, see
``trace_context``) a shared ``trace_id``.

``export_merged_trace`` renders that file as Trace Event Format JSON
that https://ui.perfetto.dev opens directly:

  * every record becomes an instant event on its emitting process's
    track (named ``node<id>/pid<pid>``), args carrying the full record;
  * each failure→recovery incident (the MTTR pairing) becomes a
    complete-event span on a synthetic "incidents" track, so downtime
    is visible as a bar, not two dots;
  * records sharing a ``trace_id`` are joined by flow arrows in emit
    order — the causally-ordered path of the incident across processes.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

from dlrover_tpu.telemetry.mttr import derive_incidents
from dlrover_tpu.telemetry.names import EventKind

# Perfetto wants process-scoped ids; the synthetic incident track uses
# a pid real processes cannot take
INCIDENT_TRACK_PID = 0
# synthetic per-request track: one tid ROW per serve request (its
# lifecycle span from submit to completion), so the serving view reads
# as one Perfetto lane per request with the flow arrows of its
# trace_id pointing at the real router/worker pid events
REQUEST_TRACK_PID = -1

_SERVE_REQUEST_KINDS = {
    EventKind.SERVE_REQUEST_SUBMITTED,
    EventKind.SERVE_REQUEST_LEASED,
    EventKind.SERVE_PREFILL_CHUNK,
    EventKind.SERVE_FIRST_TOKEN,
    EventKind.SERVE_REQUEST_DONE,
    EventKind.SERVE_REQUEST_COMPLETED,
    EventKind.SERVE_REQUEST_EVICTED,
    EventKind.SERVE_LEASE_EXPIRED,
}


def merged_trace_events(events: List[Dict]) -> List[Dict]:
    ordered = sorted(events, key=lambda r: r.get("ts", 0.0))
    out: List[Dict] = []
    seen_pids: Dict[int, str] = {}
    flows: Dict[str, List[Dict]] = {}

    for rec in ordered:
        pid = int(rec.get("pid", 0) or 0)
        node = rec.get("node", "?")
        seen_pids.setdefault(pid, f"node{node}/pid{pid}")
        ev = {
            "name": rec.get("kind", "event"),
            "cat": "events",
            "ph": "i",
            "s": "p",  # process-scoped instant
            "ts": int(rec.get("ts", 0.0) * 1e6),
            "pid": pid,
            "tid": pid,
            "args": {k: v for k, v in rec.items() if k != "kind"},
        }
        out.append(ev)
        tid = rec.get("trace_id")
        if tid:
            flows.setdefault(tid, []).append(ev)

    # per-request lanes: each request trace id whose lifecycle events
    # appear in the timeline becomes one complete-event span (first ->
    # last lifecycle event) on its own tid row of the request track
    request_rows: Dict[str, List[Dict]] = {}
    for rec in ordered:
        if rec.get("kind") in _SERVE_REQUEST_KINDS and \
                rec.get("trace_id"):
            request_rows.setdefault(rec["trace_id"], []).append(rec)
    if request_rows:
        seen_pids[REQUEST_TRACK_PID] = "serve requests"
    for row, (tid_key, chain) in enumerate(sorted(
            request_rows.items(),
            key=lambda kv: kv[1][0].get("ts", 0.0))):
        t0 = chain[0].get("ts", 0.0)
        t1 = chain[-1].get("ts", t0)
        pids = sorted({int(r.get("pid", 0) or 0) for r in chain})
        out.append({
            "name": str(chain[0].get("request_id", tid_key)),
            "cat": "serve_request",
            "ph": "X",
            "ts": int(t0 * 1e6),
            "dur": max(1, int((t1 - t0) * 1e6)),
            "pid": REQUEST_TRACK_PID,
            "tid": row,
            "args": {
                "trace_id": tid_key,
                "lifecycle": [r.get("kind") for r in chain],
                "pids": pids,
            },
        })

    # incident spans (downtime bars) on the synthetic track
    seen_pids[INCIDENT_TRACK_PID] = "incidents"
    for i, inc in enumerate(derive_incidents(ordered)):
        if inc["started_ts"] is None or inc["recovered_ts"] is None:
            continue
        out.append({
            "name": inc["scenario"],
            "cat": "incident",
            "ph": "X",
            "ts": int(inc["started_ts"] * 1e6),
            "dur": max(1, int(
                (inc["recovered_ts"] - inc["started_ts"]) * 1e6)),
            "pid": INCIDENT_TRACK_PID,
            "tid": i,
            "args": {k: v for k, v in inc.items()},
        })

    # flow arrows: consecutive records of one trace_id, in emit order
    flow_id = 0
    for tid, chain in flows.items():
        if len(chain) < 2:
            continue
        flow_id += 1
        for j, ev in enumerate(chain):
            out.append({
                "name": tid,
                "cat": "trace_id",
                "ph": "s" if j == 0 else ("f" if j == len(chain) - 1
                                          else "t"),
                "bp": "e",
                "id": flow_id,
                "ts": ev["ts"],
                "pid": ev["pid"],
                "tid": ev["tid"],
            })

    # process-name metadata so tracks read as nodes, not raw pids
    for pid, name in seen_pids.items():
        out.append({
            "name": "process_name",
            "ph": "M",
            "pid": pid,
            "args": {"name": name},
        })
    return out


def export_merged_trace(events: List[Dict], path: str) -> int:
    """Write the merged view; returns the number of trace events."""
    trace_events = merged_trace_events(events)
    payload = {
        "traceEvents": trace_events,
        "displayTimeUnit": "ms",
        "otherData": {"exporter": "dlrover_tpu.telemetry.correlate"},
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh)
    return len(trace_events)


def incident_records(events: List[Dict],
                     trace_id: Optional[str] = None) -> Dict[str, List[Dict]]:
    """Records grouped by trace id (one incident each); ``trace_id``
    narrows to a single incident."""
    groups: Dict[str, List[Dict]] = {}
    for rec in sorted(events, key=lambda r: r.get("ts", 0.0)):
        tid = rec.get("trace_id")
        if not tid or (trace_id is not None and tid != trace_id):
            continue
        groups.setdefault(tid, []).append(rec)
    return groups
