"""``python -m dlrover_tpu.telemetry`` — the observability CLI.

  mttr     derive the MTTR / recovery-count report from an event
           timeline (replaces hand-maintained MTTR artifacts)
  goodput  derive the goodput/badput wall-clock ledger from an event
           timeline (productive / compile / reshard / restart /
           checkpoint / rendezvous / idle buckets)
  diagnose cluster diagnosis: straggler/hang verdicts + node series —
           live from a master (--addr) or forensically from a
           timeline (--events)
  plan     the runtime optimizer's decision trail: running config,
           calibration factors, candidate table, chosen/rejected
           plans, predicted-vs-realized speedups — live (--addr) or
           forensically from a timeline (--events)
  attribution
           the performance-attribution plane: per-node derived MFU /
           exposed-comm-fraction / HBM gauges and the optimizer's
           memory-gate rejections — live (--addr), forensically from
           a timeline (--events), or measured device-time buckets
           from a jax.profiler trace (--trace)
  data     the shard-dispatch & input-pipeline ledger: per-dataset
           todo/doing/done queues, epoch progress + ETA, timeout
           recoveries, per-node consumption rates — live (--addr,
           DataShardRequest RPC) or forensically from a timeline's
           DATA_* events (--events)
  readiness
           the recovery-readiness plane: cluster posture, per-node
           durability verdicts (coverage / staleness / budget), and
           the priced recovery ladder (predicted MTTR per rung) —
           live (--addr, ReadinessRequest RPC) or forensically from
           a timeline's DIAG_DURABILITY / READINESS_* events
           (--events)
  events   pretty-print a timeline (newest last)
  metrics  dump Prometheus exposition: a live endpoint via --addr, or
           this process's registry (useful under ``tpurun metrics``)
  trace    merge a multi-process event timeline (--events) into ONE
           Perfetto view (incident spans + trace-id flows across
           master/agent/workers). Host spans are events of the
           profiler's own trace: docs/observability.md
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m dlrover_tpu.telemetry",
        description="dlrover_tpu observability: MTTR derivation, event "
                    "timeline, metrics exposition, trace export",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    mttr = sub.add_parser(
        "mttr", help="derive MTTR from an event timeline JSONL")
    mttr.add_argument("--events", default="",
                      help="timeline path (default: the configured "
                           "DLROVER_TPU_EVENTS_FILE sink)")
    mttr.add_argument("--out", default="",
                      help="also write the JSON report to this path")
    mttr.add_argument("--target", type=float, default=90.0,
                      help="MTTR target seconds for vs_baseline "
                           "(default 90)")
    mttr.add_argument("--predict", action="store_true",
                      help="per-incident predicted-vs-realized MTTR "
                           "columns (recovery events stamped by the "
                           "priced ladder) instead of the aggregate "
                           "report")

    gp = sub.add_parser(
        "goodput", help="derive the goodput/badput ledger from an "
                        "event timeline JSONL")
    gp.add_argument("--events", default="",
                    help="timeline path (default: the configured "
                         "DLROVER_TPU_EVENTS_FILE sink)")
    gp.add_argument("--out", default="",
                    help="also write the JSON ledger to this path")

    dg = sub.add_parser(
        "diagnose", help="cluster diagnosis: node series + "
                         "straggler/hang verdicts")
    dg.add_argument("--addr", default="",
                    help="query a live master at host:port")
    dg.add_argument("--events", default="",
                    help="derive forensically from a timeline JSONL "
                         "(default: the configured events sink)")
    dg.add_argument("--json", action="store_true",
                    help="machine-readable output")

    pl = sub.add_parser(
        "plan", help="runtime-optimizer decision trail: candidate "
                     "table, chosen/rejected plans, calibration")
    pl.add_argument("--addr", default="",
                    help="query a live master at host:port")
    pl.add_argument("--events", default="",
                    help="derive forensically from a timeline JSONL "
                         "(default: the configured events sink)")
    pl.add_argument("--limit", type=int, default=0,
                    help="only the last N decisions")
    pl.add_argument("--json", action="store_true",
                    help="machine-readable output")

    at = sub.add_parser(
        "attribution", help="performance attribution: derived MFU / "
                            "exposed-comm / HBM accounting")
    at.add_argument("--addr", default="",
                    help="query a live master at host:port")
    at.add_argument("--events", default="",
                    help="derive forensically from a timeline JSONL "
                         "(default: the configured events sink)")
    at.add_argument("--trace", default="",
                    help="parse a jax.profiler Chrome trace "
                         "(*.trace.json[.gz] file or a profile dump "
                         "dir) into device-time buckets instead; with "
                         "the timeline of the job that made it, also "
                         "into seconds by phase and by scope")
    at.add_argument("--limit", type=int, default=0,
                    help="only the last N memory-gate rejections")
    at.add_argument("--json", action="store_true",
                    help="machine-readable output")

    dt = sub.add_parser(
        "data", help="shard-dispatch & input-pipeline ledger: "
                     "todo/doing/done queues, epoch progress, "
                     "per-node consumption, timeout recoveries")
    dt.add_argument("--addr", default="",
                    help="query a live master at host:port")
    dt.add_argument("--events", default="",
                    help="derive forensically from a timeline JSONL "
                         "(default: the configured events sink)")
    dt.add_argument("--dataset", default="",
                    help="only this dataset ('' = all)")
    dt.add_argument("--json", action="store_true",
                    help="machine-readable output")

    rd = sub.add_parser(
        "readiness", help="recovery-readiness plane: posture, "
                          "per-node durability verdicts, priced "
                          "recovery ladder")
    rd.add_argument("--addr", default="",
                    help="query a live master at host:port")
    rd.add_argument("--events", default="",
                    help="derive forensically from a timeline JSONL "
                         "(default: the configured events sink)")
    rd.add_argument("--node", type=int, default=-1,
                    help="only this node's blast radius (live view)")
    rd.add_argument("--json", action="store_true",
                    help="machine-readable output")

    ev = sub.add_parser("events", help="print a timeline")
    ev.add_argument("--events", default="", help="timeline path")
    ev.add_argument("--tail", type=int, default=0,
                    help="only the last N records")
    ev.add_argument("--kind", default="",
                    help="filter to one event kind")

    met = sub.add_parser("metrics", help="dump Prometheus exposition")
    met.add_argument("--addr", default="",
                     help="scrape a live exporter at host:port instead "
                          "of dumping this process's registry")

    tr = sub.add_parser("trace", help="merge an event timeline into "
                                      "one Perfetto view (Chrome JSON)")
    tr.add_argument("--out", default="trace.json")
    tr.add_argument("--events", default="",
                    help="the event timeline (all processes) to merge; "
                         "default: DLROVER_TPU_EVENTS_FILE")

    cache = sub.add_parser(
        "cache", help="persistent XLA compile-cache stats (dir, entry "
                      "count, this process's hit/miss traffic)")
    cache.add_argument("--dir", default=None,
                       help="cache directory (default: "
                            "JAX_COMPILATION_CACHE_DIR, else the fixed "
                            "in-checkout one)")
    return p


def _resolve_events_path(arg: str) -> Optional[str]:
    from dlrover_tpu.telemetry import events as events_mod

    return arg or events_mod.default_events_path()


def _cmd_diagnose(args) -> int:
    """Live (master RPC) or forensic (timeline) cluster diagnosis."""
    if args.addr:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(args.addr)
        try:
            report = client.get_diagnosis()
        finally:
            client.close()
        report["source"] = args.addr
    else:
        from dlrover_tpu.telemetry import events as events_mod
        from dlrover_tpu.telemetry.names import EventKind

        path = _resolve_events_path(args.events)
        if not path:
            print("diagnose: no master --addr and no timeline (pass "
                  "--events or set DLROVER_TPU_EVENTS_FILE)",
                  file=sys.stderr)
            return 2
        records = events_mod.read_events(path)
        diag_kinds = {EventKind.DIAG_STRAGGLER: "straggler",
                      EventKind.DIAG_NODE_HANG: "hung"}
        verdicts = {}
        incidents = []
        for rec in records:
            kind = rec.get("kind", "")
            if kind in diag_kinds:
                node = rec.get("diag_node")
                verdicts[str(node)] = {
                    "node_id": node,
                    "verdict": diag_kinds[kind],
                    "since_ts": rec.get("ts"),
                    "trace_id": rec.get("trace_id", ""),
                    "evidence": {
                        k: v for k, v in rec.items()
                        if k not in ("kind", "ts", "mono", "pid",
                                     "node", "seq", "trace_id",
                                     "diag_node")
                    },
                }
                incidents.append(verdicts[str(node)])
            elif kind == EventKind.DIAG_RECOVERED:
                verdicts.pop(str(rec.get("diag_node")), None)
        report = {
            "source": path,
            "events": len(records),
            "verdicts": verdicts,
            "stragglers": sorted(
                v["node_id"] for v in verdicts.values()
                if v["verdict"] == "straggler"),
            "hung": sorted(
                v["node_id"] for v in verdicts.values()
                if v["verdict"] == "hung"),
            "incident_history": incidents,
        }
    if args.json:
        print(json.dumps(report))
        return 0
    stragglers = report.get("stragglers") or []
    hung = report.get("hung") or []
    nodes = report.get("nodes") or {}
    for node_id, sample in sorted(nodes.items()):
        if not sample:
            continue
        p50 = sample.get("step_p50")
        print(
            f"node {node_id}: step={sample.get('step')} "
            f"p50={p50 if p50 is not None else '-'}s "
            f"rss={sample.get('rss_mb')}MB "
            f"age={sample.get('report_age_s')}s"
        )
    for v in (report.get("verdicts") or {}).values():
        print(f"VERDICT node {v.get('node_id')}: {v.get('verdict')} "
              f"[{v.get('trace_id', '')}] evidence={v.get('evidence')}")
    if not stragglers and not hung:
        print("diagnosis: all reporting nodes healthy"
              + ("" if nodes or report.get("verdicts")
                 else " (no diagnosis records)"))
    return 0


def _print_exposed_comm(ec) -> None:
    """The predicted-vs-measured exposed-comm line shared by ``tpurun
    plan`` and ``tpurun attribution``: side by side, so an operator can
    see whether the overlap the planner paid for actually materialized
    (measured is an upper bound — far above predicted means the
    exchange is still serial)."""
    if not ec:
        return
    pred = ec.get("predicted")
    meas = ec.get("measured")
    print(f"exposed comm: predicted="
          f"{pred if pred is not None else '-'} "
          f"measured={meas if meas is not None else '-'} "
          f"(C={ec.get('dispatch_chunks')}, "
          f"{ec.get('nodes_measured', 0)} node(s) measured)")


def _cmd_plan(args) -> int:
    """Live (master RPC) or forensic (timeline) optimizer trail."""
    from dlrover_tpu.telemetry.names import EventKind
    if args.addr:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(args.addr)
        try:
            report = client.get_plan(limit=args.limit)
        finally:
            client.close()
        report["source"] = args.addr
    else:
        from dlrover_tpu.master.optimizer import (
            decision_trail_from_events,
        )
        from dlrover_tpu.telemetry import events as events_mod

        path = _resolve_events_path(args.events)
        if not path:
            print("plan: no master --addr and no timeline (pass "
                  "--events or set DLROVER_TPU_EVENTS_FILE)",
                  file=sys.stderr)
            return 2
        report = decision_trail_from_events(events_mod.read_events(path))
        report["source"] = path
        if args.limit:
            report["plans"] = report["plans"][-args.limit:]
    if args.json:
        print(json.dumps(report))
        return 0
    running = report.get("running")
    if running:
        line = (f"running: mesh={running.get('mesh')} "
                f"window={running.get('train_window')} "
                f"world={running.get('world')}")
        if running.get("dispatch_chunks"):
            line += f" C={running.get('dispatch_chunks')}"
        # the wire precisions, shown only when they deviate from the
        # bf16 default (the interesting case)
        if (running.get("moe_precision") or "bf16") != "bf16":
            line += f" p={running.get('moe_precision')}"
        if (running.get("fsdp_precision") or "bf16") != "bf16":
            line += f" fp={running.get('fsdp_precision')}"
        print(line)
    _print_exposed_comm(report.get("exposed_comm"))
    corr = report.get("corrections")
    if corr:
        print(f"calibration: compute x{corr.get('compute')} "
              f"comm x{corr.get('comm')} "
              f"dispatch x{corr.get('dispatch')} "
              f"({corr.get('samples')} passes)")
    # live view: full decision records; forensic view: per-plan rows
    for d in report.get("decisions") or []:
        line = (f"[{d.get('trace_id', '')}] {d.get('trigger')}: "
                f"{d.get('outcome')}")
        if d.get("outcome") == "chosen":
            c = d.get("chosen") or {}
            line += (f" plan={d.get('plan_id')} -> "
                     f"window={c.get('train_window')} "
                     f"mesh={c.get('mesh')} ")
            if c.get("dispatch_chunks"):
                line += f"C={c.get('dispatch_chunks')} "
            if (c.get("moe_precision") or "bf16") != "bf16":
                line += f"p={c.get('moe_precision')} "
            if (c.get("fsdp_precision") or "bf16") != "bf16":
                line += f"fp={c.get('fsdp_precision')} "
            line += f"predicted {d.get('predicted_speedup')}x"
            if d.get("applied"):
                line += (f" (applied, realized "
                         f"{d.get('realized_speedup')}x)")
        else:
            line += f" ({d.get('reason')})"
        print(line)
        for c in (d.get("candidates") or [])[:4]:
            chunk = (f" C={c.get('dispatch_chunks')}"
                     if c.get("dispatch_chunks") else "")
            print(f"    candidate window={c.get('train_window')} "
                  f"mesh={c.get('mesh')}"
                  f"{chunk}"
                  f" -> {c.get('predicted_step_s')}s/step "
                  f"({c.get('speedup')}x)")
        for m in d.get("memory_rejected") or []:
            print(f"    MEMORY-REJECTED mesh={m.get('mesh')}: "
                  f"predicted {m.get('predicted_hbm_bytes')} B > "
                  f"budget {m.get('budget_bytes')} B")
    for p in report.get("plans") or []:
        line = (f"plan {p.get('plan_id')} [{p.get('trigger', '')}]: "
                f"window={p.get('train_window')} "
                f"predicted {p.get('predicted_speedup')}x")
        if "apply_seconds" in p:
            line += (f", applied in {p.get('apply_seconds')}s "
                     f"(recompiled={p.get('recompiled')})")
        if p.get("apply_error"):
            line += f", FAILED ({p['apply_error']})"
        if p.get("realized_speedup") is not None:
            line += f", realized {p.get('realized_speedup')}x"
        print(line)
    # forensic view: rejected passes carry no plan id, so they never
    # join the per-plan rows — but a rejection IS a decision (the
    # input-bound/memory gates exist to be read), so render the trail's
    # rejection records too
    rejected = [
        r for r in (report.get("trail") or [])
        if r.get("kind") == EventKind.OPTIMIZER_PLAN_REJECTED
    ]
    for r in rejected:
        line = (f"[{r.get('trace_id', '')}] {r.get('trigger', '')}: "
                f"rejected ({r.get('reason')})")
        if r.get("input_bound_node") is not None:
            line += (f" node={r.get('input_bound_node')} "
                     f"input_wait={r.get('input_wait_frac')}")
            if r.get("peer_median_input_wait_frac") is not None:
                line += (" vs peer median "
                         f"{r.get('peer_median_input_wait_frac')}")
        print(line)
    if not (report.get("decisions") or report.get("plans")
            or rejected):
        print("plan: no optimizer decisions recorded")
    return 0


def _cmd_attribution(args) -> int:
    """Live (master RPC), forensic (timeline), or measured (trace
    parse) performance attribution."""
    if args.trace:
        from dlrover_tpu.telemetry import events as events_mod
        from dlrover_tpu.telemetry.attribution import parse_trace_path
        from dlrover_tpu.telemetry.names import EventKind

        # the timeline of the job that made the dump says which phase
        # and scope each instruction of its step belongs to
        timeline = _resolve_events_path(args.events)
        step_scopes = next(
            (rec for rec in reversed(
                events_mod.read_events(timeline) if timeline else [])
             if rec.get("kind") == EventKind.STEP_SCOPES), None)
        try:
            buckets = parse_trace_path(args.trace, step_scopes)
        except (OSError, ValueError) as e:
            print(f"attribution: trace parse of {args.trace} failed: "
                  f"{e}", file=sys.stderr)
            return 2
        if args.json:
            print(json.dumps(buckets))
            return 0
        print(f"device-time buckets over {buckets['events']} trace "
              f"event(s) ({args.trace}):")
        for key in ("wall_s", "busy_s", "idle_s", "collective_s",
                    "compute_s", "infeed_s", "other_s"):
            print(f"  {key:14s} {buckets[key]}")
        print(f"measured comm fraction (collective over categorized "
              f"device-op time): {buckets['measured_comm_frac']}")
        for title, key in (("phase", "by_phase"), ("scope", "by_scope")):
            if buckets.get(key):
                print(f"seconds by {title} (the timeline's "
                      f"step_scopes event, {timeline}):")
            for name, seconds in sorted(buckets.get(key, {}).items(),
                                        key=lambda kv: -kv[1]):
                print(f"  {name or '(no scope)':18s} {seconds}")
        return 0
    if args.addr:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(args.addr)
        try:
            report = client.get_attribution(limit=args.limit)
        finally:
            client.close()
        report["source"] = args.addr
    else:
        from dlrover_tpu.telemetry import events as events_mod
        from dlrover_tpu.telemetry.names import EventKind

        path = _resolve_events_path(args.events)
        if not path:
            print("attribution: no master --addr and no timeline "
                  "(pass --events or set DLROVER_TPU_EVENTS_FILE)",
                  file=sys.stderr)
            return 2
        records = events_mod.read_events(path)
        # newest ATTRIBUTION_CAPTURED per worker (node, pid)
        captured = {}
        for rec in records:
            if rec.get("kind") == EventKind.ATTRIBUTION_CAPTURED:
                captured[(rec.get("node"), rec.get("pid"))] = {
                    k: v for k, v in rec.items()
                    if k not in ("kind", "mono", "seq")
                }
        rejections = [
            {k: v for k, v in rec.items() if k not in ("mono", "seq")}
            for rec in records
            if rec.get("kind") == EventKind.OPTIMIZER_PLAN_REJECTED
            and str(rec.get("reason", "")).startswith("memory")
        ]
        if args.limit:
            rejections = rejections[-args.limit:]
        report = {
            "source": path,
            "events": len(records),
            "records": list(captured.values()),
            "memory_rejected": rejections,
        }
    if args.json:
        print(json.dumps(report))
        return 0
    _print_exposed_comm(report.get("exposed_comm"))
    for node_id, sample in sorted((report.get("nodes") or {}).items()):
        if not sample:
            continue
        mfu = sample.get("mfu")
        frac = sample.get("exposed_comm_frac")
        print(
            f"node {node_id}: step={sample.get('step')} "
            f"mfu={round(mfu, 4) if mfu is not None else '-'} "
            f"exposed_comm="
            f"{round(frac, 4) if frac is not None else '-'} "
            f"flops/step={sample.get('flops_per_step') or '-'} "
            f"peak_hbm={sample.get('peak_hbm_mb') or '-'}MB "
            f"headroom={sample.get('hbm_headroom_mb') or '-'}MB"
        )
    for rec in report.get("records") or []:
        print(f"record node={rec.get('node')} pid={rec.get('pid')}: "
              f"flops/step={rec.get('flops_per_step')} "
              f"intensity={rec.get('arithmetic_intensity')} "
              f"peak_hbm={rec.get('peak_hbm_mb')}MB "
              f"comm_s={rec.get('predicted_comm_total_s')} "
              f"source={rec.get('source')}")
    for rej in report.get("memory_rejected") or []:
        print(f"MEMORY-REJECTED mesh={rej.get('mesh')} "
              f"needs {rej.get('predicted_hbm_mb', rej.get('predicted_hbm_bytes'))}"
              f" > budget {rej.get('budget_mb', rej.get('budget_bytes'))}"
              f" [{rej.get('trigger', rej.get('reason', ''))}]")
    if not (report.get("nodes") or report.get("records")
            or report.get("memory_rejected")):
        print("attribution: no records (telemetry off, or no "
              "attribution capture has run)")
    return 0


def _cmd_data(args) -> int:
    """Live (master RPC) or forensic (timeline DATA_* events) shard
    ledger. Both views quote the same shard counts — the tier-1 CLI
    gate pins their agreement on a completed dataset."""
    if args.addr:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(args.addr)
        try:
            report = client.get_data_report(dataset_name=args.dataset)
        finally:
            client.close()
        report["source"] = args.addr
    else:
        from dlrover_tpu.telemetry import events as events_mod
        from dlrover_tpu.telemetry.names import EventKind

        path = _resolve_events_path(args.events)
        if not path:
            print("data: no master --addr and no timeline (pass "
                  "--events or set DLROVER_TPU_EVENTS_FILE)",
                  file=sys.stderr)
            return 2
        records = events_mod.read_events(path)
        # the newest DATA_EPOCH_END per dataset carries the cumulative
        # accounting; timeout events accumulate per dataset
        datasets = {}
        timeouts = []
        for rec in records:
            kind = rec.get("kind", "")
            name = rec.get("dataset", "")
            if args.dataset and name != args.dataset:
                continue
            if kind == EventKind.DATA_EPOCH_END:
                datasets[name] = {
                    "shards_done": rec.get("shards_done"),
                    "records_done": rec.get("records_done"),
                    "epoch": rec.get("epoch"),
                    "timeout_recovered": rec.get(
                        "timeout_recovered", 0),
                    "completed": bool(rec.get("final")),
                    "ts": rec.get("ts"),
                }
            elif kind == EventKind.DATA_SHARD_TIMEOUT:
                timeouts.append({
                    "dataset": name, "ts": rec.get("ts"),
                    "count": rec.get("count"),
                    "task_ids": rec.get("task_ids"),
                    "trace_id": rec.get("trace_id", ""),
                })
        report = {
            "source": path,
            "events": len(records),
            "datasets": datasets,
            "timeouts": timeouts,
        }
    if args.json:
        print(json.dumps(report))
        return 0
    for name, d in sorted((report.get("datasets") or {}).items()):
        line = (f"dataset {name}: todo={d.get('todo', '-')} "
                f"doing={d.get('doing', '-')} "
                f"done={d.get('shards_done')} shards "
                f"({d.get('records_done')} records) "
                f"epoch={d.get('epoch')}")
        if d.get("epoch_progress") is not None:
            line += f" progress={round(d['epoch_progress'] * 100, 1)}%"
        if d.get("eta_s") is not None:
            line += f" eta={d['eta_s']}s"
        if d.get("timeout_recovered"):
            line += f" timeout_recovered={d['timeout_recovered']}"
        if d.get("completed"):
            line += " COMPLETED"
        print(line)
    def _node_order(item):
        # node ids arrive as strings over JSON: sort numerically so a
        # 10+-node cluster doesn't print 0, 1, 10, 11, 2, ...
        try:
            return (0, int(item[0]))
        except (TypeError, ValueError):
            return (1, item[0])

    for node_id, stats in sorted((report.get("nodes") or {}).items(),
                                 key=_node_order):
        rate = stats.get("records_per_s")
        print(f"node {node_id}: shards={stats.get('shards_completed')} "
              f"records={stats.get('records_done')} "
              f"rate={rate if rate is not None else '-'}/s")
    for t in report.get("timeouts") or []:
        print(f"TIMEOUT dataset={t.get('dataset')}: "
              f"{t.get('count')} shard(s) requeued "
              f"(tasks {t.get('task_ids')}) [{t.get('trace_id', '')}]")
    if not (report.get("datasets") or report.get("nodes")
            or report.get("timeouts")):
        print("data: no shard-dispatch records (no dataset registered, "
              "or no DATA_* events in the timeline)")
    return 0


def _cmd_readiness(args) -> int:
    """Live (ReadinessRequest RPC) or forensic (timeline replay)
    readiness report. Both views quote the same posture and at-risk
    node set — the tier-1 CLI gate pins their agreement across a
    flag -> clear cycle."""
    if args.addr:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(args.addr)
        try:
            report = client.get_readiness(node_id=args.node)
        finally:
            client.close()
        report["source"] = args.addr
    else:
        from dlrover_tpu.telemetry import events as events_mod
        from dlrover_tpu.telemetry.readiness import readiness_view

        path = _resolve_events_path(args.events)
        if not path:
            print("readiness: no master --addr and no timeline (pass "
                  "--events or set DLROVER_TPU_EVENTS_FILE)",
                  file=sys.stderr)
            return 2
        report = readiness_view(events_mod.read_events(path))
        report["source"] = path
    if args.json:
        print(json.dumps(report))
        return 0
    posture = report.get("posture", "ready")
    at_risk = report.get("at_risk") or {}
    print(f"posture: {posture.upper()}"
          + (f" ({len(at_risk)} node(s) at risk)" if at_risk else ""))
    for node, v in sorted(at_risk.items()):
        print(f"AT RISK node {node}: {v.get('error_code', '')} "
              f"[{v.get('trace_id', '')}] evidence={v.get('evidence')}")
    # live view extras: per-node blast radius + calibration
    for node, d in sorted((report.get("nodes") or {}).items()):
        if not d.get("owner"):
            continue
        table = d.get("predicted_mttr") or {}
        rungs = " ".join(
            f"{r}={table[r]}s" for r in
            ("live_reshard", "peer_rebuild", "storage_restore", "init")
            if r in table)
        print(f"node {node}: regions={d.get('regions_mb')}MB "
              f"holders={d.get('holders')} "
              f"coverage={'ok' if d.get('coverage_ok') else 'LOST'} "
              f"staleness={d.get('staleness_steps')} "
              f"best_rung={d.get('best_rung')} {rungs}")
    admitted = report.get("admitted") or {}
    if admitted.get("requested"):
        print(f"replicas: admitted k={admitted.get('replicas')} of "
              f"requested {admitted.get('requested')}"
              + (f" ({admitted.get('reason')})"
                 if admitted.get("reason") else ""))
    cal = report.get("calibration") or {}
    if cal:
        print(f"calibration: link_bw={cal.get('link_bw_bytes_per_s')} "
              f"put_bw={cal.get('put_bw_bytes_per_s')} "
              f"observations={cal.get('observations')}")
    sweep = report.get("last_sweep")
    if sweep:
        print(f"last sweep: {sweep}")
    if not at_risk:
        print("durability: every owner's regions covered"
              + ("" if report.get("nodes") or report.get("sweep_events")
                 else " (no readiness records)"))
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.cmd == "plan":
        return _cmd_plan(args)

    if args.cmd == "data":
        return _cmd_data(args)

    if args.cmd == "attribution":
        return _cmd_attribution(args)

    if args.cmd == "readiness":
        return _cmd_readiness(args)

    if args.cmd == "mttr":
        from dlrover_tpu.telemetry import events as events_mod
        from dlrover_tpu.telemetry.mttr import mttr_report

        path = _resolve_events_path(args.events)
        if not path:
            print("mttr: no timeline (pass --events or set "
                  "DLROVER_TPU_EVENTS_FILE)", file=sys.stderr)
            return 2
        records = events_mod.read_events(path)
        if args.predict:
            from dlrover_tpu.telemetry.readiness import predict_report

            report = predict_report(records)
        else:
            report = mttr_report(records, target_s=args.target)
        line = json.dumps(report)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return 1 if report.get("error") else 0

    if args.cmd == "goodput":
        from dlrover_tpu.telemetry import events as events_mod
        from dlrover_tpu.telemetry.goodput import derive_goodput

        path = _resolve_events_path(args.events)
        if not path:
            print("goodput: no timeline (pass --events or set "
                  "DLROVER_TPU_EVENTS_FILE)", file=sys.stderr)
            return 2
        report = derive_goodput(events_mod.read_events(path))
        line = json.dumps(report)
        print(line)
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(line + "\n")
        return 1 if report.get("error") else 0

    if args.cmd == "diagnose":
        return _cmd_diagnose(args)

    if args.cmd == "events":
        from dlrover_tpu.telemetry import events as events_mod

        path = _resolve_events_path(args.events)
        records = (
            events_mod.read_events(path) if path
            else events_mod.recent_events()
        )
        if args.kind:
            records = [r for r in records if r.get("kind") == args.kind]
        if args.tail:
            records = records[-args.tail:]
        for rec in records:
            print(json.dumps(rec, sort_keys=True))
        return 0

    if args.cmd == "metrics":
        if args.addr:
            from dlrover_tpu.telemetry.exporter import fetch_metrics

            try:
                status, body = fetch_metrics(args.addr)
            except OSError as e:
                print(f"metrics: scrape of {args.addr} failed: {e}",
                      file=sys.stderr)
                return 2
            sys.stdout.write(body)
            return 0 if status == 200 else 1
        from dlrover_tpu.telemetry.metrics import process_registry

        sys.stdout.write(process_registry().render_prometheus())
        return 0

    if args.cmd == "trace":
        from dlrover_tpu.telemetry import events as events_mod
        from dlrover_tpu.telemetry.correlate import export_merged_trace

        path = _resolve_events_path(args.events)
        if not path:
            print("trace: no timeline (pass --events or set "
                  "DLROVER_TPU_EVENTS_FILE)", file=sys.stderr)
            return 2
        records = events_mod.read_events(path)
        n = export_merged_trace(records, args.out)
        print(f"merged {len(records)} event(s) into {n} trace "
              f"event(s) at {args.out}")
        return 0 if records else 1

    if args.cmd == "cache":
        from dlrover_tpu.utils.compile_cache import cache_stats

        stats = cache_stats(args.dir)
        print(json.dumps(stats))
        return 0

    return 2


if __name__ == "__main__":
    sys.exit(main())
