"""Recovery-readiness plane: the continuous durability audit, the
priced recovery ladder, and the blast-radius verdict pipeline.

Unit matrix for ``telemetry/readiness.py`` (RungPricer calibration +
pricing, the forensic ``predict_report`` / ``readiness_view``
derivations) and ``master/monitor/readiness.py`` (the sweep's coverage /
staleness / budget verdict cascade, gauge export with retraction, the
flag -> listener -> clear arc under one trace id), plus the
paired-median sweep-overhead gate and the in-process acceptance pin:
kill a replica holder with NO training failure -> DIAG_DURABILITY names
the at-risk owner with coverage evidence before any worker dies, the
optimizer replans under the verdict's trace id, re-replication clears
it, and the live (RPC) and forensic (events) CLI views agree
throughout.
"""

import io
import json
import sys
import time

import jax
import jax.numpy as jnp
import optax
import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.checkpoint import replication as repl
from dlrover_tpu.common.config import get_context
from dlrover_tpu.master.local_master import start_local_master
from dlrover_tpu.master.monitor.readiness import (
    VERDICT_DURABILITY,
    ReadinessAuditor,
)
from dlrover_tpu.master.replication import ReplicaDirectory
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry import (
    EventKind,
    names as tm,
    process_registry,
    read_events,
)
from dlrover_tpu.telemetry.goodput import derive_goodput
from dlrover_tpu.telemetry.readiness import (
    RUNG_INIT,
    RUNG_LADDER,
    RUNG_LIVE_RESHARD,
    RUNG_PEER_REBUILD,
    RUNG_STORAGE_RESTORE,
    RungPricer,
    cheapest_viable_rung,
    predict_report,
    readiness_view,
)
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.failover import RecoveryDecision, classify_recovery


@pytest.fixture()
def readiness_ctx(monkeypatch, tmp_path):
    """Replica plane on with test pacing (same knob discipline as
    tests/test_replication.py: the Context singleton leaks otherwise)
    plus a per-test event timeline."""
    ctx = get_context()
    saved = {k: getattr(ctx, k) for k in (
        "snapshot_replicas", "peer_restore", "replica_cadence_steps",
        "replica_min_interval_secs", "replica_budget_mb",
        "replica_chunk_kb",
    )}
    ctx.snapshot_replicas = 1
    ctx.peer_restore = True
    ctx.replica_cadence_steps = 2
    ctx.replica_min_interval_secs = 0.0
    ctx.replica_budget_mb = 64.0
    ctx.replica_chunk_kb = 4
    monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE",
                       str(tmp_path / "events.jsonl"))
    yield ctx
    for k, v in saved.items():
        setattr(ctx, k, v)


def _events(tmp_path):
    return read_events(str(tmp_path / "events.jsonl"))


def _run_json_cli(argv):
    """Invoke `tpurun <argv>` capturing stdout as parsed JSON."""
    from dlrover_tpu.trainer.run import main as tpurun

    buf, prev = io.StringIO(), sys.stdout
    sys.stdout = buf
    try:
        rc = tpurun(argv)
    finally:
        sys.stdout = prev
    return rc, json.loads(buf.getvalue())


# -- the pricer ---------------------------------------------------------------


class TestRungPricer:
    def test_priors_before_any_observation(self):
        """An uncalibrated ladder quotes the stated pessimistic priors
        in ladder order — it must never talk the planner OUT of a
        cheaper rung it has no evidence about."""
        table = RungPricer().table(region_bytes=0.0)
        assert list(table) == list(RUNG_LADDER)
        assert table[RUNG_LIVE_RESHARD] == 1.0
        assert table[RUNG_PEER_REBUILD] == 5.0
        assert table[RUNG_STORAGE_RESTORE] == 30.0
        assert table[RUNG_INIT] == 120.0

    def test_push_cycle_calibrates_peer_rebuild(self):
        """One replicator push cycle prices the rebuild transfer term:
        1 MB in 0.5 s -> link_bw 2 MB/s, so a 1 MB dead-node rebuild
        (drain 0) predicts fetch 0.5 s + device_put 1e6/2e9 s."""
        p = RungPricer()
        p.observe_push(1.0e6, 0.5)
        got = p.predict(RUNG_PEER_REBUILD, region_bytes=1.0e6,
                        drain_s=0.0)
        assert got == pytest.approx(0.5005, abs=1e-6)
        # the observation-priced rungs are untouched by the push feed
        assert p.predict(RUNG_STORAGE_RESTORE) == 30.0

    def test_realized_ema_and_correction_clamp(self):
        p = RungPricer()
        p.observe_realized(RUNG_STORAGE_RESTORE, 10.0)
        assert p.predict(RUNG_STORAGE_RESTORE) == pytest.approx(10.0)
        # a stamped predicted-vs-realized pair feeds the multiplicative
        # correction; a wild ratio clamps to [0.1, 10]
        p.observe_realized(RUNG_STORAGE_RESTORE, 10.0,
                           predicted_s=0.001)
        assert p.corr[RUNG_STORAGE_RESTORE] == pytest.approx(10.0)
        p2 = RungPricer()
        p2.observe_realized(RUNG_LIVE_RESHARD, 0.001, predicted_s=50.0)
        assert p2.corr[RUNG_LIVE_RESHARD] == pytest.approx(0.1)

    def test_unknown_rung_raises(self):
        with pytest.raises(ValueError):
            RungPricer().predict("teleport")

    def test_cheapest_viable_rung(self):
        table = {RUNG_LIVE_RESHARD: 1.0, RUNG_PEER_REBUILD: 5.0,
                 RUNG_STORAGE_RESTORE: 30.0, RUNG_INIT: 120.0}
        # non-viable rungs are skipped however cheap
        assert cheapest_viable_rung(
            table, {RUNG_STORAGE_RESTORE: True, RUNG_INIT: True},
        ) == RUNG_STORAGE_RESTORE
        # a calibrated cheaper restart outbids a live rung
        priced = dict(table, **{RUNG_PEER_REBUILD: 0.2})
        assert cheapest_viable_rung(
            priced, {r: True for r in RUNG_LADDER},
        ) == RUNG_PEER_REBUILD
        # ties break toward the ladder's traditional order
        tied = {r: 3.0 for r in RUNG_LADDER}
        assert cheapest_viable_rung(
            tied, {r: True for r in RUNG_LADDER},
        ) == RUNG_LIVE_RESHARD
        assert cheapest_viable_rung(table, {}) is None


# -- the sweep (unit, injected inventories) -----------------------------------


def _directory(nodes):
    d = ReplicaDirectory()
    for n in nodes:
        d.register(**n)
    return d


def _auditor(directory, inventory_fn, cadence=2, replicas=1,
             sweep_secs=3600.0, **kw):
    cell = {"replicas": replicas}
    a = ReadinessAuditor(
        directory, cadence_fn=lambda: cadence,
        replicas_fn=lambda: cell["replicas"],
        inventory_fn=inventory_fn, sweep_secs=sweep_secs, **kw)
    return a, cell


OWNER0 = dict(node_id=0, addr="h0", budget_mb=64.0, snapshot_mb=8.0,
              step=4)
HOLDER9 = dict(node_id=9, addr="h9", budget_mb=64.0, snapshot_mb=0.0,
               step=-1)


class TestSweepVerdicts:
    def test_healthy_coverage_prices_peer_rebuild(self, readiness_ctx,
                                                  tmp_path):
        process_registry().reset()
        d = _directory([OWNER0, HOLDER9])
        inv = {"h9": {"0": {"step": 4, "manifest": {}}}}
        a, _ = _auditor(d, lambda eps: inv)
        report = a.sweep(force=True)
        assert report["posture"] == "ready"
        assert report["at_risk_nodes"] == []
        node0 = report["nodes"]["0"]
        assert node0["owner"] and node0["coverage_ok"]
        assert node0["staleness_steps"] == 0
        assert node0["holders"] == [9]
        # a covered dead owner comes back through peer DRAM, and that
        # is the cheapest viable rung (live_reshard needs NOT owning)
        assert node0["best_rung"] == RUNG_PEER_REBUILD
        assert set(node0["predicted_mttr"]) == set(RUNG_LADDER)
        # coverage gauge: 1 for the healthy owner, labeled by node
        reg = process_registry()
        g = reg.get(tm.READINESS_COVERAGE, labels={"node": "0"})
        assert g is not None and g.value == 1.0
        assert reg.get(tm.REPLICA_ASSIGNED_K).value == 1.0
        assert reg.get(tm.REPLICA_DEGRADED_K).value == 0.0

    def test_store_only_holder_is_never_an_owner(self, readiness_ctx,
                                                 tmp_path):
        """Satellite pin: a ``snapshot_mb=0`` node is a holder, never
        an owner — it appears in the holder-load gauge but NEVER in the
        coverage gauge or the at-risk table, even with an empty
        inventory."""
        process_registry().reset()
        d = _directory([OWNER0, HOLDER9])
        a, _ = _auditor(d, lambda eps: {})
        report = a.sweep(force=True)
        node9 = report["nodes"]["9"]
        assert not node9["owner"] and node9["lender"]
        # only the owner is at risk; the store-only node's best rung is
        # the free one — nothing of the training state lives on it
        assert report["at_risk_nodes"] == ["0"]
        assert node9["best_rung"] == RUNG_LIVE_RESHARD
        reg = process_registry()
        assert reg.get(tm.READINESS_COVERAGE,
                       labels={"node": "9"}) is None
        load = reg.get(tm.REPLICA_HOLDER_LOAD_MB, labels={"node": "9"})
        assert load is not None and load.value > 0

    def test_lend_no_dram_owner_is_audited_but_not_loaded(
            self, readiness_ctx, tmp_path):
        """Satellite pin: a ``budget_mb<0`` node lends no DRAM — it is
        absent from the load/headroom gauges — but its OWN regions are
        still audited for coverage like any owner's."""
        process_registry().reset()
        stingy = dict(node_id=1, addr="h1", budget_mb=-1.0,
                      snapshot_mb=8.0, step=4)
        d = _directory([OWNER0, HOLDER9, stingy])
        inv = {"h9": {"0": {"step": 4, "manifest": {}},
                      "1": {"step": 4, "manifest": {}}}}
        a, _ = _auditor(d, lambda eps: inv)
        report = a.sweep(force=True)
        node1 = report["nodes"]["1"]
        assert node1["owner"] and not node1["lender"]
        assert node1["coverage_ok"]
        assert report["at_risk_nodes"] == []
        reg = process_registry()
        assert reg.get(tm.REPLICA_HOLDER_LOAD_MB,
                       labels={"node": "1"}) is None
        assert reg.get(tm.REPLICA_HOLDER_HEADROOM_MB,
                       labels={"node": "1"}) is None

    def test_coverage_loss_flags_then_clears_under_one_tid(
            self, readiness_ctx, tmp_path):
        process_registry().reset()
        d = _directory([OWNER0, HOLDER9])
        inv = {"h9": {"0": {"step": 4, "manifest": {}}}}
        box = {"inv": inv}
        a, _ = _auditor(d, lambda eps: box["inv"])
        calls = []
        a.add_verdict_listener(lambda n, v: calls.append((n, v)))
        assert a.sweep(force=True)["posture"] == "ready"

        box["inv"] = {}  # the holder's copy is gone
        degraded = a.sweep(force=True)
        assert degraded["posture"] == "degraded"
        assert degraded["at_risk_nodes"] == ["0"]
        assert (0, VERDICT_DURABILITY) in calls
        ev = _events(tmp_path)
        flag = [r for r in ev if r["kind"] == EventKind.DIAG_DURABILITY]
        assert flag and flag[-1]["error_code"] == "DURABILITY_COVERAGE"
        assert flag[-1]["diag_node"] == 0
        assert flag[-1]["required"] == 1 and flag[-1]["held"] == 0
        tid = flag[-1]["trace_id"]
        edge = [r for r in ev
                if r["kind"] == EventKind.READINESS_DEGRADED]
        assert edge and edge[-1]["trace_id"] == tid
        reg = process_registry()
        assert reg.get(tm.READINESS_COVERAGE,
                       labels={"node": "0"}).value == 0.0
        # a steady degraded state refreshes evidence, not the trace id
        a.sweep(force=True)
        assert a.verdicts()[0].trace_id == tid

        box["inv"] = inv  # re-replicated
        cleared = a.sweep(force=True)
        assert cleared["posture"] == "ready"
        assert (0, "healthy") in calls
        ev = _events(tmp_path)
        rec = [r for r in ev
               if r["kind"] == EventKind.DIAG_RECOVERED
               and r.get("was") == VERDICT_DURABILITY]
        assert rec and rec[-1]["trace_id"] == tid
        restored = [r for r in ev
                    if r["kind"] == EventKind.READINESS_RESTORED]
        assert restored and restored[-1]["trace_id"] == tid
        assert reg.get(tm.READINESS_COVERAGE,
                       labels={"node": "0"}).value == 1.0

    def test_staleness_beyond_cadence_budget_flags(self, readiness_ctx,
                                                   tmp_path):
        process_registry().reset()
        old = dict(OWNER0, step=10)
        d = _directory([old, HOLDER9])
        inv = {"h9": {"0": {"step": 2, "manifest": {}}}}
        a, _ = _auditor(d, lambda eps: inv, cadence=2)  # allowed = 4
        report = a.sweep(force=True)
        assert report["at_risk_nodes"] == ["0"]
        ev = _events(tmp_path)
        flag = [r for r in ev if r["kind"] == EventKind.DIAG_DURABILITY]
        assert flag[-1]["error_code"] == "REPLICA_STALE"
        assert flag[-1]["staleness_steps"] == 8
        assert flag[-1]["allowed_steps"] == 4
        g = process_registry().get(tm.READINESS_STALENESS,
                                   labels={"node": "0"})
        assert g is not None and g.value == 8.0

    def test_interval_gate_and_retraction(self, readiness_ctx,
                                          tmp_path):
        process_registry().reset()
        d = _directory([OWNER0, HOLDER9])
        inv = {"h9": {"0": {"step": 4, "manifest": {}}}}
        a, cell = _auditor(d, lambda eps: inv)
        assert a.sweep() is not None       # first tick is due
        assert a.sweep() is None           # interval-gated
        assert a.sweep(force=True) is not None
        # sweep_secs=0 disables the periodic path entirely
        off, _ = _auditor(d, lambda eps: inv, sweep_secs=0.0)
        assert off.sweep() is None
        # turning the plane off retracts the plan-wide scalars —
        # absent-not-zero, never a stale 1
        reg = process_registry()
        assert reg.get(tm.REPLICA_ASSIGNED_K) is not None
        cell["replicas"] = 0
        a.sweep(force=True)
        assert reg.get(tm.REPLICA_ASSIGNED_K) is None
        assert reg.get(tm.REPLICA_DEGRADED_K) is None


# -- sweep overhead gate (paired-median, ISSUE 15 methodology) ----------------


class TestSweepOverheadGate:
    def test_interval_gated_sweep_is_free_on_the_stats_tick(
            self, readiness_ctx, tmp_path):
        """The continuous audit must not tax the master's stats loop:
        an interval-gated ``sweep()`` call (the common, not-due case)
        adds ≤5% over the directory work the tick already does.
        Run-to-run drift on a shared box dwarfs the real cost, so the
        gate compares back-to-back pairs (alternating order), takes
        the median of per-pair ratios, and retries up to 3 attempts
        with best-of-2 legs, gating on the minimum attempt median —
        the tier-1 de-flake pattern the telemetry overhead gate uses."""
        d = _directory([OWNER0, HOLDER9] + [
            dict(node_id=n, addr=f"h{n}", budget_mb=64.0,
                 snapshot_mb=8.0, step=4) for n in (1, 2, 3, 4)
        ])
        a, _ = _auditor(d, lambda eps: {}, sweep_secs=3600.0)
        a.sweep(force=True)  # prime: every later sweep() is gated
        iters = 2000

        def leg(instrumented, best_of=1):
            best = None
            for _ in range(best_of):
                t0 = time.perf_counter()
                for _ in range(iters):
                    d.admitted_replicas(1)
                    if instrumented:
                        a.sweep()
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            return best

        def paired_median(pairs=3, best_of=1):
            ratios = []
            for i in range(pairs):
                if i % 2 == 0:
                    dt_b = leg(False, best_of)
                    dt_i = leg(True, best_of)
                else:
                    dt_i = leg(True, best_of)
                    dt_b = leg(False, best_of)
                ratios.append(dt_i / dt_b)
            return sorted(ratios)[len(ratios) // 2]

        medians = [paired_median()]
        while medians[-1] - 1.0 > 0.05 and len(medians) < 3:
            medians.append(paired_median(best_of=2))
        overhead = min(medians) - 1.0
        assert overhead <= 0.05, (
            f"readiness sweep overhead {overhead:.1%} above the 5% "
            f"budget (attempt medians {[round(m, 3) for m in medians]})"
        )


# -- forensic derivations -----------------------------------------------------


class TestPredictReport:
    def test_stamped_incident_gains_prediction_columns(self):
        t = time.time()
        records = [
            {"kind": "train_start", "ts": t, "pid": 1, "mono": 0.0},
            {"kind": "peer_rebuild_begin", "ts": t + 1, "pid": 1,
             "mono": 1.0, "predicted_mttr_s": 1.5,
             "rung": "peer_rebuild"},
            {"kind": "peer_rebuild_done", "ts": t + 3, "pid": 1,
             "mono": 3.0, "step": 4, "predicted_mttr_s": 1.5,
             "realized_mttr_s": 2.0, "rung": "peer_rebuild"},
        ]
        rep = predict_report(records)
        assert rep["priced"] == 1 and rep["within_2x"] == 1
        (row,) = [r for r in rep["incidents"]
                  if r["scenario"] == "peer_rebuild"]
        assert row["predicted_s"] == 1.5
        assert row["realized_s"] == 2.0
        assert row["rung"] == "peer_rebuild"
        assert row["ratio"] == 0.75

    def test_unstamped_incident_stays_unpriced_not_zero(self):
        t = time.time()
        records = [
            {"kind": "peer_rebuild_begin", "ts": t + 1, "pid": 1,
             "mono": 1.0},
            {"kind": "peer_rebuild_done", "ts": t + 3, "pid": 1,
             "mono": 3.0, "step": 4},
        ]
        rep = predict_report(records)
        assert rep["priced"] == 0 and rep["within_2x"] == 0
        (row,) = rep["incidents"]
        assert row["predicted_s"] is None and row["ratio"] is None


class TestReadinessView:
    def test_replays_verdict_and_posture_edges(self):
        t = time.time()
        records = [
            {"kind": "diag_durability", "ts": t, "diag_node": 0,
             "error_code": "DURABILITY_COVERAGE", "trace_id": "tid-1",
             "required": 1, "held": 0},
            {"kind": "readiness_degraded", "ts": t + 0.01,
             "trace_id": "tid-1", "nodes": [0]},
        ]
        view = readiness_view(records)
        assert view["posture"] == "degraded"
        assert view["at_risk_nodes"] == ["0"]
        assert view["at_risk"]["0"]["error_code"] == \
            "DURABILITY_COVERAGE"
        assert view["at_risk"]["0"]["trace_id"] == "tid-1"
        records += [
            {"kind": "diag_recovered", "ts": t + 5, "diag_node": 0,
             "was": "durability", "trace_id": "tid-1"},
            {"kind": "readiness_restored", "ts": t + 5.01,
             "trace_id": "tid-1"},
        ]
        view = readiness_view(records)
        assert view["posture"] == "ready"
        assert view["at_risk_nodes"] == []

    def test_flag_without_posture_edge_reads_degraded(self):
        """A rotated-away timeline that kept the flag but lost the
        posture edge: the verdict table wins — degraded is the honest
        summary."""
        view = readiness_view([
            {"kind": "diag_durability", "ts": time.time(),
             "diag_node": 2, "error_code": "REPLICA_STALE",
             "trace_id": "t"},
        ])
        assert view["posture"] == "degraded"
        assert view["at_risk_nodes"] == ["2"]


class TestGoodputDurabilityColumn:
    def test_degraded_spell_is_a_column_not_a_bucket(self):
        t = time.time()
        records = [
            {"kind": "train_start", "ts": t, "pid": 1, "mono": 0.0},
            {"kind": "readiness_degraded", "ts": t + 1, "pid": 2,
             "mono": 1.0},
            {"kind": "readiness_restored", "ts": t + 3, "pid": 2,
             "mono": 3.0},
            {"kind": "train_end", "ts": t + 10, "pid": 1,
             "mono": 10.0},
        ]
        ledger = derive_goodput(records)
        col = ledger["detail"]["durability_at_risk"]
        assert col["spells"] == 1
        assert col["seconds"] == pytest.approx(2.0, abs=0.01)

    def test_absent_when_never_at_risk(self):
        t = time.time()
        ledger = derive_goodput([
            {"kind": "train_start", "ts": t, "pid": 1, "mono": 0.0},
            {"kind": "train_end", "ts": t + 5, "pid": 1, "mono": 5.0},
        ])
        assert "durability_at_risk" not in ledger["detail"]


# -- the priced rung choice ---------------------------------------------------


class TestClassifyRecoveryPriced:
    def test_unpriced_table_keeps_the_ladder_order(self):
        assert classify_recovery(EventKind.RDZV_JOIN) == \
            RecoveryDecision.LIVE_RESHARD
        assert classify_recovery(EventKind.RDZV_JOIN, mttr_table={}) \
            == RecoveryDecision.LIVE_RESHARD
        # a table with no live price cannot move the decision
        assert classify_recovery(
            EventKind.RDZV_JOIN,
            mttr_table={RUNG_PEER_REBUILD: 0.1},
        ) == RecoveryDecision.LIVE_RESHARD

    def test_cheaper_restart_rung_outbids_live_reshard(self):
        table = {RUNG_LIVE_RESHARD: 10.0, RUNG_PEER_REBUILD: 1.0,
                 RUNG_STORAGE_RESTORE: 30.0, RUNG_INIT: 120.0}
        assert classify_recovery(EventKind.RDZV_JOIN,
                                 mttr_table=table) == \
            RecoveryDecision.PROCESS_RESTART

    def test_live_stays_when_priced_cheapest(self):
        table = {RUNG_LIVE_RESHARD: 0.5, RUNG_PEER_REBUILD: 5.0,
                 RUNG_STORAGE_RESTORE: 30.0, RUNG_INIT: 120.0}
        assert classify_recovery(EventKind.RDZV_JOIN,
                                 mttr_table=table) == \
            RecoveryDecision.LIVE_RESHARD
        # safety gates still dominate pricing
        cheap_restart = {RUNG_LIVE_RESHARD: 10.0,
                         RUNG_PEER_REBUILD: 1.0}
        assert classify_recovery(
            EventKind.RDZV_JOIN, host_healthy=False,
            mttr_table=cheap_restart,
        ) == RecoveryDecision.POD_RESTART

    def test_dlr008_covers_the_new_failure_kinds(self):
        from dlrover_tpu.analysis.ast_rules import (
            FAILURE_EVENT_ATTRS,
            FAILURE_EVENT_VALUES,
        )

        for attr in ("DIAG_DURABILITY", "READINESS_DEGRADED"):
            assert attr in FAILURE_EVENT_ATTRS
        for val in ("diag_durability", "readiness_degraded"):
            assert val in FAILURE_EVENT_VALUES


# -- acceptance pin: holder kill -> verdict -> replan -> clear ----------------


def _linear_trainer(master, node_id=0):
    def init_fn(rng):
        return {"w": jax.random.normal(rng, (4, 2)), "b": jnp.zeros((2,))}

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    rngs = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(rngs[0], (16, 4))
    batch = {"x": x, "y": x @ jax.random.normal(rngs[1], (4, 2))}
    trainer = ElasticTrainer(
        init_fn, loss_fn, optax.adam(0.1), batch,
        strategy=Strategy(mesh=MeshPlan(data=-1)),
        master_client=MasterClient(master.addr, node_id=node_id),
        ckpt_dir="",
    )
    return trainer, batch


def _register_holder(master, node_id=9):
    store = repl.ReplicaStore()
    srv, port = repl.start_replica_server(store, host="127.0.0.1")
    client = MasterClient(master.addr, node_id=node_id)
    client.report_replica_endpoint(
        addr=f"127.0.0.1:{port}", budget_mb=64.0, snapshot_mb=0.0,
        step=-1)
    client.close()
    return store, srv


def _push_through_replicator(trainer, state, master, store):
    replicator = repl.SnapshotReplicator(
        trainer._master_client, node_id=0)
    try:
        snap = trainer.snapshot(state)
        assert replicator.submit(snap.tree, snap.meta, snap.step)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if store.inventory().get("0"):
                break
            time.sleep(0.05)
        assert store.inventory().get("0"), "push never landed"
        return snap
    finally:
        replicator.stop()


class TestReadinessEndToEnd:
    def test_holder_kill_flags_owner_before_any_worker_dies(
            self, readiness_ctx, tmp_path):
        """The acceptance pin: kill a replica HOLDER (no training
        failure anywhere) -> the audit names the at-risk OWNER with
        coverage evidence before any worker dies, the optimizer replans
        under the verdict's trace id, re-replication clears it, one
        incident id spans flag -> replan -> clear, and the live (RPC)
        and forensic (events) CLI views agree at every posture."""
        events_path = str(tmp_path / "events.jsonl")
        master = start_local_master()
        try:
            store, srv = _register_holder(master, node_id=9)
            trainer, batch = _linear_trainer(master, node_id=0)
            state = trainer.prepare()
            for _ in range(3):
                state, _ = trainer.step(state, batch)
            _push_through_replicator(trainer, state, master, store)
            seed = MasterClient(master.addr, node_id=0)
            seed.report_trainer_config(
                world=1, mesh_shape={"data": 1}, train_window=4,
                global_batch=8)
            seed.close()

            auditor = master.servicer.readiness_auditor
            ready = auditor.sweep(force=True)
            assert ready["posture"] == "ready", ready["at_risk"]
            node0 = ready["nodes"]["0"]
            assert node0["owner"] and node0["coverage_ok"]
            assert node0["best_rung"] == RUNG_PEER_REBUILD
            # the push cycle calibrated the transfer term: recovery
            # plans now carry real prices, not priors
            assert ready["calibration"]["link_bw_bytes_per_s"]
            plan_client = MasterClient(master.addr, node_id=0)
            plan = plan_client.get_recovery_plan()
            plan_client.close()
            prices = plan["predicted_mttr"]
            assert set(prices) == set(RUNG_LADDER)
            assert 0 < prices[RUNG_PEER_REBUILD] < 5.0

            # kill the HOLDER: nothing about training fails
            srv.stop(grace=0)
            degraded = auditor.sweep(force=True)
            assert degraded["posture"] == "degraded"
            assert degraded["at_risk_nodes"] == ["0"]
            ev = _events(tmp_path)
            assert not any(r["kind"] == EventKind.WORKER_FAILED
                           for r in ev), \
                "the verdict must precede any worker death"
            flag = [r for r in ev
                    if r["kind"] == EventKind.DIAG_DURABILITY]
            assert flag and flag[-1]["diag_node"] == 0
            assert flag[-1]["error_code"] == "DURABILITY_COVERAGE"
            assert flag[-1]["required"] == 1 and flag[-1]["held"] == 0
            tid = flag[-1]["trace_id"]
            # the degradation reached the optimizer under the SAME
            # incident id (verdict listener -> durability:<node> replan)
            opt = [r for r in ev
                   if r["kind"] in (EventKind.OPTIMIZER_REPLAN,
                                    EventKind.OPTIMIZER_PLAN_REJECTED)
                   and r.get("trace_id") == tid]
            assert opt, "no optimizer decision under the verdict tid"

            # live/forensic CLI agreement while degraded
            rc_l, live = _run_json_cli(
                ["readiness", "--addr", master.addr, "--json"])
            rc_f, forensic = _run_json_cli(
                ["readiness", "--events", events_path, "--json"])
            assert rc_l == 0 and rc_f == 0
            assert live["posture"] == forensic["posture"] == "degraded"
            assert live["at_risk_nodes"] == \
                forensic["at_risk_nodes"] == ["0"]

            # re-replication: a fresh holder re-registers as node 9
            # and the owner pushes again
            store2, srv2 = _register_holder(master, node_id=9)
            _push_through_replicator(trainer, state, master, store2)
            cleared = auditor.sweep(force=True)
            assert cleared["posture"] == "ready"
            ev = _events(tmp_path)
            rec = [r for r in ev
                   if r["kind"] == EventKind.DIAG_RECOVERED
                   and r.get("was") == VERDICT_DURABILITY]
            assert rec and rec[-1]["trace_id"] == tid
            restored = [r for r in ev
                        if r["kind"] == EventKind.READINESS_RESTORED]
            assert restored and restored[-1]["trace_id"] == tid

            # agreement holds after the clear too
            rc_l, live = _run_json_cli(
                ["readiness", "--addr", master.addr, "--json"])
            rc_f, forensic = _run_json_cli(
                ["readiness", "--events", events_path, "--json"])
            assert rc_l == 0 and rc_f == 0
            assert live["posture"] == forensic["posture"] == "ready"
            assert live["at_risk_nodes"] == \
                forensic["at_risk_nodes"] == []
            srv2.stop(grace=0)
        finally:
            master.stop()
