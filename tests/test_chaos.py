"""Chaos tests: REAL faults (SIGKILL, flaky rpc, torn checkpoint) against
real components — the integration layer mocked-fault unit tests miss.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training_agent import (
    AgentConfig,
    ElasticTrainingAgent,
)
from dlrover_tpu.agent.worker_group import WorkerSpec
from dlrover_tpu.diagnosis.fault_injection import (
    corrupt_checkpoint,
    kill_workers,
    make_flaky,
)
from dlrover_tpu.master.local_master import start_local_master

TESTDATA = os.path.join(os.path.dirname(__file__), "testdata")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER_ENV = {
    "PYTHONPATH": REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
}


@pytest.fixture()
def master():
    m = start_local_master()
    yield m
    m.stop()


def _derived_mttr(events_path):
    """Run the real CLI derivation over a chaos run's event timeline."""
    from dlrover_tpu.telemetry import read_events
    from dlrover_tpu.telemetry.mttr import mttr_report

    return mttr_report(read_events(events_path))


def test_external_sigkill_triggers_restart(master, tmp_path, monkeypatch):
    """A worker killed from OUTSIDE (SIGKILL, like an OOM killer or
    preemption — not a polite exception) must be detected by the monitor
    loop and restarted within the budget."""
    events_path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_path)
    client = MasterClient(master.addr, node_id=0)
    config = AgentConfig(
        node_rank=0, node_id=0, nproc_per_node=1, min_nodes=1, max_nodes=1,
        max_restarts=2, monitor_interval=0.2, rdzv_waiting_timeout=5.0,
    )
    spec = WorkerSpec(
        entrypoint=os.path.join(TESTDATA, "chaos_worker.py"),
        nproc_per_node=1, env=dict(WORKER_ENV),
    )
    agent = ElasticTrainingAgent(config, spec, client, host_ip="127.0.0.1")

    result = {}
    thread = threading.Thread(
        target=lambda: result.update(rc=agent.run()), daemon=True
    )
    thread.start()

    # wait for the round-0 worker process, then SIGKILL it
    deadline = time.monotonic() + 30
    pids = []
    while time.monotonic() < deadline:
        procs = getattr(agent._worker_group, "_procs", [])
        pids = [p.pid for p in procs if p.poll() is None]
        if pids:
            break
        time.sleep(0.1)
    assert pids, "worker never spawned"
    assert kill_workers(pids)

    thread.join(timeout=60)
    assert not thread.is_alive(), "agent did not finish after chaos kill"
    assert result["rc"] == 0
    assert agent._worker_group.restart_round >= 1
    # the MTTR artifact is DERIVED from the timeline this run produced:
    # worker_failed (SIGKILL classified by exit code) -> workers_started
    report = _derived_mttr(events_path)
    wf = report["detail"]["by_scenario"].get("worker_failure")
    assert wf and wf["count"] >= 1, report
    assert report["value"] > 0
    assert "error" not in report, report

    # -- cross-process trace correlation: the incident id minted at
    # failure detection must stamp the AGENT's failure edge, the
    # MASTER's ingress-side error_report (propagated through gRPC
    # metadata), the recovery edge, and the relaunched WORKER's own
    # startup events (propagated through the worker environment)
    from dlrover_tpu.telemetry import read_events

    records = read_events(events_path)
    failed = [r for r in records if r["kind"] == "worker_failed"]
    assert failed, records
    tid = failed[0].get("trace_id", "")
    assert tid.startswith("inc-"), failed[0]
    stamped = {r["kind"] for r in records if r.get("trace_id") == tid}
    assert "error_report" in stamped, stamped  # master ingress (RPC md)
    assert "workers_started" in stamped, stamped  # agent recovery edge
    assert "train_start" in stamped, stamped  # relaunched worker (env)
    stamped_pids = {r["pid"] for r in records
                    if r.get("trace_id") == tid}
    assert len(stamped_pids) >= 2, (
        "the incident id never crossed a process boundary")

    # -- merged Perfetto trace: the incident's master/agent/worker
    # records land in ONE view, joined by the shared trace id
    from dlrover_tpu.telemetry.correlate import (
        export_merged_trace,
        incident_records,
    )

    merged_path = str(tmp_path / "merged_trace.json")
    n = export_merged_trace(records, merged_path)
    assert n > 0
    import json

    payload = json.load(open(merged_path))
    names_seen = {e["name"] for e in payload["traceEvents"]}
    assert "worker_failure" in names_seen  # incident downtime span
    chain = incident_records(records)[tid]
    assert len(chain) >= 3

    # -- goodput ledger over the same timeline: buckets partition the
    # job wall-time (>= 99%) and the restart downtime is attributed
    from dlrover_tpu.telemetry.goodput import derive_goodput

    ledger = derive_goodput(records)
    assert ledger["detail"]["coverage"] >= 0.99, ledger
    assert ledger["detail"]["buckets"]["restart"]["seconds"] > 0, ledger

    # -- the CLI gate: `tpurun goodput` / `tpurun diagnose` must keep
    # working against a real chaos timeline (exit 0, parseable output)
    from dlrover_tpu.trainer.run import main as tpurun

    assert tpurun(["goodput", "--events", events_path]) == 0
    assert tpurun(["diagnose", "--events", events_path]) == 0
    assert tpurun(["trace", "--events", events_path,
                   "--out", str(tmp_path / "cli_trace.json")]) == 0


def test_hang_without_heartbeat_triggers_relaunch(master, tmp_path,
                                                  monkeypatch):
    """A worker whose process stays alive but whose step loop freezes
    (the TPU hang mode: a collective waiting on a dead peer) must be
    detected via the heartbeat gap and relaunched — the reference's
    --relaunch_on_hanging semantics."""
    events_path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_path)
    client = MasterClient(master.addr, node_id=0)
    config = AgentConfig(
        node_rank=0, node_id=0, nproc_per_node=1, min_nodes=1, max_nodes=1,
        max_restarts=2, monitor_interval=0.2, rdzv_waiting_timeout=5.0,
        # must exceed worker python startup on a loaded 1-core host, or
        # the restarted round gets falsely flagged before its first beat
        hang_timeout=8.0,
    )
    spec = WorkerSpec(
        entrypoint=os.path.join(TESTDATA, "hang_worker.py"),
        nproc_per_node=1, env=dict(WORKER_ENV),
    )
    agent = ElasticTrainingAgent(config, spec, client, host_ip="127.0.0.1")
    rc = agent.run()
    assert rc == 0
    assert agent._worker_group.restart_round >= 1
    # the hang was reported to the master's failure log as node 0
    assert 0 in client.failed_nodes()
    client.close()
    # derived MTTR: hang_detected -> workers_started, with the HANG
    # error code carried on the failure edge
    from dlrover_tpu.telemetry import read_events

    report = _derived_mttr(events_path)
    hang = report["detail"]["by_scenario"].get("hang")
    assert hang and hang["count"] >= 1, report
    assert report["value"] > 0
    hang_edges = [r for r in read_events(events_path)
                  if r["kind"] == "hang_detected"]
    assert hang_edges and hang_edges[0]["error_code"] == "HANG"


def test_long_phase_lease_defers_hang_judgment(tmp_path):
    """A declared bounded no-beat window (recompile/restore lease) must
    count as liveness until its deadline — and a stale lease from before
    a restart must not extend the fresh round's clock."""
    from dlrover_tpu.agent.worker_group import WorkerGroup, WorkerSpec

    spec = WorkerSpec(entrypoint="x", heartbeat_dir=str(tmp_path))
    group = WorkerGroup(spec)
    group.started_at = time.time() - 100  # round began 100 s ago

    # no beats, no lease: gap is the full 100 s
    latest, beaten = group.latest_heartbeat()
    assert not beaten and time.time() - latest > 90

    # write the lease through the REAL producer (announce_long_phase) —
    # the heartbeat dir itself contains "hb_" like the agent's tempdir,
    # which a naive whole-path prefix swap would corrupt
    import dlrover_tpu.diagnosis.hang_detector as hd
    from dlrover_tpu.common.constants import NodeEnv

    hb_dir = tmp_path / "dlrover_hb_test"
    old_env = os.environ.get(NodeEnv.HEARTBEAT_DIR)
    os.environ[NodeEnv.HEARTBEAT_DIR] = str(hb_dir)
    hd._heartbeat_path = None
    hd._heartbeat_resolved = False
    try:
        spec2 = WorkerSpec(entrypoint="x", heartbeat_dir=str(hb_dir))
        group2 = WorkerGroup(spec2)
        group2.started_at = time.time() - 100
        hd.announce_long_phase(300)
        assert (hb_dir / "lease_0").exists()
        latest, _ = group2.latest_heartbeat()
        assert time.time() - latest < 5

        # the next heartbeat (phase over) clears the lease
        hd.touch_heartbeat()
        assert not (hb_dir / "lease_0").exists()

        # a stale lease is ignored once a new round starts after it
        hd.announce_long_phase(300)
        group2.started_at = time.time() + 1
        latest, _ = group2.latest_heartbeat()
        assert latest == group2.started_at
    finally:
        hd._heartbeat_path = None
        hd._heartbeat_resolved = False
        if old_env is None:
            os.environ.pop(NodeEnv.HEARTBEAT_DIR, None)
        else:
            os.environ[NodeEnv.HEARTBEAT_DIR] = old_env


# budget triage (PR 16): retry counting + desynchronized backoff are
# pinned tier-1 by test_replication's flaky-servicer test; the full
# agent-chaos variant rides slow
@pytest.mark.slow
def test_flaky_rpc_absorbed_by_retries(master):
    """Inject UNAVAILABLE below the retry decorator on a deterministic
    fraction of calls; the dynamic-sharding flow must still complete."""
    client = MasterClient(master.addr, node_id=0)
    stats = make_flaky(client._channel, drop_rate=0.25, seed=7)

    client.report_dataset_shard_params(
        dataset_name="chaos_ds", dataset_size=24, batch_size=3,
        num_epochs=1, num_minibatches_per_shard=2,
    )
    # a post-call injected fault on get_task LOSES the response: the shard
    # sits in "doing" until the timeout monitor requeues it. Drive that
    # recovery deterministically (timeout=0 == one monitor tick) between
    # drain rounds — completion must survive both fault modes.
    done = 0
    for _attempt in range(6):
        while True:
            task = client.get_task("chaos_ds")
            if task is None or task.task_id < 0:
                break
            client.report_task_result("chaos_ds", task.task_id)
            done += 1
        if done >= 4:
            break
        dataset = master.task_manager.get_dataset("chaos_ds")
        dataset.recover_timeout_tasks(0)
    assert done == 4  # 24 records / (3*2) per shard, every shard completed
    assert stats.injected > 0, "no faults were actually injected"
    client.close()


def test_peer_rebuild_after_sigkill_is_bitwise_and_storage_free(
        master, tmp_path, monkeypatch):
    """The checkpoint-free recovery wedge (ISSUE 15 acceptance):
    SIGKILL a worker whose snapshot regions are replicated on a
    surviving peer -> the master's verdict excludes the dead node from
    holder lists -> the relaunched worker rebuilds its state by
    STREAMING it out of the peer's DRAM (no checkpoint directory even
    exists) -> its post-recovery steps are BITWISE an uninterrupted
    run's, the whole recovery rides ONE incident trace id across >= 2
    pids, and the MTTR/goodput derivations record the peer_rebuild
    scenario with zero storage bytes."""
    import subprocess
    import sys

    from dlrover_tpu.checkpoint import replication as repl

    events_path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_path)
    # the MASTER owns k ("k peer agents chosen by the master"): the
    # plan request is priced against ITS Context knob, so the master
    # process — pytest here — must carry it, not just the workers
    from dlrover_tpu.common.config import get_context

    monkeypatch.setattr(get_context(), "snapshot_replicas", 1)
    # the surviving peer: an in-test replica store registered as node 9
    # (its process — pytest — survives the worker's death)
    store = repl.ReplicaStore()
    srv, port = repl.start_replica_server(store, host="127.0.0.1")
    holder_client = MasterClient(master.addr, node_id=9)
    holder_client.report_replica_endpoint(
        addr=f"127.0.0.1:{port}", budget_mb=64.0, snapshot_mb=0.0,
        step=-1)

    status = tmp_path / "status.jsonl"
    worker_env = {
        **WORKER_ENV,
        "PEER_STATUS": str(status),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",
        "DLROVER_TPU_SNAPSHOT_REPLICAS": "1",
        "DLROVER_TPU_REPLICA_CADENCE_STEPS": "2",
        "DLROVER_TPU_REPLICA_MIN_INTERVAL_SECS": "0",
        "DLROVER_TPU_PEER_RESTORE": "true",
    }
    config = AgentConfig(
        node_rank=0, node_id=0, nproc_per_node=1, min_nodes=1,
        max_nodes=1, max_restarts=2, monitor_interval=0.2,
        rdzv_waiting_timeout=5.0,
    )
    spec = WorkerSpec(
        entrypoint=os.path.join(TESTDATA, "peer_worker.py"),
        nproc_per_node=1, env=worker_env,
    )
    client = MasterClient(master.addr, node_id=0)
    agent = ElasticTrainingAgent(config, spec, client,
                                 host_ip="127.0.0.1")
    result = {}
    thread = threading.Thread(
        target=lambda: result.update(rc=agent.run()), daemon=True
    )
    thread.start()
    try:
        # wait until a replica has COMMITTED on the surviving peer,
        # then SIGKILL the worker mid-step
        deadline = time.monotonic() + 120
        pids = []
        while time.monotonic() < deadline:
            procs = getattr(agent._worker_group, "_procs", [])
            pids = [p.pid for p in procs if p.poll() is None]
            if pids and store.inventory().get("0"):
                break
            time.sleep(0.1)
        assert store.inventory().get("0"), \
            "no replica ever committed on the surviving peer"
        assert pids and kill_workers(pids)

        thread.join(timeout=180)
        assert not thread.is_alive(), "agent never finished"
        assert result["rc"] == 0
        assert agent._worker_group.restart_round >= 1
    finally:
        holder_client.close()
        client.close()
        srv.stop(grace=0)

    # -- the recovered run resumed at the replicated step and finished
    records = [json.loads(ln) for ln in
               status.read_text().splitlines()]
    ends = [r for r in records if r.get("event") == "end"]
    assert ends, records[-3:]
    end = ends[-1]
    assert end["round"] >= 1
    resumed = end["resumed_step"]
    assert resumed >= 2, "relaunched worker did not peer-restore"
    assert end["final_step"] == resumed + 3
    # the relaunched worker keeps replicating: the surviving peer's
    # freshest commit is at (or past) the recovered run's progress
    assert store.inventory()["0"]["manifest"]["meta"][
        "host_step"] >= resumed

    # -- bitwise: an UNINTERRUPTED run to the same step produces the
    # identical params (same rng stream, same batches — the rebuild
    # lost nothing and invented nothing)
    ref_status = tmp_path / "ref_status.jsonl"
    ref_env = {
        **os.environ, **WORKER_ENV,
        "PEER_STATUS": str(ref_status),
        "PEER_REFERENCE": "1",
        "PEER_TOTAL_STEPS": str(end["final_step"]),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",
        "DLROVER_TPU_SNAPSHOT_REPLICAS": "0",
    }
    ref = subprocess.run(
        [sys.executable, os.path.join(TESTDATA, "peer_worker.py")],
        env=ref_env, timeout=180,
    )
    assert ref.returncode == 0
    ref_end = [json.loads(ln) for ln in
               ref_status.read_text().splitlines()][-1]
    assert ref_end["final_step"] == end["final_step"]
    assert ref_end["digest"] == end["digest"], (
        "post-recovery params diverged from the uninterrupted run")

    # -- zero storage reads on the recovery path, derived + asserted
    from dlrover_tpu.telemetry import read_events

    timeline = read_events(events_path)
    done = [r for r in timeline if r["kind"] == "peer_rebuild_done"]
    assert done, "no peer_rebuild_done edge in the timeline"
    assert done[-1]["storage_bytes"] == 0
    assert done[-1]["bytes_from_peers"] > 0

    # -- the rung the worker walked was PRICED: both the prediction it
    # fetched with the recovery plan and the realized fetch+put cost
    # are stamped on the recovery event, and the prediction is within
    # 2x of reality either way (the readiness acceptance pin — the
    # link_bw term is calibrated from the replicator's own push cycles
    # over this same localhost RPC path)
    predicted = done[-1].get("predicted_mttr_s")
    realized = done[-1].get("realized_mttr_s")
    assert predicted is not None and predicted > 0, done[-1]
    assert realized is not None and realized > 0, done[-1]
    assert done[-1].get("rung") == "peer_rebuild"
    assert predicted <= 2.0 * realized + 0.05, (predicted, realized)
    assert realized <= 2.0 * predicted + 0.05, (predicted, realized)
    assert not [r for r in timeline if r["kind"] == "ckpt_restore"], (
        "the recovery path touched storage")

    # -- one incident trace id spans agent-side failure detection and
    # the relaunched worker's peer rebuild (>= 2 pids)
    failed = [r for r in timeline if r["kind"] == "worker_failed"]
    assert failed
    tid = failed[0].get("trace_id", "")
    assert tid.startswith("inc-")
    stamped = {r["kind"] for r in timeline
               if r.get("trace_id") == tid}
    assert "peer_rebuild_done" in stamped, stamped
    assert "workers_started" in stamped, stamped
    pids_stamped = {r["pid"] for r in timeline
                    if r.get("trace_id") == tid}
    assert len(pids_stamped) >= 2

    # -- the MTTR scenario + goodput ledger record the recovery
    report = _derived_mttr(events_path)
    pr = report["detail"]["by_scenario"].get("peer_rebuild")
    assert pr and pr["count"] >= 1, report
    wf = report["detail"]["by_scenario"].get("worker_failure")
    assert wf and wf["count"] >= 1, report
    from dlrover_tpu.telemetry.goodput import derive_goodput

    ledger = derive_goodput(timeline)
    assert ledger["detail"]["coverage"] >= 0.99, ledger
    assert ledger["detail"]["buckets"]["peer_rebuild"]["seconds"] > 0


@pytest.mark.slow
def test_kill_restart_soak(master):
    """Repeated external SIGKILL cycles: every round must be detected,
    reported, and restarted until the budget genuinely runs out —
    recovery machinery that only survives ONE fault is not recovery."""
    rounds = 3
    client = MasterClient(master.addr, node_id=0)
    config = AgentConfig(
        node_rank=0, node_id=0, nproc_per_node=1, min_nodes=1, max_nodes=1,
        max_restarts=rounds, monitor_interval=0.2,
        rdzv_waiting_timeout=5.0,
    )
    spec = WorkerSpec(
        entrypoint=os.path.join(TESTDATA, "soak_worker.py"),
        nproc_per_node=1, env=dict(WORKER_ENV),
    )
    agent = ElasticTrainingAgent(config, spec, client, host_ip="127.0.0.1")
    result = {}
    thread = threading.Thread(
        target=lambda: result.update(rc=agent.run()), daemon=True
    )
    thread.start()

    killed = 0
    deadline = time.monotonic() + 120
    while killed < rounds and time.monotonic() < deadline:
        procs = getattr(agent._worker_group, "_procs", [])
        pids = [p.pid for p in procs if p.poll() is None]
        round_now = agent._worker_group.restart_round
        if pids and round_now == killed:
            time.sleep(0.5)  # let the round take a breath, then kill it
            if kill_workers(pids):
                killed += 1
        time.sleep(0.1)
    assert killed == rounds, f"only injected {killed}/{rounds} kills"

    thread.join(timeout=60)
    assert not thread.is_alive()
    assert result["rc"] == 0  # final (uninjected) round completes
    assert agent._worker_group.restart_round == rounds


def test_corrupt_latest_checkpoint_falls_back(tmp_path):
    """Torn-write the newest checkpoint; restore must come back from the
    newest GOOD step instead of crashing."""
    from dlrover_tpu.checkpoint.manager import (
        ElasticCheckpointManager,
        abstract_like,
    )

    mgr = ElasticCheckpointManager(
        str(tmp_path / "ckpt"), async_save=False, staging_dir="",
    )
    state = {"w": jnp.full((64, 64), 1.0), "step": jnp.asarray(1)}
    assert mgr.save(1, state, force=True)
    state2 = {"w": jnp.full((64, 64), 2.0), "step": jnp.asarray(2)}
    assert mgr.save(2, state2, force=True)
    mgr.wait()

    step2_dir = mgr._step_dir(mgr.directory, 2)
    assert os.path.isdir(step2_dir)
    assert corrupt_checkpoint(step2_dir, mode="truncate") is not None

    out = mgr.restore(abstract_like(state))
    assert out is not None
    assert out["step"] == 1
    np.testing.assert_allclose(np.asarray(out["state"]["w"]), 1.0)

    # the corrupt step must be quarantined: otherwise it keeps winning
    # latest_step() and blocks the resumed job's re-save at step 2
    assert mgr.latest_step() == 1
    assert not os.path.isdir(step2_dir)
    assert mgr.save(2, state2, force=True), (
        "re-save at the quarantined step number must be accepted"
    )
    mgr.wait()
    assert mgr.latest_step() == 2
    out2 = mgr.restore(abstract_like(state))
    assert out2["step"] == 2
    np.testing.assert_allclose(np.asarray(out2["state"]["w"]), 2.0)
    mgr.close()


def test_corrupt_primary_recovers_same_step_from_staging(tmp_path):
    """When the primary copy of the latest step is torn but the host-DRAM
    mirror still holds that step (digest gate rejects it only because the
    PRIMARY is now corrupt), the fallback must restore the SAME step from
    staging — losing zero progress — and quarantine the bad primary."""
    from dlrover_tpu.checkpoint.manager import (
        ElasticCheckpointManager,
        abstract_like,
    )

    mgr = ElasticCheckpointManager(
        str(tmp_path / "ckpt"), async_save=False,
        staging_dir=str(tmp_path / "shm"),
    )
    state1 = {"w": jnp.full((64, 64), 1.0), "step": jnp.asarray(1)}
    state2 = {"w": jnp.full((64, 64), 2.0), "step": jnp.asarray(2)}
    assert mgr.save(1, state1, force=True)
    mgr.wait()
    assert mgr.save(2, state2, force=True)
    mgr.wait()
    assert mgr.staged_step() == 2

    corrupt_checkpoint(mgr._step_dir(mgr.directory, 2), mode="truncate")
    out = mgr.restore(abstract_like(state1))
    assert out is not None
    assert out["step"] == 2, "staging held step 2 — no progress loss"
    np.testing.assert_allclose(np.asarray(out["state"]["w"]), 2.0)
    assert not os.path.isdir(mgr._step_dir(mgr.directory, 2))
    mgr.close()


def test_primary_loss_recovers_from_staging_across_restart(tmp_path):
    """The storage-outage story end to end ACROSS a process restart: the
    primary root is wiped, a new manager (same run identity) comes up,
    and the host-DRAM mirror restores — a path-local uuid would have
    been lost with the primary and wrongly rejected the mirror. A
    DIFFERENT job identity must still refuse the mirror."""
    import shutil

    from dlrover_tpu.checkpoint.manager import (
        ElasticCheckpointManager,
        abstract_like,
    )

    primary = str(tmp_path / "ckpt")
    staging = str(tmp_path / "shm")
    state = {"w": jnp.full((32, 32), 5.0), "step": jnp.asarray(3)}

    mgr1 = ElasticCheckpointManager(
        primary, async_save=False, staging_dir=staging,
        run_identity="jobA",
    )
    assert mgr1.save(3, state, force=True)
    mgr1.wait()
    assert mgr1.staged_step() == 3
    mgr1.close()

    shutil.rmtree(primary)  # the outage

    mgr2 = ElasticCheckpointManager(
        primary, async_save=False, staging_dir=staging,
        run_identity="jobA",
    )
    out = mgr2.restore(abstract_like(state))
    assert out is not None and out["step"] == 3
    np.testing.assert_allclose(np.asarray(out["state"]["w"]), 5.0)
    mgr2.close()

    shutil.rmtree(primary)
    mgr3 = ElasticCheckpointManager(
        primary, async_save=False, staging_dir=staging,
        run_identity="jobB",
    )
    assert mgr3.restore(abstract_like(state)) is None
    mgr3.close()


def test_shuffled_text_shards_honor_permutation(tmp_path):
    """A shuffled text dataset's shards carry record_indices; the batch
    source must train on that permutation, not contiguous ranges."""
    from dlrover_tpu.trainer.text_reader import (
        LineIndexedFile,
        ShardedTextBatches,
    )
    from dlrover_tpu.agent.sharding_client import ShardingClient

    path = tmp_path / "c.txt"
    path.write_text("".join(f"rec{i:03d}\n" for i in range(32)))
    reader = LineIndexedFile(str(path))

    m = start_local_master()
    try:
        client = MasterClient(m.addr, node_id=0)
        sc = ShardingClient(
            client, dataset_name="shuf", batch_size=4,
            dataset_size=reader.count(), num_epochs=1,
            num_minibatches_per_shard=1, shuffle=True,
            storage_type="text",
        )
        source = ShardedTextBatches(sc, reader, batch_size=4, seq_len=16)
        seen = []
        for batch in source:
            for row in batch["input_ids"]:
                chars = bytes(int(t) - 2 for t in row[1:] if t >= 2)
                seen.append(chars.decode())
        # every record consumed exactly once, and NOT in file order
        assert sorted(set(seen)) == [f"rec{i:03d}" for i in range(32)]
        assert seen != sorted(seen), "shuffle produced file order?"
        client.close()
    finally:
        m.stop()


def test_explicit_step_restore_still_raises_on_corruption(tmp_path):
    """Fallback only applies to auto-selected steps: explicitly asking for
    a specific (corrupt) step must fail loudly, not silently substitute."""
    from dlrover_tpu.checkpoint.manager import (
        ElasticCheckpointManager,
        abstract_like,
    )

    mgr = ElasticCheckpointManager(
        str(tmp_path / "ckpt"), async_save=False, staging_dir="",
    )
    state = {"w": jnp.full((64, 64), 1.0)}
    assert mgr.save(1, state, force=True)
    assert mgr.save(2, {"w": jnp.full((64, 64), 2.0)}, force=True)
    mgr.wait()
    corrupt_checkpoint(mgr._step_dir(mgr.directory, 2), mode="truncate")
    with pytest.raises(Exception):
        mgr.restore(abstract_like(state), step=2)
    mgr.close()


def _preempt_cycle(tmp_path, extra_env=None, step_deadline=120,
                   exit_wait=60, restart_timeout=180):
    """Shared preemption-grace protocol: run the preempt worker to >= 3
    steps, SIGTERM it, assert a clean in-grace exit, restart it against
    the emergency checkpoint, and return (killed_step, records)."""
    import json
    import signal
    import subprocess
    import sys

    script = os.path.join(TESTDATA, "preempt_worker.py")
    status = tmp_path / "status.jsonl"
    env = {
        **os.environ, **WORKER_ENV,
        "PREEMPT_CKPT_DIR": str(tmp_path / "ckpt"),
        "PREEMPT_STATUS": str(status),
        "JAX_PLATFORMS": "cpu",
        # default single-device worker: the conftest's 8-device forcing
        # would make ElasticTrainer adjust the 1x1 mesh to the full
        # world; pipelined callers override XLA_FLAGS themselves
        "XLA_FLAGS": "",
        **(extra_env or {}),
    }

    def read_status():
        if not status.exists():
            return []
        out = []
        for ln in status.read_text().splitlines():
            try:
                out.append(json.loads(ln))
            except json.JSONDecodeError:
                pass  # torn write: next poll re-reads
        return out

    p = subprocess.Popen([sys.executable, script], env=env)
    try:
        deadline = time.time() + step_deadline
        steps = []
        while time.time() < deadline:
            steps = [r for r in read_status() if r.get("event") == "step"]
            if len(steps) >= 3:
                break
            assert p.poll() is None, (
                f"worker died rc={p.returncode} before 3 steps: "
                f"{read_status()[-3:]}"
            )
            time.sleep(0.2)
        assert len(steps) >= 3, "worker never reached 3 steps"
        p.send_signal(signal.SIGTERM)  # the preemption notice
        rc = p.wait(timeout=exit_wait)
    finally:
        if p.poll() is None:
            p.kill()
    # clean exit inside the grace window, not a crash
    assert rc == 0, f"worker exited {rc}"
    records = read_status()
    end = [r for r in records if r.get("event") == "end"]
    assert end and end[0]["preempted"] is True, records[-3:]
    killed_step = end[0]["final_step"]
    step_events = [r["step"] for r in records
                   if r.get("event") == "step"]
    # the save happened AT the in-flight step (<= 1 step of lost work)
    assert killed_step >= step_events[-1] - 1

    # restart: the worker must resume from the emergency checkpoint
    env["PREEMPT_TOTAL_STEPS"] = str(killed_step + 2)
    p2 = subprocess.run(
        [sys.executable, script], env=env, timeout=restart_timeout,
    )
    assert p2.returncode == 0
    records = read_status()
    begins = [r for r in records if r.get("event") == "begin"]
    assert len(begins) == 2, begins
    # the restart RESUMED from the emergency save, not from scratch,
    # and ran exactly the remaining steps
    assert begins[1]["resumed_step"] == killed_step, (
        f"resumed at {begins[1]['resumed_step']}, emergency save was at "
        f"{killed_step}"
    )
    ends = [r for r in records if r.get("event") == "end"]
    assert ends[-1]["final_step"] == killed_step + 2
    return killed_step, records


@pytest.mark.slow
def test_preemption_grace_saves_at_killed_step(tmp_path):
    """SIGTERM mid-training with NO periodic checkpoint cadence: the
    executor's preemption-grace handler flushes an emergency save at
    the in-flight step and exits cleanly; a restarted worker resumes at
    exactly that step — lost work <= 1 step, not the save cadence
    (reference design goal: flash checkpoint,
    ``docs/blogs/stabilize_llm_training_cn.md:215``)."""
    _preempt_cycle(tmp_path)


@pytest.mark.slow
def test_preemption_mid_window_drains_and_resumes(tmp_path):
    """SIGTERM while the ASYNC loop (train_window=4) has several step
    dispatches in flight: the executor drains the window — every
    dispatched step materializes — then flushes the emergency save at
    the last materialized step, and a restarted worker resumes exactly
    there. The shared cycle's invariants (clean in-grace exit, <= 1
    step lost, resume-at-killed-step, completion) all run against the
    pipelined loop."""
    killed_step, records = _preempt_cycle(
        tmp_path, extra_env={"PREEMPT_WINDOW": "4"},
    )
    # the drain materialized the full in-flight chain before the save:
    # the per-step status events reach the killed step with no holes
    step_events = [r["step"] for r in records if r.get("event") == "step"]
    pre_kill = [s for s in step_events if s <= killed_step]
    assert pre_kill == list(range(1, killed_step + 1)), pre_kill


@pytest.mark.slow
def test_preemption_grace_under_pipeline(tmp_path):
    """The SIGTERM preemption-grace save also holds when the worker is
    mid-PIPELINED training on a pipe mesh: the emergency checkpoint
    flushes pipe-sharded stage-stacked state, and the restarted worker
    resumes at the killed step through the same pipelined shardings."""
    killed_step, records = _preempt_cycle(
        tmp_path,
        extra_env={
            "PREEMPT_PIPELINE": "1",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
        step_deadline=180, exit_wait=90, restart_timeout=240,
    )
    assert killed_step >= 2  # the cycle's invariants all ran pipelined


@pytest.mark.slow
def test_second_sigterm_escapes_slow_step_without_corrupting_save(
    tmp_path,
):
    """Preemption grace under a SLOW device step (VERDICT r5 weak #5):
    the grace design finishes the in-flight step before saving, so when
    a step blocks for longer than the supervisor's patience the FIRST
    SIGTERM is flagged but never acted on. The handler's one-shot
    re-arm is the escape hatch: a SECOND SIGTERM must kill the process
    the ordinary way (no SIGTERM-proof worker), and the staged
    checkpoint chain committed by earlier steps must survive the hard
    kill — the restarted worker resumes from it, not from scratch."""
    import json
    import signal
    import subprocess
    import sys

    script = os.path.join(TESTDATA, "preempt_worker.py")
    status = tmp_path / "status.jsonl"
    env = {
        **os.environ, **WORKER_ENV,
        "PREEMPT_CKPT_DIR": str(tmp_path / "ckpt"),
        "PREEMPT_STATUS": str(status),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",
        "PREEMPT_SLOW_AFTER": "3",  # step 3 wedges for 300s
        "PREEMPT_SLOW_SECS": "300",
    }

    def read_status():
        if not status.exists():
            return []
        out = []
        for ln in status.read_text().splitlines():
            try:
                out.append(json.loads(ln))
            except json.JSONDecodeError:
                pass  # torn write: next poll re-reads
        return out

    p = subprocess.Popen([sys.executable, script], env=env)
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            if any(r.get("event") == "slow" for r in read_status()):
                break
            assert p.poll() is None, (
                f"worker died rc={p.returncode} before wedging: "
                f"{read_status()[-3:]}"
            )
            time.sleep(0.2)
        assert any(r.get("event") == "slow" for r in read_status()), (
            "worker never reached the slow step"
        )
        p.send_signal(signal.SIGTERM)  # notice #1: flagged, swallowed
        time.sleep(2.0)
        # the loop is blocked inside the step path: the flag cannot be
        # checked, so the worker must still be alive (and would sit in
        # the wedge for the full 300s without the escape hatch)
        assert p.poll() is None, (
            f"first SIGTERM already ended the worker (rc={p.returncode})"
            " — the slow step never blocked the grace path"
        )
        p.send_signal(signal.SIGTERM)  # notice #2: the escape hatch
        rc = p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
    # killed the ordinary way (default disposition), NOT a clean exit
    # and NOT a 300s hang
    assert rc != 0, "second SIGTERM should not exit 0 (no save ran)"
    records = read_status()
    assert not any(r.get("event") == "end" for r in records), (
        "wedged worker should die hard, not reach the end path"
    )
    steps = [r["step"] for r in records if r.get("event") == "step"]
    assert steps and max(steps) == 3

    # restart WITHOUT the wedge: the per-step staged saves from before
    # the kill must be uncorrupted — resume from one of them (>= 1),
    # never from scratch (0), and train to completion
    env.pop("PREEMPT_SLOW_AFTER")
    env.pop("PREEMPT_SLOW_SECS")
    env["PREEMPT_TOTAL_STEPS"] = "5"
    p2 = subprocess.run([sys.executable, script], env=env, timeout=180)
    assert p2.returncode == 0
    records = read_status()
    begins = [r for r in records if r.get("event") == "begin"]
    assert len(begins) == 2, begins
    resumed = begins[1]["resumed_step"]
    assert 1 <= resumed <= 3, (
        f"restart resumed at {resumed}: the staged save chain did not "
        f"survive the hard kill"
    )
    ends = [r for r in records if r.get("event") == "end"]
    assert ends and ends[-1]["final_step"] == 5
