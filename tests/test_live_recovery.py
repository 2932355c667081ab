"""Live elastic recovery (ISSUE 5): the in-process snapshot -> reshard
-> resume fast path, the warm program cache, recovery classification,
and the derived ``live_reshard`` MTTR scenario.

The chaos-parity headline: scaling 8 -> 4 devices via ``live_reshard``
must produce the SAME loss/param trajectory as a cold restart from the
same host-DRAM snapshot — optimizer state resharded correctly, no step
skipped or replayed.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.checkpoint import HostSnapshot
from dlrover_tpu.parallel.mesh import MeshPlan, topology_key
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.trainer.conf import Configuration
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.executor import TrainExecutor, TrainHook
from dlrover_tpu.trainer.failover import (
    RecoveryDecision,
    classify_recovery,
)
from dlrover_tpu.telemetry.names import EventKind


def _make_trainer(**kwargs):
    def init_fn(rng):
        return {"w": jax.random.normal(rng, (4, 2)), "b": jnp.zeros((2,))}

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    rngs = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(rngs[0], (16, 4))
    batch = {"x": np.asarray(x),
             "y": np.asarray(x @ jax.random.normal(rngs[1], (4, 2)))}
    kwargs.setdefault("strategy", Strategy(mesh=MeshPlan(data=2, fsdp=4)))
    # adam: the optimizer STATE carries momentum arrays, so the parity
    # test can assert they reshard (sgd's state is empty)
    trainer = ElasticTrainer(
        init_fn, loss_fn, optax.adam(1e-2), batch, **kwargs
    )
    return trainer, batch


def _leaves_bitwise_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb)
    )


class TestLiveReshardParity:
    def test_scale_down_matches_cold_restart_from_same_snapshot(
        self, tmp_path, monkeypatch
    ):
        """The chaos-parity acceptance: 8 -> 4 via live reshard vs a
        cold restart (fresh trainer on 4 devices) resumed from the SAME
        host snapshot, stepped over the same batches with the same rng
        stream — bit-identical losses and params, every step present
        exactly once. Also the producer of the event timeline the MTTR
        derivation test below consumes."""
        events_file = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_file)

        trainer, batch = _make_trainer()
        state = trainer.prepare()
        for _ in range(5):
            state, _ = trainer.step(state, batch)
        snap = trainer.snapshot(state)
        assert snap.step == 5
        rng_at_reshard = trainer._rng

        # live path: reshard in place, then 5 more steps
        half = jax.devices()[:4]
        state_live = trainer.live_reshard(state, devices=half,
                                          snapshot=snap, reason="chaos")
        assert state_live.params["w"].sharding.mesh.devices.size == 4
        # optimizer state resharded onto the 4-device mesh too
        opt_leaves = [
            leaf for leaf in jax.tree.leaves(state_live.opt_state)
            if hasattr(leaf, "sharding")
        ]
        assert opt_leaves
        assert all(
            leaf.sharding.mesh.devices.size == 4 for leaf in opt_leaves
        )
        # params bit-identical to the drained snapshot
        assert _leaves_bitwise_equal(
            jax.device_get(state_live.params), snap.tree.params
        )
        live_losses = []
        for _ in range(5):
            state_live, m = trainer.step(state_live, batch)
            live_losses.append(float(m["loss"]))
        assert int(state_live.step) == 10  # no step skipped or replayed

        # cold path: a fresh trainer compiled directly for 4 devices
        # (the post-reshard strategy), state restored from the SAME
        # snapshot, rng realigned to the reshard point
        cold_trainer, _ = _make_trainer(
            strategy=trainer.accelerated.strategy, devices=half
        )
        cold_trainer.prepare()
        state_cold = snap.restore(
            cold_trainer.accelerated.state_sharding
        )
        cold_trainer._rng = rng_at_reshard
        cold_losses = []
        for _ in range(5):
            state_cold, m = cold_trainer.step(state_cold, batch)
            cold_losses.append(float(m["loss"]))
        assert cold_losses == live_losses
        assert _leaves_bitwise_equal(state_live.params, state_cold.params)

    def test_mttr_cli_derives_live_reshard_scenario(self, tmp_path,
                                                    monkeypatch):
        """``python -m dlrover_tpu.telemetry mttr`` must attribute the
        live-reshard incident from the chaos timeline — the same
        derivation pipeline the production events feed."""
        events_file = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_file)
        trainer, batch = _make_trainer()
        state = trainer.prepare()
        state, _ = trainer.step(state, batch)
        trainer.live_reshard(state, devices=jax.devices()[:4])

        from dlrover_tpu.telemetry.cli import main as telemetry_main

        out = str(tmp_path / "mttr.json")
        rc = telemetry_main(["mttr", "--events", events_file,
                             "--out", out])
        assert rc == 0
        with open(out) as fh:
            report = json.loads(fh.read())
        by_scenario = report["detail"]["by_scenario"]
        assert by_scenario["live_reshard"]["count"] >= 1
        assert report["detail"]["unrecovered"] == 0


class TestExecutorLiveReshard:
    def test_request_drains_window_and_resumes(self):
        """request_live_reshard at a dispatch boundary: the in-flight
        window drains, the world shrinks in place, and the loop runs to
        train_steps with every step materialized exactly once."""
        trainer, batch = _make_trainer()
        half = jax.devices()[:4]
        seen = []

        class Recorder(TrainHook):
            def after_step(self, step, metrics):
                seen.append(step)

        class ReshardAt(TrainHook):
            def __init__(self, box):
                self.box = box
                self.fired = False

            def before_step(self, step):
                if step == 5 and not self.fired:
                    self.fired = True
                    self.box[0].request_live_reshard(half)

        box = []
        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * 100,
            hooks=[Recorder(), ReshardAt(box)],
            conf=Configuration({"train_steps": 10, "log_every_steps": 0,
                                "train_window": 4}),
        )
        box.append(executor)
        out = executor.train_and_evaluate()
        assert out["step"] == 10
        assert seen == list(range(1, 11))
        assert trainer.accelerated.mesh.devices.size == 4
        assert executor.state.params["w"].sharding.mesh.devices.size == 4

    def test_request_without_new_world_is_skipped(self):
        """The failover monitor can re-fire while nodes wait at the
        rendezvous, but without renegotiated coordinates (no explicit
        devices, ambient world unchanged) a reshard would be churn onto
        the identical topology — the executor must skip it, not
        snapshot+device_put every poll."""
        from dlrover_tpu.telemetry import events as events_mod

        trainer, batch = _make_trainer()

        class ReshardAt(TrainHook):
            def __init__(self, box):
                self.box = box

            def before_step(self, step):
                if step == 3:
                    self.box[0].request_live_reshard(None)

        box = []
        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * 100,
            hooks=[ReshardAt(box)],
            conf=Configuration({"train_steps": 6, "log_every_steps": 0,
                                "train_window": 2}),
        )
        box.append(executor)
        events_mod.clear_ring()
        out = executor.train_and_evaluate()
        assert out["step"] == 6
        assert trainer.accelerated.mesh.devices.size == 8  # untouched
        assert trainer.compile_count == 1  # no rebuild happened
        kinds = {r["kind"] for r in events_mod.recent_events()}
        assert EventKind.LIVE_RESHARD_BEGIN not in kinds

    def test_failover_monitor_routes_survivable_change_to_reshard(self):
        """Nodes waiting at the rendezvous while this process is healthy
        = survivable: the monitor must fire on_reshard, not on_change."""
        import time

        from dlrover_tpu.trainer.failover import TrainingFailover

        class StubMaster:
            waiting = 0

            def query_ps_nodes(self):
                class _N:
                    nodes = []

                return _N()

            def num_nodes_waiting(self):
                return self.waiting

        master = StubMaster()
        fired = {"restart": 0, "reshard": 0}
        monitor = TrainingFailover(
            master,
            on_change=lambda: fired.__setitem__(
                "restart", fired["restart"] + 1),
            on_reshard=lambda: fired.__setitem__(
                "reshard", fired["reshard"] + 1),
            poll_interval=0.02,
        )
        monitor.start()
        master.waiting = 2
        time.sleep(0.3)
        monitor.stop()
        assert fired["reshard"] >= 1
        assert fired["restart"] == 0


class TestProgramCache:
    def test_same_topology_return_pays_zero_recompiles(self):
        """8 -> 4 -> 8: the return to the original topology must hit
        the in-process program cache — zero accelerate() compiles, and
        the previously-compiled executables are reused as-is."""
        trainer, batch = _make_trainer()
        state = trainer.prepare()
        state, _ = trainer.step(state, batch)
        full_result = trainer.accelerated
        exe_before = full_result.compiled_cache_size()
        assert trainer.compile_count == 1

        state = trainer.live_reshard(state, devices=jax.devices()[:4])
        assert trainer.compile_count == 2
        state, _ = trainer.step(state, batch)

        state = trainer.live_reshard(state, devices=None)
        assert trainer.compile_count == 2  # cache hit: no new compile
        assert trainer.accelerated is full_result
        state, m = trainer.step(state, batch)
        assert np.isfinite(float(m["loss"]))
        # the reused program did not retrace either
        assert full_result.compiled_cache_size() == exe_before

    def test_prewarm_compiles_standby_topology_once(self):
        trainer, batch = _make_trainer()
        trainer.prepare()
        half = jax.devices()[:4]
        assert trainer.prewarm(devices=half) is True
        count = trainer.compile_count
        assert trainer.prewarm(devices=half) is False  # already cached
        assert trainer.compile_count == count

    def test_topology_key_is_order_and_identity_sensitive(self):
        devs = jax.devices()
        assert topology_key(devs) != topology_key(devs[:4])
        assert topology_key(devs) == topology_key(list(devs))
        assert topology_key(devs[::-1]) != topology_key(devs)


class TestRecoveryClassification:
    def test_decision_tree(self):
        # survivable: a peer's failure / a scale plan, healthy self
        assert classify_recovery(
            EventKind.WORKER_FAILED
        ) == RecoveryDecision.LIVE_RESHARD
        assert classify_recovery(
            EventKind.SCALE_PLAN_APPLIED
        ) == RecoveryDecision.LIVE_RESHARD
        # own casualty: in-process recovery cannot help
        assert classify_recovery(
            EventKind.WORKER_FAILED, self_affected=True
        ) == RecoveryDecision.PROCESS_RESTART
        # no viable survivor world: nothing to reshard onto
        assert classify_recovery(
            EventKind.SCALE_PLAN_APPLIED, world_viable=False
        ) == RecoveryDecision.PROCESS_RESTART
        # sick host: escalate past the process
        assert classify_recovery(
            EventKind.WORKER_FAILED, host_healthy=False
        ) == RecoveryDecision.POD_RESTART
        # non-survivable kinds default to a restart
        assert classify_recovery(
            EventKind.NONFINITE_STEP
        ) == RecoveryDecision.PROCESS_RESTART

    def test_scale_plan_stamped_live_reshard(self):
        from dlrover_tpu.common.node import Node
        from dlrover_tpu.master.node.job_auto_scaler import JobAutoScaler
        from dlrover_tpu.master.scaler.base_scaler import ScalePlan

        plan = ScalePlan(launch_nodes=[Node("worker", 1)])
        assert plan.resizes_world_only()

        class StubJobManager:
            executed = None

            def execute_scale_plan(self, p):
                StubJobManager.executed = p

        class StubSpeed:
            def reset_running_speed_monitor(self):
                ...

        scaler = JobAutoScaler(StubJobManager(), None, StubSpeed())
        scaler.execute_job_optimization_plan(plan)
        assert plan.recovery == RecoveryDecision.LIVE_RESHARD
        assert plan.to_dict()["recovery"] == "live_reshard"

        # a PS-topology change is NOT a pure resize: never stamped live
        ps_plan = ScalePlan(ps_addrs=["a:1"])
        assert not ps_plan.resizes_world_only()
        scaler.execute_job_optimization_plan(ps_plan)
        assert ps_plan.recovery == ""

        # a group-resource-only plan could be a cpu/memory re-spec (pod
        # relaunch required) — indistinguishable from a count bump at
        # the plan level, so never stamped live
        from dlrover_tpu.common.node import NodeGroupResource, NodeResource

        respec = ScalePlan(node_group_resources={
            "worker": NodeGroupResource(
                count=4, node_resource=NodeResource(cpu=8, memory=1024)
            )
        })
        assert not respec.resizes_world_only()
        scaler.execute_job_optimization_plan(respec)
        assert respec.recovery == ""


class TestAgentDelegation:
    def _agent(self, live_recovery, grace=120.0):
        from dlrover_tpu.agent.training_agent import (
            AgentConfig,
            ElasticTrainingAgent,
        )

        agent = ElasticTrainingAgent.__new__(ElasticTrainingAgent)
        agent._config = AgentConfig(live_recovery=live_recovery,
                                    live_reshard_grace=grace)
        agent._reshard_deadline = None

        class StubGroup:
            restart_round = 0

        agent._worker_group = StubGroup()
        return agent

    def test_survivable_change_delegated_then_grace_fallback(self):
        import time

        agent = self._agent(live_recovery=True, grace=0.05)
        # first poll: delegate (skip the restart)
        assert agent._maybe_delegate_reshard() is True
        # inside the grace window: still delegated
        assert agent._maybe_delegate_reshard() is True
        time.sleep(0.06)
        # grace expired, change unabsorbed: fall back to restart
        assert agent._maybe_delegate_reshard() is False
        # the next event opens a fresh window
        assert agent._maybe_delegate_reshard() is True

    def test_knob_off_keeps_classic_restart(self):
        agent = self._agent(live_recovery=False)
        assert agent._maybe_delegate_reshard() is False


class TestKnobWiring:
    def test_tpurun_exposes_live_recovery_flag(self):
        from dlrover_tpu.trainer.run import build_parser

        args = build_parser().parse_args(["--live_recovery", "t.py"])
        assert args.live_recovery is True
        args = build_parser().parse_args(["t.py"])
        assert args.live_recovery is False

    def test_context_env_override(self, monkeypatch):
        from dlrover_tpu.common.config import Context

        assert Context().live_recovery is True  # default on
        monkeypatch.setenv("DLROVER_TPU_LIVE_RECOVERY", "0")
        assert Context().live_recovery is False

    def test_executor_knob_off_routes_to_restart(self):
        """live_recovery=False: the failover monitor gets NO on_reshard
        callback — every change takes the classic restart path."""
        trainer, batch = _make_trainer()

        class StubMaster:
            def num_nodes_waiting(self):
                return 0

            def query_ps_nodes(self):
                class _N:
                    nodes = []

                return _N()

        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch],
            master_client=StubMaster(),
            conf=Configuration({"live_recovery": False,
                                "log_every_steps": 0}),
        )
        assert executor._failover._on_reshard is None
        executor2 = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch],
            master_client=StubMaster(),
            conf=Configuration({"log_every_steps": 0}),
        )
        assert executor2._failover._on_reshard is not None


class TestRenegotiate:
    def test_live_round_tagged_in_timeline(self):
        from dlrover_tpu.agent.rendezvous import MasterRendezvousHandler
        from dlrover_tpu.telemetry import events as events_mod

        class StubClient:
            def report_rdzv_params(self, *a, **kw):
                ...

            def join_rendezvous(self, *a, **kw):
                ...

            def get_comm_world(self, name, rank):
                class _World:
                    round = 7
                    world = {0: 1}
                    coordinator_addr = "127.0.0.1:1"

                return _World()

        handler = MasterRendezvousHandler(
            StubClient(), node_rank=0, host_ip="127.0.0.1",
        )
        events_mod.clear_ring()
        info = handler.renegotiate(timeout=5.0)
        assert info.round == 7 and info.group_world_size == 1
        ring = events_mod.recent_events()
        joins = [r for r in ring if r["kind"] == EventKind.RDZV_JOIN]
        completes = [r for r in ring
                     if r["kind"] == EventKind.RDZV_COMPLETE]
        assert joins and joins[-1].get("live") is True
        assert completes and completes[-1].get("live") is True
        # an ordinary round is NOT tagged
        events_mod.clear_ring()
        handler.next_rendezvous(timeout=5.0)
        ring = events_mod.recent_events()
        joins = [r for r in ring if r["kind"] == EventKind.RDZV_JOIN]
        assert joins and "live" not in joins[-1]


class TestCompileCacheCli:
    def test_cache_cli_reports_stats(self, tmp_path):
        from dlrover_tpu.telemetry.cli import main as telemetry_main

        root = str(tmp_path / "cc")
        rc = telemetry_main(["cache", "--dir", root])
        assert rc == 0
        # the stats are also reachable programmatically with the same
        # shape the CLI printed
        from dlrover_tpu.utils.compile_cache import cache_stats

        stats = cache_stats(root)
        assert stats["entries"] == 0
        assert stats["dir"] == root
        assert {"hits", "misses", "requests"} <= set(stats)
