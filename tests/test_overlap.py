"""Hide the network (ISSUE 10): chunked double-buffered expert dispatch,
FSDP layer prefetch, and the overlap-aware pricing the optimizer acts on.

Pins, per the acceptance criteria:

  * chunked ``grouped_ep`` (C > 1) matches the single-shard oracle
    EXACTLY fwd+bwd with ``dropped_frac == 0`` and zero recompiles
    across steps — on the 4-way CPU mesh the issue names;
  * the shared ``ops.ring`` ring-all-to-all reproduces
    ``lax.all_to_all`` block for block;
  * ``estimate``'s exposed-comm term is monotone non-increasing in C
    (both directions) with BYTES invariant, and the fsdp-prefetch
    exposure never exceeds the serial pricing;
  * the runtime optimizer enumerates ``dispatch_chunks`` only for a
    ``grouped_ep`` job, chooses a C plan for a comm-bound spec,
    publishes it with unchanged knobs as sentinels, and the worker
    applies it LIVE through the prewarmed program cache with ZERO
    recompiles at the swap (``ElasticTrainer.retune`` gate + the
    master→RPC→plan-hook e2e);
  * G108 fires on the committed serial fixture and stays clean on an
    overlapped schedule;
  * G106 audits the CHUNKED schedule's collective bytes within
    tolerance (the ppermute ring's wire bytes match the one-shot
    all-to-all it replaces, minus the diagonal block).
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.common.config import get_context
from dlrover_tpu.models import llama
from dlrover_tpu.ops.moe import MoEConfig, init_moe_params, moe_ffn
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.planner import (
    DeviceSpec,
    ModelSpec,
    estimate,
    model_spec_from_llama,
    overlap_exposed_comm,
    predicted_collective_bytes,
)
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.trainer.elastic import ElasticTrainer

TESTDATA = os.path.join(os.path.dirname(__file__), "testdata")


@pytest.fixture(autouse=True)
def _telemetry_on():
    ctx = get_context()
    prev = ctx.telemetry_enabled
    ctx.telemetry_enabled = True
    yield
    ctx.telemetry_enabled = prev


# -- the shared ring helper ---------------------------------------------------


class TestRingAllToAll:
    # budget triage (PR 16): the primitive is exercised tier-1 through
    # the grouped_ep dropless/skew tests and the chunked-dispatch
    # oracle; the standalone lax parity check rides slow
    @pytest.mark.slow
    def test_matches_lax_all_to_all_and_differentiates(self):
        """The ppermute-ring decomposition IS an all_to_all: same
        blocks, and its transpose runs the mirrored ring (grads flow).
        """
        from jax.sharding import Mesh, PartitionSpec as P

        from dlrover_tpu.ops.ring import ring_all_to_all

        n = 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
        shard_map = jax.shard_map
        kw = {"check_vma": False}
        x = jnp.asarray(
            np.random.RandomState(0).randn(n, n, 6), jnp.float32
        )  # global [n, n, 6], dim 0 sharded

        def ring_body(xl):
            return ring_all_to_all(xl[0], "x", n)[None]

        def a2a_body(xl):
            from jax import lax

            return lax.all_to_all(xl[0], "x", 0, 0)[None]

        ring_fn = shard_map(ring_body, mesh=mesh, in_specs=P("x"),
                            out_specs=P("x"), **kw)
        a2a_fn = shard_map(a2a_body, mesh=mesh, in_specs=P("x"),
                           out_specs=P("x"), **kw)
        np.testing.assert_array_equal(
            np.asarray(ring_fn(x)), np.asarray(a2a_fn(x))
        )

        g_ring = jax.grad(lambda v: (ring_fn(v) ** 2).sum())(x)
        g_a2a = jax.grad(lambda v: (a2a_fn(v) ** 2).sum())(x)
        np.testing.assert_array_equal(
            np.asarray(g_ring), np.asarray(g_a2a)
        )


# -- chunked grouped_ep vs the oracle (the 4-way CPU mesh) --------------------


class TestChunkedDispatch:
    E = 8
    P = 4  # the 4-way expert submesh the issue names

    def _mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:self.P]), ("expert",))

    def _params_x(self, d=16, f=32, b=2, s=16):
        rng = np.random.RandomState(0)
        params = init_moe_params(jax.random.PRNGKey(0), d, f, self.E)
        x = jnp.asarray(rng.randn(b, s, d), jnp.float32)
        return params, x

    def _cfg(self, chunks, top_k=2):
        return MoEConfig(num_experts=self.E, top_k=top_k,
                         dispatch="grouped_ep", ep_axes=("expert",),
                         mesh=self._mesh(), dispatch_chunks=chunks)

    def test_fwd_and_grads_match_oracle_c124(self):
        """The acceptance pin: C ∈ {1, 2, 4} all reproduce the
        single-shard einsum oracle exactly, forward AND backward
        (top_k=2 — cross-round queue fill rides the exchanged ranks),
        with nothing dropped — chunking is a pure schedule knob."""
        params, x = self._params_x()  # n = Tl*k = 8*2 = 16 per shard
        oracle = MoEConfig(num_experts=self.E, top_k=2,
                           capacity_factor=float(self.E),
                           eval_capacity_factor=float(self.E),
                           dispatch="einsum")

        def grad_fn(cfg):
            def loss(p, x):
                o, a, m = moe_ffn(p, x, cfg, train=False)
                return (o.astype(jnp.float32) ** 2).sum() + a, m

            # jit: the interpret-mode kernels are traced once instead
            # of re-executed op by op (minutes vs seconds on CPU)
            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True))

        (l_o, _), g_o = grad_fn(oracle)(params, x)
        for chunks in (1, 2, 4):
            (l_c, m_c), g_c = grad_fn(self._cfg(chunks))(params, x)
            assert float(l_c) == pytest.approx(float(l_o), rel=1e-4)
            assert float(m_c["dropped_frac"]) == 0.0
            for lo, lc in zip(jax.tree.leaves(g_o),
                              jax.tree.leaves(g_c)):
                np.testing.assert_allclose(
                    np.asarray(lc), np.asarray(lo),
                    rtol=1e-3, atol=1e-4,
                    err_msg=f"grad mismatch at C={chunks}")

    def test_zero_recompiles_across_steps_chunked(self):
        """Static shapes survive the chunked exchange too: one compile
        serves arbitrary routing, including full skew onto one expert.
        """
        params, x0 = self._params_x()
        cfg = MoEConfig(num_experts=self.E, top_k=2,
                        dispatch="grouped_ep", ep_axes=("expert",),
                        mesh=self._mesh(), kernel_interpret=True,
                        dispatch_chunks=4)

        @jax.jit
        def step(p, x):
            o, a, m = moe_ffn(p, x, cfg, train=False)
            return o.sum() + a, m["dropped_frac"]

        rs = np.random.RandomState(7)
        for i in range(3):
            if i == 2:  # adversarial: skew all tokens onto one expert
                p = dict(params)
                p["router"]["kernel"] = (
                    params["router"]["kernel"].at[:, 0].add(50.0)
                )
                _, dropped = step(p, jnp.asarray(
                    rs.randn(*x0.shape), jnp.float32))
                assert float(dropped) == 0.0
            else:
                step(params, jnp.asarray(
                    rs.randn(*x0.shape), jnp.float32))
        assert step._cache_size() == 1

    def test_indivisible_chunks_degrade_to_serial(self):
        """n % C != 0 must not change the layout mid-trace: the config
        degrades to the one-shot exchange (logged), same numbers."""
        params, x = self._params_x()  # n = 16 per shard

        def run(cfg):
            return jax.jit(lambda p, v: moe_ffn(
                p, v, cfg, train=False))(params, x)

        out1, aux1, _ = run(self._cfg(1))
        out3, aux3, _ = run(self._cfg(3))
        np.testing.assert_array_equal(np.asarray(out1),
                                      np.asarray(out3))
        assert float(aux1) == float(aux3)


# -- overlap-aware pricing ----------------------------------------------------


def _moe_spec(chunks=1, **over):
    base = dict(
        param_count=25_000_000_000, num_layers=32, hidden_size=4096,
        seq_len=8192, global_batch=64, num_experts=64, moe_top_k=2,
        moe_dispatch="grouped_ep", moe_dispatch_chunks=chunks,
    )
    base.update(over)
    return ModelSpec(**base)


class TestOverlapPricing:
    DEV = DeviceSpec(hbm_bytes=95e9)
    MESH = MeshPlan(data=4, fsdp=16)

    def test_exposed_comm_non_increasing_in_chunks_both_ways(self):
        """The acceptance pin: exposed comm is monotone non-increasing
        in C for fixed bytes — checked in both directions, with the
        serial figure invariant (it is the same exchange)."""
        exposed = []
        serial = []
        for c in (1, 2, 4, 8):
            bd = estimate(self.MESH, _moe_spec(c), self.DEV).breakdown
            exposed.append(bd["moe_disp_comm_s"])
            serial.append(bd["moe_disp_comm_serial_s"])
        for a, b in zip(exposed, exposed[1:]):
            assert b <= a
        for a, b in zip(list(reversed(exposed)),
                        list(reversed(exposed))[1:]):
            assert b >= a
        assert exposed[0] == serial[0]  # C=1 IS the serial schedule
        assert len(set(serial)) == 1
        # and the chunked schedule genuinely buys step time here
        assert exposed[-1] < exposed[0]

    def test_bytes_invariant_in_chunks(self):
        """The G106 contract: chunking reshapes the schedule, never the
        traffic — predicted collective bytes identical at every C."""
        b1 = predicted_collective_bytes(self.MESH, _moe_spec(1),
                                        self.DEV)
        b8 = predicted_collective_bytes(self.MESH, _moe_spec(8),
                                        self.DEV)
        assert b1 == b8

    def test_step_time_and_exposed_frac_non_increasing_in_chunks(self):
        scores = [estimate(self.MESH, _moe_spec(c), self.DEV)
                  for c in (1, 2, 4, 8)]
        for a, b in zip(scores, scores[1:]):
            assert b.step_time_s <= a.step_time_s
            assert (b.breakdown["exposed_comm_frac"]
                    <= a.breakdown["exposed_comm_frac"])
        for s in scores:
            assert 0.0 <= s.breakdown["exposed_comm_frac"] <= 1.0

    def test_overlap_formula_edges(self):
        assert overlap_exposed_comm(0.0, 5.0, 8) == 0.0
        assert overlap_exposed_comm(1.0, 5.0, 1) == 1.0
        # fully hideable: only the un-overlappable head remains
        assert overlap_exposed_comm(1.0, 100.0, 4) == pytest.approx(
            0.25)
        # nothing to hide under: the serial cost survives
        assert overlap_exposed_comm(1.0, 0.0, 4) == pytest.approx(1.0)

    def test_fsdp_prefetch_exposes_no_more_than_serial(self):
        spec = dict(param_count=7_000_000_000, num_layers=32,
                    hidden_size=4096, seq_len=4096, global_batch=64)
        off = estimate(MeshPlan(fsdp=32), ModelSpec(**spec), self.DEV)
        on = estimate(MeshPlan(fsdp=32),
                      ModelSpec(fsdp_prefetch=True, **spec), self.DEV)
        assert (on.breakdown["fsdp_comm_s"]
                <= off.breakdown["fsdp_comm_s"])
        assert on.step_time_s <= off.step_time_s
        # the serial twin still shows the pre-overlap figure
        assert (on.breakdown["fsdp_comm_serial_s"]
                == off.breakdown["fsdp_comm_s"])

    def test_llama_spec_resolves_context_chunks(self, monkeypatch):
        cfg = llama.llama_tiny(num_experts=8,
                               moe_dispatch="grouped_ep")
        monkeypatch.setattr(get_context(), "dispatch_chunks", 4)
        assert model_spec_from_llama(cfg, 8).moe_dispatch_chunks == 4
        cfg2 = llama.llama_tiny(num_experts=8,
                                moe_dispatch="grouped_ep",
                                moe_dispatch_chunks=2)
        assert model_spec_from_llama(cfg2, 8).moe_dispatch_chunks == 2


# -- the optimizer's dispatch_chunks knob family ------------------------------


class _Store:
    def __init__(self):
        self._s = {}

    def node_ids(self):
        return list(self._s)

    def latest(self, nid):
        return self._s.get(nid)


class _Snap:
    def __init__(self, step_p50, exposed=None):
        self.ts = time.time()
        self.step_p50 = step_p50
        self.dispatch_p50 = None
        self.exposed_comm_frac = exposed
        self.input_wait_frac = None


def _moe_model_info():
    return comm.ModelInfo(
        num_params=25_000_000_000, hidden_size=4096, num_layers=32,
        seq_len=8192, num_experts=64, moe_top_k=2, ffn_mult=2.7,
    )


def _small_moe_model_info():
    """A spec that FITS the 8-device (2x2x2) CPU mesh under the v5e-ish
    memory gate while staying dispatch-comm-bound, so the chunk family
    wins the wedge's ranking honestly."""
    return comm.ModelInfo(
        num_params=200_000_000, hidden_size=2048, num_layers=16,
        seq_len=4096, num_experts=32, moe_top_k=2, ffn_mult=2.7,
    )


def _running_report(moe_dispatch="grouped_ep", chunks=1):
    return comm.TrainerConfigReport(
        node_id=0, world=64, mesh_shape={"data": 4, "fsdp": 16},
        train_window=4, moe_dispatch=moe_dispatch,
        dispatch_chunks=chunks, global_batch=64,
    )


class TestOptimizerChunkKnob:
    def _opt(self, store, published):
        from dlrover_tpu.master.optimizer import RuntimeOptimizer

        return RuntimeOptimizer(
            store, publish=published.append, mesh_candidates=False,
            device=DeviceSpec(hbm_bytes=95e9), min_speedup=1.02,
        )

    def test_chunk_family_enumerated_only_for_grouped_ep(self):
        store = _Store()
        store._s[0] = _Snap(16.6)
        opt = self._opt(store, [])
        opt.update_model_info(_moe_model_info())
        opt.update_running_config(_running_report("gather"))
        run = opt._running
        _, _, _, chunk_opts, _, _ = opt._knob_options(run)
        assert chunk_opts == [1]  # parked off grouped_ep
        opt.update_running_config(_running_report("grouped_ep"))
        _, _, _, chunk_opts, _, _ = opt._knob_options(opt._running)
        assert chunk_opts == [1, 2, 4, 8]

    def test_replan_chooses_and_publishes_a_chunk_plan(self):
        """Comm-bound grouped_ep spec → the C family wins the ranking;
        unchanged knobs publish as sentinels so the worker can tell a
        pure chunk swap from a mesh/K change."""
        store = _Store()
        store._s[0] = _Snap(16.6)
        published = []
        opt = self._opt(store, published)
        opt.update_model_info(_moe_model_info())
        opt.update_running_config(_running_report())
        d = opt.replan("test")
        assert d.outcome == "chosen"
        assert d.chosen["dispatch_chunks"] > 1
        assert d.chosen["moe_dispatch"] == "grouped_ep"
        cfg = published[0]
        assert cfg.dispatch_chunks == d.chosen["dispatch_chunks"]
        assert cfg.train_window == -1  # sentinel: unchanged
        assert cfg.mesh_shape is None
        assert cfg.moe_dispatch == ""

    def test_exposed_comm_view_pairs_predicted_and_measured(self):
        store = _Store()
        store._s[0] = _Snap(16.6, exposed=0.74)
        store._s[1] = _Snap(16.5, exposed=0.70)
        opt = self._opt(store, [])
        opt.update_model_info(_moe_model_info())
        opt.update_running_config(_running_report(chunks=2))
        view = opt.exposed_comm_view()
        assert 0.0 < view["predicted"] < 1.0
        assert view["measured"] == pytest.approx(0.72)
        assert view["nodes_measured"] == 2
        assert view["dispatch_chunks"] == 2
        # and the plan report carries the pair
        rep = opt.to_report()
        assert rep["exposed_comm"]["measured"] == view["measured"]

    def test_candidate_key_carries_chunks(self):
        """The cooldown/blacklist identity must distinguish chunk
        degrees or a failed C=8 apply would blacklist C=2 too."""
        from dlrover_tpu.master.optimizer.runtime_optimizer import (
            CandidateScore,
        )

        a = CandidateScore(mesh=MeshPlan(data=8),
                           train_window=4, moe_dispatch="grouped_ep",
                           dispatch_chunks=2)
        b = CandidateScore(mesh=MeshPlan(data=8),
                           train_window=4, moe_dispatch="grouped_ep",
                           dispatch_chunks=8)
        assert a.key != b.key


# -- live apply: retune/prewarm through the program cache ---------------------


def _moe_trainer(tmpdir="", chunks=1, **kwargs):
    cfg = llama.llama_tiny(num_experts=8, moe_dispatch="grouped_ep")
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(8, 17))
    batch = {
        "input_ids": jnp.asarray(ids[:, :-1]),
        "labels": jnp.asarray(ids[:, 1:]),
    }
    trainer = ElasticTrainer(
        llama.make_init_fn(cfg),
        llama.make_loss_fn(cfg),
        optax.adafactor(1e-3),
        batch,
        strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                          rule_set="moe_ep"),
        dispatch_chunks=chunks,
        # chunk degree pinned explicitly so the spec does not resolve
        # a stale Context value at build time (the trainer pins Context
        # only inside _build, so a 0 here would price the last build's)
        model_spec=model_spec_from_llama(
            llama.llama_tiny(num_experts=8, moe_dispatch="grouped_ep",
                             moe_dispatch_chunks=max(1, chunks)), 8),
        **kwargs,
    )
    return trainer, batch


class TestRetuneChunksZeroRecompile:
    # the ~20 s retune e2e is slow-marked per the ISSUE 12 tier-1
    # triage: the prewarm→retune→program-cache mechanics are
    # knob-agnostic and stay tier-1 via PR 7's test_optimizer e2e
    # wedges plus the newest family's gate (test_fsdp_wire
    # TestRetuneFsdpPrecisionZeroRecompile — same cache path, same
    # Context-pin contract); the chunk knob's OWN identity keeps its
    # cheap tier-1 pins (program key, plan-hook routing) below
    @pytest.mark.slow
    def test_prewarmed_chunk_retune_swaps_with_zero_recompiles(self):
        """The acceptance gate: retune() across C values through the
        program cache — a prewarmed chunk degree applies with ZERO
        recompiles, and retuning BACK hits the original program."""
        trainer, batch = _moe_trainer()
        state = trainer.prepare()
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])
        assert trainer.dispatch_chunks == 1

        compiled = trainer.prewarm(dispatch_chunks=2)
        assert compiled  # C=2 is a new program
        assert trainer.dispatch_chunks == 1  # prewarm must not switch

        before = trainer.compile_count
        state = trainer.retune(state, dispatch_chunks=2)
        assert trainer.compile_count == before  # ZERO recompiles
        assert trainer.dispatch_chunks == 2
        assert get_context().dispatch_chunks == 2  # trace knob pinned
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])

        # back to C=1: the startup program is still in the cache
        before = trainer.compile_count
        state = trainer.retune(state, dispatch_chunks=1)
        assert trainer.compile_count == before
        assert trainer.dispatch_chunks == 1
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])

    def test_program_key_distinguishes_chunk_degrees(self):
        trainer, _ = _moe_trainer()
        strategy = trainer._resolved_strategy(8)
        k1 = trainer._program_key(jax.devices(), strategy)
        trainer.dispatch_chunks = 4
        k4 = trainer._program_key(jax.devices(), strategy)
        assert k1 != k4


class TestPlanHookRoutesChunks:
    def test_chunk_plan_reaches_request_retune(self):
        from dlrover_tpu.trainer.executor import OptimizerPlanHook

        class _Ex:
            def __init__(self):
                self.retunes = []

            def request_retune(self, **kw):
                self.retunes.append(kw)

        class _Client:
            def get_parallel_config(self):
                return comm.ParallelConfig(
                    dispatch_chunks=4, plan_id="plan-c4",
                    trace_id="inc-c", predicted_speedup=1.3)

        hook = OptimizerPlanHook(_Client(), poll_secs=0)
        ex = _Ex()
        hook._executor = ex
        hook.poll_once()
        assert ex.retunes[0]["dispatch_chunks"] == 4
        assert ex.retunes[0]["train_window"] is None
        assert ex.retunes[0]["plan_id"] == "plan-c4"


# -- the replan e2e wedge: master → RPC → live chunk apply --------------------


@pytest.mark.slow
class TestChunkReplanWedge:
    """Slow-marked (~80 s; ISSUE 11 budget triage): the closed replan
    loop is tier-1-covered by PR 7's e2e wedges (test_optimizer), and
    the chunk-specific live apply by TestRetuneChunksZeroRecompile +
    the knob/plan-hook unit tests above — the 870 s tier-1 budget on
    this 1-core box cannot carry a ~80 s wedge per knob family."""

    def test_optimizer_selects_chunks_and_worker_applies_live(
            self, tmp_path, monkeypatch):
        """The acceptance wedge: a comm-bound MoE job reports its
        config → the master's optimizer prices the chunk family,
        chooses C > 1, publishes → the worker's plan hook drains and
        applies it through the prewarmed program cache with ZERO
        recompiles at the swap → the ack marks the decision applied."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.local_master import start_local_master
        from dlrover_tpu.telemetry import EventKind, read_events
        from dlrover_tpu.trainer.conf import Configuration
        from dlrover_tpu.trainer.executor import (
            OptimizerPlanHook,
            TrainExecutor,
            TrainHook,
        )

        events_path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_path)
        ctx = get_context()
        monkeypatch.setattr(ctx, "replan_min_speedup", 1.02)
        # the live apply pins the chosen knobs into the Context (the
        # trace-time contract) — and since ISSUE 11 the plan may carry
        # moe_precision alongside dispatch_chunks; register restores
        # so the chosen values don't leak into later tests' trace-time
        # resolution
        monkeypatch.setattr(ctx, "dispatch_chunks", ctx.dispatch_chunks)
        monkeypatch.setattr(ctx, "moe_precision", ctx.moe_precision)
        master = start_local_master()
        opt = master.servicer.runtime_optimizer
        # the candidate space under test is the chunk family; mesh
        # re-factorizations have their own wedge (test_optimizer)
        opt._mesh_candidates = False
        opt._device = DeviceSpec(hbm_bytes=95e9)
        try:
            from dlrover_tpu.trainer.executor import (
                NodeRuntimeReportHook,
            )

            client = MasterClient(master.addr, node_id=0)
            client.report_model_info(_small_moe_model_info())
            trainer, batch = _moe_trainer()
            steps = 24
            ex = TrainExecutor(
                trainer, train_iter_fn=lambda: [batch] * steps,
                hooks=[NodeRuntimeReportHook(client, every_steps=4,
                                             min_interval_s=0)],
                conf=Configuration({
                    "train_steps": steps, "log_every_steps": 0,
                    "train_window": 2, "preemption_grace": False,
                    "plan_poll_secs": 0, "runtime_report_steps": 0,
                }),
            )
            ex._master_client = client
            plan_hook = OptimizerPlanHook(client, poll_secs=0)
            plan_hook._executor = ex

            class _Drive(TrainHook):
                """Trigger the replan once the node series has a
                measured anchor, then poll for the published plan."""

                fired = False

                def after_step(self, step, metrics):
                    if step >= 8 and not _Drive.fired:
                        _Drive.fired = True
                        opt.replan("wedge")
                    if step >= 10 and step % 4 == 2:
                        plan_hook.poll_once()

            ex._hooks.append(_Drive())
            ex.train_and_evaluate()
            client.close()

            decisions = opt.decisions()
            chosen = [d for d in decisions
                      if d["outcome"] == "chosen"]
            assert chosen, decisions
            d = chosen[-1]
            assert d["chosen"]["dispatch_chunks"] > 1
            assert d["applied"], d
            assert trainer.dispatch_chunks == \
                d["chosen"]["dispatch_chunks"]
            done = [r for r in read_events(events_path)
                    if r.get("kind") == EventKind.OPTIMIZER_APPLY_DONE
                    and r.get("plan_id") == d["plan_id"]]
            assert done and done[-1]["recompiled"] == 0, done
            assert done[-1]["dispatch_chunks"] == \
                d["chosen"]["dispatch_chunks"]
        finally:
            master.stop()


# -- the CLI line: predicted vs measured side by side -------------------------


class TestExposedCommCLI:
    def test_plan_and_attribution_print_the_pair(self, capsys):
        from dlrover_tpu.telemetry.cli import _print_exposed_comm

        _print_exposed_comm({
            "predicted": 0.69, "measured": 0.74,
            "nodes_measured": 2, "dispatch_chunks": 4,
        })
        out = capsys.readouterr().out
        assert "predicted=0.69" in out
        assert "measured=0.74" in out
        assert "C=4" in out
        # absent halves render as '-', and an empty view prints nothing
        _print_exposed_comm({"predicted": None, "measured": None,
                             "nodes_measured": 0,
                             "dispatch_chunks": 1})
        assert "predicted=-" in capsys.readouterr().out
        _print_exposed_comm(None)
        assert capsys.readouterr().out == ""


# -- lint: G108 + the chunked G106 audit + prefetch G105 ----------------------


class TestG108SerializedCollective:
    def _fixture(self):
        with open(os.path.join(TESTDATA, "g108_serial.hlo")) as fh:
            return fh.read()

    def test_fires_on_the_committed_serial_fixture(self):
        from dlrover_tpu.analysis.graph_lint import (
            check_serialized_collectives,
        )

        findings = check_serialized_collectives(self._fixture())
        assert len(findings) == 1
        assert findings[0].rule_id == "G108"
        assert "all-gather" in findings[0].message

    def test_clean_when_independent_compute_intervenes(self):
        from dlrover_tpu.analysis.graph_lint import (
            check_serialized_collectives,
        )

        overlapped = self._fixture().replace(
            "ROOT %consume",
            "%other = f32[4194304]{0} fusion(f32[4194304]{0} "
            "%scaled), kind=kLoop\n  ROOT %consume",
        )
        assert check_serialized_collectives(overlapped) == []

    def test_small_collectives_are_ignored(self):
        from dlrover_tpu.analysis.graph_lint import (
            check_serialized_collectives,
        )

        small = self._fixture().replace("4194304", "1024")
        assert check_serialized_collectives(small) == []

    def test_wired_into_the_rule_set(self):
        from dlrover_tpu.analysis.graph_lint import (
            ALL_GRAPH_RULES,
            GRAPH_RULE_DOCS,
        )

        assert "G108" in ALL_GRAPH_RULES
        assert "G108" in GRAPH_RULE_DOCS


class TestChunkedGraphLint:
    # slow-marked per the ISSUE 12 tier-1 triage (~12 s, a full
    # accelerate+compile): the G106 audit machinery stays tier-1 via
    # test_lint_clean + test_fsdp_wire's quantized-program audit, and
    # the chunk bytes-invariance via the planner unit pins; the
    # chunked compile re-proof rides tpulint / the slow lane
    @pytest.mark.slow
    def test_chunked_program_passes_the_audit_and_stays_clean(self):
        """G106 on the CHUNKED schedule: the ppermute ring's measured
        collective bytes stay within tolerance of the same planner
        prediction the one-shot all_to_all audits against — and the
        full rule set (donation G105, serialized G108 included) stays
        clean on the chunked program."""
        from dlrover_tpu.analysis.graph_lint import lint_train_step

        report = lint_train_step(
            llama.llama_tiny(num_experts=8, moe_dispatch="grouped_ep",
                             moe_dispatch_chunks=2),
            label="llama_tiny_moe[grouped_ep,C=2]",
        )
        assert report.findings == [
        ], [f.render() for f in report.findings]
        # the ring actually ran: collective-permute traffic appears
        assert report.measured_bytes.get("collective-permute", 0) > 0


class TestPrefetchLint:
    @pytest.mark.slow  # PR 13 triage: a second lint-compile of the
    # prefetch program — prefetch numerics stay tier-1 via the
    # fsdp-wire prefetch oracle (test_fsdp_wire TestFsdpWireOracle::
    # test_prefetch_path_holds_the_oracle_too) and G105 machinery via
    # test_lint_clean
    def test_prefetch_keeps_donation_and_numerics(self):
        """G105 (donation) must survive the prefetch-restructured scan,
        and the prefetched forward matches the plain one to fp32
        roundoff (the schedule changes, the math does not)."""
        from dlrover_tpu.analysis.graph_lint import lint_train_step

        report = lint_train_step(
            llama.llama_tiny(param_dtype=jnp.bfloat16,
                             compute_dtype=jnp.bfloat16,
                             fsdp_prefetch=True),
            label="llama_tiny[prefetch]",
        )
        assert report.findings == [
        ], [f.render() for f in report.findings]

        cfg_off = llama.llama_tiny()
        cfg_on = llama.llama_tiny(fsdp_prefetch=True)
        params = llama.init(jax.random.PRNGKey(0), cfg_off)
        ids = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg_off.vocab_size, size=(2, 16)))
        out_off, _ = llama.apply(params, ids, cfg_off)
        out_on, _ = llama.apply(params, ids, cfg_on)
        np.testing.assert_allclose(np.asarray(out_on),
                                   np.asarray(out_off),
                                   rtol=1e-5, atol=1e-5)
