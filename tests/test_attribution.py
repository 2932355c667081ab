"""Performance-attribution plane (ISSUE 8): per-compiled-program
device-time & HBM accounting.

Covers: the shared peak-residency accounting and the ONE MFU formula
(utils/prof), the attribution capture + program-cache keyed reuse
(telemetry.attribution / ElasticTrainer.attribution), the derived
MFU / exposed-comm gauges through the real executor (CPU-mesh e2e
smoke, pinned against the fixture-free utils/prof path), the
jax.profiler trace parser against a committed fixture, the runtime
optimizer's memory-feasibility gate (PLAN_REJECTED memory evidence),
G107, the device-memory absent-not-zero guard, the goodput model-FLOPs
column, the `tpurun attribution` CLI, and the ≤5% attribution-overhead
paired gate.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import signal
import time

import jax
import jax.numpy as jnp
import optax
import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.common.config import get_context
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry import names as tm
from dlrover_tpu.telemetry import attribution as attr_mod
from dlrover_tpu.telemetry.events import clear_ring, recent_events
from dlrover_tpu.telemetry.metrics import process_registry
from dlrover_tpu.trainer.conf import Configuration
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.executor import (
    NodeRuntimeReportHook,
    TrainExecutor,
    TrainHook,
)

FIXTURE = os.path.join(os.path.dirname(__file__), "testdata",
                       "attribution_trace.json")

PEAK = 1e9  # deterministic MFU denominator for the CPU mesh


@pytest.fixture(autouse=True)
def _attribution_context():
    """Pin the attribution knobs per test and restore after."""
    ctx = get_context()
    saved = (ctx.telemetry_enabled, ctx.attribution_enabled,
             ctx.device_peak_flops, ctx.device_hbm_budget_bytes)
    ctx.telemetry_enabled = True
    ctx.attribution_enabled = True
    ctx.device_peak_flops = PEAK
    ctx.device_hbm_budget_bytes = 0.0
    yield ctx
    (ctx.telemetry_enabled, ctx.attribution_enabled,
     ctx.device_peak_flops, ctx.device_hbm_budget_bytes) = saved


def _make_trainer(**kwargs):
    def init_fn(rng):
        return {"w": jax.random.normal(rng, (16, 8))}

    def loss_fn(params, batch, rng):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2), {}

    rngs = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(rngs[0], (32, 16))
    batch = {"x": x, "y": x @ jax.random.normal(rngs[1], (16, 8))}
    trainer = ElasticTrainer(
        init_fn, loss_fn, optax.sgd(0.05), batch,
        strategy=Strategy(mesh=MeshPlan(data=-1)), **kwargs,
    )
    return trainer, batch


# -- shared shims (satellite: one cost_analysis compatibility helper) --------


class _FakeMem:
    argument_size_in_bytes = 100
    temp_size_in_bytes = 50
    output_size_in_bytes = 30
    alias_size_in_bytes = 20


class _FakeCompiled:
    def __init__(self, cost, mem=_FakeMem()):
        self._cost = cost
        self._mem = mem

    def cost_analysis(self):
        return self._cost

    def memory_analysis(self):
        return self._mem


class TestSharedShims:
    def test_compiled_peak_bytes_accounting(self):
        from dlrover_tpu.utils.prof import compiled_peak_bytes

        # args + temps + outputs - donated aliases
        assert compiled_peak_bytes(_FakeCompiled({})) == 160

        class NoMem:
            def memory_analysis(self):
                return None

        assert compiled_peak_bytes(NoMem()) == 0

    def test_derived_mfu_is_the_one_formula(self):
        from dlrover_tpu.utils.prof import ProfileResult, derived_mfu

        assert derived_mfu(100.0, 0.001, 1e6) == pytest.approx(0.1)
        assert derived_mfu(100.0, 0.0, 1e6) == 0.0
        assert derived_mfu(100.0, 0.001, 0.0) == 0.0
        pr = ProfileResult(
            steps_per_sec=1000.0, step_time_ms=1.0,
            flops_per_step=100.0, achieved_flops_per_sec=100_000.0,
            param_count=1, peak_memory_bytes=0,
        )
        assert pr.mfu(1e6) == pytest.approx(
            derived_mfu(100.0, 0.001, 1e6))


# -- trace parser (satellite: committed fixture, known totals) ---------------


class TestTraceParser:
    def test_fixture_category_totals(self):
        buckets = attr_mod.parse_trace_path(FIXTURE)
        assert buckets["events"] == 6
        assert buckets["compute_s"] == pytest.approx(0.030)
        assert buckets["collective_s"] == pytest.approx(0.015)
        assert buckets["infeed_s"] == pytest.approx(0.002)
        assert buckets["other_s"] == pytest.approx(0.003)
        # busy = the busiest single lane (tid 1: 45 ms; tid 2: 5 ms)
        assert buckets["busy_s"] == pytest.approx(0.045)
        assert buckets["wall_s"] == pytest.approx(0.058)
        assert buckets["idle_s"] == pytest.approx(0.013)
        # comm share of CATEGORIZED device-op time: 15 / (15+30+2)
        assert buckets["measured_comm_frac"] == pytest.approx(
            15 / 47, abs=1e-4)

    def test_host_lanes_cannot_dilute_comm_frac(self):
        # a fully-overlapping host TraceMe lane must not double-count
        # busy time or shrink the measured communication share
        records = [
            {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 1_000_000,
             "name": "all-reduce.1"},
            {"ph": "X", "pid": 1, "tid": 99, "ts": 0, "dur": 1_000_000,
             "name": "TraceMe host step"},
        ]
        buckets = attr_mod.parse_trace_events(records)
        assert buckets["busy_s"] == pytest.approx(1.0)
        assert buckets["idle_s"] == pytest.approx(0.0)
        assert buckets["measured_comm_frac"] == pytest.approx(1.0)

    def test_gzip_and_directory_discovery(self, tmp_path):
        profile = tmp_path / "plugins" / "profile" / "run1"
        profile.mkdir(parents=True)
        gz = profile / "host.trace.json.gz"
        with gzip.open(gz, "wt") as fh:
            fh.write(open(FIXTURE).read())
        assert attr_mod.find_trace_files(str(tmp_path)) == [str(gz)]
        buckets = attr_mod.parse_trace_path(str(tmp_path))
        assert buckets["collective_s"] == pytest.approx(0.015)
        assert buckets["source_files"] == 1

    def test_categorize_op_collective_wins_over_fusion(self):
        # a fused collective is traffic, not compute
        assert attr_mod.categorize_op("fusion.all-reduce.3") == \
            "collective"
        assert attr_mod.categorize_op("fusion.99") == "compute"
        assert attr_mod.categorize_op("mystery") == "other"

    def test_empty_trace(self):
        buckets = attr_mod.parse_trace_events([])
        assert buckets["busy_s"] == 0.0
        assert buckets["measured_comm_frac"] == 0.0


# -- which phase and scope an instruction belongs to --------------------------

# a step as the v5e's compiler prints one, by hand: an entry with a
# ``while`` whose body is the layers' scan, two fusions (the body of one
# spans two phases), a transposed operation that carries its primal's
# ``forward``, a replayed one, a nested scope, a reducer's computation
# and an instruction the compiler gave no metadata
_STEP_HLO = """HloModule jit_train_step, entry_computation_layout={()->()}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(train_step)/backward/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/ffn/mul"}
  ROOT %add.1 = f32[8]{0} add(%multiply.1, %param_0), metadata={op_name="jit(train_step)/backward/transpose(jvp())/while/body/closed_call/checkpoint/ffn/add_any"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(train_step)/forward/jvp()/while/body/closed_call/attention/neg"}
}

%region_0.1 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y), metadata={op_name="reduce_sum"}
}

%body.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[8]{0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/backward/transpose(jvp())/while/body/closed_call/checkpoint/ffn/add_any"}
  %flash_fwd.3 = f32[8]{0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/backward/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attention/flash_fwd/pallas_call"}
  %dot.4 = f32[8]{0} dot(%flash_fwd.3, %fusion.7), metadata={op_name="jit(train_step)/backward/transpose(jvp(mtp))/mla/jit(forward)/dot_general"}
  %copy.5 = f32[8]{0} copy(%dot.4)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%get-tuple-element.1, %copy.5)
}

%cond.1 (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %constant.1 = pred[] constant(true)
}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/forward/jvp()/while/body/closed_call/attention/neg"}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2
  %tuple.2 = (s32[], f32[8]{0}) tuple(%p, %fusion.2)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.2), condition=%cond.1, body=%body.1, metadata={op_name="jit(train_step)/backward/transpose(jvp())/while"}
  %reduce.1 = f32[] reduce(%fusion.3, %p), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(train_step)/optimizer/reduce_sum"}
  ROOT %multiply.9 = f32[8]{0} multiply(%fusion.3, %fusion.3), metadata={op_name="jit(train_step)/forward/jvp()/head_loss/while/body/checkpoint/mul"}
}
"""


class TestStepScopeTable:
    @pytest.mark.parametrize("op_name, key", [
        # the halves of train_step, and a scope inside a layer scan
        ("jit(train_step)/forward/jvp()/while/body/closed_call/ffn/"
         "dot_general", "forward|ffn"),
        ("jit(train_step)/optimizer/mul", "optimizer|"),
        # a transposed operation carries its primal's forward too
        ("jit(train_step)/backward/transpose(jvp())/while/body/"
         "closed_call/checkpoint/attention/forward/mul",
         "backward|attention"),
        # what a checkpoint runs again, inside the backward pass
        ("jit(train_step)/backward/transpose(jvp())/while/body/"
         "closed_call/checkpoint/rematted_computation/gdn/gdn_chunk/"
         "exp", "replay|gdn/gdn_chunk"),
        # a scope right inside a transform is wrapped by it
        ("jit(train_step)/forward/jvp(mtp)/mla/jit(silu)/mul",
         "forward|mtp/mla"),
        # a function's name is no scope, whatever it is called
        ("jit(train_step)/forward/jvp()/jit(ffn)/jit(backward)/mul",
         "forward|"),
        ("reduce_sum", "none|"),
        ("", "none|"),
    ])
    def test_an_op_names_phase_and_scope_path(self, op_name, key):
        assert attr_mod.scope_key(op_name) == key

    def test_the_scope_names_are_one_list(self):
        scopes = {v for k, v in vars(tm.DeviceScope).items()
                  if k.isupper() and isinstance(v, str)}
        assert scopes == set(tm.DeviceScope.ALL)
        assert len(tm.DeviceScope.ALL) == len(scopes)
        assert not scopes & {"forward", "backward", "optimizer",
                             "rematted_computation"}

    def test_the_table_of_a_hand_written_step(self):
        table = attr_mod.step_scope_table(_STEP_HLO)
        assert table["instructions"] == {
            "backward|ffn": ["fusion.7"],
            "replay|attention": ["flash_fwd.3"],
            "backward|mtp/mla": ["dot.4"],
            "forward|attention": ["fusion.2", "fusion.3"],
            "optimizer|": ["reduce.1"],
            "forward|head_loss": ["multiply.9"],
            # a reducer's own computation, and no metadata
            "none|": ["add.9", "copy.5"],
        }
        # a fusion's body is no row, a while is its body's rows, and
        # what only names a value (a parameter, a tuple and its
        # element, a constant) runs nothing a trace could show
        named = {n for names in table["instructions"].values()
                 for n in names}
        assert not named & {"multiply.1", "add.1", "negate.1", "while.1",
                            "p", "arg", "get-tuple-element.1", "tuple.1",
                            "constant.1"}
        # fusion.7's body replays and transposes; its own metadata
        # says backward, and that is where a trace's time goes
        assert table["mixed_phase"] == {"count": 1, "names": ["fusion.7"]}

    def test_a_fusion_without_metadata_goes_where_its_body_is(self):
        table = attr_mod.step_scope_table(_STEP_HLO)
        assert "fusion.3" in table["instructions"]["forward|attention"]
        assert "copy.5" in table["instructions"]["none|"]

    def test_by_instruction_gives_the_innermost_scope(self):
        where = attr_mod.scopes_by_instruction(
            attr_mod.step_scope_table(_STEP_HLO))
        assert where["dot.4"] == ("backward", "mla")
        assert where["reduce.1"] == ("optimizer", "")
        assert where["flash_fwd.3"] == ("replay", "attention")

    def test_capture_keeps_a_table_of_every_working_instruction(self):
        trainer, batch = _make_trainer()
        trainer.prepare()
        record = attr_mod.capture_attribution(
            trainer.accelerated, example_batch=batch, emit=False,
            step_scopes=True)
        named = {n for names in record.step_scopes["instructions"]
                 .values() for n in names}
        state = jax.eval_shape(trainer.accelerated.init_fn,
                               jax.random.PRNGKey(0))
        text = trainer.accelerated.train_step.lower(
            state, batch, jax.ShapeDtypeStruct((2,), jnp.uint32)
        ).compile().as_text()
        from dlrover_tpu.analysis.graph_lint import _computations

        fused = set(re.findall(r" fusion\(.*calls=%?([\w.\-]+)", text))
        expected = set()
        for comp, body in _computations(text).items():
            if comp.lstrip("%") in fused:
                continue
            for line in body.splitlines():
                head = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
                if head and not re.search(
                        r"[\]\})] (?:while|call|conditional|parameter|"
                        r"get-tuple-element|tuple|constant|bitcast)\(",
                        line):
                    expected.add(head.group(1))
        assert expected and named == expected
        phases = {k.split("|")[0] for k in
                  record.step_scopes["instructions"]}
        assert {"forward", "backward", "optimizer"} <= phases
        # not asked: nothing is built, and no record's dict carries it
        plain = attr_mod.capture_attribution(
            trainer.accelerated, example_batch=batch, emit=False)
        assert plain.step_scopes is None
        assert "step_scopes" not in record.to_dict()

    def test_a_trace_with_the_table_splits_its_busy_compute(self):
        table = attr_mod.step_scope_table(_STEP_HLO)
        events = [("fusion.2", 0, 10_000), ("fusion.7", 10_000, 20_000),
                  ("flash_fwd.3", 30_000, 5_000), ("dot.4", 35_000, 5_000),
                  ("reduce.1", 40_000, 2_000), ("copy.5", 42_000, 1_000)]
        records = [{"ph": "X", "pid": 1, "tid": 1, "ts": ts, "dur": dur,
                    "name": name} for name, ts, dur in events]
        # a host lane and a container are named by nothing
        records += [{"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 43_000,
                     "name": "dlrover:step_dispatch"},
                    {"ph": "X", "pid": 1, "tid": 2, "ts": 10_000,
                     "dur": 30_000, "name": "while.1"}]
        plain = attr_mod.parse_trace_events(records)
        assert "by_phase" not in plain and "by_scope" not in plain
        split = attr_mod.parse_trace_events(records, table)
        assert split["by_phase"] == {
            "forward": 0.01, "backward": 0.025, "replay": 0.005,
            "optimizer": 0.002, "none": 0.001}
        assert split["by_scope"] == {
            "attention": 0.015, "ffn": 0.02, "mla": 0.005, "": 0.003}
        assert sum(split["by_phase"].values()) == pytest.approx(
            split["busy_s"])
        assert sum(split["by_scope"].values()) == pytest.approx(
            split["busy_s"])
        assert {k: v for k, v in split.items()
                if k not in ("by_phase", "by_scope")} == plain


# -- capture ----------------------------------------------------------------


class TestCapture:
    def test_capture_reads_exact_cost_and_collectives(self):
        trainer, _ = _make_trainer()
        trainer.prepare()
        record = trainer.attribution()
        assert record is not None
        assert record.flops_per_step > 0
        assert record.bytes_accessed_per_step > 0
        assert record.n_devices == len(jax.devices())
        assert record.source == "hlo"
        # a data-parallel mesh must show the gradient all-reduce
        assert record.collective_bytes.get("all-reduce", 0) > 0
        assert record.predicted_comm_total_s == pytest.approx(
            sum(record.predicted_comm_s.values()))
        assert record.peak_flops_per_s == PEAK
        assert record.predicted_compute_s == pytest.approx(
            record.flops_per_step / PEAK)

    def test_record_cached_by_program_key(self):
        trainer, _ = _make_trainer()
        trainer.prepare()
        first = trainer.attribution()
        assert trainer.attribution() is first  # no re-capture

    def test_disabled_returns_none(self, _attribution_context):
        trainer, _ = _make_trainer()
        trainer.prepare()
        _attribution_context.attribution_enabled = False
        assert trainer.attribution() is None

    def test_planner_source_with_model_spec(self):
        from dlrover_tpu.parallel.planner import TPU_SPECS, ModelSpec

        spec = ModelSpec(param_count=1000, num_layers=2,
                         hidden_size=16, seq_len=8, global_batch=32)
        trainer, batch = _make_trainer()
        trainer.prepare()
        # the CPU has no datasheet: the link bandwidths the planner
        # prices collectives with come from an explicit spec
        record = attr_mod.capture_attribution(
            trainer.accelerated, example_batch=batch,
            model_spec=spec, device_spec=TPU_SPECS["v5e"], emit=False)
        assert record.source == "planner"
        # planner families, not HLO kinds
        assert set(record.predicted_comm_s) <= {
            "tp", "fsdp", "dp", "seq", "pipe", "moe_dispatch"}

    def test_derived_quantities_clamp(self):
        trainer, _ = _make_trainer()
        trainer.prepare()
        record = trainer.attribution()
        assert record.mfu(0.0) == 0.0
        assert 0.0 <= record.exposed_comm_fraction(1e-12) <= 1.0
        assert record.exposed_comm_fraction(1e9) == pytest.approx(
            1.0, abs=1e-6)
        assert record.arithmetic_intensity > 0


# -- executor e2e smoke (satellite: gauges in /metrics, MFU agreement) -------


class TestExecutorSmoke:
    def _run(self, trainer, batch, steps=24, **conf):
        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * steps,
            conf=Configuration({
                "train_steps": steps, "log_every_steps": 0,
                "train_window": 2, "preemption_grace": False,
                **conf,
            }),
        )
        executor.train_and_evaluate()
        return executor

    def test_gauges_exported_and_agree_with_prof(self):
        process_registry().reset()
        clear_ring()
        trainer, batch = _make_trainer()
        self._run(trainer, batch)
        reg = process_registry()
        mfu_g = reg.get(tm.ATTR_MFU)
        assert mfu_g is not None and mfu_g.value > 0
        assert reg.get(tm.ATTR_EXPOSED_COMM_FRAC) is not None
        assert 0.0 <= reg.get(tm.ATTR_EXPOSED_COMM_FRAC).value <= 1.0
        prom = reg.render_prometheus()
        for name in (tm.ATTR_MFU, tm.ATTR_EXPOSED_COMM_FRAC,
                     tm.ATTR_FLOPS_PER_STEP, tm.ATTR_ARITH_INTENSITY,
                     tm.ATTR_PEAK_HBM_MB, tm.ATTR_COMM_PREDICTED_S):
            assert name in prom
        # the capture event landed with the record attached
        captured = [e for e in recent_events()
                    if e["kind"] == tm.EventKind.ATTRIBUTION_CAPTURED]
        assert captured and captured[-1]["flops_per_step"] > 0

        # MFU agreement with the fixture-free utils/prof path: the
        # FLOPs side is EXACT (same compiled cost analysis through the
        # same shim), and for one shared step time the record's MFU and
        # the profiler's MFU are the SAME number — the one-formula pin
        from dlrover_tpu.utils.prof import DryRunner, analyze_cost

        result = trainer.accelerated
        sharded = result.shard_batch(batch)
        cost = analyze_cost(result.train_step, trainer.prepare(),
                            sharded, jax.random.PRNGKey(0))
        assert reg.get(tm.ATTR_FLOPS_PER_STEP).value == pytest.approx(
            cost.flops)
        profile = DryRunner(warmup=1, steps=3).profile(
            result.train_step, trainer.prepare(), sharded)
        record = trainer.attribution()
        assert record.flops_per_step == pytest.approx(
            profile.flops_per_step)
        assert record.mfu(1.0 / profile.steps_per_sec) == \
            pytest.approx(profile.mfu(PEAK))

    def test_no_fake_zero_before_first_measured_step(self):
        # between capture (train start) and the first materialized
        # step, the STATIC gauges exist but the DERIVED ones must be
        # absent — a scrape during a minutes-long first compile must
        # not read mfu=0 as if the job were measured dead
        process_registry().reset()
        trainer, batch = _make_trainer()
        executor = TrainExecutor(
            trainer, train_iter_fn=lambda: [batch],
            conf=Configuration({"train_steps": 1,
                                "preemption_grace": False}),
        )
        executor.state = trainer.prepare()
        executor._fetch_attribution()
        reg = process_registry()
        assert reg.get(tm.ATTR_FLOPS_PER_STEP) is not None
        assert reg.get(tm.ATTR_MFU) is None
        assert reg.get(tm.ATTR_EXPOSED_COMM_FRAC) is None

    def test_attribution_off_means_absent_not_zero(
            self, _attribution_context):
        process_registry().reset()
        _attribution_context.attribution_enabled = False
        trainer, batch = _make_trainer()
        self._run(trainer, batch, steps=8)
        assert process_registry().get(tm.ATTR_MFU) is None
        assert process_registry().get(tm.ATTR_FLOPS_PER_STEP) is None

    def test_unknown_device_kind_publishes_no_utilization(
            self, _attribution_context):
        # the CPU is a device kind with no datasheet here: no spec, no
        # peak — the static facts are still exported, the utilization
        # gauges are ABSENT (never priced against another chip's peak)
        process_registry().reset()
        _attribution_context.device_peak_flops = 0.0
        assert attr_mod.resolve_device_spec() is None
        assert attr_mod.resolve_peak_flops() == 0.0
        assert attr_mod.resolve_hbm_budget() == 0.0
        trainer, batch = _make_trainer()
        self._run(trainer, batch, steps=8)
        reg = process_registry()
        assert reg.get(tm.ATTR_FLOPS_PER_STEP).value > 0
        assert reg.get(tm.ATTR_MFU) is None
        assert reg.get(tm.ATTR_EXPOSED_COMM_FRAC) is None

    def test_device_kind_table_matches_real_kinds(self):
        # the v5e reports itself as "TPU v5 lite" (the deviceless
        # v5e:2x2 topology says so too); substrings do not match
        from dlrover_tpu.parallel.planner import TPU_SPECS

        table = attr_mod._DEVICE_KIND_TO_GEN
        assert TPU_SPECS[table["TPU v5 lite"]].hbm_bytes == 16e9
        assert table["TPU v5"] == "v5p"
        assert "cpu" not in table and "TPU v5 litepod" not in table


# -- the window's events: step_scopes, profile_window.memory -----------------


class _KickAt(TrainHook):
    """Sends this process the profile signal before given steps."""

    def __init__(self, *steps):
        self._steps = steps

    def before_step(self, step):
        if step in self._steps:
            os.kill(os.getpid(), signal.SIGUSR2)


class _ChipWithStats:
    def __init__(self, in_use, reserved):
        self._stats = {"bytes_in_use": in_use, "bytes_reserved": reserved,
                       "peak_bytes_in_use": in_use + 7,
                       "bytes_limit": 1000, "num_allocs": 3}

    def memory_stats(self):
        return self._stats


class TestWindowEvents:
    def _run(self, monkeypatch, kicks=(), steps=14, **conf):
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda *a, **k: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        clear_ring()
        trainer, batch = _make_trainer()
        TrainExecutor(
            trainer, train_iter_fn=lambda: [batch] * steps,
            hooks=[_KickAt(*kicks)],
            conf=Configuration({
                "train_steps": steps, "log_every_steps": 0,
                "train_window": 2, "preemption_grace": False,
                "trace_num_steps": 2, **conf,
            }),
        ).train_and_evaluate()
        return (trainer, batch), [e for e in recent_events() if e["kind"] in (
            tm.EventKind.STEP_SCOPES, tm.EventKind.PROFILE_WINDOW)]

    def test_step_scopes_once_a_program_before_profile_window(
            self, monkeypatch):
        (trainer, batch), events = self._run(
            monkeypatch, kicks=(4, 9), profile_signal="USR2")
        assert [e["kind"] for e in events] == [
            "step_scopes", "profile_window", "profile_window"]
        scopes = events[0]
        record = trainer.attribution()
        assert scopes["program"] == record.program_key != ""
        # the table went out with the event: the record a program
        # that the trainer keeps holds it no longer
        assert record.step_scopes is None
        table = attr_mod.capture_attribution(
            trainer.accelerated, example_batch=batch, emit=False,
            step_scopes=True).step_scopes
        assert scopes["instructions"] == table["instructions"]
        assert scopes["mixed_phase"] == table["mixed_phase"]
        assert any(k.startswith("optimizer|")
                   for k in scopes["instructions"])

    def test_a_scheduled_window_says_its_scopes_too(self, monkeypatch,
                                                    tmp_path):
        _, events = self._run(monkeypatch, trace_dir=str(tmp_path),
                              trace_start_step=3)
        assert [e["kind"] for e in events] == ["step_scopes",
                                               "profile_window"]

    def test_no_window_no_table_and_no_event(self, monkeypatch):
        (trainer, _), events = self._run(monkeypatch)
        assert events == []
        assert trainer.attribution().step_scopes is None

    def test_no_attribution_no_step_scopes(self, monkeypatch,
                                           _attribution_context):
        _attribution_context.attribution_enabled = False
        _, events = self._run(monkeypatch, kicks=(4,),
                              profile_signal="USR2")
        assert [e["kind"] for e in events] == ["profile_window"]

    def test_memory_only_where_the_backend_keeps_statistics(
            self, monkeypatch):
        # the CPU keeps none: the field is absent, not zeros
        _, events = self._run(monkeypatch, kicks=(4,),
                              profile_signal="USR2")
        assert "memory" not in events[-1]
        # the fullest chip's, by what it holds: in use and reserved
        monkeypatch.setattr(jax, "local_devices", lambda: [
            _ChipWithStats(100, 50), _ChipWithStats(80, 300),
            jax.devices()[0]])
        _, events = self._run(monkeypatch, kicks=(4,),
                              profile_signal="USR2")
        assert events[-1]["memory"] == {
            "bytes_in_use": 80, "bytes_reserved": 300,
            "peak_bytes_in_use": 87, "bytes_limit": 1000}


# -- memory-feasibility gate --------------------------------------------------


def _big_model_optimizer(hbm_bytes=2e9, budget=0.0):
    from dlrover_tpu.master.monitor.node_series import NodeRuntimeStore
    from dlrover_tpu.master.optimizer import RuntimeOptimizer
    from dlrover_tpu.parallel.planner import DeviceSpec

    get_context().device_hbm_budget_bytes = budget
    opt = RuntimeOptimizer(NodeRuntimeStore(), cooldown_secs=0,
                           device=DeviceSpec(hbm_bytes=hbm_bytes))
    opt.update_model_info(comm.ModelInfo(
        num_params=300_000_000, hidden_size=2048, num_layers=16,
        seq_len=2048))
    opt.update_running_config(comm.TrainerConfigReport(
        node_id=0, world=8, mesh_shape={"fsdp": 8}, train_window=4,
        global_batch=8))
    return opt


class TestMemoryFeasibilityGate:
    def test_oversized_candidates_rejected_with_memory_reason(self):
        clear_ring()
        opt = _big_model_optimizer(hbm_bytes=1e9)  # nothing fits
        decision = opt.replan("straggler:2")
        assert decision is not None
        assert decision.outcome == "rejected"
        assert decision.reason == "memory_infeasible:all"
        assert decision.memory_rejected
        entry = decision.memory_rejected[0]
        assert entry["predicted_hbm_bytes"] > entry["budget_bytes"]
        # the PLAN_REJECTED memory evidence is in the event timeline
        # (what `tpurun plan --events` / `tpurun attribution` read)
        rejected = [e for e in recent_events()
                    if e["kind"] == tm.EventKind.OPTIMIZER_PLAN_REJECTED
                    and str(e.get("reason", "")).startswith("memory")]
        assert rejected
        # the per-pass evidence record carries the worst offender
        evidence = [e for e in rejected if "predicted_hbm_mb" in e]
        assert evidence
        assert evidence[-1]["predicted_hbm_mb"] > \
            evidence[-1]["budget_mb"]
        # and in the queryable trail (tpurun plan / attribution --addr)
        assert opt.memory_rejections()
        trail = opt.to_report()["decisions"][-1]
        assert trail["memory_rejected"]
        # evidence is worst-first: the event's named offender is the
        # true maximum even when the retained list is trimmed
        sizes = [m["predicted_hbm_bytes"]
                 for m in decision.memory_rejected]
        assert sizes == sorted(sizes, reverse=True)
        assert evidence[-1]["predicted_hbm_mb"] == pytest.approx(
            sizes[0] / 1e6, rel=0.01)

    def test_partial_gate_still_prices_feasible_meshes(self):
        # budget between the sharded (fsdp) and replicated (data) cost:
        # the data-heavy meshes die at the gate, the fsdp ones price
        opt = _big_model_optimizer(hbm_bytes=95e9, budget=4.0e9)
        decision = opt.replan("recovered:2")
        assert decision is not None
        assert decision.candidates  # something still priced
        assert decision.memory_rejected  # and something was gated
        gated = {json.dumps(m["mesh"], sort_keys=True)
                 for m in decision.memory_rejected}
        priced = {json.dumps(c["mesh"], sort_keys=True)
                  for c in decision.candidates}
        assert gated.isdisjoint(priced)

    def test_memory_infeasible_error_carries_evidence(self):
        from dlrover_tpu.master.optimizer.calibration import (
            CostCalibrator,
            MemoryInfeasibleError,
        )
        from dlrover_tpu.parallel.planner import DeviceSpec, ModelSpec

        cal = CostCalibrator(
            model=ModelSpec(param_count=300_000_000, num_layers=16,
                            hidden_size=2048, seq_len=2048,
                            global_batch=8),
            device=DeviceSpec(hbm_bytes=1e9),
        )
        with pytest.raises(MemoryInfeasibleError) as err:
            cal.price(MeshPlan(data=8))
        assert err.value.memory_bytes > err.value.budget_bytes
        # the CURRENT config is observably running: never gated
        assert cal.price(MeshPlan(data=8), require_fit=False) > 0


# -- G107 ---------------------------------------------------------------------


class TestG107:
    def test_check_memory_budget_pure(self):
        from dlrover_tpu.analysis.graph_lint import check_memory_budget

        assert check_memory_budget(0, 1e9) == []  # unknown peak
        assert check_memory_budget(1e9, 0) == []  # unknown budget
        assert check_memory_budget(1e9, 2e9) == []  # fits
        findings = check_memory_budget(3e9, 2e9)
        assert len(findings) == 1
        assert findings[0].rule_id == "G107"
        assert "3.00 GB" in findings[0].message

    def test_lint_train_step_fires_on_tiny_budget(self):
        from dlrover_tpu.analysis.graph_lint import lint_train_step

        report = lint_train_step(rules={"G107"}, hbm_budget_bytes=16.0)
        assert [f.rule_id for f in report.findings] == ["G107"]

    def test_g107_in_rule_registry(self):
        from dlrover_tpu.analysis.graph_lint import (
            ALL_GRAPH_RULES,
            GRAPH_RULE_DOCS,
        )

        assert "G107" in ALL_GRAPH_RULES
        assert "G107" in GRAPH_RULE_DOCS


# -- device-memory guard (satellite: absent, never 0) ------------------------


class _NoStatsDevice:
    device_kind = "cpu"


class _StatsDevice:
    device_kind = "TPU v5e"

    @staticmethod
    def memory_stats():
        return {"bytes_in_use": 100 * 1024 * 1024,
                "bytes_limit": 16 * 1024 * 1024 * 1024}


class TestDeviceMemoryGuard:
    def test_no_stats_backend_reports_none(self, monkeypatch):
        hook = NodeRuntimeReportHook(master_client=None, every_steps=1,
                                     min_interval_s=0)
        hook._devices = [_NoStatsDevice()]
        assert hook._device_memory_mb() == (None, None)

    def test_stats_backend_reports_usage_and_headroom(self):
        hook = NodeRuntimeReportHook(master_client=None, every_steps=1,
                                     min_interval_s=0)
        hook._devices = [_StatsDevice(), _StatsDevice()]
        in_use, headroom = hook._device_memory_mb()
        assert in_use == pytest.approx(200.0)
        assert headroom == pytest.approx(2 * 16 * 1024 - 200.0)

    def test_node_series_exports_absent_as_no_gauge(self):
        from dlrover_tpu.master.monitor.node_series import (
            NodeRuntimeStore,
        )

        process_registry().reset()
        store = NodeRuntimeStore()
        report = comm.NodeRuntimeReport(
            node_id=7, step=10, steps_total=10.0,
            bounds=[0.001, 0.01], step_time_counts=[5, 5, 0],
            rss_mb=10.0, device_mem_mb=None, mfu=None,
        )
        sample = store.ingest(report)
        assert sample.device_mem_mb is None and sample.mfu is None
        reg = process_registry()
        assert reg.get(tm.NODE_DEVICE_MEM_MB,
                       labels={"node": "7"}) is None
        assert reg.get(tm.NODE_MFU, labels={"node": "7"}) is None
        # present values DO export
        store.ingest(comm.NodeRuntimeReport(
            node_id=7, step=20, steps_total=20.0,
            bounds=[0.001, 0.01], step_time_counts=[9, 11, 0],
            rss_mb=10.0, device_mem_mb=123.0, mfu=0.5,
            exposed_comm_frac=0.25, hbm_headroom_mb=1000.0))
        assert reg.get(tm.NODE_DEVICE_MEM_MB,
                       labels={"node": "7"}).value == 123.0
        assert reg.get(tm.NODE_MFU, labels={"node": "7"}).value == 0.5
        assert reg.get(tm.NODE_EXPOSED_COMM_FRAC,
                       labels={"node": "7"}).value == 0.25
        # a stat that BECOMES absent (program swap, failed re-capture)
        # RETRACTS its series — the stale 0.5 must not export forever
        store.ingest(comm.NodeRuntimeReport(
            node_id=7, step=30, steps_total=30.0,
            bounds=[0.001, 0.01], step_time_counts=[15, 15, 0],
            rss_mb=10.0, device_mem_mb=None, mfu=None))
        assert reg.get(tm.NODE_MFU, labels={"node": "7"}) is None
        assert reg.get(tm.NODE_DEVICE_MEM_MB,
                       labels={"node": "7"}) is None


# -- straggler verdict gains the comm-vs-compute label -----------------------


class TestStragglerBoundEvidence:
    def test_verdict_labeled_comm_bound(self):
        from dlrover_tpu.master.monitor.node_series import (
            NodeRuntimeStore,
        )
        from dlrover_tpu.master.monitor.straggler import (
            StragglerDetector,
        )

        store = NodeRuntimeStore()
        detector = StragglerDetector(store, ratio=2.0,
                                     confirm_windows=1, hang_secs=0)
        bounds = [0.001, 0.01, 0.1]

        def report(node, counts, **extra):
            store.ingest(comm.NodeRuntimeReport(
                node_id=node, step=10, steps_total=10.0,
                bounds=bounds, step_time_counts=counts, **extra))
            detector.observe(node)

        report(0, [10, 0, 0, 0], exposed_comm_frac=0.2)
        report(1, [10, 0, 0, 0], exposed_comm_frac=0.25)
        report(2, [0, 0, 10, 0], mfu=0.01, exposed_comm_frac=0.8)
        verdicts = detector.verdicts()
        assert verdicts[2]["verdict"] == "straggler"
        evidence = verdicts[2]["evidence"]
        # RELATIVE judgement: 0.8 vs the peers' 0.225 median
        assert evidence["bound"] == "comm-bound"
        assert evidence["exposed_comm_frac"] == pytest.approx(0.8)
        assert evidence["peer_median_comm_frac"] == pytest.approx(
            0.225)
        assert evidence["mfu"] == pytest.approx(0.01)

    def test_verdict_labeled_compute_bound_when_frac_tracks_peers(self):
        from dlrover_tpu.master.monitor.node_series import (
            NodeRuntimeStore,
        )
        from dlrover_tpu.master.monitor.straggler import (
            StragglerDetector,
        )

        store = NodeRuntimeStore()
        detector = StragglerDetector(store, ratio=2.0,
                                     confirm_windows=1, hang_secs=0)
        bounds = [0.001, 0.01, 0.1]

        def report(node, counts, **extra):
            store.ingest(comm.NodeRuntimeReport(
                node_id=node, step=10, steps_total=10.0,
                bounds=bounds, step_time_counts=counts, **extra))
            detector.observe(node)

        # every node (straggler included) shows the same high upper
        # bound — the extra step time is NOT extra communication
        report(0, [10, 0, 0, 0], exposed_comm_frac=0.6)
        report(1, [10, 0, 0, 0], exposed_comm_frac=0.6)
        report(2, [0, 0, 10, 0], exposed_comm_frac=0.65)
        evidence = detector.verdicts()[2]["evidence"]
        assert evidence["bound"] == "compute-bound"


# -- goodput model-FLOPs column ----------------------------------------------


class TestGoodputModelFlops:
    def test_column_derived_from_attribution_record(self):
        from dlrover_tpu.telemetry.goodput import derive_goodput

        events = [
            {"kind": "train_start", "ts": 0.0, "node": "0", "pid": 1,
             "step": 0},
            {"kind": tm.EventKind.ATTRIBUTION_CAPTURED, "ts": 1.0,
             "node": "0", "pid": 1, "flops_per_step": 100.0,
             "n_devices": 4},
            {"kind": "train_end", "ts": 11.0, "node": "0", "pid": 1,
             "step": 50},
        ]
        report = derive_goodput(events)
        col = report["detail"]["model_flops"]
        assert col["flops_per_step"] == pytest.approx(400.0)
        assert col["steps"] == 50
        assert col["total"] == pytest.approx(20000.0)
        assert col["per_productive_second"] > 0

    def test_column_integrates_across_elastic_resizes(self):
        # steps 0-100 on 8 devices, then a resize re-captures at 4
        # devices and the job runs to step 150: each phase is charged
        # at ITS OWN record's rate, not the newest record's
        from dlrover_tpu.telemetry.goodput import derive_goodput

        events = [
            {"kind": "train_start", "ts": 0.0, "node": "0", "pid": 1,
             "step": 0},
            {"kind": tm.EventKind.ATTRIBUTION_CAPTURED, "ts": 1.0,
             "node": "0", "pid": 1, "flops_per_step": 100.0,
             "n_devices": 8},
            {"kind": "train_end", "ts": 50.0, "node": "0", "pid": 1,
             "step": 100},
            {"kind": "train_start", "ts": 60.0, "node": "0", "pid": 1,
             "step": 100},
            {"kind": tm.EventKind.ATTRIBUTION_CAPTURED, "ts": 61.0,
             "node": "0", "pid": 1, "flops_per_step": 100.0,
             "n_devices": 4},
            {"kind": "train_end", "ts": 90.0, "node": "0", "pid": 1,
             "step": 150},
        ]
        col = derive_goodput(events)["detail"]["model_flops"]
        assert col["records"] == 2
        assert col["steps"] == 150
        # 100 steps @ 800 flops + 50 steps @ 400 flops
        assert col["total"] == pytest.approx(100 * 800 + 50 * 400)

    def test_no_record_no_column(self):
        from dlrover_tpu.telemetry.goodput import derive_goodput

        events = [
            {"kind": "train_start", "ts": 0.0, "node": "0", "pid": 1},
            {"kind": "train_end", "ts": 5.0, "node": "0", "pid": 1,
             "step": 9},
        ]
        assert "model_flops" not in derive_goodput(events)["detail"]


# -- CLI ----------------------------------------------------------------------


class TestAttributionCli:
    def test_forensic_events_view(self, tmp_path, capsys):
        from dlrover_tpu.telemetry.cli import main as cli_main

        path = tmp_path / "events.jsonl"
        records = [
            {"kind": tm.EventKind.ATTRIBUTION_CAPTURED, "ts": 1.0,
             "node": "0", "pid": 42, "flops_per_step": 123.0,
             "arithmetic_intensity": 0.5, "peak_hbm_mb": 1.5,
             "predicted_comm_total_s": 0.001, "source": "hlo"},
            {"kind": tm.EventKind.OPTIMIZER_PLAN_REJECTED, "ts": 2.0,
             "node": "0", "pid": 1, "reason": "memory_infeasible",
             "mesh": {"data": 8}, "predicted_hbm_mb": 7000.0,
             "budget_mb": 1600.0},
        ]
        path.write_text(
            "".join(json.dumps(r) + "\n" for r in records))
        rc = cli_main(["attribution", "--events", str(path), "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["records"][0]["flops_per_step"] == 123.0
        assert out["memory_rejected"][0]["reason"] == \
            "memory_infeasible"

    def test_trace_view(self, capsys):
        from dlrover_tpu.telemetry.cli import main as cli_main

        rc = cli_main(["attribution", "--trace", FIXTURE, "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["measured_comm_frac"] == pytest.approx(15 / 47,
                                                          abs=1e-4)

    def test_tpurun_routes_attribution(self, capsys):
        from dlrover_tpu.trainer.run import main as tpurun_main

        rc = tpurun_main(["attribution", "--trace", FIXTURE, "--json"])
        assert rc == 0
        assert json.loads(
            capsys.readouterr().out)["busy_s"] == pytest.approx(0.045)


# -- overhead gate (satellite: attribution collection stays cheap) -----------


class _TimedRegion(TrainHook):
    def __init__(self, warmup):
        self.warmup = warmup
        self.t0 = None

    def before_step(self, step):
        if step == self.warmup + 1 and self.t0 is None:
            self.t0 = time.perf_counter()


class TestAttributionOverheadGate:
    def test_overhead_within_budget(self, _attribution_context):
        """Attribution must stay observation-only: ≤5% step-loop
        overhead with derivation ON vs OFF, as the median of
        back-to-back paired ratios (run drift on a shared 1-core box
        dwarfs the real cost — two gauge stores per materialization).
        The one-off capture compile lands at TRAIN START (inside the
        COMPILE_FIRST_STEP window), so the timed region sees only the
        per-step cost."""
        steps, warmup = 280, 8
        trainer, batch = _make_trainer()

        def run(enabled):
            _attribution_context.attribution_enabled = enabled
            timer = _TimedRegion(warmup)
            executor = TrainExecutor(
                trainer,
                train_iter_fn=lambda: [batch] * (warmup + steps),
                hooks=[timer],
                conf=Configuration({
                    "train_steps": warmup + steps,
                    "log_every_steps": 0, "train_window": 4,
                    "preemption_grace": False,
                }),
            )
            executor.train_and_evaluate()
            return time.perf_counter() - timer.t0

        run(True)  # prime: capture + program compile out of the pairs

        def leg(enabled, best_of):
            # best_of > 1 takes the MIN over repeats — the floor
            # estimator that filters one-off scheduler stalls (the
            # residual flake on a shared 1-core box)
            return min(run(enabled) for _ in range(best_of))

        def paired_median(pairs=3, best_of=1):
            ratios = []
            for i in range(pairs):
                if i % 2 == 0:
                    dt_off = leg(False, best_of)
                    dt_on = leg(True, best_of)
                else:
                    dt_on = leg(True, best_of)
                    dt_off = leg(False, best_of)
                ratios.append(dt_on / dt_off)
            return sorted(ratios)[len(ratios) // 2]

        # same escalation discipline as the telemetry overhead gate
        # (tests/test_telemetry.py): up to 3 attempts gated on the MIN
        # of attempt medians, retries escalating to best-of-2 legs.
        # The first attempt costs exactly what the old 5-pair gate
        # did; a clean tree stops failing tier-1 on scheduler noise,
        # while the large regressions this gate exists for (≥10%,
        # e.g. capture placement inside the timed loop) fail every
        # attempt.
        medians = [paired_median()]
        while medians[-1] - 1.0 > 0.05 and len(medians) < 3:
            medians.append(paired_median(best_of=2))
        overhead = min(medians) - 1.0
        assert overhead <= 0.05, (
            f"attribution overhead {overhead:.1%} above the 5% budget "
            f"(attempt medians {[round(m, 3) for m in medians]})"
        )
