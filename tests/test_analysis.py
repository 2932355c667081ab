"""Static-analysis subsystem: AST rule units (one firing + one clean
case per rule id), SPMD graph-lint fixtures, the four-dispatch MoE
collective audit, and the cost-model perturbation regression."""

import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.analysis import graph_lint
from dlrover_tpu.analysis.ast_rules import lint_source
from dlrover_tpu.analysis.findings import Baseline, Finding
from dlrover_tpu.parallel.mesh import MeshPlan


def rules_of(findings):
    return sorted({f.rule_id for f in findings})


def lint_snip(code):
    return lint_source(textwrap.dedent(code), "snippet.py")


# -- AST rules --------------------------------------------------------------


class TestDLR001GrpcTimeout:
    def test_fires_on_stub_call_without_timeout(self):
        findings = lint_snip("""
            import grpc

            class C:
                def __init__(self, channel):
                    self._get = channel.unary_unary("/svc/get")

                def get(self, msg):
                    return self._get(msg)
        """)
        assert rules_of(findings) == ["DLR001"]
        assert findings[0].scope == "C.get"

    def test_clean_with_timeout(self):
        findings = lint_snip("""
            import grpc

            class C:
                def __init__(self, channel):
                    self._get = channel.unary_unary("/svc/get")

                def get(self, msg):
                    return self._get(msg, timeout=30.0)
        """)
        assert findings == []

    def test_fires_on_future_fanout_without_timeout(self):
        findings = lint_snip("""
            import grpc

            def fanout(stub, frames):
                return [stub.future(f) for f in frames]
        """)
        assert rules_of(findings) == ["DLR001"]

    def test_no_grpc_import_no_rule(self):
        # .future() on arbitrary objects outside grpc modules is not ours
        findings = lint_snip("""
            def fanout(stub, frames):
                return [stub.future(f) for f in frames]
        """)
        assert findings == []


class TestDLR002SwallowedException:
    def test_fires_on_silent_pass(self):
        findings = lint_snip("""
            def poll(client):
                try:
                    return client.num_nodes_waiting()
                except Exception:
                    return 0
        """)
        assert rules_of(findings) == ["DLR002"]

    def test_clean_when_logged(self):
        findings = lint_snip("""
            def poll(client, logger):
                try:
                    return client.num_nodes_waiting()
                except Exception as e:
                    logger.warning("poll failed: %s", e)
                    return 0
        """)
        assert findings == []

    def test_clean_when_reraised_or_narrow(self):
        findings = lint_snip("""
            def a(x):
                try:
                    return int(x)
                except ValueError:
                    return 0

            def b(x):
                try:
                    return int(x)
                except Exception:
                    raise
        """)
        assert findings == []


class TestDLR003ThreadDaemon:
    def test_fires_without_daemon(self):
        findings = lint_snip("""
            import threading

            def start(fn):
                t = threading.Thread(target=fn)
                t.start()
        """)
        assert rules_of(findings) == ["DLR003"]

    def test_clean_with_daemon(self):
        findings = lint_snip("""
            import threading

            def start(fn):
                t = threading.Thread(target=fn, daemon=True)
                t.start()
        """)
        assert findings == []


class TestDLR004ImpureInJit:
    def test_fires_on_time_in_jitted_fn(self):
        findings = lint_snip("""
            import time
            import jax

            @jax.jit
            def step(x):
                t0 = time.time()
                return x * t0
        """)
        assert rules_of(findings) == ["DLR004"]

    def test_fires_on_np_random_under_partial_jit(self):
        findings = lint_snip("""
            import functools
            import jax
            import numpy as np

            @functools.partial(jax.jit, static_argnums=0)
            def step(n, x):
                return x + np.random.uniform()
        """)
        assert rules_of(findings) == ["DLR004"]

    def test_clean_outside_jit_and_with_jax_random(self):
        findings = lint_snip("""
            import time
            import jax

            def host_loop(x):
                return time.time()

            @jax.jit
            def step(x, key):
                return x + jax.random.normal(key, x.shape)
        """)
        assert findings == []


class TestDLR005MutableDefault:
    def test_fires_on_function_default(self):
        findings = lint_snip("""
            def merge(extra={}):
                return dict(extra)
        """)
        assert rules_of(findings) == ["DLR005"]

    def test_fires_on_annotated_class_attr(self):
        findings = lint_snip("""
            from typing import Dict, List

            class RegistryConf:
                entries: List[str] = []
        """)
        assert rules_of(findings) == ["DLR005"]
        assert findings[0].scope == "RegistryConf"

    def test_clean_with_classvar_none_or_factory(self):
        findings = lint_snip("""
            from dataclasses import dataclass, field
            from typing import ClassVar, Dict, List, Optional

            class Registry:
                entries: ClassVar[List[str]] = []

            @dataclass
            class Conf:
                tags: List[str] = field(default_factory=list)

            def merge(extra=None):
                return dict(extra or {})
        """)
        assert findings == []


class TestDLR006HostSyncOnMetrics:
    def test_fires_on_float_item_asarray(self):
        findings = lint_snip("""
            import numpy as np

            def after_step(step, metrics):
                loss = float(metrics["loss"])
                gn = metrics["grad_norm"].item()
                arr = np.asarray(metrics.get("aux"))
                return loss, gn, arr
        """)
        assert rules_of(findings) == ["DLR006"]
        assert len(findings) == 3
        assert findings[0].scope == "after_step"

    def test_fires_on_device_get_of_self_metrics(self):
        findings = lint_snip("""
            import jax

            class Loop:
                def log(self):
                    return jax.device_get(self.step_metrics)
        """)
        assert rules_of(findings) == ["DLR006"]

    def test_clean_on_non_metric_values(self):
        findings = lint_snip("""
            import numpy as np

            def report(v, config):
                rate = float(v)
                lim = config.limit.item()
                return np.asarray([rate, lim])
        """)
        assert findings == []


class TestDLR007UnregisteredMetricName:
    def test_fires_on_literal_names(self):
        findings = lint_snip("""
            from dlrover_tpu.telemetry import emit_event, get_registry

            def instrument(reg):
                c = reg.counter("my_adhoc_total")
                g = get_registry().gauge(name="my_gauge")
                emit_event("my_event", step=1)
                return c, g
        """)
        assert rules_of(findings) == ["DLR007"]
        assert len(findings) == 3

    def test_clean_with_names_constants(self):
        findings = lint_snip("""
            from dlrover_tpu.telemetry import (
                emit_event, get_registry, names as tm,
            )

            def instrument(reg):
                c = reg.counter(tm.TRAIN_STEPS)
                emit_event(tm.EventKind.TRAIN_START, step=1)
                return c
        """)
        assert findings == []

    def test_telemetry_package_itself_is_exempt(self):
        from dlrover_tpu.analysis.ast_rules import lint_source

        findings = lint_source(
            'def counter(name):\n    return counter("literal")\n',
            "dlrover_tpu/telemetry/metrics.py",
        )
        assert findings == []

    def test_unrelated_counter_class_is_not_matched(self):
        # collections.Counter / .count() must not trip the rule
        findings = lint_snip("""
            from collections import Counter

            def tally(words):
                c = Counter("abc")
                return c, words.count("x")
        """)
        assert findings == []


class TestDLR008FailureEventErrorCode:
    def test_fires_on_missing_or_empty_code(self):
        findings = lint_snip("""
            from dlrover_tpu.telemetry import EventKind, emit_event

            def report(rank):
                emit_event(EventKind.WORKER_FAILED, local_rank=rank)
                emit_event(EventKind.HANG_DETECTED, error_code="")
        """)
        assert rules_of(findings) == ["DLR008"]
        assert len(findings) == 2

    def test_fires_on_string_literal_kind(self):
        # inside the telemetry package a literal kind is DLR007-exempt,
        # but the failure-class code requirement still applies
        findings = lint_source(
            "from dlrover_tpu.telemetry import emit_event\n"
            "def f():\n"
            "    emit_event('diag_straggler', diag_node=2)\n",
            "dlrover_tpu/telemetry/whatever.py",
        )
        assert rules_of(findings) == ["DLR008"]

    def test_clean_with_codes_and_on_non_failure_kinds(self):
        findings = lint_snip("""
            from dlrover_tpu.telemetry import EventKind, emit_event

            def report(rc, reason):
                emit_event(EventKind.WORKER_FAILED,
                           error_code=f"EXIT_{rc}")
                emit_event(EventKind.ERROR_REPORT, error_code=reason)
                emit_event(EventKind.TRAIN_START, step=0)
                emit_event(EventKind.WORKERS_STARTED, round=1)
        """)
        assert findings == []

    def test_telemetry_package_is_not_exempt(self):
        # unlike DLR007, a failure emit inside the telemetry package
        # itself must still carry a code
        findings = lint_source(
            "from dlrover_tpu.telemetry import EventKind, emit_event\n"
            "def f():\n"
            "    emit_event(EventKind.NONFINITE_STEP, step=1)\n",
            "dlrover_tpu/telemetry/whatever.py",
        )
        assert rules_of(findings) == ["DLR008"]


class TestBaseline:
    def test_filter_allows_counts_and_reports_stale(self):
        f1 = Finding("DLR002", "a.py", 10, "m", scope="A.f")
        f2 = Finding("DLR002", "a.py", 20, "m", scope="A.f")
        base = Baseline.from_findings([f1, f2])
        # both findings covered
        new, stale = base.filter([f1, f2])
        assert new == [] and stale == []
        # a third in the same scope is NEW
        f3 = Finding("DLR002", "a.py", 30, "m", scope="A.f")
        new, _ = base.filter([f1, f2, f3])
        assert len(new) == 1
        # fixing one leaves a stale count so the ratchet shrinks
        new, stale = base.filter([f1])
        assert new == [] and stale == [f1.baseline_key]

    def test_round_trip(self, tmp_path):
        base = Baseline.from_findings(
            [Finding("DLR001", "b.py", 1, "m", scope="g")]
        )
        path = str(tmp_path / "baseline.json")
        base.save(path)
        assert Baseline.load(path).entries == base.entries

    def test_missing_file_is_empty(self, tmp_path):
        assert Baseline.load(str(tmp_path / "nope.json")).entries == {}


# -- graph lint: per-rule fixtures ------------------------------------------


class TestGraphRuleFixtures:
    def test_g102_fires_on_debug_callback(self):
        def f(x):
            jax.debug.print("x sum {}", x.sum())
            return x * 2

        low = jax.jit(f).lower(jnp.ones((4,)))
        findings = graph_lint.check_host_callbacks(low.as_text())
        assert rules_of(findings) == ["G102"]

    def test_g102_clean_without_callback(self):
        low = jax.jit(lambda x: x * 2).lower(jnp.ones((4,)))
        assert graph_lint.check_host_callbacks(low.as_text()) == []

    def test_g103_fires_on_python_scalar_arg(self):
        low = jax.jit(lambda x, s: x * s).lower(jnp.ones((4,)), 0.5)
        findings = graph_lint.check_weak_type_inputs(
            getattr(low, "args_info", None)
        )
        assert rules_of(findings) == ["G103"]

    def test_g103_clean_with_strong_scalar(self):
        low = jax.jit(lambda x, s: x * s).lower(
            jnp.ones((4,)), jnp.float32(0.5)
        )
        assert graph_lint.check_weak_type_inputs(
            getattr(low, "args_info", None)
        ) == []

    def test_g104_fires_on_f32_dots_under_bf16_policy(self):
        low = jax.jit(lambda a, b: a @ b).lower(
            jnp.ones((8, 8), jnp.float32), jnp.ones((8, 8), jnp.float32)
        )
        findings = graph_lint.check_dtype_drift(low.as_text(), "bfloat16")
        assert rules_of(findings) == ["G104"]

    def test_g104_clean_on_bf16_dots(self):
        low = jax.jit(lambda a, b: a @ b).lower(
            jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8), jnp.bfloat16)
        )
        assert graph_lint.check_dtype_drift(low.as_text(), "bfloat16") == []

    def test_g104_not_applicable_to_f32_policy(self):
        low = jax.jit(lambda a, b: a @ b).lower(
            jnp.ones((8, 8), jnp.float32), jnp.ones((8, 8), jnp.float32)
        )
        assert graph_lint.check_dtype_drift(low.as_text(), "float32") == []

    def test_g105_donation_detected_and_missed(self):
        state = {"w": jnp.ones((16, 16)), "m": jnp.ones((16, 16))}
        step = lambda s: jax.tree.map(lambda x: x + 1.0, s)  # noqa: E731
        donated = jax.jit(step, donate_argnums=(0,)).lower(state).compile()
        plain = jax.jit(step).lower(state).compile()
        assert graph_lint.check_donation(donated.as_text(), 2) == []
        findings = graph_lint.check_donation(plain.as_text(), 2)
        assert rules_of(findings) == ["G105"]

    def test_g101_replicated_param_under_sharded_strategy(self):
        from types import SimpleNamespace

        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("fsdp",))
        big = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        plan = MeshPlan(data=1, fsdp=8)
        replicated = SimpleNamespace(
            params={"w": NamedSharding(mesh, PartitionSpec())}
        )
        sharded = SimpleNamespace(
            params={"w": NamedSharding(mesh, PartitionSpec("fsdp", None))}
        )
        abstract = SimpleNamespace(params={"w": big})
        assert rules_of(graph_lint.check_param_shardings(
            replicated, abstract, plan)) == ["G101"]
        assert graph_lint.check_param_shardings(
            sharded, abstract, plan) == []
        # pure-DP strategies replicate by design: not a finding
        assert graph_lint.check_param_shardings(
            replicated, abstract, MeshPlan(data=8, fsdp=1)) == []
        # deliberately-replicated SMALL tensors (norm scales, biases —
        # under rel_frac of total param bytes) are fine
        small = jax.ShapeDtypeStruct((64,), jnp.float32)
        mixed_shard = SimpleNamespace(params={
            "w": NamedSharding(mesh, PartitionSpec("fsdp", None)),
            "scale": NamedSharding(mesh, PartitionSpec()),
        })
        mixed_abs = SimpleNamespace(params={"w": big, "scale": small})
        assert graph_lint.check_param_shardings(
            mixed_shard, mixed_abs, plan) == []

    def test_g101_full_param_gather_text_fixture(self):
        total = 1024 * 256 * 4
        hoisted = ("  %ag = f32[1024,256]{1,0} all-gather("
                   "f32[128,256]{1,0} %p), dimensions={0}\n")
        per_layer = ("  %ag = f32[64,256]{1,0} all-gather("
                     "f32[8,256]{1,0} %p), dimensions={0}\n")
        assert rules_of(graph_lint.check_full_param_gather(
            hoisted, total)) == ["G101"]
        assert graph_lint.check_full_param_gather(per_layer, total) == []
        # bigger-than-the-param-set gathers are activation movement
        # (capacity-MoE one-hots) — G106's domain, not G101's
        assert graph_lint.check_full_param_gather(
            hoisted, total // 2) == []

    def test_g106_audit_both_directions(self):
        assert graph_lint.collective_audit(1e6, 1e6) == []
        assert rules_of(
            graph_lint.collective_audit(100e6, 1e6)) == ["G106"]
        assert rules_of(
            graph_lint.collective_audit(1e6, 100e6)) == ["G106"]
        # sub-KiB predictions (single-chip meshes) skip the ratio
        assert graph_lint.collective_audit(1e6, 0.0) == []


# -- graph lint: end-to-end over the real train step ------------------------


@pytest.fixture(scope="module")
def dense_report():
    return graph_lint.lint_train_step()


@pytest.fixture(scope="module")
def moe_reports():
    return graph_lint.moe_dispatch_audit()


class TestGraphLintEndToEnd:
    def test_head_train_step_is_clean(self, dense_report):
        assert dense_report.findings == []

    def test_measures_every_collective_family_planner_prices(
            self, dense_report):
        # data x fsdp x tensor mesh: gathers + reduces must both appear
        kinds = set(dense_report.measured_bytes)
        assert "all-gather" in kinds and "all-reduce" in kinds
        assert dense_report.predicted_total > 0

    def test_moe_audit_clean_for_all_four_dispatches(self, moe_reports):
        assert [r.label for r in moe_reports] == [
            "llama_tiny_moe[gather]", "llama_tiny_moe[einsum]",
            "llama_tiny_moe[grouped]", "llama_tiny_moe[grouped_ep]",
        ]
        for rep in moe_reports:
            assert rep.findings == [], (
                rep.label, [f.render() for f in rep.findings]
            )

    def test_grouped_ep_prediction_includes_dispatch_bytes(
            self, moe_reports):
        by_label = {r.label: r for r in moe_reports}
        ep = by_label["llama_tiny_moe[grouped_ep]"]
        assert ep.predicted_bytes["moe_dispatch"] > 0
        # capacity dispatches price the overhead as compute, not comm
        assert by_label["llama_tiny_moe[gather]"].predicted_bytes[
            "moe_dispatch"] == 0

    def test_perturbed_cost_term_fails_the_audit(self, moe_reports):
        """The cost-model-rot regression (ISSUE 2 satellite): corrupting
        one planner term must trip G106 against the UNCHANGED compiled
        measurement. Inflation uses 10000x: the einsum dispatch already
        sits ~16.7x above its prediction (GSPMD realizes the one-hot
        capacity movement as per-layer gathers the model prices as
        compute), so a single-term inflation must clear tol * that
        headroom — with margin — before the symmetric band flags it."""
        for rep in moe_reports:
            perturbed = dict(rep.predicted_bytes)
            perturbed["moe_dispatch"] = (
                perturbed["moe_dispatch"] or perturbed["fsdp"]) * 10_000
            findings = graph_lint.collective_audit(
                rep.measured_total, sum(perturbed.values()),
                path=rep.label,
            )
            assert rules_of(findings) == ["G106"], rep.label
            shrunk = {k: v / 100 for k, v in rep.predicted_bytes.items()}
            findings = graph_lint.collective_audit(
                rep.measured_total, sum(shrunk.values()), path=rep.label,
            )
            assert rules_of(findings) == ["G106"], rep.label

    def test_seeded_callback_violation_end_to_end(self):
        """A debug print smuggled into the loss must trip G102 through
        the same accelerate -> lower -> lint_artifacts path the CLI
        runs (lower only, no compile: the check reads StableHLO)."""
        import optax

        from dlrover_tpu.models import llama
        from dlrover_tpu.parallel.accelerate import accelerate
        from dlrover_tpu.parallel.strategy import Strategy

        config = llama.llama_tiny()
        base_loss = llama.make_loss_fn(config)

        def noisy_loss(params, batch, rng):
            jax.debug.print("step!")
            return base_loss(params, batch, rng)

        rng = np.random.RandomState(0)
        ids = rng.randint(0, config.vocab_size,
                          size=(4, config.max_seq_len + 1))
        batch = {"input_ids": jnp.asarray(ids[:, :-1]),
                 "labels": jnp.asarray(ids[:, 1:])}
        result = accelerate(
            llama.make_init_fn(config), noisy_loss, optax.sgd(1e-3),
            batch,
            strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                              rule_set="llama"),
        )
        abstract_state = jax.eval_shape(
            result.init_fn, jax.random.PRNGKey(0))
        abstract_batch = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
        lowered = result.train_step.lower(
            abstract_state, abstract_batch,
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        rep = graph_lint.lint_artifacts(
            stablehlo=lowered.as_text(), rules={"G102"}, label="seeded")
        assert rules_of(rep.findings) == ["G102"]


class TestAotLintSurface:
    def test_report_json_carries_findings_only_when_lint_ran(self):
        from dlrover_tpu.parallel.aot import AotReport

        kwargs = dict(
            model="m", topology="t", n_devices=8, mesh={}, params=1,
            global_batch=8, seq_len=128, fits=True,
            hbm_per_device_bytes=1e9, hbm_capacity_bytes=9e9,
            flops_per_step=1e12, predicted_step_time_s=0.1,
            predicted_mfu=0.5, compile_time_s=1.0,
        )
        assert "lint_findings" not in AotReport(**kwargs).to_json()
        ran = AotReport(**kwargs, lint_findings=[
            Finding("G106", "m@t", 0, "drift")
        ]).to_json()
        assert '"lint_findings"' in ran and "G106" in ran


# -- planner byte/second consistency ----------------------------------------


class TestPlannerBytesConsistency:
    def test_estimate_and_bytes_share_formulas(self):
        from dlrover_tpu.parallel import planner

        model = planner.ModelSpec(
            param_count=7_000_000_000, num_layers=32, hidden_size=4096,
            seq_len=4096, global_batch=64, num_heads=32, kv_heads=8,
        )
        dev = planner.TPU_SPECS["v5p"]
        plan = MeshPlan(data=2, fsdp=4, seq=2, tensor=2)
        score = planner.estimate(plan, model, dev)
        pred = planner.predicted_collective_bytes(plan, model, dev)
        assert score.breakdown["tp_comm_s"] == pytest.approx(
            pred["tp"] / dev.ici_bw)
        assert score.breakdown["fsdp_comm_s"] == pytest.approx(
            pred["fsdp"] / dev.ici_bw)
        assert score.breakdown["dp_comm_s"] == pytest.approx(
            pred["dp"] / dev.ici_bw)
        assert score.breakdown["seq_comm_s"] == pytest.approx(
            pred["seq"] / dev.ici_bw)

    def test_moe_dispatch_bytes_match_breakdown(self):
        from dlrover_tpu.parallel import planner

        model = planner.ModelSpec(
            param_count=1_000_000_000, num_layers=8, hidden_size=2048,
            seq_len=2048, global_batch=32, num_experts=8,
            moe_dispatch="grouped_ep",
        )
        dev = planner.TPU_SPECS["v5e"]
        plan = MeshPlan(data=2, fsdp=4)
        score = planner.estimate(plan, model, dev)
        pred = planner.predicted_collective_bytes(plan, model, dev)
        assert pred["moe_dispatch"] > 0
        assert score.breakdown["moe_disp_comm_s"] == pytest.approx(
            pred["moe_dispatch"] / dev.ici_bw)


# -- CLI: concurrency pass + suppression plumbing ---------------------------


class TestCliConcurrencySurface:
    FIXTURE = textwrap.dedent("""
        import threading, time

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def run(self):
                with self._lock:
                    time.sleep(1.0){note}
    """)

    def test_concurrency_finding_flows_through_cli(self, tmp_path,
                                                   capsys):
        from dlrover_tpu.analysis import cli

        bad = tmp_path / "locked_sleep.py"
        bad.write_text(self.FIXTURE.format(note=""))
        rc = cli.main([str(bad), "--ast-only",
                       "--baseline", str(tmp_path / "nb.json")])
        assert rc == 1
        assert "DLR009" in capsys.readouterr().out

    def test_suppressed_counts_in_text_summary(self, tmp_path, capsys):
        from dlrover_tpu.analysis import cli

        ok = tmp_path / "suppressed.py"
        ok.write_text(self.FIXTURE.format(
            note="  # dlrlint: disable=DLR009 paced by master"))
        rc = cli.main([str(ok), "--ast-only",
                       "--baseline", str(tmp_path / "nb.json")])
        out = capsys.readouterr().out
        assert rc == 0
        assert "1 inline-suppressed (DLR009" in out

    def test_suppressed_counts_in_json_output(self, tmp_path, capsys):
        import json as _json

        from dlrover_tpu.analysis import cli

        ok = tmp_path / "suppressed.py"
        ok.write_text(self.FIXTURE.format(
            note="  # dlrlint: disable=DLR009 paced by master"))
        rc = cli.main([str(ok), "--ast-only", "--json",
                       "--baseline", str(tmp_path / "nb.json")])
        data = _json.loads(capsys.readouterr().out)
        assert rc == 0
        assert data["suppressed"] == {"DLR009": 1}

    def test_changed_with_unresolvable_ref_exits_2(self, capsys):
        from dlrover_tpu.analysis import cli

        rc = cli.main(["--changed=no-such-ref-zzz", "--ast-only"])
        assert rc == 2
        assert "git could not resolve" in capsys.readouterr().err

    def test_changed_scopes_to_the_package(self, monkeypatch, capsys):
        # a diff touching only tests/ must not make the incremental
        # loop stricter than the full gate (which lints the package)
        import dlrover_tpu
        from dlrover_tpu.analysis import cli

        root = os.path.dirname(os.path.dirname(
            os.path.abspath(dlrover_tpu.__file__)))
        monkeypatch.setattr(
            cli, "_changed_files",
            lambda _root, _ref: [os.path.join(root, "tests",
                                              "test_aot.py")])
        rc = cli.main(["--changed=HEAD", "--ast-only"])
        assert rc == 0
        assert "0 changed .py files" in capsys.readouterr().out
