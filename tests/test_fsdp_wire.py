"""Quantize the dense wire (ISSUE 12): fp8 FSDP param gathers +
error-feedback gradient reduce-scatters, priced, audited,
optimizer-retunable.

Pins, per the acceptance criteria:

  * the fp8 dense-gather wire is BITWISE equal to the "fsdp_qdq"
    dequant-exact oracle fwd AND bwd (loss + grads), plain scan and
    fsdp_prefetch alike — the transform is pure-forward;
  * the error-feedback gradient path telescopes: the cumulative
    applied-gradient error equals the final residual EXACTLY (bounded),
    while quantize-without-feedback accumulates linearly — and at the
    model level the fp8-EF loss trajectory stays bounded against bf16
    AND strictly tighter than the no-feedback control;
  * the residual rides TrainState: zeros at init, sharded like params,
    surviving checkpoint save→restore and live reshard 8→4;
  * ``planner`` splits the fsdp term into dtype-aware gather legs +
    the param-dtype reduce-scatter with bf16 twins, the fp8/bf16 byte
    ratio pinned to the one formula, and the G106 audit both clean on
    the quantized program and firing on perturbed predictions in both
    directions;
  * the fsdp_precision knob resolves config > Context(env) > default,
    keys the program cache (|fp=), prewarm+retunes with ZERO
    recompiles, the optimizer's candidate key / churn / blacklist
    carry it, and the executor negative-acks a plan the backend's fp8
    probe cannot honor;
  * G109 gains per-family entries (moe vs fsdp vs grad) in
    ``quant_baseline.json``, fire/clean per family.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.common.config import get_context
from dlrover_tpu.models import llama
from dlrover_tpu.ops.quantize import (
    dequantize_block_scaled,
    error_feedback_qdq,
    qdq,
    quantize_block_scaled,
)
from dlrover_tpu.parallel.accelerate import (
    accelerate,
    resolve_grad_precision,
)
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.planner import (
    DeviceSpec,
    ModelSpec,
    estimate,
    model_spec_from_llama,
    predicted_collective_bytes,
)
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.trainer.elastic import ElasticTrainer


@pytest.fixture(autouse=True)
def _telemetry_on():
    ctx = get_context()
    prev = ctx.telemetry_enabled
    ctx.telemetry_enabled = True
    yield
    ctx.telemetry_enabled = prev


def _dense_cfg(**over):
    over.setdefault("num_layers", 4)
    return llama.llama_tiny(**over)


def _probe_batch(cfg, rows=4, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, size=(rows, cfg.max_seq_len + 1))
    return {"input_ids": jnp.asarray(ids[:, :-1]),
            "labels": jnp.asarray(ids[:, 1:])}


_LG_CACHE = {}


def _loss_and_grads(precision, prefetch=False):
    """Cached per (precision, prefetch): the oracle tests compare the
    same programs from several angles — compile each once."""
    key = (precision, prefetch)
    if key in _LG_CACHE:
        return _LG_CACHE[key]
    _LG_CACHE[key] = _loss_and_grads_uncached(precision, prefetch)
    return _LG_CACHE[key]


def _loss_and_grads_uncached(precision, prefetch):
    cfg = _dense_cfg(fsdp_precision=precision, fsdp_prefetch=prefetch)
    batch = _probe_batch(cfg)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    loss_fn = llama.make_loss_fn(cfg)
    val_grad = jax.jit(jax.value_and_grad(
        lambda p, b, r: loss_fn(p, b, r)[0]))
    loss, grads = val_grad(params, batch, jax.random.PRNGKey(1))
    return jax.device_get(loss), jax.device_get(grads)


def _trees_bitwise(a, b) -> bool:
    return all(
        bool((np.asarray(x) == np.asarray(y)).all())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# -- the dequant-exact oracle: fp8 == fsdp_qdq, fwd AND bwd -------------------


class TestFsdpWireOracle:
    def test_fp8_matches_qdq_oracle_bitwise_fwd_and_bwd(self):
        """The acceptance pin: the quantized wire changes transport,
        never numbers — quantization commutes with the per-layer slice
        the scan takes, so fp8 (quantized xs, dequant at consumption)
        and fsdp_qdq (decode before the wire) are bitwise equal in
        loss AND in every gradient leaf (both straight-through)."""
        l_q, g_q = _loss_and_grads("fp8")
        l_r, g_r = _loss_and_grads("fp8_qdq")
        assert l_q.tobytes() == l_r.tobytes()
        assert _trees_bitwise(g_q, g_r)

    def test_fp8_drifts_from_bf16_but_boundedly(self):
        """The wire IS a weight-qdq: bf16 and fp8 losses legitimately
        differ (the G109 fsdp family ratchets it), but by rounding
        magnitudes, not structure."""
        l_b, g_b = _loss_and_grads("bf16")
        l_q, _ = _loss_and_grads("fp8")
        assert l_b.tobytes() != l_q.tobytes()
        assert abs(float(l_b) - float(l_q)) / abs(float(l_b)) < 5e-3
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree.leaves(g_b))

    def test_prefetch_path_holds_the_oracle_too(self):
        """fsdp_prefetch + fp8: the wire forms ride the double-buffered
        carry (dequant still at consumption) and the oracle contract
        survives the restructure bitwise; prefetch-vs-plain matches to
        float roundoff as always."""
        l_q, g_q = _loss_and_grads("fp8", prefetch=True)
        l_r, g_r = _loss_and_grads("fp8_qdq", prefetch=True)
        assert l_q.tobytes() == l_r.tobytes()
        assert _trees_bitwise(g_q, g_r)
        l_plain, _ = _loss_and_grads("fp8")
        np.testing.assert_allclose(float(l_q), float(l_plain),
                                   rtol=1e-5)

    def test_only_rank3_kernels_ride_the_wire(self):
        """Vector params (norm scales) stay exact and rank-4 expert
        tensors (consumed shard-local, never gathered) are excluded."""
        from dlrover_tpu.models.llama import _quantize_layer_stack

        cfg = _dense_cfg(num_experts=4, moe_dispatch="gather")
        params = llama.init(jax.random.PRNGKey(0), cfg)
        wire = _quantize_layer_stack(params["layers"], "fp8")
        assert wire  # the dense kernels are wired
        assert not any("input_norm" in k or "post_norm" in k
                       for k in wire)
        assert not any("experts" in k for k in wire)
        assert any(k.endswith("router/kernel") for k in wire)


# -- knob resolution ----------------------------------------------------------


class TestFsdpKnobResolution:
    def test_config_wins_then_context_then_default(self, monkeypatch):
        from dlrover_tpu.models.llama import resolve_fsdp_precision

        ctx = get_context()
        monkeypatch.setattr(ctx, "fsdp_precision", "fp8")
        assert resolve_fsdp_precision(_dense_cfg()) == "fp8"
        assert resolve_fsdp_precision(
            _dense_cfg(fsdp_precision="bf16")) == "bf16"
        monkeypatch.setattr(ctx, "fsdp_precision", "bf16")
        assert resolve_fsdp_precision(_dense_cfg()) == "bf16"

    def test_unknown_precision_raises(self):
        from dlrover_tpu.models.llama import resolve_fsdp_precision

        with pytest.raises(ValueError, match="FSDP wire precision"):
            resolve_fsdp_precision(_dense_cfg(fsdp_precision="int4"))

    def test_probe_failure_degrades_to_bf16(self, monkeypatch):
        from dlrover_tpu.models.llama import resolve_fsdp_precision
        from dlrover_tpu.ops import shard_compat

        monkeypatch.setattr(shard_compat, "fp8_wire_supported",
                            lambda: False)
        assert resolve_fsdp_precision(
            _dense_cfg(fsdp_precision="fp8")) == "bf16"

    def test_model_spec_resolves_the_context_knob(self, monkeypatch):
        ctx = get_context()
        monkeypatch.setattr(ctx, "fsdp_precision", "fp8")
        spec = model_spec_from_llama(_dense_cfg(), 8)
        assert spec.fsdp_precision == "fp8"
        spec = model_spec_from_llama(
            _dense_cfg(fsdp_precision="bf16"), 8)
        assert spec.fsdp_precision == "bf16"

    def test_grad_precision_resolution(self, monkeypatch):
        ctx = get_context()
        monkeypatch.setattr(ctx, "grad_precision", "fp8")
        assert resolve_grad_precision() == "fp8"
        assert resolve_grad_precision("bf16") == "bf16"
        with pytest.raises(ValueError, match="grad precision"):
            resolve_grad_precision("int4")
        from dlrover_tpu.ops import shard_compat

        monkeypatch.setattr(shard_compat, "fp8_wire_supported",
                            lambda: False)
        assert resolve_grad_precision("fp8") == "bf16"


# -- planner: dtype-aware gather/scatter split twins --------------------------


def _dense_spec(precision="bf16", **over):
    base = dict(
        param_count=7_000_000_000, num_layers=32, hidden_size=4096,
        seq_len=4096, global_batch=64, num_heads=32, kv_heads=32,
        fsdp_precision=precision,
    )
    base.update(over)
    return ModelSpec(**base)


class TestPlannerFsdpSplit:
    PLAN = MeshPlan(data=2, fsdp=4)

    def test_bf16_reproduces_the_historical_formula(self):
        spec = _dense_spec("bf16")
        fsdp = predicted_collective_bytes(self.PLAN, spec)["fsdp"]
        shard = spec.param_count * spec.param_bytes
        assert fsdp == pytest.approx(3 * shard * 3 / 4)

    def test_fp8_byte_ratio_pinned_to_the_one_formula(self):
        """gather legs at 1 + 4/block bytes/elem, the reduce-scatter
        at param bytes: ratio = (2*wire + param) / (3*param). The
        pricing, the audit comparison and the bench wire-bytes ratio
        all read this formula — they cannot drift apart."""
        b = predicted_collective_bytes(self.PLAN, _dense_spec())["fsdp"]
        q = predicted_collective_bytes(
            self.PLAN, _dense_spec("fp8"))["fsdp"]
        wire = 1.0 + 4.0 / 32
        assert q / b == pytest.approx((2 * wire + 2.0) / (3 * 2.0))

    def test_qdq_prices_at_the_full_precision_wire(self):
        b = predicted_collective_bytes(self.PLAN, _dense_spec())["fsdp"]
        r = predicted_collective_bytes(
            self.PLAN, _dense_spec("fp8_qdq"))["fsdp"]
        assert r == b  # the oracle never wins on bytes it does not save

    def test_breakdown_twins_quantized_leq_bf16_both_directions(self):
        s_b = estimate(self.PLAN, _dense_spec("bf16"))
        s_q = estimate(self.PLAN, _dense_spec("fp8"))
        for s in (s_b, s_q):
            for key in ("fsdp_gather_s", "fsdp_gather_serial_s",
                        "fsdp_scatter_s", "fsdp_comm_bf16_s",
                        "fsdp_comm_bf16_serial_s"):
                assert key in s.breakdown
        # at bf16 the twins collapse
        assert s_b.breakdown["fsdp_comm_s"] == pytest.approx(
            s_b.breakdown["fsdp_comm_bf16_s"])
        # quantized: cheaper than its own bf16 twin, twin equals the
        # bf16 program's actual cost (both directions of the pin)
        assert (s_q.breakdown["fsdp_comm_s"]
                < s_q.breakdown["fsdp_comm_bf16_s"])
        assert s_q.breakdown["fsdp_comm_bf16_s"] == pytest.approx(
            s_b.breakdown["fsdp_comm_s"])
        # the scatter leg is precision-invariant (GSPMD ships the
        # param dtype regardless)
        assert s_q.breakdown["fsdp_scatter_s"] == pytest.approx(
            s_b.breakdown["fsdp_scatter_s"])

    def test_prefetch_overlap_composes_with_the_quantized_gather(self):
        s = estimate(self.PLAN, _dense_spec("fp8", fsdp_prefetch=True))
        b = s.breakdown
        assert b["fsdp_gather_s"] < b["fsdp_gather_serial_s"]
        # the reduce-scatter has nothing later to hide under
        assert b["fsdp_comm_s"] == pytest.approx(
            b["fsdp_gather_s"] + b["fsdp_scatter_s"])

    def test_audit_fires_on_perturbed_predictions_both_directions(self):
        """The PR 2-style regression pin: a cost term drifting 1000x in
        EITHER direction must fail the G106 audit loudly."""
        from dlrover_tpu.analysis.graph_lint import collective_audit

        fsdp = predicted_collective_bytes(
            self.PLAN, _dense_spec("fp8"))["fsdp"]
        assert collective_audit(fsdp, fsdp) == []
        over = collective_audit(fsdp * 1000.0, fsdp)
        under = collective_audit(fsdp / 1000.0, fsdp)
        assert over and over[0].rule_id == "G106"
        assert "does not price" in over[0].message
        assert under and under[0].rule_id == "G106"
        assert "overprices" in under[0].message


# -- compiled wire bytes + G106 clean on the quantized program ----------------


class TestFsdpWireBytesAndLint:
    @pytest.mark.slow  # PR 13 triage: a second copy of a lint-compile
    # test — the G106 audit machinery stays tier-1 via test_lint_clean
    # + test_analysis, and the dtype-aware fsdp byte formula stays
    # tier-1 via the planner perturbation/ratio pins in this file
    def test_quantized_program_audits_clean_with_shrunk_gathers(self):
        """The acceptance pin: G106 audits the fp8 dense program's
        collective bytes against the dtype-aware prediction within the
        existing tolerance AND the compiled all-gather bytes come out
        well under the bf16 twin's — the shrink is verified on the
        COMPILED HLO, not asserted from the formula. (On the CPU
        backend the e4m3 transport legalizes to f16, so the measured
        ratio lands near 0.5x rather than the true-fp8 0.28x — the
        documented PR 11 caveat, docs/parallelism.md.)"""
        from dlrover_tpu.analysis.graph_lint import lint_train_step

        rep_q = lint_train_step(
            _dense_cfg(fsdp_precision="fp8",
                       param_dtype=jnp.bfloat16,
                       compute_dtype=jnp.bfloat16),
            label="llama_tiny[fsdp,fp8]",
        )
        assert rep_q.findings == [], [
            f.render() for f in rep_q.findings]
        rep_b = lint_train_step(
            _dense_cfg(fsdp_precision="bf16",
                       param_dtype=jnp.bfloat16,
                       compute_dtype=jnp.bfloat16),
            label="llama_tiny[fsdp,bf16]",
        )
        assert rep_b.findings == [], [
            f.render() for f in rep_b.findings]
        ag_q = rep_q.measured_bytes.get("all-gather", 0)
        ag_b = rep_b.measured_bytes.get("all-gather", 0)
        assert ag_q > 0 and ag_b > 0
        assert ag_q < ag_b, (ag_q, ag_b)
        # and the prediction the audit compared against used the
        # dtype-aware split
        assert rep_q.predicted_bytes["fsdp"] \
            < rep_b.predicted_bytes["fsdp"]


# -- error feedback: the telescoping contract ---------------------------------


class TestErrorFeedbackTelescoping:
    def test_residual_is_exactly_the_quantization_error(self):
        g = jnp.asarray(
            np.random.RandomState(0).randn(8, 64).astype(np.float32))
        r = jnp.zeros_like(g)
        gq, nr = error_feedback_qdq(g, r)
        np.testing.assert_array_equal(
            np.asarray(gq) + np.asarray(nr), np.asarray(g))

    def test_cumulative_error_telescopes_vs_accumulating(self):
        """The EF identity: sum(applied) = sum(raw) - final_residual,
        so the cumulative applied-gradient error stays bounded by ONE
        quantization error — while quantize-without-feedback applies
        the same biased rounding every step and its cumulative error
        grows LINEARLY. Pinned on a constant gradient whose qdq error
        is nonzero by construction."""
        rng = np.random.RandomState(1)
        g = jnp.asarray(rng.randn(4, 64).astype(np.float32) * 1e-2)
        per_step_err = float(jnp.max(jnp.abs(qdq(g).astype(g.dtype) - g)))
        assert per_step_err > 0  # the constant g must actually round
        steps = 64
        r = jnp.zeros_like(g)
        applied_fb = jnp.zeros_like(g)
        applied_nofb = jnp.zeros_like(g)
        for _ in range(steps):
            gq, r = error_feedback_qdq(g, r)
            applied_fb = applied_fb + gq
            gq_n, _ = error_feedback_qdq(g, jnp.zeros_like(g),
                                         feedback=False)
            applied_nofb = applied_nofb + gq_n
        raw_sum = np.asarray(g) * steps
        err_fb = np.abs(np.asarray(applied_fb) - raw_sum).max()
        err_nofb = np.abs(np.asarray(applied_nofb) - raw_sum).max()
        # telescoped: the cumulative error IS the final residual (up
        # to f32 summation order across the 64 accumulated steps)
        np.testing.assert_allclose(
            err_fb, np.abs(np.asarray(r)).max(), rtol=1e-2)
        # bounded by ~one step's error vs ~steps * error
        assert err_fb <= 4 * per_step_err
        assert err_nofb > 8 * err_fb

    def test_no_feedback_mode_drops_the_error(self):
        g = jnp.asarray(
            np.random.RandomState(2).randn(2, 32).astype(np.float32))
        r = jnp.full_like(g, 0.5)
        gq, nr = error_feedback_qdq(g, r, feedback=False)
        assert float(jnp.abs(nr).max()) == 0.0
        # and the raw g (not g + r) was quantized
        gq_ref, _ = error_feedback_qdq(g, jnp.zeros_like(g))
        np.testing.assert_array_equal(np.asarray(gq), np.asarray(gq_ref))


class TestGradWireModelLevel:
    def _run(self, gp, steps=24, lr=1e-3):
        cfg = llama.llama_tiny(num_layers=2)
        batch = _probe_batch(cfg, rows=4)
        result = accelerate(
            llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
            optax.sgd(lr), batch,
            strategy=Strategy(mesh=MeshPlan(data=1), rule_set="llama"),
            devices=jax.devices()[:1],
            grad_precision=gp,
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        sharded = result.shard_batch(batch)
        losses = []
        for _ in range(steps):
            state, m = result.train_step(state, sharded,
                                         jax.random.PRNGKey(7))
            losses.append(float(m["loss"]))
        return np.array(losses), state

    # budget triage (PR 16): the error-feedback contract stays pinned
    # tier-1 by the residual-telescoping units and the G109 grad-family
    # ratchet; the model-level trajectory comparison rides slow
    @pytest.mark.slow
    def test_loss_trajectory_bounded_and_tighter_than_no_feedback(self):
        """The acceptance pin: over N repeated-batch SGD steps in the
        linear regime, the fp8-EF loss trajectory stays bounded
        against bf16 AND strictly tighter than quantize-without-
        feedback (whose biased rounding compounds step over step)."""
        l_bf, state_b = self._run("bf16")
        l_fp8, state = self._run("fp8")
        l_nofb, _ = self._run("fp8_nofb")
        dev_fb = np.abs(l_fp8 - l_bf).max()
        dev_nofb = np.abs(l_nofb - l_bf).max()
        assert dev_fb < 1e-3, (dev_fb, dev_nofb)
        assert dev_fb < dev_nofb, (dev_fb, dev_nofb)
        # the residual is live state by the end of the run — and only
        # when the quantized path carries it (bf16 stays structurally
        # unchanged), mirroring the param tree leaf-for-leaf
        assert state_b.wire_residual is None
        assert state.wire_residual is not None
        assert float(optax.global_norm(state.wire_residual)) > 0
        assert (jax.tree_util.tree_structure(state.wire_residual)
                == jax.tree_util.tree_structure(state.params))


# -- the residual rides the state machinery -----------------------------------


def _dense_trainer(grad_precision="bf16", fsdp_precision="bf16",
                   n_layers=2, mesh=None, **kwargs):
    cfg = llama.llama_tiny(num_layers=n_layers)
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
        strategy=Strategy(mesh=mesh or MeshPlan(data=2, fsdp=2,
                                                tensor=2),
                          rule_set="llama"),
        fsdp_precision=fsdp_precision,
        grad_precision=grad_precision,
        model_spec=model_spec_from_llama(
            llama.llama_tiny(num_layers=n_layers,
                             fsdp_precision=fsdp_precision or "bf16"),
            8),
        **kwargs,
    )
    return trainer, batch


class TestResidualRidesStateMachinery:
    def test_checkpoint_save_restore_preserves_the_residual(
            self, tmp_path):
        """The residual is training state proper: a save→restore round
        trip through the elastic checkpoint manager reproduces it
        bit-for-bit (losing it would re-apply the compressed error the
        feedback already accounted for)."""
        trainer, batch = _dense_trainer(grad_precision="fp8",
                                        ckpt_dir=str(tmp_path))
        state = trainer.prepare()
        for _ in range(3):
            state, _ = trainer.step(state, batch)
        trainer.save(state, force=True)
        trainer.finalize()
        res_before = jax.device_get(state.wire_residual)
        assert float(optax.global_norm(res_before)) > 0

        trainer2, _ = _dense_trainer(grad_precision="fp8",
                                     ckpt_dir=str(tmp_path))
        restored = trainer2.prepare()
        assert int(restored.step) == int(state.step)
        assert _trees_bitwise(
            jax.device_get(restored.wire_residual), res_before)
        trainer2.finalize()

    def test_live_reshard_8_to_4_reshards_the_residual(self):
        """The acceptance pin: an 8→4 live reshard carries the
        residual through HostSnapshot and device_puts it against the
        survivor world's shardings — values identical, training
        resumes, and the residual keeps evolving."""
        trainer, batch = _dense_trainer(grad_precision="fp8")
        state = trainer.prepare()
        for _ in range(2):
            state, _ = trainer.step(state, batch)
        res_before = jax.device_get(state.wire_residual)
        assert float(optax.global_norm(res_before)) > 0

        state = trainer.live_reshard(state, devices=jax.devices()[:4])
        assert trainer.accelerated.mesh.devices.size == 4
        assert _trees_bitwise(
            jax.device_get(state.wire_residual), res_before)
        # the resharded residual is consistent with the new sharding:
        # another step runs and updates it
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])
        res_after = jax.device_get(state.wire_residual)
        assert not _trees_bitwise(res_after, res_before)


# -- live retune through the program cache ------------------------------------


class TestRetuneFsdpPrecisionZeroRecompile:
    @pytest.mark.slow  # PR 13 triage: the per-knob retune gate — the
    # prewarm/retune/program-cache mechanics stay tier-1 via PR 7's
    # test_optimizer e2e wedges and the serving retune/resize gates
    # (tests/test_serving.py); the fsdp-specific key identity stays
    # tier-1 below (test_program_key_carries_both_precisions)
    def test_prewarmed_fsdp_retune_swaps_with_zero_recompiles(self):
        """The tier-1 live-apply gate (the PR 11 pattern): retune()
        across dense-wire precisions through the program cache — a
        prewarmed fp8 wire applies with ZERO recompiles, and retuning
        BACK hits the original program."""
        trainer, batch = _dense_trainer()
        state = trainer.prepare()
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])
        assert trainer.fsdp_precision == "bf16"

        compiled = trainer.prewarm(fsdp_precision="fp8")
        assert compiled  # fp8 is a new program
        assert trainer.fsdp_precision == "bf16"  # prewarm must not switch
        assert get_context().fsdp_precision == "bf16"

        before = trainer.compile_count
        state = trainer.retune(state, fsdp_precision="fp8")
        assert trainer.compile_count == before  # ZERO recompiles
        assert trainer.fsdp_precision == "fp8"
        assert get_context().fsdp_precision == "fp8"  # trace knob pinned
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])

        # back to bf16: the startup program is still in the cache
        before = trainer.compile_count
        state = trainer.retune(state, fsdp_precision="bf16")
        assert trainer.compile_count == before
        assert trainer.fsdp_precision == "bf16"
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])

    def test_program_key_carries_both_precisions(self):
        trainer, _ = _dense_trainer(grad_precision="fp8")
        strategy = trainer._resolved_strategy(8)
        k_q = trainer._program_key(jax.devices(), strategy)
        assert "|fp=bf16" in k_q and "|gp=fp8" in k_q
        trainer.fsdp_precision = "fp8"
        k_fp = trainer._program_key(jax.devices(), strategy)
        assert "|fp=fp8" in k_fp and k_fp != k_q


# -- optimizer: the fsdp_precision knob family --------------------------------


class _Store:
    def __init__(self):
        self._s = {}

    def node_ids(self):
        return list(self._s)

    def latest(self, nid):
        return self._s.get(nid)


class _Snap:
    def __init__(self, step_p50):
        import time

        self.ts = time.time()
        self.step_p50 = step_p50
        self.dispatch_p50 = None
        self.exposed_comm_frac = None
        self.input_wait_frac = None


def _dense_model_info():
    """A gather-bound dense shape: at data=2 x fsdp=32 the per-step
    param traffic dominates, so the fp8 dense wire wins the ranking
    honestly."""
    return comm.ModelInfo(
        num_params=70_000_000_000, hidden_size=8192, num_layers=80,
        seq_len=2048,
    )


def _dense_running_report(fsdp_precision="bf16"):
    return comm.TrainerConfigReport(
        node_id=0, world=64, mesh_shape={"data": 2, "fsdp": 32},
        train_window=4,
        fsdp_precision=fsdp_precision, global_batch=64,
    )


class TestOptimizerFsdpKnob:
    def _opt(self, store, published):
        from dlrover_tpu.master.optimizer import RuntimeOptimizer

        return RuntimeOptimizer(
            store, publish=published.append, mesh_candidates=False,
            device=DeviceSpec(hbm_bytes=95e9), min_speedup=1.02,
        )

    def test_family_parked_until_the_worker_reports_the_knob(self):
        store = _Store()
        store._s[0] = _Snap(16.6)
        opt = self._opt(store, [])
        opt.update_model_info(_dense_model_info())
        opt.update_running_config(comm.TrainerConfigReport(
            node_id=0, world=64, mesh_shape={"data": 2, "fsdp": 32},
            train_window=4, global_batch=64,
        ))  # no fsdp_precision reported
        *_, fsdp_opts = opt._knob_options(opt._running)
        assert fsdp_opts == ["bf16"]  # parked
        opt.update_running_config(_dense_running_report())
        *_, fsdp_opts = opt._knob_options(opt._running)
        assert fsdp_opts == ["bf16", "fp8"]

    def test_replan_chooses_and_publishes_an_fsdp_plan(self):
        """Gather-bound dense spec → the fp8 dense wire wins; unchanged
        knobs publish as sentinels so the worker can tell a pure wire
        swap from a mesh/K change."""
        store = _Store()
        store._s[0] = _Snap(16.6)
        published = []
        opt = self._opt(store, published)
        opt.update_model_info(_dense_model_info())
        opt.update_running_config(_dense_running_report())
        d = opt.replan("test")
        assert d.outcome == "chosen", d.to_dict()
        assert d.chosen["fsdp_precision"] == "fp8"
        cfg = published[0]
        assert cfg.fsdp_precision == "fp8"
        assert cfg.train_window == -1  # sentinel: unchanged
        assert cfg.mesh_shape is None
        assert cfg.moe_precision == ""

    def test_candidate_key_carries_the_knob(self):
        from dlrover_tpu.master.optimizer.runtime_optimizer import (
            CandidateScore,
        )

        a = CandidateScore(mesh=MeshPlan(data=2, fsdp=32),
                           train_window=4,
                           moe_dispatch="", fsdp_precision="bf16")
        b = CandidateScore(mesh=MeshPlan(data=2, fsdp=32),
                           train_window=4,
                           moe_dispatch="", fsdp_precision="fp8")
        assert a.key != b.key
        assert "|fp=fp8" in b.key

    def test_failed_apply_blacklists_the_fsdp_tuple(self):
        store = _Store()
        store._s[0] = _Snap(16.6)
        opt = self._opt(store, [])
        opt.update_model_info(_dense_model_info())
        opt.update_running_config(_dense_running_report())
        d = opt.replan("test")
        assert d.outcome == "chosen"
        key = d.chosen_key
        assert "|fp=fp8" in key
        opt.update_running_config(comm.TrainerConfigReport(
            node_id=0, world=64, mesh_shape={"data": 2, "fsdp": 32},
            train_window=4,
            fsdp_precision="bf16", global_batch=64,
            plan_id=d.plan_id, apply_failed=True,
        ))
        assert key in opt._failed_keys
        d2 = opt.replan("retry")
        if d2 is not None and d2.outcome == "chosen":
            assert d2.chosen_key != key


class TestPlanHookRoutesFsdpPrecision:
    def test_fsdp_plan_reaches_request_retune(self):
        from dlrover_tpu.trainer.executor import OptimizerPlanHook

        class _Ex:
            def __init__(self):
                self.retunes = []

            def request_retune(self, **kw):
                self.retunes.append(kw)

        class _Client:
            def get_parallel_config(self):
                return comm.ParallelConfig(
                    fsdp_precision="fp8", plan_id="plan-fp",
                    trace_id="inc-fp", predicted_speedup=1.3)

        hook = OptimizerPlanHook(_Client(), poll_secs=0)
        ex = _Ex()
        hook._executor = ex
        hook.poll_once()
        assert ex.retunes[0]["fsdp_precision"] == "fp8"
        assert ex.retunes[0]["moe_precision"] is None
        assert ex.retunes[0]["train_window"] is None
        assert ex.retunes[0]["plan_id"] == "plan-fp"


class TestExecutorNacksUnsupportedFsdpPlan:
    def test_probe_degraded_plan_is_negative_acked(self):
        """A backend whose fp8 probe fails must NOT ack an fp8 plan it
        silently runs as bf16 — the phantom apply would be re-chosen
        after every trigger, each cycle paying a futile drain."""
        from dlrover_tpu.trainer.executor import TrainExecutor

        class _Trainer:
            fsdp_precision = "bf16"
            moe_precision = "bf16"
            dispatch_chunks = 1

            @staticmethod
            def _effective_precision(p):
                return "bf16"  # the probe failed: everything degrades

            class accelerated:  # noqa: N801 - attribute stand-in
                pass

        ex = TrainExecutor.__new__(TrainExecutor)
        ex._trainer = _Trainer()
        acks = []
        ex._report_trainer_config = (
            lambda **kw: acks.append(kw))
        ex._apply_plan_scoped({"fsdp_precision": "fp8",
                               "plan_id": "plan-x"}, "plan-x")
        assert acks and acks[0]["apply_failed"] is True
        assert acks[0]["plan_id"] == "plan-x"


# -- G109 per-family drift entries --------------------------------------------


class TestG109Families:
    def test_fsdp_family_clean_against_the_committed_baseline(self):
        from dlrover_tpu.analysis.graph_lint import (
            quantization_drift_audit,
        )

        rep = quantization_drift_audit(family="fsdp")
        assert rep.label.startswith("llama_tiny[fsdp,fp8]@")
        assert rep.findings == [], [f.render() for f in rep.findings]

    def test_grad_family_clean_against_the_committed_baseline(self):
        from dlrover_tpu.analysis.graph_lint import (
            quantization_drift_audit,
        )

        rep = quantization_drift_audit(family="grad")
        assert rep.label.startswith("llama_tiny[grad,fp8]@")
        assert rep.findings == [], [f.render() for f in rep.findings]

    def test_each_family_fires_independently(self):
        """A regressed family fails against ITS OWN ratchet — the
        entries are per family, so a dense-wire regression cannot hide
        under the MoE family's baseline (and vice versa)."""
        import json

        from dlrover_tpu.analysis.graph_lint import (
            check_quantization_drift,
            quantization_drift_baseline_path,
        )

        with open(quantization_drift_baseline_path()) as fh:
            entries = json.load(fh)["entries"]
        for fam_label in ("llama_tiny[fsdp,fp8]@cpu",
                          "llama_tiny[grad,fp8]@cpu",
                          "llama_tiny_moe[grouped_ep,fp8]@cpu"):
            assert fam_label in entries, entries.keys()
            base = entries[fam_label]["drift"]
            assert check_quantization_drift(base, base) == []  # clean
            fired = check_quantization_drift(
                max(base * 100, 1e-2), base)
            assert fired and fired[0].rule_id == "G109"

    def test_unknown_family_raises(self):
        from dlrover_tpu.analysis.graph_lint import (
            measure_quantization_drift,
        )

        with pytest.raises(ValueError, match="drift family"):
            measure_quantization_drift(family="int4")


# -- the e2e replan wedge + bench wedge (slow-marked per the triage) ----------


@pytest.mark.slow
class TestFsdpReplanWedge:
    """Slow-marked (~90 s): the full master→RPC→live-apply loop is
    tier-1-covered by PR 7's e2e wedges (test_optimizer) and the
    dense-wire guarantees by TestRetuneFsdpPrecisionZeroRecompile +
    the optimizer/plan-hook unit tests above — the tier-1 budget on
    this 1-core box (870 s for the whole suite) cannot carry another
    ~90 s wedge per knob family."""

    def test_optimizer_selects_fp8_and_worker_applies_live(
            self, tmp_path, monkeypatch):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.local_master import start_local_master
        from dlrover_tpu.telemetry import EventKind, read_events
        from dlrover_tpu.trainer.conf import Configuration
        from dlrover_tpu.trainer.executor import (
            NodeRuntimeReportHook,
            OptimizerPlanHook,
            TrainExecutor,
            TrainHook,
        )

        events_path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_path)
        ctx = get_context()
        monkeypatch.setattr(ctx, "replan_min_speedup", 1.02)
        # the live apply pins the chosen knobs into the Context (the
        # trace-time contract) — register restores so they don't leak
        # into later tests' trace-time resolution
        monkeypatch.setattr(ctx, "fsdp_precision", ctx.fsdp_precision)
        monkeypatch.setattr(ctx, "dispatch_chunks", ctx.dispatch_chunks)
        monkeypatch.setattr(ctx, "moe_precision", ctx.moe_precision)
        master = start_local_master()
        opt = master.servicer.runtime_optimizer
        opt._mesh_candidates = False
        opt._device = DeviceSpec(hbm_bytes=95e9)
        try:
            client = MasterClient(master.addr, node_id=0)
            # gather-bound dense shape that still fits the memory gate
            # (at data=2 x fsdp=4 the fsdp term dominates the step)
            client.report_model_info(comm.ModelInfo(
                num_params=8_000_000_000, hidden_size=8192,
                num_layers=32, seq_len=2048,
            ))
            trainer, batch = _dense_trainer(
                n_layers=4, mesh=MeshPlan(data=2, fsdp=4))
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
            chosen = [d for d in decisions if d["outcome"] == "chosen"]
            assert chosen, decisions
            d = chosen[-1]
            assert d["chosen"]["fsdp_precision"] == "fp8"
            assert d["applied"], d
            assert trainer.fsdp_precision == "fp8"
            done = [r for r in read_events(events_path)
                    if r.get("kind") == EventKind.OPTIMIZER_APPLY_DONE
                    and r.get("plan_id") == d["plan_id"]]
            assert done and done[-1]["recompiled"] == 0, done
            assert done[-1]["fsdp_precision"] == "fp8"
        finally:
            master.stop()
