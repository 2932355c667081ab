"""Low-precision everything (ISSUE 11): block-scaled fp8 MoE dispatch +
wire-compressed collectives, priced by the planner and audited by the
lint.

Pins, per the acceptance criteria:

  * fp8 ``grouped_ep`` matches the quantize→dequant reference oracle
    ("fp8_qdq" — identical math, full-precision wire) EXACTLY fwd+bwd
    on the 4-way CPU mesh, ``dropped_frac == 0``, zero recompiles;
  * ``grouped_matmul_quantized`` (dequant-in-kernel) is bitwise equal
    to dequantize-then-``grouped_matmul``, forward and dw;
  * quantize/dequant round-trip properties: block-scale shapes, zero
    blocks, denormals, error bounds;
  * the precision knob resolves config > Context(env) > default, keys
    the program cache, prewarm+retunes with ZERO recompiles, and the
    optimizer's candidate key / churn / blacklist carry it;
  * ``planner.estimate`` carries ``moe_disp_comm_bf16_s`` twins with
    quantized <= bf16 pinned both directions, and
    ``predicted_collective_bytes`` matches the wire-bytes formula the
    G106 audit is compared against;
  * the e2e replan wedge: the optimizer prices the precision family,
    chooses fp8 for a comm-bound MoE job, and the worker applies it
    live through the prewarmed program cache with zero recompiles;
  * G109 fires on a drifting fixture and is clean on HEAD against the
    committed ``quant_baseline.json``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.common.config import get_context
from dlrover_tpu.models import llama
from dlrover_tpu.ops.moe import (
    MoEConfig,
    init_moe_params,
    moe_ffn,
    resolve_moe_precision,
)
from dlrover_tpu.ops.quantize import (
    FP8_MAX,
    dequantize_block_scaled,
    quantize_block_scaled,
    resolve_quant_block,
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


# -- quantize/dequant round-trip properties -----------------------------------


class TestQuantizeRoundTrip:
    def test_block_scale_shapes(self):
        x = jnp.asarray(np.random.RandomState(0).randn(5, 7, 64),
                        jnp.float32)
        v, s = quantize_block_scaled(x)
        assert v.shape == x.shape and v.dtype == jnp.float8_e4m3fn
        assert s.shape == (5, 7, 64 // resolve_quant_block(64))
        assert s.dtype == jnp.float32

    def test_resolve_quant_block_divides(self):
        assert resolve_quant_block(64) == 32
        assert resolve_quant_block(16) == 16
        assert resolve_quant_block(48) == 24  # largest divisor <= 32
        assert resolve_quant_block(7) == 7
        assert 96 % resolve_quant_block(96) == 0

    def test_indivisible_block_raises(self):
        x = jnp.zeros((2, 10), jnp.float32)
        with pytest.raises(ValueError, match="does not divide"):
            quantize_block_scaled(x, block=4)  # 10 % 4 != 0

    def test_zero_blocks_encode_to_exact_zeros(self):
        """An all-zero block must not divide by zero: the scale clamps
        to 1.0 and the rows decode to exact zeros — the property the
        dispatch's zero-sentinel pad rows rely on."""
        x = jnp.zeros((4, 64), jnp.float32)
        v, s = quantize_block_scaled(x)
        assert np.all(np.asarray(s) == 1.0)
        assert np.all(np.asarray(dequantize_block_scaled(v, s)) == 0.0)

    def test_denormal_blocks_rescale_into_range(self):
        """Values far below e4m3's smallest normal up-scale into range
        (scale = amax/448): a uniform tiny block round-trips exactly
        (its max lands on the representable 448), random tiny blocks
        keep e4m3 relative precision instead of flushing to zero."""
        tiny = jnp.full((2, 64), 1e-20, jnp.float32)
        v, s = quantize_block_scaled(tiny)
        np.testing.assert_array_equal(
            np.asarray(dequantize_block_scaled(v, s)), np.asarray(tiny))
        rnd = jnp.asarray(
            np.random.RandomState(0).randn(4, 64) * 1e-18, jnp.float32)
        back = np.asarray(dequantize_block_scaled(
            *quantize_block_scaled(rnd)))
        assert np.all(back[np.asarray(rnd) != 0] != 0)

    def test_deep_denormal_scale_floors_instead_of_minting_nan(self):
        """A block whose max is nonzero but so small that amax/448
        underflows must NOT divide by a flushed-to-zero scale (inf ->
        NaN in e4m3): the scale floors at the smallest normal f32 and
        the block encodes to finite values (zeros — below fp8's
        resolution). Guards the flush-to-zero (TPU) backend contract."""
        x = jnp.full((2, 64), 1e-43, jnp.float32)  # subnormal f32
        v, s = quantize_block_scaled(x)
        assert np.all(np.asarray(s) >= np.finfo(np.float32).tiny)
        back = np.asarray(dequantize_block_scaled(v, s))
        assert np.all(np.isfinite(back))

    def test_error_bound_relative_to_block_max(self):
        """The block-scaled contract: every element's round-trip error
        is bounded by its BLOCK's max (e4m3's 3 mantissa bits: half an
        ulp at the top of the range = amax * 2^-4) — per-element
        relative error is unbounded for tiny values sharing a block
        with a large one, which is exactly the trade the 32-channel
        neighborhood keeps local."""
        x = np.random.RandomState(1).randn(64, 64).astype(np.float32) * 10
        v, s = quantize_block_scaled(jnp.asarray(x))
        back = np.asarray(dequantize_block_scaled(v, s))
        amax = np.abs(x.reshape(64, 2, 32)).max(axis=-1)  # per block
        err = np.abs(back - x).reshape(64, 2, 32)
        assert np.all(err <= amax[:, :, None] * 2.0 ** -4 + 1e-7)
        # and the block max is representable at the top of the range
        assert float(jnp.max(jnp.abs(v.astype(jnp.float32)))) \
            == pytest.approx(FP8_MAX)


# -- the dequant-in-kernel grouped matmul -------------------------------------


class TestGroupedMatmulQuantized:
    def _case(self):
        from dlrover_tpu.ops.grouped_matmul import (
            grouped_matmul,
            grouped_matmul_quantized,
        )

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(256, 64), jnp.float32)
        w = jnp.asarray(rng.randn(4, 64, 96), jnp.float32)
        te = jnp.asarray([0, 1, 2, 3], jnp.int32)  # block_t=64 tiles
        v, s = quantize_block_scaled(x)
        xd = dequantize_block_scaled(v, s)
        return grouped_matmul, grouped_matmul_quantized, v, s, xd, w, te

    def test_fwd_bitwise_equals_dequant_reference(self):
        """The oracle contract: dequant IN KERNEL == dequant outside
        then the plain kernel, bit for bit (the multiply runs in f32 at
        the same point of the computation either way)."""
        gm, gmq, v, s, xd, w, te = self._case()
        y_ref = gm(xd, w, te, 64, 512, True)
        y_q = gmq(v, s, w, te, 64, 512, True)
        assert np.asarray(y_q).tobytes() == np.asarray(y_ref).tobytes()

    def test_dw_bitwise_equals_dequant_reference(self):
        gm, gmq, v, s, xd, w, te = self._case()
        g_ref = jax.grad(
            lambda w_: (gm(xd, w_, te, 64, 512, True) ** 2).sum())(w)
        g_q = jax.grad(
            lambda w_: (gmq(v, s, w_, te, 64, 512, True) ** 2).sum())(w)
        assert np.asarray(g_q).tobytes() == np.asarray(g_ref).tobytes()


# -- fp8 grouped_ep vs the quantize→dequant oracle (4-way CPU mesh) -----------


class TestFp8GroupedEp:
    E = 8
    P = 4  # the 4-way expert submesh the acceptance names

    def _mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:self.P]), ("expert",))

    def _params_x(self, d=16, f=32, b=2, s=16):
        rng = np.random.RandomState(0)
        params = init_moe_params(jax.random.PRNGKey(0), d, f, self.E)
        x = jnp.asarray(rng.randn(b, s, d), jnp.float32)
        return params, x

    def _cfg(self, precision, chunks=1):
        return MoEConfig(num_experts=self.E, top_k=2,
                         dispatch="grouped_ep", ep_axes=("expert",),
                         mesh=self._mesh(), dispatch_chunks=chunks,
                         precision=precision)

    def _grad_fn(self, cfg):
        def loss(p, x):
            o, a, m = moe_ffn(p, x, cfg, train=False)
            return (o.astype(jnp.float32) ** 2).sum() + a, m

        # jit: interpret-mode kernels trace once instead of re-running
        # op by op (the PR 10 lesson)
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))

    def test_fp8_matches_qdq_oracle_bitwise_fwd_bwd(self):
        """The acceptance pin: the fp8 wire (quantized exchange,
        dequant-in-kernel, quantized backward cotangents) is BITWISE
        equal to the quantize→dequant reference with a full-precision
        wire — fwd and bwd, at C in {1, 2} — and nothing is dropped.
        Quantization commutes with the row permutation; any deviation
        means the wire changed the math."""
        params, x = self._params_x()
        for chunks in (1, 2):
            (l_q, m_q), g_q = self._grad_fn(
                self._cfg("fp8", chunks))(params, x)
            (l_r, _), g_r = self._grad_fn(
                self._cfg("fp8_qdq", chunks))(params, x)
            assert float(l_q) == float(l_r), f"loss differs at C={chunks}"
            assert float(m_q["dropped_frac"]) == 0.0
            for a, b in zip(jax.tree.leaves(g_q), jax.tree.leaves(g_r)):
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), \
                    f"grad differs at C={chunks}"
        # the wire is the smaller one in the COMPILED program too, by
        # the counter the G106 audit and the attribution record read:
        # fp8 ships under 0.8 of the bf16 twin's all-to-all bytes
        # (values and scales both counted; f32 rows here, so 0.56),
        # the qdq reference the bf16 twin's own
        from dlrover_tpu.analysis.graph_lint import collective_bytes_by_kind

        a2a = {
            precision: collective_bytes_by_kind(
                self._grad_fn(self._cfg(precision)).lower(params, x)
                .compile().as_text())["all-to-all"]
            for precision in ("bf16", "fp8", "fp8_qdq")}
        assert 0 < a2a["fp8"] < 0.8 * a2a["bf16"], a2a
        assert a2a["fp8_qdq"] == a2a["bf16"], a2a

    # NOTE: "fp8 stays close to bf16" is covered by the G109 drift
    # audit below (quantization_drift_audit measures exactly that on
    # the llama twin pair) — no separate micro test, the tier-1 budget
    # is a first-class constraint on this 1-core box.

    def test_zero_recompiles_across_steps_fp8(self):
        params, x0 = self._params_x()
        cfg = self._cfg("fp8", chunks=2)

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

    def test_probe_failure_degrades_to_bf16(self, monkeypatch):
        from dlrover_tpu.ops import shard_compat

        monkeypatch.setattr(shard_compat, "_FP8_WIRE_SUPPORTED", False)
        assert resolve_moe_precision(
            MoEConfig(num_experts=4, precision="fp8")) == "bf16"


# -- knob resolution order: config > env(Context) > default -------------------


class TestPrecisionKnobResolution:
    def test_explicit_config_wins(self, monkeypatch):
        monkeypatch.setattr(get_context(), "moe_precision", "bf16")
        assert resolve_moe_precision(
            MoEConfig(num_experts=4, precision="fp8")) == "fp8"

    def test_empty_config_resolves_context(self, monkeypatch):
        monkeypatch.setattr(get_context(), "moe_precision", "fp8")
        assert resolve_moe_precision(MoEConfig(num_experts=4)) == "fp8"

    def test_default_is_bf16(self, monkeypatch):
        monkeypatch.setattr(get_context(), "moe_precision", "bf16")
        assert resolve_moe_precision(MoEConfig(num_experts=4)) == "bf16"

    def test_unknown_precision_raises(self):
        with pytest.raises(ValueError, match="unknown MoE precision"):
            resolve_moe_precision(
                MoEConfig(num_experts=4, precision="int3"))

    def test_llama_spec_resolves_context_precision(self, monkeypatch):
        cfg = llama.llama_tiny(num_experts=8,
                               moe_dispatch="grouped_ep")
        monkeypatch.setattr(get_context(), "moe_precision", "fp8")
        assert model_spec_from_llama(cfg, 8).moe_precision == "fp8"
        cfg2 = llama.llama_tiny(num_experts=8,
                                moe_dispatch="grouped_ep",
                                moe_precision="bf16")
        assert model_spec_from_llama(cfg2, 8).moe_precision == "bf16"


# -- planner: dtype-aware bytes + breakdown twins -----------------------------


def _moe_spec(precision="bf16", **over):
    base = dict(
        param_count=25_000_000_000, num_layers=32, hidden_size=4096,
        seq_len=8192, global_batch=64, num_experts=64, moe_top_k=2,
        moe_dispatch="grouped_ep", moe_precision=precision,
    )
    base.update(over)
    return ModelSpec(**base)


class TestPlannerPrecision:
    DEV = DeviceSpec(hbm_bytes=95e9)
    MESH = MeshPlan(data=4, fsdp=16)

    def test_wire_bytes_formula(self):
        """The ONE formula the pricing, the audit and the bench read:
        fp8 = 1 byte of values + 4/block bytes of scale side-band per
        element; bf16 = dtype_bytes."""
        spec = _moe_spec("fp8")
        assert spec.moe_wire_bytes_per_elem() == 1.0 + 4.0 / 32.0
        assert _moe_spec("bf16").moe_wire_bytes_per_elem() == 2.0

    def test_predicted_bytes_match_the_audit_source_formula(self):
        b_bf = predicted_collective_bytes(
            self.MESH, _moe_spec("bf16"), self.DEV)
        b_q = predicted_collective_bytes(
            self.MESH, _moe_spec("fp8"), self.DEV)
        ratio = b_q["moe_dispatch"] / b_bf["moe_dispatch"]
        assert ratio == pytest.approx((1.0 + 4.0 / 32.0) / 2.0)
        # only the dispatch family changes: the other wires are
        # untouched by the MoE precision knob
        for k in ("tp", "fsdp", "dp", "seq", "pipe"):
            assert b_q[k] == b_bf[k]

    def test_breakdown_twins_and_monotonicity_both_directions(self):
        """The acceptance pin: quantized comm seconds <= bf16, checked
        both directions, with the bf16 twin invariant (it is the same
        exchange priced at the compute dtype)."""
        bf = estimate(self.MESH, _moe_spec("bf16"), self.DEV).breakdown
        q = estimate(self.MESH, _moe_spec("fp8"), self.DEV).breakdown
        assert bf["moe_disp_comm_s"] == bf["moe_disp_comm_bf16_s"]
        assert q["moe_disp_comm_s"] <= q["moe_disp_comm_bf16_s"]
        assert q["moe_disp_comm_bf16_s"] == bf["moe_disp_comm_s"]
        assert q["moe_disp_comm_s"] < bf["moe_disp_comm_s"]
        # and back: pricing the quantized spec at bf16 recovers the
        # serial figure exactly
        assert q["moe_disp_comm_bf16_serial_s"] \
            == bf["moe_disp_comm_serial_s"]

    def test_step_time_non_increasing_under_fp8(self):
        bf = estimate(self.MESH, _moe_spec("bf16"), self.DEV)
        q = estimate(self.MESH, _moe_spec("fp8"), self.DEV)
        assert q.step_time_s <= bf.step_time_s

    def test_qdq_reference_prices_its_actual_f32_wire(self):
        """The oracle exchanges DEQUANTIZED f32 rows (that is its
        point): it prices at 4 bytes/elem — never at bytes it does not
        save, so it can never win a ranking."""
        ref = _moe_spec("fp8_qdq")
        assert ref.moe_wire_bytes_per_elem() == 4.0

    def test_precision_composes_with_chunks(self):
        """The two knobs are orthogonal: chunking reshapes the exposed
        share, precision reshapes the bytes — fp8+C=4 is <= each alone."""
        both = estimate(self.MESH,
                        _moe_spec("fp8", moe_dispatch_chunks=4),
                        self.DEV).breakdown
        only_c = estimate(self.MESH,
                          _moe_spec("bf16", moe_dispatch_chunks=4),
                          self.DEV).breakdown
        only_p = estimate(self.MESH, _moe_spec("fp8"),
                          self.DEV).breakdown
        assert both["moe_disp_comm_s"] <= only_c["moe_disp_comm_s"]
        assert both["moe_disp_comm_s"] <= only_p["moe_disp_comm_s"]


# -- the optimizer's precision knob family ------------------------------------


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


def _moe_model_info():
    return comm.ModelInfo(
        num_params=25_000_000_000, hidden_size=4096, num_layers=32,
        seq_len=8192, num_experts=64, moe_top_k=2, ffn_mult=2.7,
    )


def _running_report(moe_dispatch="grouped_ep", precision="bf16"):
    return comm.TrainerConfigReport(
        node_id=0, world=64, mesh_shape={"data": 4, "fsdp": 16},
        train_window=4, moe_dispatch=moe_dispatch,
        dispatch_chunks=1, moe_precision=precision, global_batch=64,
    )


class TestOptimizerPrecisionKnob:
    def _opt(self, store, published):
        from dlrover_tpu.master.optimizer import RuntimeOptimizer

        return RuntimeOptimizer(
            store, publish=published.append, mesh_candidates=False,
            device=DeviceSpec(hbm_bytes=95e9), min_speedup=1.02,
        )

    def test_precision_family_enumerated_only_for_grouped_ep(self):
        store = _Store()
        store._s[0] = _Snap(16.6)
        opt = self._opt(store, [])
        opt.update_model_info(_moe_model_info())
        opt.update_running_config(_running_report("gather"))
        *_, precision_opts, _fsdp_opts = opt._knob_options(opt._running)
        assert precision_opts == ["bf16"]  # parked off grouped_ep
        opt.update_running_config(_running_report("grouped_ep"))
        *_, precision_opts, _fsdp_opts = opt._knob_options(opt._running)
        assert precision_opts == ["bf16", "fp8"]

    def test_replan_chooses_and_publishes_a_precision_plan(self):
        """Comm-bound grouped_ep spec → the fp8 wire wins (alone or
        composed with chunking); unchanged knobs publish as sentinels."""
        store = _Store()
        store._s[0] = _Snap(16.6)
        published = []
        opt = self._opt(store, published)
        opt.update_model_info(_moe_model_info())
        opt.update_running_config(_running_report())
        d = opt.replan("test")
        assert d.outcome == "chosen"
        assert d.chosen["moe_precision"] == "fp8"
        cfg = published[0]
        assert cfg.moe_precision == "fp8"
        assert cfg.train_window == -1  # sentinel: unchanged
        assert cfg.mesh_shape is None
        assert cfg.moe_dispatch == ""

    def test_candidate_key_carries_precision(self):
        """The cooldown/blacklist identity must distinguish precisions
        or a failed fp8 apply would blacklist the bf16 twin too."""
        from dlrover_tpu.master.optimizer.runtime_optimizer import (
            CandidateScore,
        )

        a = CandidateScore(mesh=MeshPlan(data=8),
                           train_window=4, moe_dispatch="grouped_ep",
                           moe_precision="bf16")
        b = CandidateScore(mesh=MeshPlan(data=8),
                           train_window=4, moe_dispatch="grouped_ep",
                           moe_precision="fp8")
        assert a.key != b.key
        assert "|p=fp8" in b.key

    def test_failed_apply_blacklists_the_precision_tuple(self):
        store = _Store()
        store._s[0] = _Snap(16.6)
        opt = self._opt(store, [])
        opt.update_model_info(_moe_model_info())
        opt.update_running_config(_running_report())
        d = opt.replan("test")
        assert d.outcome == "chosen"
        key = d.chosen_key
        assert "|p=fp8" in key
        opt.update_running_config(comm.TrainerConfigReport(
            node_id=0, world=64, mesh_shape={"data": 4, "fsdp": 16},
            train_window=4,
            moe_dispatch="grouped_ep", dispatch_chunks=1,
            moe_precision="bf16", global_batch=64,
            plan_id=d.plan_id, apply_failed=True,
        ))
        assert key in opt._failed_keys
        # the blacklisted tuple never re-publishes
        d2 = opt.replan("retry")
        assert d2 is None or (d2.chosen or {}).get("key") != key
        if d2 is not None and d2.outcome == "chosen":
            assert d2.chosen_key != key


# -- live apply: retune/prewarm through the program cache ---------------------


def _moe_trainer(precision="bf16", **kwargs):
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
        moe_precision=precision,
        model_spec=model_spec_from_llama(
            llama.llama_tiny(num_experts=8, moe_dispatch="grouped_ep",
                             moe_precision=precision or "bf16"), 8),
        **kwargs,
    )
    return trainer, batch


class TestRetunePrecisionZeroRecompile:
    # the ~16 s retune e2e is slow-marked per the ISSUE 12 tier-1
    # triage: the prewarm→retune→program-cache mechanics are
    # knob-agnostic and stay tier-1 via PR 7's test_optimizer e2e
    # wedges plus the newest family's gate (test_fsdp_wire
    # TestRetuneFsdpPrecisionZeroRecompile); the precision knob's OWN
    # identity keeps its cheap tier-1 pins (program key, plan-hook
    # routing) below
    @pytest.mark.slow
    def test_prewarmed_precision_retune_swaps_with_zero_recompiles(self):
        """The acceptance gate: retune() across precisions through the
        program cache — a prewarmed fp8 wire applies with ZERO
        recompiles, and retuning BACK hits the original program."""
        trainer, batch = _moe_trainer()
        state = trainer.prepare()
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])
        assert trainer.moe_precision == "bf16"

        compiled = trainer.prewarm(moe_precision="fp8")
        assert compiled  # fp8 is a new program
        assert trainer.moe_precision == "bf16"  # prewarm must not switch
        assert get_context().moe_precision == "bf16"

        before = trainer.compile_count
        state = trainer.retune(state, moe_precision="fp8")
        assert trainer.compile_count == before  # ZERO recompiles
        assert trainer.moe_precision == "fp8"
        assert get_context().moe_precision == "fp8"  # trace knob pinned
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])

        # back to bf16: the startup program is still in the cache
        before = trainer.compile_count
        state = trainer.retune(state, moe_precision="bf16")
        assert trainer.compile_count == before
        assert trainer.moe_precision == "bf16"
        state, m = trainer.step(state, batch)
        assert bool(m["finite"])

    def test_program_key_distinguishes_precisions(self):
        trainer, _ = _moe_trainer()
        strategy = trainer._resolved_strategy(8)
        k_bf = trainer._program_key(jax.devices(), strategy)
        trainer.moe_precision = "fp8"
        k_q = trainer._program_key(jax.devices(), strategy)
        assert k_bf != k_q and "|p=fp8" in k_q


class TestPlanHookRoutesPrecision:
    def test_precision_plan_reaches_request_retune(self):
        from dlrover_tpu.trainer.executor import OptimizerPlanHook

        class _Ex:
            def __init__(self):
                self.retunes = []

            def request_retune(self, **kw):
                self.retunes.append(kw)

        class _Client:
            def get_parallel_config(self):
                return comm.ParallelConfig(
                    moe_precision="fp8", plan_id="plan-p8",
                    trace_id="inc-p", predicted_speedup=1.4)

        hook = OptimizerPlanHook(_Client(), poll_secs=0)
        ex = _Ex()
        hook._executor = ex
        hook.poll_once()
        assert ex.retunes[0]["moe_precision"] == "fp8"
        assert ex.retunes[0]["train_window"] is None
        assert ex.retunes[0]["dispatch_chunks"] is None
        assert ex.retunes[0]["plan_id"] == "plan-p8"


# -- the replan e2e wedge: master → RPC → live fp8 apply ----------------------


def _small_moe_model_info():
    """Fits the 8-device CPU mesh under the v5e-ish memory gate while
    staying dispatch-comm-bound, so the precision family wins the
    wedge's ranking honestly (the chunk-wedge spec, reused)."""
    return comm.ModelInfo(
        num_params=200_000_000, hidden_size=2048, num_layers=16,
        seq_len=4096, num_experts=32, moe_top_k=2, ffn_mult=2.7,
    )


@pytest.mark.slow
class TestPrecisionReplanWedge:
    """Slow-marked (~90 s): the full master→RPC→live-apply loop is
    tier-1-covered by PR 7's e2e wedges (test_optimizer) and the
    precision-specific guarantees by TestRetunePrecisionZeroRecompile
    + the optimizer/plan-hook unit tests above — the tier-1 budget on
    this 1-core box (870 s for the whole suite) cannot carry a second
    ~90 s wedge per knob family."""

    def test_optimizer_selects_fp8_and_worker_applies_live(
            self, tmp_path, monkeypatch):
        """The acceptance wedge: a comm-bound MoE job reports its
        config → the master's optimizer prices the precision family,
        chooses the fp8 wire, publishes → the worker's plan hook
        drains and applies it through the prewarmed program cache with
        ZERO recompiles at the swap → the ack marks the decision
        applied."""
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
        # trace-time contract) — register restores so the chosen
        # chunks/precision don't leak into later tests' trace-time
        # resolution
        monkeypatch.setattr(ctx, "dispatch_chunks", ctx.dispatch_chunks)
        monkeypatch.setattr(ctx, "moe_precision", ctx.moe_precision)
        master = start_local_master()
        opt = master.servicer.runtime_optimizer
        opt._mesh_candidates = False
        opt._device = DeviceSpec(hbm_bytes=95e9)
        try:
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
            assert d["chosen"]["moe_precision"] == "fp8"
            assert d["applied"], d
            assert trainer.moe_precision == "fp8"
            done = [r for r in read_events(events_path)
                    if r.get("kind") == EventKind.OPTIMIZER_APPLY_DONE
                    and r.get("plan_id") == d["plan_id"]]
            assert done and done[-1]["recompiled"] == 0, done
            assert done[-1]["moe_precision"] == "fp8"
        finally:
            master.stop()


# -- lint: the G106 audit of the quantized program + G109 ---------------------


class TestFp8GraphLint:
    # slow-marked per the ISSUE 12 tier-1 triage (~13 s, two full
    # accelerate+compiles): the G106-on-a-quantized-program coverage
    # stays tier-1 via test_fsdp_wire's dense-wire audit (same audit
    # machinery, same dtype-aware prediction path), the moe wire ratio
    # via the planner formula pins; the moe compile re-proof rides
    # tpulint / the slow lane
    @pytest.mark.slow
    def test_quantized_program_passes_the_audit_with_halved_row_bytes(
            self):
        """The acceptance pin: G106 audits the fp8 program's
        collective bytes against the dtype-aware prediction within the
        existing tolerance AND the measured all-to-all row bytes come
        out well under the bf16 twin's (values + scales both counted
        on both sides) — the halving is verified on the COMPILED HLO,
        not asserted from the formula."""
        from dlrover_tpu.analysis.graph_lint import lint_train_step

        # chunks pinned to 1 explicitly: at C>1 the rows ride the
        # ppermute ring ("collective-permute"), and this test's point
        # is the all-to-all comparison (a leaked Context chunk knob
        # from an earlier live apply must not reroute it)
        rep_q = lint_train_step(
            llama.llama_tiny(num_experts=8, moe_dispatch="grouped_ep",
                             moe_precision="fp8",
                             moe_dispatch_chunks=1),
            label="llama_tiny_moe[grouped_ep,fp8]",
        )
        assert rep_q.findings == [], [
            f.render() for f in rep_q.findings]
        rep_b = lint_train_step(
            llama.llama_tiny(num_experts=8, moe_dispatch="grouped_ep",
                             moe_precision="bf16",
                             moe_dispatch_chunks=1),
            label="llama_tiny_moe[grouped_ep,bf16]",
        )
        assert rep_b.findings == [], [
            f.render() for f in rep_b.findings]
        a2a_q = rep_q.measured_bytes.get("all-to-all", 0)
        a2a_b = rep_b.measured_bytes.get("all-to-all", 0)
        assert a2a_q > 0 and a2a_b > 0
        # f32 tokens on this config: 4-byte rows drop to 1.125 -> well
        # under 0.8 even with the int32 count exchange riding along
        assert a2a_q / a2a_b < 0.8, (a2a_q, a2a_b)
        # and the prediction the audit compared against used the
        # dtype-aware formula
        assert rep_q.predicted_bytes["moe_dispatch"] \
            < rep_b.predicted_bytes["moe_dispatch"]


class TestG109QuantizationDrift:
    def test_fires_on_a_drifting_fixture(self):
        from dlrover_tpu.analysis.graph_lint import (
            check_quantization_drift,
        )

        findings = check_quantization_drift(0.5, 9e-5)
        assert len(findings) == 1
        assert findings[0].rule_id == "G109"
        assert "regressed" in findings[0].message

    def test_clean_inside_the_ratchet_and_default_tolerance(self):
        from dlrover_tpu.analysis.graph_lint import (
            check_quantization_drift,
        )

        assert check_quantization_drift(2e-4, 9e-5) == []  # < 4x
        assert check_quantization_drift(0.01, None) == []  # default tol
        assert check_quantization_drift(0.5, None)  # over default

    def test_floor_protects_near_zero_baselines(self):
        from dlrover_tpu.analysis.graph_lint import (
            check_quantization_drift,
        )

        # baseline ~0: reassociation noise must not fire
        assert check_quantization_drift(5e-6, 1e-9) == []

    def test_clean_on_head_against_the_committed_baseline(self):
        """The acceptance pin: the HEAD fp8 program's drift sits inside
        the committed quant_baseline.json ratchet."""
        from dlrover_tpu.analysis.graph_lint import (
            quantization_drift_audit,
        )

        rep = quantization_drift_audit()
        assert rep.findings == [], [f.render() for f in rep.findings]

    def test_wired_into_the_rule_set_and_baseline_is_versioned(self):
        import json

        from dlrover_tpu.analysis.graph_lint import (
            ALL_GRAPH_RULES,
            GRAPH_RULE_DOCS,
            quantization_drift_baseline_path,
        )

        assert "G109" in ALL_GRAPH_RULES
        assert "G109" in GRAPH_RULE_DOCS
        with open(quantization_drift_baseline_path()) as fh:
            data = json.load(fh)
        assert data["version"] == 1
        # entries are keyed per EXECUTING backend (@cpu here): a
        # baseline ratcheted on one backend's kernels must not judge
        # another's
        assert any(k.startswith("llama_tiny_moe[grouped_ep,fp8]@")
                   for k in data["entries"])
