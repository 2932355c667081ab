"""The differential switches of ``models/mla_moe.py`` and what they
rest on: PolyNorm and the parameterised gate stage of ``ops.moe.
held_expert_ffn`` against the plain forms and ``jax.grad`` of them; the
selection bias's rule by hand on a made-up load, through a save and a
restore, and absent from a model without it; a scan that chooses a
layer's attention kind against the layers run one at a time; what
``layer_plan`` refuses; the plain rotary and scale at ``rope_factor``
1."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import mla_moe
from dlrover_tpu.ops import moe
from dlrover_tpu.parallel.accelerate import StepBuffers, accelerate
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

SWITCHES = dict(
    num_layers=4, first_k_dense=1, num_heads=10, num_kv_heads=2,
    num_noise_heads=2, sliding_window=16, full_attention_layers=(2,),
    window_block=16, ffn_activation="poly_norm", router_bias_rate=1e-4,
    attn_output_gate=True, balance_loss_weight=0.0, rope_factor=1.0,
    n_routed_experts=24, experts_held=tuple(range(6)),
    routed_scaling_factor=2.0, param_dtype=jnp.float32,
    compute_dtype=jnp.float32, flash_block_q=16, flash_block_k=32)


def tiny(**overrides):
    return mla_moe.mla_moe_tiny(**{**SWITCHES, **overrides})


def batch_of(config, rows=2, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def plain_poly_norm(z, w, scale=0.5, clamp=0.5, eps=1e-6):
    def n(t):
        return t / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)

    return scale * (w["weight"][0] * n(z ** 3) + w["weight"][1] * n(z ** 2)
                    + w["weight"][2] * n(z)
                    + jnp.clip(w["bias"][0], -clamp, clamp))


@pytest.mark.parametrize("bias", [0.2, 0.9, -0.7])
def test_polynorm_is_the_plain_form_and_so_are_its_gradients(bias):
    z = jax.random.normal(jax.random.PRNGKey(0), (12, 40)) * 2.0
    w = {"weight": jnp.asarray([0.3, -0.2, 0.6]), "bias": jnp.asarray([bias])}
    act = mla_moe.poly_norm(0.5, 0.5, 1e-6)
    assert act is mla_moe.poly_norm(0.5, 0.5, 1e-6)  # one program
    np.testing.assert_allclose(act(z, w), plain_poly_norm(z, w), rtol=1e-5,
                               atol=1e-6)
    weight = jax.random.normal(jax.random.PRNGKey(1), z.shape)
    got = jax.grad(lambda z, w: jnp.sum(act(z, w) * weight), (0, 1))(z, w)
    want = jax.grad(lambda z, w: jnp.sum(plain_poly_norm(z, w) * weight),
                    (0, 1))(z, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # past its clamp the bias is held and takes no gradient
    assert (float(got[1]["bias"][0]) == 0) == (abs(bias) > 0.5)
    # a zero row (a pad row of the held experts' buffer) gives the
    # bias's term alone: finite, and times the row's zero ``up`` nothing
    assert bool(jnp.all(jnp.isfinite(act(jnp.zeros((2, 40)), w))))
    # bf16 rows come back bf16, computed in float32
    low = act(z.astype(jnp.bfloat16), w)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(jnp.float32),
                               plain_poly_norm(z, w), atol=0.05)


def _experts(key, held, d, f, act=None):
    k = jax.random.split(key, 3)
    out = {"gate": {"kernel": jax.random.normal(k[0], (held, d, f)) * 0.2},
           "up": {"kernel": jax.random.normal(k[1], (held, d, f)) * 0.2},
           "down": {"kernel": jax.random.normal(k[2], (held, f, d)) * 0.2}}
    if act is not None:
        out["act"] = act
    return out


def test_the_held_experts_gate_stage_takes_a_row_wise_activation():
    """``held_expert_ffn`` with PolyNorm's leaves among the experts
    against ``held_expert_ffn_reference`` and ``jax.grad`` of it: the
    output, the gradients of the kernels, of the tokens, of the weights
    and of the activation's four numbers."""
    d, f, held, tokens, k = 16, 24, (0, 1, 2, 5), 40, 3
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    act = {"weight": jnp.asarray([0.4, 0.1, 0.5]), "bias": jnp.asarray([.2])}
    experts = _experts(key[0], len(held), d, f, act)
    x = jax.random.normal(key[1], (tokens, d))
    top_i = jax.random.randint(key[2], (tokens, k), 0, 8)
    top_w = jax.nn.softmax(jax.random.normal(key[3], (tokens, k)))
    fn = mla_moe.poly_norm(0.5, 0.5, 1e-6)
    rows = moe.held_row_ladder(tokens, k, 8, len(held), 4.0, 8)

    def kernels(experts, x, top_w):
        return moe.held_expert_ffn(experts, x, top_i, top_w, held, rows, 8,
                                   True, activation=fn)[0]

    def plain(experts, x, top_w):
        return moe.held_expert_ffn_reference(experts, x, top_i, top_w, held,
                                             activation=fn)

    np.testing.assert_allclose(kernels(experts, x, top_w),
                               plain(experts, x, top_w), atol=1e-5)
    weight = jax.random.normal(jax.random.PRNGKey(9), (tokens, d))
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * weight), (0, 1, 2))(
        experts, x, top_w)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), (0, 1, 2))(
        experts, x, top_w)
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert any("act" in jax.tree_util.keystr(p) for p, _ in flat)
    for (path, a), b in zip(flat, jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
        assert float(jnp.abs(b).max()) > 0


def test_the_static_elementwise_path_holds_no_activation_leaf():
    """SiLU experts: no ``act`` among the leaves or the gradients, and
    the traced program is the one the parameterised path does not
    touch (the same jaxpr whether or not this PR's code is there is
    what the lowered cells' comparison shows, ``PERF.md`` section 6)."""
    held, tokens, k = (0, 1), 16, 2
    experts = _experts(jax.random.PRNGKey(0), 2, 8, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, 8))
    top_i = jax.random.randint(jax.random.PRNGKey(2), (tokens, k), 0, 4)
    top_w = jnp.full((tokens, k), 0.5)
    grads = jax.grad(lambda e: jnp.sum(moe.held_expert_ffn(
        e, x, top_i, top_w, held, 64, 8, True)[0]))(experts)
    assert set(grads) == {"gate", "up", "down"}
    np.testing.assert_allclose(
        moe.held_expert_ffn(experts, x, top_i, top_w, held, 64, 8, True)[0],
        moe.held_expert_ffn_reference(experts, x, top_i, top_w, held),
        atol=1e-5)


def test_the_bias_rule_by_hand_on_a_made_up_load():
    load = jnp.asarray([[10., 0., 4., 2.], [3., 3., 3., 3.]])  # means 4, 3
    bias = jnp.asarray([[0.5, -0.5, 0.0, 0.0], [0.1, 0.2, 0.3, 0.4]])
    new = moe.selection_bias_update(bias, load, 1e-2)
    # layer 0: signs (-, +, 0, +), delta (-1, 1, 0, 1)e-2, its mean
    # 0.25e-2 taken out; layer 1: the load is the mean, nothing moves
    np.testing.assert_allclose(new[0], [0.5 - 0.0125, -0.5 + 0.0075,
                                        -0.0025, 0.0075], atol=1e-7)
    np.testing.assert_allclose(new[1], bias[1], atol=1e-7)
    assert float(jnp.sum(new[0] - bias[0])) == pytest.approx(0, abs=1e-7)
    top_i = jnp.asarray([[0, 1], [0, 2], [3, 0]], jnp.int32)
    np.testing.assert_array_equal(moe.expert_load(top_i, 5), [3, 1, 1, 1, 0])
    # the bias decides who is selected and never a weight
    logits = jnp.asarray([[0.0, 0.1, 0.2, 0.3]])
    plain_i, plain_w, _ = moe.sigmoid_topk_routing(logits, 2)
    moved_i, moved_w, _ = moe.sigmoid_topk_routing(
        logits, 2, selection_bias=jnp.asarray([1.0, 0.0, 0.0, 0.0]))
    assert sorted(plain_i[0].tolist()) == [2, 3]
    assert sorted(moved_i[0].tolist()) == [0, 3]
    s = jax.nn.sigmoid(logits[0])
    assert float(jnp.sum(moved_w)) == pytest.approx(1.0)
    assert float(moved_w[0, moved_i[0].tolist().index(0)]) == pytest.approx(
        float(s[0] / (s[0] + s[3])))


def _accelerated(config):
    loss_fn = mla_moe.make_loss_fn(config)
    batch = batch_of(config)
    result = accelerate(
        mla_moe.make_init_fn(config), loss_fn, optax.adafactor(1e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=1, fsdp=1), rule_set="mla_moe",
                          remat_policy=""),
        devices=jax.devices()[:1])
    return result, batch


def test_the_step_moves_the_bias_and_a_restored_job_continues_from_it(
        tmp_path):
    """``TrainState.buffers``: the step returns the bias moved by its
    own loads (the rule applied to the loss function's aux by hand
    gives the same), a checkpoint holds it, and a state restored from
    the checkpoint steps on to the same bias and loss as the one that
    never stopped."""
    from dlrover_tpu.checkpoint import ElasticCheckpointManager, abstract_like

    config = tiny(use_kernels=False)
    result, batch = _accelerated(config)
    state = result.init_fn(jax.random.PRNGKey(0))
    def bias(s):
        return s.buffers["moe_layers"]["moe"]["router"]["bias"]

    assert bias(state).shape == (3, 24) and not np.asarray(bias(state)).any()
    assert bias(state).dtype == jnp.float32
    assert isinstance(mla_moe.make_loss_fn(config).step_buffers, StepBuffers)
    sharded = batch_of(config)
    # by hand: the loads the loss function counts at the initial state
    _, aux = mla_moe.make_loss_fn(config)(state.params, batch, None,
                                          state.buffers)
    load = aux[mla_moe.ROUTER_LOAD]["moe_layers"]
    assert load.shape == (3, 24) and float(load.sum()) == 3 * 2 * 64 * 4
    want = moe.selection_bias_update(bias(state), load, 1e-4)
    state, metrics = result.train_step(state, result.shard_batch(sharded),
                                       jax.random.PRNGKey(1))
    np.testing.assert_allclose(bias(state), want, atol=1e-9)
    assert 0 < float(metrics["router_bias_abs"]) <= 1e-4
    assert mla_moe.ROUTER_LOAD not in metrics
    # save, step on, restore, step again: the same bias and loss
    manager = ElasticCheckpointManager(str(tmp_path / "ckpt"),
                                       async_save=False)
    assert manager.save(1, state, force=True)
    manager.wait()
    target = abstract_like(state, result.state_sharding)
    on, m_on = result.train_step(state, result.shard_batch(sharded),
                                 jax.random.PRNGKey(2))
    restored = manager.restore(target, step=1)["state"]
    np.testing.assert_array_equal(bias(restored), np.asarray(want))
    again, m_again = result.train_step(
        restored, result.shard_batch(sharded), jax.random.PRNGKey(2))
    np.testing.assert_array_equal(bias(again), bias(on))
    assert float(m_again["loss"]) == float(m_on["loss"])
    assert float(m_on["router_bias_abs"]) > float(metrics["router_bias_abs"])
    # the rules shard the buffer as they would the parameter: whole
    spec = result.state_sharding.buffers["moe_layers"]["moe"]["router"][
        "bias"].spec
    assert all(axis is None for axis in spec)


def test_a_model_without_the_rate_has_no_buffer_and_no_instruction():
    config = tiny(use_kernels=False, router_bias_rate=0.0, router_bias=True)
    loss_fn = mla_moe.make_loss_fn(config)
    assert not hasattr(loss_fn, "step_buffers")
    result, batch = _accelerated(config)
    state = result.init_fn(jax.random.PRNGKey(0))
    assert state.buffers is None
    assert len(jax.tree.leaves(state)) == len(jax.tree.leaves(
        (state.step, state.params, state.opt_state)))
    text = result.train_step.lower(
        state, result.shard_batch(batch), jax.random.PRNGKey(0)).as_text()
    assert "router_bias" not in text
    # the parameter kind of bias is a leaf the optimizer leaves alone
    def leaf(s):
        return s.params["moe_layers"]["moe"]["router"]["bias"]

    before = np.asarray(leaf(state))  # the step donates its state
    new, metrics = result.train_step(state, result.shard_batch(batch),
                                     jax.random.PRNGKey(0))
    assert "router_bias_abs" not in metrics and new.buffers is None
    np.testing.assert_array_equal(leaf(new), before)


def test_a_scan_that_chooses_the_attention_kind_is_the_layers_one_by_one():
    """The expert layers' stack holds window, full, window layers: the
    scan carries a flag a layer and the kernel wrapper branches once
    forward and once backward. Against the same layers run outside any
    scan, each at its static kind: the same hidden states and the same
    gradients."""
    config = tiny(remat_policy="none", router_bias_rate=0.0)
    assert mla_moe.attention_plan(config) == ["window", "window", "full",
                                              "window"]
    assert mla_moe.layer_kinds(config) == {"dense": 1, "moe": 3, "full": 1,
                                           "window": 3}
    params = mla_moe.init(jax.random.PRNGKey(0), config)
    batch = batch_of(config, rows=1)

    def unrolled(params, ids):
        c = config
        rotary = mla_moe._rotary_tables(ids.shape[1], c)
        x = params["embed_tokens"]["embedding"][ids].astype(c.compute_dtype)
        plan = mla_moe.attention_plan(c)
        stacks = [("dense", "dense_layers", 1), ("moe", "moe_layers", 3)]
        i = 0
        for kind, name, count in stacks:
            for k in range(count):
                layer = mla_moe._layer(c, kind, rotary,
                                       attention_kind=plan[i])
                x, _ = layer(x, jax.tree.map(lambda a: a[k], params[name]))
                i += 1
        return mla_moe._rms(x, params["norm"], c)

    def scanned(params, ids):
        return mla_moe.apply_hidden(params, ids, config)[0]

    ids = batch["input_ids"]
    np.testing.assert_allclose(scanned(params, ids), unrolled(params, ids),
                               atol=2e-5)
    weight = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 64))
    got = jax.grad(lambda p: jnp.sum(scanned(p, ids) * weight))(params)
    want = jax.grad(lambda p: jnp.sum(unrolled(p, ids) * weight))(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-5 + 1e-4 * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))
    # and the kernels' path is XLA's dense one
    dense = dataclasses.replace(config, use_kernels=False)
    np.testing.assert_allclose(
        scanned(params, ids), mla_moe.apply_hidden(params, ids, dense)[0],
        atol=2e-5)


def test_the_differential_layers_checkpoints_keep_out_and_lse(monkeypatch):
    """A dense window layer, a scan that chooses its layers' kind and a
    prediction module's window layer, each under ``"full"`` on the
    interpreter's kernels: the loss and every gradient are bit for bit
    what the checkpoints give with nothing kept (``apply_remat`` as the
    parent called it), and the gradient program has each forward kernel
    once a call site (the scan's switch one of each kind) where the
    parent's has it twice."""
    from dlrover_tpu.ops.remat import apply_remat
    from dlrover_tpu.telemetry.names import StepCounter

    config = tiny(use_kernels=True, mtp_layers=1, remat_policy="full",
                  router_bias_rate=0.0)
    assert mla_moe.attention_plan(config) == [
        "window", "window", "full", "window", "window"]
    params = mla_moe.init(jax.random.PRNGKey(0), config)
    batch = batch_of(config, rows=1)

    def run():
        # one trace gives the text and the program that runs
        traced = jax.jit(jax.value_and_grad(mla_moe.make_loss_fn(
            config, head_chunk=16), has_aux=True)).trace(params, batch, None)
        return str(traced.jaxpr), traced.lower().compile()(
            params, batch, None)

    kept, ((loss, aux), grad) = run()
    assert float(aux[StepCounter.ATTN_KEPT_BYTES]) == 5 * 10 * 64 * (
        16 * 4 + 4)
    monkeypatch.setattr(mla_moe, "apply_remat", lambda fn, policy, keep: (
        apply_remat(fn, policy)))
    replayed, ((loss_w, _), grad_w) = run()
    assert float(loss) == float(loss_w)
    jax.tree.map(np.testing.assert_array_equal, grad, grad_w)
    for kernel, ours, parents in (("flash_mla_fwd", 1, 2),
                                  ("flash_mla_win_fwd", 3, 6),
                                  ("flash_mla_dkv", 1, 1),
                                  ("flash_mla_win_dkv", 3, 3)):
        assert (kept.count(f"name={kernel}"),
                replayed.count(f"name={kernel}")) == (ours, parents), kernel


@pytest.mark.parametrize("overrides,match", [
    (dict(index_n_heads=2, hc_mult=1, mtp_layers=0), "not written"),
    (dict(gated_norm_rank=4), "not written"),
    (dict(n_group=4, topk_group=2), "not written"),
    (dict(num_kv_heads=3), "whole groups"),
    (dict(num_noise_heads=5), "one noise head a group"),
    (dict(num_kv_heads=10, num_noise_heads=10), "no signal head"),
    (dict(ffn_activation="gelu"), "ffn_activation"),
    (dict(router_bias=True), "not both"),
    (dict(full_attention_layers=(9,)), "full_attention_layers"),
    (dict(sliding_window=0), "full_attention_layers"),
])
def test_the_plan_refuses_what_is_not_written(overrides, match):
    with pytest.raises(ValueError, match=match):
        mla_moe.layer_plan(tiny(**overrides))


def test_the_four_present_configurations_plans_are_what_they_were():
    for overrides in (dict(), dict(hc_mult=4, mtp_layers=1,
                                   router_bias=True),
                      dict(index_n_heads=2, attn_output_gate=True,
                           gated_norm_rank=4, n_group=4, topk_group=2)):
        c = mla_moe.mla_moe_tiny(**overrides)
        assert mla_moe.layer_plan(c) == ["dense", "moe", "moe"]
        assert mla_moe.layer_kinds(c) == {"dense": 1, "moe": 2}
        assert set(mla_moe.attention_plan(c)) == {"full"}
        assert c.kv_heads == c.out_heads == c.num_heads
        assert not hasattr(mla_moe.make_loss_fn(c), "step_buffers")
        leaves = jax.tree_util.tree_leaves_with_path(
            jax.eval_shape(lambda: mla_moe.init(jax.random.PRNGKey(0), c)))
        assert not [p for p, _ in leaves if "act" in jax.tree_util.keystr(p)
                    or "lam_proj" in jax.tree_util.keystr(p)]


def test_rope_factor_one_is_the_plain_rotary_and_the_plain_scale():
    """What a configuration that computes no YaRN relies on
    (``rope_scaling.apply_yarn_scaling`` false): at ``rope_factor`` 1
    the scale is ``d^-0.5`` and the inverse frequencies are ``1 /
    theta^(2i/d)``, whatever the other YaRN fields say."""
    c = mla_moe.MlaMoeConfig(rope_factor=1.0, rope_theta=10000.0,
                             rope_mscale=0.7, rope_mscale_all_dim=1.3,
                             rope_beta_fast=32.0, rope_beta_slow=1.0)
    assert c.softmax_scale == (128 + 64) ** -0.5
    plain = [10000.0 ** (-2 * i / 64) for i in range(32)]
    np.testing.assert_allclose(mla_moe.yarn_inv_freq(c), plain, rtol=1e-12)
    cos, sin = mla_moe._rotary_tables(8, c)
    np.testing.assert_allclose(
        cos, np.cos(np.arange(8)[:, None] * np.asarray(plain)), atol=1e-6)
    # and YaRN proper still scales both
    yarn = dataclasses.replace(c, rope_factor=32.0, rope_mscale=1.0,
                               rope_mscale_all_dim=1.0)
    m = 0.1 * math.log(32.0) + 1.0
    assert yarn.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert mla_moe.yarn_inv_freq(yarn)[-1] == pytest.approx(plain[-1] / 32)
