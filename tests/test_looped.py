"""``models/looped.py``: one stack of layers run several times a step on
shared weights, an exit gate after each pass, the head and its loss once
a pass under the gate's weights; against the family's plain reference
(``chipbench/families/looped/reference.py``) in value and in every
leaf's gradient."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.families.looped import job  # noqa: E402
from dlrover_tpu.models import looped  # noqa: E402
from dlrover_tpu.models.losses import masked_lm_loss  # noqa: E402
from dlrover_tpu.parallel.accelerate import accelerate  # noqa: E402
from dlrover_tpu.parallel.mesh import MeshPlan  # noqa: E402
from dlrover_tpu.parallel.sharding_rules import (  # noqa: E402
    _flatten_with_paths,
    looped_rules,
)
from dlrover_tpu.parallel.strategy import RULE_SETS, Strategy  # noqa: E402
from dlrover_tpu.telemetry.attribution import scope_key  # noqa: E402
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter  # noqa: E402

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)
KERNELS = dict(use_kernels=True)


def toy(**assumed):
    """The family's toy configuration (two layers run three times,
    float32): what the reference reads."""
    with open(os.path.join(REPO, "tests", "chipbench",
                           "tiny_looped.json")) as f:
        model = json.load(f)
    model["assumed"].update(assumed)
    return model


def batch_of(config, rows=1, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def perturbed(config):
    """Initial weights with the norm scales and the gate's bias moved
    off their starting values, so that a dropped one would show."""
    def moved(key):
        return jax.tree.map(
            lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                  a.shape, a.dtype)
            if a.shape[-1] != config.vocab_size else a,
            looped.init(key, config))

    return jax.jit(moved)(jax.random.PRNGKey(3))


def test_param_count_at_the_published_sizes():
    """ISSUE 65's arithmetic: a layer 4 x 2048^2 + 3 x 2048 x 5632
    matmul parameters and four norms, the table and the head apiece,
    the final norm, the gate's kernel and bias."""
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_380_224 + 8_192
    c = looped.LoopedConfig(num_layers=12)
    assert looped.param_count(c) == (
        12 * layer + 2 * 49152 * 2048 + 2048 + 2049) == 817_991_681
    assert looped.param_count(looped.LoopedConfig()) == (
        48 * layer + 2 * 49152 * 2048 + 2048 + 2049)
    assert looped.layer_kinds(c) == {"attn_full": 12}
    init_fn = looped.make_init_fn(c)
    assert init_fn.layer_kinds == {"attn_full": 12} and init_fn.passes == 4


@pytest.mark.parametrize("bad", [dict(num_passes=0), dict(num_layers=0),
                                 dict(num_heads=4, num_kv_heads=3)])
def test_a_shape_the_model_cannot_have_is_refused(bad):
    with pytest.raises(ValueError):
        looped.init(jax.random.PRNGKey(0), looped.looped_tiny(**bad))


@pytest.mark.parametrize("path, head_chunk", [("xla", 0), ("xla", 32),
                                              ("kernels", 32)])
def test_the_module_agrees_with_the_familys_reference(path, head_chunk):
    """Value and gradient, float32: the loss, the ``T`` cross entropies,
    the mean exit distribution and the last pass's normed states within
    1e-5, and the gradient of EVERY leaf within 1e-4 of its largest
    entry against ``jax.grad`` of the reference (a Python loop over the
    passes: a shared leaf's gradient there is autodiff's sum over its
    uses, here the transposed loop's)."""
    model = toy()
    config = job.model_config(
        model, **(KERNELS if path == "kernels" else dict(use_kernels=False)))
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    ids, labels = batch["input_ids"][0], batch["labels"][0]

    def program(p):
        parts = looped.loss_parts(p, batch, config, head_chunk)
        return parts["loss"], parts

    def plain(p):
        parts = job.reference_parts(model, config, p, ids, labels)
        return parts["loss"], parts

    (loss, parts), grads = jax.value_and_grad(program, has_aux=True)(params)
    (want, ref), ref_grads = jax.value_and_grad(plain, has_aux=True)(params)
    assert abs(float(loss) - float(want)) < 1e-5
    found = job.readings(parts, ref)
    assert found["pass_loss_diff"] < 1e-5 and found["exit_diff"] < 1e-6
    assert found["median_token_error"] < 1e-5
    assert parts["pass_losses"].shape == (3,)
    assert abs(float(parts["exit_distribution"].sum()) - 1.0) < 1e-6
    for (where, got), (_, ref_g) in zip(_flatten_with_paths(grads),
                                        _flatten_with_paths(ref_grads)):
        assert got.shape == ref_g.shape, where
        scale = float(jnp.abs(ref_g).max())
        assert scale > 0.0, where  # every leaf is used, the gate's too
        assert float(jnp.abs(got - ref_g).max()) < 1e-4 * scale, where


def test_the_bf16_configuration_stays_within_its_band():
    """bf16 parameters and compute (what the cell states) against the
    float32 reference on the same bf16 weights: the loss within 2e-2,
    the last states' median token within 3%, and every leaf's gradient
    within 12% of its norm (a direction, not a digit: bf16 rounds every
    activation of 6 layer passes and sums a shared leaf's three uses in
    bf16); float32 reads 1e-6 in all three (the test above)."""
    model = toy(param_dtype="bfloat16", compute_dtype="bfloat16")
    config = job.model_config(model, use_kernels=False)
    params = perturbed(config)
    batch = batch_of(config, seed=12)

    def program(p):
        parts = looped.loss_parts(p, batch, config, 32)
        return parts["loss"], parts

    def plain(p):
        parts = job.reference_parts(model, config, p, batch["input_ids"][0],
                                    batch["labels"][0])
        return parts["loss"], parts

    (loss, parts), grads = jax.value_and_grad(program, has_aux=True)(params)
    (want, ref), ref_grads = jax.value_and_grad(plain, has_aux=True)(params)
    assert abs(float(loss) - float(want)) < 2e-2
    assert job.readings(parts, ref)["median_token_error"] < 3e-2
    for (where, got), (_, ref_g) in zip(_flatten_with_paths(grads),
                                        _flatten_with_paths(ref_grads)):
        assert got.dtype == jnp.bfloat16, where
        got, ref_g = (np.asarray(a, np.float32) for a in (got, ref_g))
        assert np.linalg.norm(got - ref_g) < 0.12 * np.linalg.norm(ref_g), (
            where, np.linalg.norm(got - ref_g) / np.linalg.norm(ref_g))


def test_a_shared_leafs_gradient_is_the_sum_over_the_passes():
    """The stack's gradient under ``T`` passes is the sum of the
    gradients of ``T`` separate copies of the stack, one a pass: the
    same loss written with the copies unrolled in Python."""
    c = looped.looped_tiny(**F32)
    params = perturbed(c)
    batch = batch_of(c, seed=5)

    def unrolled(stacks):
        x = params["embed_tokens"]["embedding"][batch["input_ids"]]
        states = []
        for stack in stacks:
            x = _one_pass(params, stack, x, c)
            states.append(x)
        p, entropy = looped.exit_distribution(jnp.stack(states),
                                              params["exit_gate"])
        logits = jnp.stack(states) @ params["lm_head"]["kernel"]
        nll = -jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1),
            jnp.broadcast_to(batch["labels"], p.shape)[..., None],
            axis=-1)[..., 0]
        return ((p * nll).sum(axis=0) - c.exit_entropy_beta * entropy).mean()

    copies = [params["layers"]] * c.num_passes
    by_pass = jax.grad(unrolled)(copies)
    shared = jax.grad(lambda p: looped.loss_parts(p, batch, c)["loss"])(
        params)["layers"]
    summed = jax.tree.map(lambda *g: sum(g), *by_pass)
    for (where, got), (_, want) in zip(_flatten_with_paths(shared),
                                       _flatten_with_paths(summed)):
        assert float(jnp.abs(got - want).max()) < 1e-5 * max(
            1.0, float(jnp.abs(want).max())), where
    # and no pass's share is nothing: the sum is of T terms
    for g in by_pass:
        assert float(jnp.abs(g["mlp"]["down_proj"]["kernel"]).max()) > 0


def _one_pass(params, stack, x, c):
    """One pass of ``stack`` over the stream ``x`` and the final norm,
    through the module's own layer."""
    layer = looped._layer(c, looped._rotary_tables(x.shape[1], c))
    for i in range(c.num_layers):
        x, _ = layer(x, jax.tree.map(lambda a: a[i], stack))
    return looped.rms_norm(x, params["norm"]["scale"], c.rms_norm_eps)


def test_one_pass_is_a_sandwich_decoder_under_the_plain_loss():
    """``T = 1``: ``p_1`` = 1 and ``H`` = 0, so the gate's term drops
    out and the loss is the cross entropy of a one-pass sandwich
    decoder: ``masked_lm_loss`` of ``apply``'s logits, the reference's
    ``L_1`` at one pass, and no gradient reaches the gate."""
    model = toy()
    model["total_ut_steps"] = 1
    config = job.model_config(model, use_kernels=False)
    assert config.num_passes == 1
    params = perturbed(config)
    batch = batch_of(config, rows=2, seed=7)
    loss, aux = looped.make_loss_fn(config, 32)(params, batch, None)
    logits = looped.apply(params, batch["input_ids"], config)
    assert abs(float(loss) - float(masked_lm_loss(
        logits, batch["labels"]))) < 1e-6
    row = job.reference_parts(model, config, params, batch["input_ids"][0],
                              batch["labels"][0])
    alone = looped.loss_parts(
        params, jax.tree.map(lambda a: a[:1], batch), config, 32)
    assert abs(float(alone["loss"]) - float(row["pass_losses"][0])) < 1e-5
    assert abs(float(alone["loss"]) - float(row["loss"])) < 1e-5
    assert float(aux[StepCounter.LOOP_EXIT_ENTROPY]) == 0.0
    assert float(aux[StepCounter.LOOP_EXIT_MEAN_PASS]) == 1.0
    assert float(aux[StepCounter.LOOP_LOSS_FIRST]) == float(
        aux[StepCounter.LOOP_LOSS_LAST])
    grads = jax.grad(lambda p: looped.make_loss_fn(config, 32)(
        p, batch, None)[0])(params)
    assert float(jnp.abs(grads["exit_gate"]["kernel"]).max()) == 0.0
    assert float(jnp.abs(grads["exit_gate"]["bias"]).max()) == 0.0


def test_the_exit_distribution_is_a_distribution():
    """``p`` sums to 1 a token whatever the gate says, the last pass
    takes the rest of the mass and ignores its own gate, and the entropy
    lies in [0, ln T]; a gate that is shut everywhere sends every token
    to the last pass."""
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    states = jax.random.normal(k[0], (4, 2, 8, 16))
    gate = {"kernel": 3.0 * jax.random.normal(k[1], (16, 1)),
            "bias": jnp.array([0.3])}
    p, entropy = looped.exit_distribution(states, gate)
    assert p.shape == (4, 2, 8) and entropy.shape == (2, 8)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    assert float(entropy.min()) >= 0.0
    assert float(entropy.max()) <= np.log(4.0) + 1e-6
    lam = jax.nn.sigmoid(states @ gate["kernel"][:, 0] + 0.3)
    np.testing.assert_allclose(p[0], lam[0], atol=1e-6)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), atol=1e-6)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), atol=1e-6)
    moved = states.at[3].set(-states[3])  # the last gate is not read
    np.testing.assert_allclose(looped.exit_distribution(moved, gate)[0], p)
    shut = {"kernel": jnp.zeros((16, 1)), "bias": jnp.array([-40.0])}
    p, entropy = looped.exit_distribution(states, shut)
    np.testing.assert_allclose(p[3], 1.0, atol=1e-6)
    assert float(jnp.abs(entropy).max()) < 1e-6


def test_the_counters_are_the_steps_means():
    c = looped.looped_tiny(**F32)
    params = perturbed(c)
    batch = batch_of(c, rows=2)
    loss, aux = looped.make_loss_fn(c, 32)(params, batch, None)
    parts = looped.loss_parts(params, batch, c, 0)
    assert set(aux) == {StepCounter.LOOP_EXIT_ENTROPY,
                        StepCounter.LOOP_EXIT_MEAN_PASS,
                        StepCounter.LOOP_LOSS_FIRST,
                        StepCounter.LOOP_LOSS_LAST} <= set(StepCounter.ALL)
    assert 0.0 < float(aux[StepCounter.LOOP_EXIT_ENTROPY]) < np.log(3.0)
    assert 1.0 < float(aux[StepCounter.LOOP_EXIT_MEAN_PASS]) < 3.0
    assert abs(float(aux[StepCounter.LOOP_EXIT_MEAN_PASS]) - float(
        (jnp.arange(1, 4) * parts["exit_distribution"]).sum())) < 1e-6
    assert abs(float(aux[StepCounter.LOOP_LOSS_FIRST])
               - float(parts["pass_losses"][0])) < 1e-5
    assert abs(float(aux[StepCounter.LOOP_LOSS_LAST])
               - float(parts["pass_losses"][2])) < 1e-5
    # a chunk of half a row and a whole row give one objective
    assert abs(float(loss) - float(parts["loss"])) < 1e-5
    assert DeviceScope.EXIT_GATE in DeviceScope.ALL


def test_a_part_runs_under_its_scope_and_the_loop_is_no_unrolling():
    """The lowered value-and-gradient of the loss: attention under
    ``attn_full`` and the MLP under ``ffn`` with each sublayer's two
    norms inside, the gate under ``exit_gate``, the head under
    ``head_loss``; the flash kernels have one layer's call sites (the
    forward, its replay, the backward's two), not ``T x L`` of them."""
    c = looped.looped_tiny(**F32, **KERNELS)
    params = looped.init(jax.random.PRNGKey(0), c)
    batch = batch_of(c)
    loss_fn = looped.make_loss_fn(c, 32)
    text = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, None)[0])).lower(params).as_text(
            debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    keys = {scope_key(name).split("|")[1] for name in names}
    assert {"attn_full", "ffn", "exit_gate", "head_loss"} <= keys
    # the norms' rsqrt: four a layer, inside the sublayers' scopes, and
    # the final one outside both
    where = {scope_key(n).split("|")[1] for n in names if "rsqrt" in n}
    assert {"attn_full", "ffn", ""} <= where
    jaxpr = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p: loss_fn(p, batch, None)[0]))(params))
    calls = [len(re.findall(rf"name={name}\b", jaxpr))
             for name in ("flash_fwd", "flash_dkv", "flash_dq")]
    assert calls == [2, 1, 1], calls


def test_rule_set_is_registered_and_names_every_leaf():
    assert RULE_SETS["looped"] is looped_rules
    shapes = jax.eval_shape(looped.make_init_fn(looped.LoopedConfig(
        num_layers=12)), jax.random.PRNGKey(0))
    sizes = {"data": 1, "fsdp": 2, "tensor": 2}
    rules = looped_rules()
    for path, leaf in _flatten_with_paths(shapes):
        spec = rules.spec_for(path, leaf.shape, sizes)
        if path.startswith("layers/"):  # never the stacked axis
            assert spec[0] is None, (path, spec)
        if path.endswith("scale") or path.startswith("exit_gate/"):
            assert all(s is None for s in spec), (path, spec)
        elif "_proj/" in path:
            assert "fsdp" in spec and "tensor" in spec, (path, spec)
        else:  # the table and the head
            assert "fsdp" in spec and "tensor" in spec, (path, spec)


@pytest.mark.parametrize("mesh", [dict(data=2, fsdp=2),
                                  dict(data=2, fsdp=2, tensor=2)],
                         ids=["fsdp", "fsdp-tensor"])
def test_sharded_on_virtual_devices_gives_the_single_device_loss(mesh):
    """On the CPU's virtual devices under the ``looped`` rules, the
    flash kernels under ``shard_map``: the first step's loss is the
    single-device loss, a leaf lands where its rule puts it (a layer's
    shard is then gathered once a pass), and the loss falls."""
    c = looped.looped_tiny(**F32, **KERNELS)
    batch = batch_of(c, rows=4)
    loss_fn = looped.make_loss_fn(c, head_chunk=32)
    devices = int(np.prod(list(mesh.values())))
    result = accelerate(
        looped.make_init_fn(c), loss_fn, optax.adam(3e-3), batch,
        strategy=Strategy(mesh=MeshPlan(**mesh), rule_set="looped",
                          remat_policy=""),
        devices=jax.devices()[:devices])
    state = result.init_fn(jax.random.PRNGKey(0))
    alone, _ = loss_fn(jax.device_get(state.params), batch, None)
    layers = state.params["layers"]
    tensor = "tensor" if "tensor" in mesh else None
    assert tuple(layers["attn"]["q_proj"]["kernel"].sharding.spec) == (
        None, "fsdp", tensor)
    assert tuple(layers["mlp"]["down_proj"]["kernel"].sharding.spec) == (
        None, tensor, "fsdp")
    assert not any(layers["mlp_out_norm"]["scale"].sharding.spec)
    assert not any(state.params["exit_gate"]["kernel"].sharding.spec)
    sharded = result.shard_batch(batch)
    losses = []
    for i in range(6):
        state, metrics = result.train_step(state, sharded,
                                           jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    assert abs(losses[0] - float(alone)) < 1e-5
    assert np.isfinite(losses).all() and losses[-1] < losses[0] * 0.99
    assert 0.0 < float(metrics[StepCounter.LOOP_EXIT_ENTROPY]) < np.log(3.0)
