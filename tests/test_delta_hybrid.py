"""``models/delta_hybrid.py``: the layer plan from ``layer_types``, the
sizes, the module against the family's plain reference (the chain as a
scan and the kernels in the interpreter), the kinds of layer, the
reordered norm, the counter, and the rule set on virtual devices."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.families.delta_hybrid import job, reference  # noqa: E402
from dlrover_tpu.models import delta_hybrid as dh  # noqa: E402
from dlrover_tpu.models.common import rms_norm  # noqa: E402
from dlrover_tpu.ops.attention_ref import mha_reference  # noqa: E402
from dlrover_tpu.parallel.accelerate import accelerate  # noqa: E402
from dlrover_tpu.parallel.mesh import MeshPlan  # noqa: E402
from dlrover_tpu.parallel.sharding_rules import (  # noqa: E402
    _flatten_with_paths,
    delta_hybrid_rules,
)
from dlrover_tpu.parallel.strategy import RULE_SETS, Strategy  # noqa: E402
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter  # noqa: E402

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)
KERNELS = dict(use_kernels=True, flash_block_q=32, flash_block_k=32)
LINEAR, FULL = dh.LINEAR, dh.FULL


def batch_of(config, rows=1, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def toy():
    """The family's toy configuration (two periods of a linear and a
    full layer, float32): what the reference reads."""
    with open(os.path.join(REPO, "tests", "chipbench",
                           "tiny_delta_hybrid.json")) as f:
        return json.load(f)


def perturbed(config):
    """Initial weights with the norm scales moved off 1, so that a
    dropped norm would show, and a table of std 1, so that the first
    layer reads more than rounding."""
    def moved(key):
        params = dh.init(key, config)
        params = jax.tree.map(
            lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                  a.shape, a.dtype), params)
        params["embed_tokens"]["embedding"] *= 10.0
        return params

    return jax.jit(moved)(jax.random.PRNGKey(3))


def hidden(config, params, batch):
    return dh.apply_hidden(params, batch["input_ids"], config)[0]


def test_the_layer_plan_is_one_period_of_the_list():
    c = dh.DeltaHybridConfig()
    assert dh.layer_plan(c) == [LINEAR, LINEAR, LINEAR, FULL]
    assert dh.layer_kinds(c) == {"gdn": 24, "attn_full": 8}
    cut = dataclasses.replace(c, num_layers=8)
    assert dh.layer_kinds(cut) == {"gdn": 6, "attn_full": 2}
    assert dh.make_init_fn(cut).layer_kinds == {"gdn": 6, "attn_full": 2}
    other = dh.delta_hybrid_tiny(num_layers=6,
                                 layer_types=(FULL, LINEAR, LINEAR) * 2)
    assert dh.layer_plan(other) == [FULL, LINEAR, LINEAR]


@pytest.mark.parametrize("depth", [3, 6, 30])
def test_a_depth_that_is_no_whole_number_of_periods_is_refused(depth):
    with pytest.raises(ValueError, match="no whole number of periods"):
        dh.layer_plan(dataclasses.replace(dh.DeltaHybridConfig(),
                                          num_layers=depth))


def test_a_list_that_cannot_name_every_layer_is_refused():
    with pytest.raises(ValueError, match="at least as long as the depth"):
        dh.layer_plan(dh.delta_hybrid_tiny(num_layers=10))
    with pytest.raises(ValueError, match="its kind"):
        dh.layer_plan(dh.delta_hybrid_tiny(
            layer_types=(LINEAR, "sliding_attention") * 4))


def test_param_count_at_the_published_sizes():
    """ISSUE 43's count of the equations: a linear layer 215,570,172,
    a full layer 185,809,920, table and head 770,703,360: 7.43 B, the
    catalog's "7B"; the benchmark's cut at depth 8, whole vocabulary
    and a quarter of it."""
    linear, full = 215_570_172, 185_809_920
    table_and_head = 2 * 100352 * 3840
    c = dh.DeltaHybridConfig()
    assert dh.param_count(c) == (24 * linear + 8 * full + table_and_head
                                 + 3840)
    assert 7.4e9 < dh.param_count(c) < 7.5e9
    cut = dataclasses.replace(c, num_layers=8)
    assert dh.param_count(cut) == 2_435_748_072
    assert dh.param_count(dataclasses.replace(
        cut, vocab_size=25088)) == 1_857_720_552
    shapes = jax.eval_shape(dh.make_init_fn(cut), jax.random.PRNGKey(0))
    assert sorted(shapes["layers"]) == ["0", "1", "2", "3"]
    # the two kinds keep their own trees, each stacked over the periods
    assert "a_log" in shapes["layers"]["0"]["mixer"]
    assert "q_norm" in shapes["layers"]["3"]["mixer"]
    assert shapes["layers"]["2"]["mixer"]["v_conv"]["kernel"].shape == (
        2, 4, 5760)
    assert shapes["layers"]["3"]["mixer"]["q_norm"]["scale"].shape == (
        2, 3840)


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_the_module_agrees_with_the_familys_reference(path):
    """Loss and every gradient against ``chipbench/families/
    delta_hybrid/reference.py`` (float32, the rule token by token, a
    dense masked softmax a head) on seeded weights: the chain as a scan
    over chunks with XLA's dense attention, and the ``gdn_*`` and flash
    kernels in the interpreter. Both sides are float32 and differ by
    the order of their sums: 1e-4 of a gradient's largest entry."""
    model = toy()
    config = job.model_config(model, use_kernels=path == "kernels",
                              flash_block_q=32, flash_block_k=32)
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    loss_fn = dh.make_loss_fn(config, head_chunk=32)

    def ref(p):
        return reference.loss(
            model, batch["input_ids"][0], batch["labels"][0],
            p["embed_tokens"]["embedding"], job.reference_layers(p, config),
            p["norm"]["scale"], p["lm_head"]["kernel"])

    (got, aux), grad = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, None)
    want, grad_want = jax.value_and_grad(ref)(params)
    assert abs(float(got) - float(want)) < 1e-5
    assert 0.2 < float(aux[StepCounter.GDN_NEG_EIG]) < 0.8
    flat = jax.tree_util.tree_leaves_with_path(grad)
    assert len(flat) == (13 + 5) + (6 + 5) + 3
    for (where, a), b in zip(flat, jax.tree.leaves(grad_want)):
        limit = 1e-4 * float(jnp.abs(b).max()) + 1e-7
        assert float(jnp.abs(a - b).max()) < limit, jax.tree_util.keystr(
            where)
        assert float(jnp.abs(b).max()) > 0, jax.tree_util.keystr(where)


def test_an_all_full_stack_is_a_plain_mha_stack():
    """``layer_types`` all full: every layer is q/k/v, the two norms
    over all columns, causal softmax without positions, then the FFN,
    each sublayer's output normalised and added. Written out here with
    the shared pieces."""
    c = dh.delta_hybrid_tiny(layer_types=(FULL,) * 4, **F32)
    assert dh.layer_plan(c) == [FULL]
    params = perturbed(c)
    batch = batch_of(c)
    x = params["embed_tokens"]["embedding"][batch["input_ids"]]
    eps = c.rms_norm_eps
    b, s, _ = x.shape

    def heads(u):
        return u.reshape(b, s, 4, 16).transpose(0, 2, 1, 3)

    for i in range(4):
        p = jax.tree.map(lambda a: a[i], params["layers"]["0"])
        m = p["mixer"]
        q = heads(rms_norm(x @ m["q_proj"]["kernel"], m["q_norm"]["scale"],
                           eps))
        k = heads(rms_norm(x @ m["k_proj"]["kernel"], m["k_norm"]["scale"],
                           eps))
        a = mha_reference(q, k, heads(x @ m["v_proj"]["kernel"]),
                          causal=True)
        a = a.transpose(0, 2, 1, 3).reshape(b, s, 64) @ m["o_proj"]["kernel"]
        x = x + rms_norm(a, p["attn_norm"]["scale"], eps)
        f = (jax.nn.silu(x @ p["mlp"]["gate_proj"]["kernel"])
             * (x @ p["mlp"]["up_proj"]["kernel"])
             ) @ p["mlp"]["down_proj"]["kernel"]
        x = x + rms_norm(f, p["ffn_norm"]["scale"], eps)
    want = rms_norm(x, params["norm"]["scale"], eps)
    got, share = dh.apply_hidden(params, batch["input_ids"], c)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(share) == 0.0  # no linear layer, no update to count


def test_the_kinds_follow_layer_types():
    """The same parameters cannot serve the other kind, so the kinds
    are told apart by what they compute: a linear layer's output at a
    token does not change when a LATER token changes (causal), changes
    when an earlier one does, and a full layer's too; the plan's order
    is the list's."""
    c = dh.delta_hybrid_tiny(layer_types=(LINEAR, LINEAR, FULL, FULL) * 2,
                             **F32)
    assert dh.layer_plan(c) == [LINEAR, LINEAR, FULL, FULL]
    params = perturbed(c)
    assert "a_log" in params["layers"]["1"]["mixer"]
    assert "k_norm" in params["layers"]["2"]["mixer"]
    batch = batch_of(c)
    ids = batch["input_ids"]
    want = hidden(c, params, batch)
    later = hidden(c, params, {"input_ids": ids.at[0, 40].set(
        (ids[0, 40] + 1) % c.vocab_size)})
    assert bool(jnp.all(later[:, :40] == want[:, :40]))
    assert float(jnp.abs(later[:, 40:] - want[:, 40:]).max()) > 1e-3


def test_the_norm_is_on_the_sublayers_output():
    """Scaling a sublayer's last matrix changes nothing (its output is
    normalised before it is added), which no pre-norm block would
    allow; scaling ``attn_norm`` moves the result."""
    c = dh.delta_hybrid_tiny(**F32)
    params = perturbed(c)
    batch = batch_of(c)
    want = hidden(c, params, batch)

    def with_scaled(where, leaf, factor):
        changed = jax.tree.map(lambda a: a, params)
        node = changed["layers"]["0"]
        for key in where[:-1]:
            node = node[key]
        node[where[-1]] = {leaf: node[where[-1]][leaf] * factor}
        return hidden(c, changed, batch)

    for where in (("mixer", "o_proj"), ("mlp", "down_proj")):
        same = with_scaled(where, "kernel", 3.0)
        assert float(jnp.abs(same - want).max()) < 1e-4, where
    moved = with_scaled(("attn_norm",), "scale", 3.0)
    assert float(jnp.abs(moved - want).max()) > 0.05


def test_the_counter_is_the_share_of_beta_over_one():
    """``gdn_neg_eig`` is 0 exactly where ``beta`` is not doubled, and
    the share of ``2 sigmoid(x W_b) > 1`` where it is."""
    c = dh.delta_hybrid_tiny(**F32)
    params = perturbed(c)
    batch = batch_of(c, rows=2)
    _, aux = dh.make_loss_fn(c)(params, batch, None)
    assert 0.2 < float(aux[StepCounter.GDN_NEG_EIG]) < 0.8
    off = dataclasses.replace(c, linear_allow_neg_eigval=False)
    _, aux = dh.make_loss_fn(off)(params, batch, None)
    assert float(aux[StepCounter.GDN_NEG_EIG]) == 0.0
    assert StepCounter.GDN_NEG_EIG in StepCounter.ALL


def test_a_part_runs_under_its_scope():
    c = dh.delta_hybrid_tiny(**F32, **KERNELS)
    params = dh.init(jax.random.PRNGKey(0), c)
    ids = batch_of(c)["input_ids"]
    text = jax.jit(lambda p, ids: dh.apply_hidden(p, ids, c)).lower(
        params, ids).as_text(debug_info=True)
    for scope in (DeviceScope.GDN, DeviceScope.ATTN_FULL, DeviceScope.FFN):
        assert f"/{scope}/" in text, scope
    # on the kernels a chunk is prepared inside ``gdn_rule_fwd``: XLA
    # runs nothing of the rule under ``gdn_chunk`` (PR 66); on the scan
    # the two steps' preparation is there, inside the linear layer's
    # scope
    assert "gdn_rule_fwd" in text and "gdn_fwd" not in text
    assert DeviceScope.GDN_CHUNK not in text
    plain = dh.delta_hybrid_tiny(**F32)
    text = jax.jit(lambda p, ids: dh.apply_hidden(p, ids, plain)).lower(
        params, ids).as_text(debug_info=True)
    assert f"/{DeviceScope.GDN}/" in text[:text.index(
        f"/{DeviceScope.GDN_CHUNK}/") + 20]
    assert {DeviceScope.GDN, DeviceScope.GDN_CHUNK} <= set(DeviceScope.ALL)


def test_rule_set_is_registered_and_names_every_leaf():
    assert RULE_SETS["delta_hybrid"] is delta_hybrid_rules
    shapes = jax.eval_shape(dh.make_init_fn(dh.DeltaHybridConfig(
        num_layers=8, vocab_size=25088)), jax.random.PRNGKey(0))
    sizes = {"data": 1, "fsdp": 2, "tensor": 2}
    rules = delta_hybrid_rules()
    for path, leaf in _flatten_with_paths(shapes):
        spec = rules.spec_for(path, leaf.shape, sizes)
        if path.startswith("layers/"):  # never the stacked axis
            assert spec[0] is None, (path, spec)
        if path.endswith("scale"):
            assert all(s is None for s in spec), (path, spec)
        elif "_proj/" in path:
            assert "fsdp" in spec and "tensor" in spec, (path, spec)
        elif "_conv/" in path or path.endswith(("a_log", "dt_bias")):
            assert spec[-1] == "tensor" and "fsdp" not in spec, (path, spec)
        elif leaf.size > 1e6:
            assert "fsdp" in spec, (path, spec)


def test_sharded_on_virtual_devices_gives_the_single_device_loss():
    """fsdp x tensor on the CPU's virtual devices under the
    ``delta_hybrid`` rules, the ``gdn_*`` and flash kernels under
    ``shard_map``: the first step's loss is the single-device loss, a
    kernel lands where its rule puts it, and the loss falls."""
    c = dh.delta_hybrid_tiny(**F32, **KERNELS)
    batch = batch_of(c, rows=4)
    loss_fn = dh.make_loss_fn(c, head_chunk=16)
    result = accelerate(
        dh.make_init_fn(c), loss_fn, optax.adam(3e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                          rule_set="delta_hybrid", remat_policy=""))
    state = result.init_fn(jax.random.PRNGKey(0))
    alone, _ = loss_fn(jax.device_get(state.params), batch, None)
    linear = state.params["layers"]["0"]["mixer"]
    assert tuple(linear["g_proj"]["kernel"].sharding.spec) == (
        None, "fsdp", "tensor")
    assert tuple(linear["v_conv"]["kernel"].sharding.spec) == (
        None, None, "tensor")
    assert tuple(linear["a_log"].sharding.spec) == (None, "tensor")
    sharded = result.shard_batch(batch)
    losses = []
    for i in range(6):
        state, metrics = result.train_step(state, sharded,
                                           jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    assert abs(losses[0] - float(alone)) < 1e-5
    assert np.isfinite(losses).all() and losses[-1] < losses[0] * 0.97
    assert 0.0 < float(metrics[StepCounter.GDN_NEG_EIG]) < 1.0
