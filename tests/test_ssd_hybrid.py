"""``models/ssd_hybrid.py``: the layer plan from ``layer_types``, the
sizes, the module against the family's plain reference (the chunked
form as a scan and the kernels in the interpreter), the tied table, the
faults the comparison has to catch, the counter, the scopes, the plain
flash kernels under an explicit scale, and the rule set on virtual
devices."""

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
sys.path.insert(0, os.path.join(REPO, "tests", "chipbench"))

import ssd_hybrid_controls as controls  # noqa: E402
from chipbench.families.ssd_hybrid import job, reference  # noqa: E402
from dlrover_tpu.models import ssd_hybrid as sh  # noqa: E402
from dlrover_tpu.models.losses import masked_lm_loss  # noqa: E402
from dlrover_tpu.ops.attention_ref import mha_reference  # noqa: E402
from dlrover_tpu.ops.flash_attention import flash_attention  # noqa: E402
from dlrover_tpu.parallel.accelerate import accelerate  # noqa: E402
from dlrover_tpu.parallel.mesh import MeshPlan  # noqa: E402
from dlrover_tpu.parallel.sharding_rules import (  # noqa: E402
    _flatten_with_paths,
    ssd_hybrid_rules,
)
from dlrover_tpu.parallel.strategy import RULE_SETS, Strategy  # noqa: E402
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter  # noqa: E402

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)
KERNELS = dict(use_kernels=True, flash_block_q=32, flash_block_k=32)
MAMBA, ATTENTION = sh.MAMBA, sh.ATTENTION


def published(**overrides):
    return sh.SsdHybridConfig(**dict(sh.PUBLISHED_MULTIPLIERS, **overrides))


def batch_of(config, rows=1, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def toy():
    """The family's toy configuration (two periods of a Mamba-2 and an
    attention layer, float32): what the reference reads."""
    with open(os.path.join(REPO, "tests", "chipbench",
                           "tiny_ssd_hybrid.json")) as f:
        return json.load(f)


def perturbed(config):
    """Initial weights with the norm scales, the skips and the biases
    moved off their starting values, so that a dropped one would
    show."""
    def moved(key):
        return jax.tree.map(
            lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                  a.shape, a.dtype),
            sh.init(key, config))

    return jax.jit(moved)(jax.random.PRNGKey(3))


def test_the_layer_plan_is_one_period_of_the_list():
    c = published()
    assert sh.layer_plan(c) == 5 * [MAMBA] + [ATTENTION] + 4 * [MAMBA]
    assert [i for i, kind in enumerate(c.layer_types)
            if kind == ATTENTION] == [5, 15, 25, 35]
    assert sh.layer_kinds(c) == {"ssd": 36, "attn_full": 4}
    cut = dataclasses.replace(c, num_layers=20)
    assert sh.layer_kinds(cut) == {"ssd": 18, "attn_full": 2}
    assert sh.make_init_fn(cut).layer_kinds == {"ssd": 18, "attn_full": 2}
    other = sh.ssd_hybrid_tiny(num_layers=6,
                               layer_types=(ATTENTION, MAMBA, MAMBA) * 2)
    assert sh.layer_plan(other) == [ATTENTION, MAMBA, MAMBA]


@pytest.mark.parametrize("depth", [5, 15, 39])
def test_a_depth_that_is_no_whole_number_of_periods_is_refused(depth):
    with pytest.raises(ValueError, match="no whole number of periods"):
        sh.init(jax.random.PRNGKey(0), published(num_layers=depth))


def test_a_list_that_cannot_name_every_layer_is_refused():
    with pytest.raises(ValueError, match="at least as long as the depth"):
        sh.layer_plan(sh.ssd_hybrid_tiny(num_layers=10))
    with pytest.raises(ValueError, match="'mamba' or 'attention'"):
        sh.layer_plan(sh.ssd_hybrid_tiny(
            layer_types=(MAMBA, "full_attention") * 4))
    with pytest.raises(ValueError, match="do not divide"):
        sh.init(jax.random.PRNGKey(0), sh.ssd_hybrid_tiny(num_kv_heads=3))


def test_the_multipliers_have_no_default():
    """A configuration that leaves one out does not get a silent 1."""
    for name in sh.PUBLISHED_MULTIPLIERS:
        some = {k: v for k, v in sh.PUBLISHED_MULTIPLIERS.items()
                if k != name}
        with pytest.raises(TypeError, match=name):
            sh.SsdHybridConfig(**some)
    assert sh.PUBLISHED_MULTIPLIERS == dict(
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=1 / 64, logits_scaling=8.0)


def test_param_count_at_the_published_sizes():
    """ISSUE 57's count of the equations: a Mamba layer 76,182,976, an
    attention layer 60,821,504, a period 746,468,288, the tied table
    205,520,896 once: 3.19 B, the catalog's "3B"; the benchmark's cut
    at two periods."""
    mamba, attention = 76_182_976, 60_821_504
    table = 100352 * 2048
    assert 9 * mamba + attention == 746_468_288
    c = published()
    assert sh.param_count(c) == 36 * mamba + 4 * attention + table + 2048
    assert 3.1e9 < sh.param_count(c) < 3.3e9
    cut = dataclasses.replace(c, num_layers=20)
    assert sh.param_count(cut) == 1_698_459_520
    shapes = jax.eval_shape(sh.make_init_fn(cut), jax.random.PRNGKey(0))
    assert sorted(shapes["layers"], key=int) == [str(j) for j in range(10)]
    assert "lm_head" not in shapes  # the head is the table
    # the two kinds keep their own trees, each stacked over the periods
    mixer = shapes["layers"]["0"]["mixer"]
    assert mixer["in_proj"]["kernel"].shape == (2, 2048, 4096 + 4352 + 64)
    assert mixer["conv"]["kernel"].shape == (2, 4, 4352)
    assert mixer["conv"]["bias"].shape == (2, 4352)
    assert mixer["norm"]["scale"].shape == (2, 4096)
    assert mixer["a_log"].shape == mixer["d_skip"].shape == (2, 64)
    assert shapes["layers"]["5"]["mixer"]["k_proj"]["kernel"].shape == (
        2, 2048, 512)
    assert shapes["layers"]["5"]["mlp"]["gate_up_proj"]["kernel"].shape == (
        2, 2048, 16384)


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_the_module_agrees_with_the_familys_reference(path):
    """Loss and every gradient against ``chipbench/families/
    ssd_hybrid/reference.py`` (float32, the recurrence token by token,
    a dense masked softmax a head) on seeded weights: the chunked form
    as a scan over chunks with XLA's dense attention, and the ``ssd_*``
    and flash kernels in the interpreter. Both sides are float32 and
    differ by the order of their sums: 1e-4 of a gradient's largest
    entry (``A_log``'s is the sum of differences of running sums,
    ``tests/test_ssd.py``: 1e-3)."""
    model = toy()
    config = job.model_config(model, use_kernels=path == "kernels",
                              flash_block_q=32, flash_block_k=32)
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    loss_fn = sh.make_loss_fn(config, head_chunk=32)

    def ref(p):
        return reference.loss(
            model, batch["input_ids"][0], batch["labels"][0],
            p["embed_tokens"]["embedding"], job.reference_layers(p, config),
            p["norm"]["scale"])

    (got, aux), grad = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, None)
    want, grad_want = jax.value_and_grad(ref)(params)
    assert abs(float(got) - float(want)) < 1e-5
    assert 0.01 < float(aux[StepCounter.SSD_DT_MEAN]) < 0.1
    flat = jax.tree_util.tree_leaves_with_path(grad)
    assert len(flat) == (8 + 4) + (4 + 4) + 2
    for (where, a), b in zip(flat, jax.tree.leaves(grad_want)):
        name = jax.tree_util.keystr(where)
        limit = (1e-3 if "a_log" in name else 1e-4) * float(
            jnp.abs(b).max()) + 1e-7
        assert float(jnp.abs(a - b).max()) < limit, name
        assert float(jnp.abs(b).max()) > 0, name


def test_the_tied_tables_gradient_is_the_sum_of_both_uses():
    """The table is read twice, as the embedding and as the head: its
    gradient is the sum of the gradient through each with the other
    held fixed."""
    c = sh.ssd_hybrid_tiny(**F32)
    params = perturbed(c)
    batch = batch_of(c, rows=2)
    table = params["embed_tokens"]["embedding"]

    def loss(as_table, as_head):
        hidden, _ = sh.apply_hidden(
            dict(params, embed_tokens={"embedding": as_table}),
            batch["input_ids"], c)
        logits = (sh._scaled(hidden, c) @ as_head.T).astype(jnp.float32)
        return masked_lm_loss(logits, batch["labels"], 0.0)

    through_table, through_head = jax.grad(loss, argnums=(0, 1))(table,
                                                                 table)
    for head_chunk in (0, 16):
        whole = jax.grad(lambda p: sh.make_loss_fn(
            c, head_chunk=head_chunk)(p, batch, None)[0])(params)[
                "embed_tokens"]["embedding"]
        assert float(jnp.abs(whole - (through_table + through_head)).max()
                     ) < 1e-6
    assert float(jnp.abs(through_table).max()) > 1e-4
    assert float(jnp.abs(through_head).max()) > 1e-4
    # and ``apply`` gives the logits the loss is taken of
    logits = sh.apply(params, batch["input_ids"], c)
    assert abs(float(masked_lm_loss(logits, batch["labels"], 0.0))
               - float(loss(table, table))) < 1e-6


@pytest.mark.parametrize("fault", controls.CONTROLS,
                         ids=[f.replace(" ", "-") for f in controls.CONTROLS])
def test_the_comparison_catches(fault):
    """Each control (ISSUE 57's six and four more), put into the
    reference alone, moves the median token's hidden state away from
    the program's by 10 times this comparison's limit (1e-5 in
    float32) and more (the bf16 state, rounded once a token, reads 29
    times, every other 2,900 times and more): the hidden states are the
    limit that feels a mechanism (the loss at random weights hardly
    does, ``job.py``). Sound, the two sides read 2e-7."""
    model = toy()
    config = job.model_config(model)
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    program = sh.apply_hidden(params, batch["input_ids"], config)[0][0]

    def apart():
        plain = []
        job.reference_loss_of(model, config, params, batch["input_ids"][0],
                              batch["labels"][0], hidden=plain)
        return job.hidden_error(program, plain[0])

    assert apart() < 0.1 * job.HIDDEN_TOL["float32"]
    with controls.applied(model, fault):
        moved = apart()
    print(fault, moved)
    assert not moved <= 10 * job.HIDDEN_TOL["float32"], (fault, moved)
    assert model == toy()  # the control is taken out again


def test_the_counter_is_the_mean_step():
    """``ssd_dt_mean`` is the mean of ``softplus(dt_raw + dt_bias)``:
    a few hundredths at the assumed initialisation (a step log-uniform
    in [1e-3, 1e-1] has a mean of 0.0215; ``dt_raw`` spreads it), and
    ``softplus(0)`` = 0.693 where the bias is left out at small
    weights. The band the chip's reading is held to: 0.015 to 0.06."""
    c = sh.ssd_hybrid_tiny(**F32)
    params = sh.init(jax.random.PRNGKey(0), c)
    batch = batch_of(c, rows=2)
    _, aux = sh.make_loss_fn(c)(params, batch, None)
    assert 0.015 < float(aux[StepCounter.SSD_DT_MEAN]) < 0.06
    wide = published(num_layers=10, vocab_size=512, max_seq_len=256, **F32,
                     shared_intermediate_size=64, use_kernels=False)
    _, aux = sh.make_loss_fn(wide)(
        sh.init(jax.random.PRNGKey(1), wide), batch_of(wide), None)
    assert 0.015 < float(aux[StepCounter.SSD_DT_MEAN]) < 0.06
    bare = jax.tree_util.tree_map_with_path(
        lambda where, a: jnp.zeros_like(a) if "dt_bias" in jax.tree_util
        .keystr(where) or "in_proj" in jax.tree_util.keystr(where) else a,
        params)
    _, aux = sh.make_loss_fn(c)(bare, batch, None)
    assert abs(float(aux[StepCounter.SSD_DT_MEAN]) - np.log(2.0)) < 1e-6
    assert StepCounter.SSD_DT_MEAN in StepCounter.ALL
    assert {DeviceScope.SSD, DeviceScope.SSD_CHUNK} <= set(DeviceScope.ALL)


def test_a_part_runs_under_its_scope():
    c = sh.ssd_hybrid_tiny(**F32, **KERNELS)
    params = sh.init(jax.random.PRNGKey(0), c)
    text = jax.jit(lambda p, ids: sh.apply_hidden(p, ids, c)).lower(
        params, batch_of(c)["input_ids"]).as_text(debug_info=True)
    for scope in (DeviceScope.SSD, DeviceScope.SSD_CHUNK,
                  DeviceScope.ATTN_FULL, DeviceScope.FFN):
        assert f"/{scope}/" in text, scope
    # the cumulative sums are inside the Mamba layer's scope
    assert f"/{DeviceScope.SSD}/" in text[:text.index(
        f"/{DeviceScope.SSD_CHUNK}/") + 20]
    assert "ssd_fwd" in text


@pytest.mark.parametrize("scale", [1.0 / 64, None])
def test_an_explicit_scale_replaces_the_root_in_all_three_kernels(scale):
    """No other model of the benchmark passes ``scale`` to the plain
    flash kernels: at a head of 64 with four query heads a KV head, the
    forward and both backward kernels (in the interpreter) use the
    published 1/64 where it is given and ``head_dim ** -0.5`` where it
    is not, as ``ops/attention_ref.py`` does."""
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(k[0], (1, 8, 128, 64))
    key = jax.random.normal(k[1], (1, 2, 128, 64))
    v = jax.random.normal(k[2], (1, 2, 128, 64))
    weight = jax.random.normal(k[3], (1, 8, 128, 64))

    def kernels(q, k, v):
        return flash_attention(q, k, v, True, scale, 64, 64, True)

    def plain(q, k, v, scale=scale):
        return mha_reference(q, k, v, causal=True, scale=scale)

    def grads(fn):
        return jax.grad(lambda *a: (fn(*a) * weight).sum(),
                        argnums=range(3))(q, key, v)

    def rel(a, b):
        return float(jnp.abs(a - b).max() / jnp.abs(b).max())

    assert rel(kernels(q, key, v), plain(q, key, v)) < 1e-5
    for got, want in zip(grads(kernels), grads(plain)):
        assert rel(got, want) < 1e-4
    # and the two scales are not one: the test would see a kernel that
    # kept the root
    other = 0.125 if scale is not None else 1.0 / 64
    assert rel(plain(q, key, v, other), plain(q, key, v)) > 0.05
    for got, want in zip(grads(lambda *a: plain(*a, other)), grads(plain)):
        assert rel(got, want) > 0.05


def test_rule_set_is_registered_and_names_every_leaf():
    assert RULE_SETS["ssd_hybrid"] is ssd_hybrid_rules
    shapes = jax.eval_shape(sh.make_init_fn(published(num_layers=20)),
                            jax.random.PRNGKey(0))
    sizes = {"data": 1, "fsdp": 2, "tensor": 2}
    rules = ssd_hybrid_rules()
    for path, leaf in _flatten_with_paths(shapes):
        spec = rules.spec_for(path, leaf.shape, sizes)
        if path.startswith("layers/"):  # never the stacked axis
            assert spec[0] is None, (path, spec)
        if path.endswith("scale") or "/conv/" in path:
            assert all(s is None for s in spec), (path, spec)
        elif "in_proj/" in path or "gate_up_proj/" in path:
            assert tuple(spec) == (None, "fsdp", None), (path, spec)
        elif "_proj/" in path:
            assert "fsdp" in spec and "tensor" in spec, (path, spec)
        elif path.endswith(("a_log", "dt_bias", "d_skip")):
            assert tuple(spec) == (None, "tensor"), (path, spec)
        elif leaf.size > 1e6:
            assert "fsdp" in spec, (path, spec)


def test_sharded_on_virtual_devices_gives_the_single_device_loss():
    """``fsdp=2`` on the CPU's virtual devices under the ``ssd_hybrid``
    rules, the ``ssd_*`` and flash kernels under ``shard_map``: the
    first step's loss is the single-device loss, a kernel lands where
    its rule puts it, and the loss falls."""
    c = sh.ssd_hybrid_tiny(**F32, **KERNELS)
    batch = batch_of(c, rows=4)
    loss_fn = sh.make_loss_fn(c, head_chunk=16)
    result = accelerate(
        sh.make_init_fn(c), loss_fn, optax.adam(3e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2),
                          rule_set="ssd_hybrid", remat_policy=""),
        devices=jax.devices()[:4])
    state = result.init_fn(jax.random.PRNGKey(0))
    alone, _ = loss_fn(jax.device_get(state.params), batch, None)
    mamba = state.params["layers"]["0"]["mixer"]
    assert tuple(mamba["in_proj"]["kernel"].sharding.spec)[:2] == (
        None, "fsdp")
    assert tuple(mamba["out_proj"]["kernel"].sharding.spec)[2] == "fsdp"
    assert not any(mamba["conv"]["kernel"].sharding.spec)
    assert tuple(state.params["embed_tokens"]["embedding"].sharding.spec
                 )[1] == "fsdp"
    sharded = result.shard_batch(batch)
    losses = []
    for i in range(6):
        state, metrics = result.train_step(state, sharded,
                                           jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    assert abs(losses[0] - float(alone)) < 1e-5
    assert np.isfinite(losses).all() and losses[-1] < losses[0] * 0.99
    assert all(b < a for a, b in zip(losses, losses[1:]))
    assert 0.0 < float(metrics[StepCounter.SSD_DT_MEAN]) < 0.1
