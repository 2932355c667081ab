"""``models/kda_mla_moe.py``: the layer plan from ``layer_group_size``,
the sizes, the module against the family's plain reference (the rule's
chain as a scan and the kernels in the interpreter), the shares of an
expert layer adding up to the uncut layer, the faults the comparison has
to catch, the counters, the bias the step moves, the scopes, and the
rule set on virtual devices."""

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

import kda_mla_moe_controls as controls  # noqa: E402
from chipbench.families.kda_mla_moe import job, reference  # noqa: E402
from dlrover_tpu.models import kda_mla_moe as km  # noqa: E402
from dlrover_tpu.ops import moe  # noqa: E402
from dlrover_tpu.parallel.accelerate import StepBuffers, accelerate  # noqa: E402
from dlrover_tpu.parallel.mesh import MeshPlan  # noqa: E402
from dlrover_tpu.parallel.sharding_rules import (  # noqa: E402
    _flatten_with_paths,
    kda_mla_moe_rules,
)
from dlrover_tpu.parallel.strategy import RULE_SETS, Strategy  # noqa: E402
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter  # noqa: E402

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)
KERNELS = dict(use_kernels=True, flash_block_q=32, flash_block_k=32)
KDA, MLA = km.KDA, km.MLA


def batch_of(config, rows=1, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def toy():
    """The family's toy configuration (a leading dense KDA layer and
    one group of two KDA, an MLA and a KDA expert layer, float32): what
    the reference reads."""
    with open(os.path.join(REPO, "tests", "chipbench",
                           "tiny_kda_mla_moe.json")) as f:
        return json.load(f)


def perturbed(config):
    """Initial weights with the norm scales moved off their starting
    values, so that a dropped one would show."""
    def moved(key):
        return jax.tree.map(
            lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                  a.shape, a.dtype),
            km.init(key, config))

    return jax.jit(moved)(jax.random.PRNGKey(3))


def test_the_layer_plan_is_one_groups_runs_from_the_first_expert_layer_on():
    c = km.KdaMlaMoeConfig(num_layers=7, first_k_dense=1)
    assert km.mixer_kinds(c, 7) == 5 * [KDA] + [MLA] + [KDA]
    assert km.layer_plan(c) == [(KDA, 4), (MLA, 1), (KDA, 1)]
    assert [km.layer_slot(c, i) for i in range(6)] == [
        ("0", 0, 0), ("0", 0, 1), ("0", 0, 2), ("0", 0, 3), ("1", 0, 0),
        ("2", 0, 0)]
    assert km.layer_kinds(c) == {"kda": 6, "mla": 1, "dense": 1, "moe": 6}
    assert km.make_init_fn(c).layer_kinds == km.layer_kinds(c)
    # the published depth: 35 + 7, five KDA then one MLA a group
    kinds = km.mixer_kinds(km.KdaMlaMoeConfig(), 42)
    assert (kinds.count(KDA), kinds.count(MLA)) == (35, 7)
    assert [i for i, k in enumerate(kinds) if k == MLA] == [
        5, 11, 17, 23, 29, 35, 41]
    two = km.KdaMlaMoeConfig(num_layers=14, first_k_dense=2)
    assert km.layer_plan(two) == [(KDA, 3), (MLA, 1), (KDA, 2)]
    assert km.layer_slot(two, 11) == ("2", 1, 1)
    assert km.layer_plan(km.kda_mla_moe_tiny()) == [(KDA, 2), (MLA, 1),
                                                    (KDA, 1)]


@pytest.mark.parametrize("overrides,match", [
    (dict(num_layers=42, first_k_dense=2), "no whole number of periods"),
    (dict(num_layers=8, first_k_dense=1), "no whole number of periods"),
    (dict(num_layers=12, first_k_dense=6), "all KDA"),
    (dict(num_layers=2, first_k_dense=2), "at least one expert layer"),
    (dict(num_layers=7, first_k_dense=1, router_bias_rate=0.0),
     "router_bias_rate is positive"),
])
def test_the_plan_refuses_what_is_not_written(overrides, match):
    with pytest.raises(ValueError, match=match):
        km.layer_plan(km.KdaMlaMoeConfig(**overrides))


def test_param_count_at_the_published_sizes():
    """ISSUE 62's count of the equations: a KDA mixer 63.05 M, an MLA
    mixer 31.97 M, the dense FFN 47.19 M, an expert 5.90 M, the router
    1.31 M; the benchmark's cut (a dense KDA layer, five KDA and one
    MLA expert layers with 32 of 512 experts held, an eighth of the
    vocabulary) 1,733.8 M, and 1,167.6 M with 16 held."""
    kda = 6 * 2560 * 4096 + 2560 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
           + 4096 * 2560 + 512 + 192 + 128 + 64)
    assert round(kda / 1e6, 2) == 63.05 and round(mla / 1e6, 2) == 31.97
    expert, router = 3 * 2560 * 768, 2560 * 512
    cut = km.KdaMlaMoeConfig(num_layers=7, first_k_dense=1,
                             vocab_size=19648,
                             experts_held=tuple(range(32)))
    moe_ffn = router + 33 * expert
    assert km.param_count(cut) == (
        6 * kda + mla + 7 * 2 * 2560 + 3 * 2560 * 6144 + 6 * moe_ffn
        + 2 * 19648 * 2560 + 2560) == 1_733_803_328
    assert km.param_count(dataclasses.replace(
        cut, experts_held=tuple(range(16)))) == 1_733_803_328 - 6 * 16 * expert
    shapes = jax.eval_shape(km.make_init_fn(cut), jax.random.PRNGKey(0))
    assert sorted(shapes["layers"], key=int) == ["0", "1", "2"]
    # the two kinds keep their own trees, each stacked over the groups
    # and over its run's layers
    mixer = shapes["layers"]["0"]["mixer"]
    assert mixer["f_proj"]["kernel"].shape == (1, 4, 2560, 4096)
    assert mixer["q_conv"]["kernel"].shape == (1, 4, 4, 4096)
    assert mixer["a_log"].shape == (1, 4, 32)
    assert mixer["dt_bias"].shape == (1, 4, 4096)
    assert mixer["o_norm"]["scale"].shape == (1, 4, 128)
    assert shapes["layers"]["2"]["mixer"]["f_proj"]["kernel"].shape == (
        1, 1, 2560, 4096)
    latent = shapes["layers"]["1"]["mixer"]
    assert "q_a_proj" not in latent
    assert latent["q_proj"]["kernel"].shape == (1, 1, 2560, 32 * 192)
    assert latent["g_proj"]["kernel"].shape == (1, 1, 2560, 32)
    assert shapes["layers"]["1"]["moe"]["experts"]["gate"]["kernel"
                                                           ].shape == (
        1, 1, 32, 2560, 768)
    assert shapes["dense_layers"]["mlp"]["gate_proj"]["kernel"].shape == (
        1, 2560, 6144)
    assert "moe" not in shapes["dense_layers"]


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_the_module_agrees_with_the_familys_reference(path):
    """Loss and every gradient against ``chipbench/families/
    kda_mla_moe/reference.py`` (float32, the rule token by token, a
    dense masked softmax a head, the experts in a loop) on seeded
    weights: the chain as a scan over chunks with XLA's dense attention
    and the einsum experts, and the ``kda_*``, flash and grouped-matmul
    kernels in the interpreter. Both sides are float32 and differ by
    the order of their sums: 2e-4 of a gradient's largest entry (the
    gate's ``a_log`` and ``dt_bias`` through the sub-chunk's factors:
    1e-3)."""
    model = toy()
    config = job.model_config(model, use_kernels=path == "kernels",
                              flash_block_q=32, flash_block_k=32)
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    loss_fn = km.make_loss_fn(config, head_chunk=32)

    def ref(p):
        return reference.loss(
            model, batch["input_ids"][0], batch["labels"][0],
            p["embed_tokens"]["embedding"], job.reference_layers(p, config),
            p["norm"]["scale"], p["lm_head"]["kernel"])

    (got, aux), grad = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, None)
    want, grad_want = jax.value_and_grad(ref)(params)
    assert abs(float(got) - float(want)) < 1e-5
    assert float(aux[StepCounter.MOE_ROWS_DROPPED]) == 0
    assert -0.2 < float(aux[StepCounter.KDA_LOG_DECAY_MEAN]) < -0.001
    flat = jax.tree_util.tree_leaves_with_path(grad)
    for (where, a), b in zip(flat, jax.tree.leaves(grad_want)):
        name = jax.tree_util.keystr(where)
        limit = (1e-3 if "a_log" in name or "dt_bias" in name else 2e-4
                 ) * float(jnp.abs(b).max()) + 1e-7
        assert float(jnp.abs(a - b).max()) < limit, name
        assert float(jnp.abs(b).max()) > 0, name


def test_the_shares_add_up_to_the_uncut_layer():
    """An expert layer cut four ways: each share computes the whole
    router, the shared expert and its own eight of 32 routed experts
    (``experts_held``); the shares' routed parts, with the shared
    expert counted once, add up to the uncut reference's expert layer
    (all 32 held)."""
    whole = km.kda_mla_moe_tiny(**F32)
    params = perturbed(whole)
    layer = jax.tree.map(lambda a: a[0, 0], params["layers"]["0"])["moe"]
    z = jax.random.normal(jax.random.PRNGKey(8), (1, 64, 64))
    model = toy()
    w = {"w_router": layer["router"]["kernel"],
         "router_bias": jnp.zeros((32,)),
         "shared": job._named(layer["shared"], job.GLU_NAMES),
         "experts": job._named(layer["experts"], job.EXPERT_NAMES)}
    with jax.default_matmul_precision("highest"):
        want, _, _ = reference.expert_layer(z[0], w, model,
                                            held=list(range(32)))
        shared = reference.shared_expert(z[0], w["shared"])
    total = jnp.zeros_like(want)
    for share in range(4):
        held = tuple(range(8 * share, 8 * share + 8))
        c = dataclasses.replace(whole, experts_held=held)
        mine = dict(layer, experts=jax.tree.map(
            lambda a: a[8 * share:8 * share + 8], layer["experts"]))
        y, _, stats = km._moe(z, mine, c, None, jnp.zeros((32,)))
        assert float(stats["rows_dropped"]) == 0
        total = total + (y[0] - shared)
    assert float(jnp.abs(total + shared - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    # and one share alone is not the layer
    assert float(jnp.abs(y[0] - want).max()) > 1e-2 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("fault", controls.CONTROLS,
                         ids=[f.replace(" ", "-") for f in controls.CONTROLS])
def test_the_comparison_catches(fault):
    """Each control, put into the reference alone, moves one of the
    three hidden-state numbers of ``correct`` (the final hidden states,
    the last KDA layer's mixer alone, the MLA layer's mixer alone) away
    from the program's by 10 times this comparison's limits in float32
    and more. Sound, the three read under a tenth of their limits."""
    model = toy()
    config = job.model_config(model)
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    ids, labels = batch["input_ids"][0], batch["labels"][0]
    program = km.apply_hidden(params, batch["input_ids"], config)[0][0]

    def apart():
        plain, mixers = [], {}
        job.reference_loss_of(model, config, params, ids, labels,
                              hidden=plain, mixers=mixers)
        index, read, gave = mixers[KDA]
        p = job.program_layer(params, config, index)[0]["mixer"]
        kda = job.hidden_error(km.kda_mixer(read[None], p, config)[0][0],
                               gave)
        index, read, gave = mixers[MLA]
        p = job.program_layer(params, config, index)[0]["mixer"]
        mla = job.hidden_error(km.mla_mixer(
            read[None], p, config, km.rotary_tables(64, config))[0], gave)
        return (job.hidden_error(program, plain[0]) / job.HIDDEN_TOL[
            "float32"], kda / job.KDA_TOL["float32"],
            mla / job.MLA_TOL["float32"])

    assert max(apart()) < 0.1
    with controls.applied(model, fault):
        moved = apart()
    print(fault, moved)
    assert not max(moved) <= 10, (fault, moved)
    assert model == toy()  # the control is taken out again


def test_the_counters_are_the_gates_and_the_group_limits():
    """``kda_log_decay_mean`` is the mean of ``-5 sigmoid(exp(A_log) (u
    W_f + dt_bias))``: inside (-5, 0) and a few hundredths under 0 at
    the assumed initialisation; the group limit lets a token reach this
    chip's experts (all in group 0) only where group 0 is among its two
    kept of four."""
    c = km.kda_mla_moe_tiny(**F32, experts_held=tuple(range(8)))
    params = km.init(jax.random.PRNGKey(0), c)
    batch = batch_of(c, rows=2)
    _, aux = km.make_loss_fn(c)(params, batch, None)
    assert -0.2 < float(aux[StepCounter.KDA_LOG_DECAY_MEAN]) < -0.001
    reach = float(aux[StepCounter.MOE_GROUP_REACH]) / float(
        aux[StepCounter.MOE_GROUP_TOKENS])
    assert float(aux[StepCounter.MOE_GROUP_TOKENS]) == 4 * 2 * 64
    assert 0.3 < reach < 0.7
    load = aux[km.ROUTER_LOAD]
    assert jax.tree.map(lambda a: a.shape, load) == {
        "0": (1, 2, 32), "1": (1, 1, 32), "2": (1, 1, 32)}
    # every layer's own: each a token's four selections
    for rows in jax.tree.leaves(load):
        np.testing.assert_array_equal(rows.sum(axis=-1), 2 * 64 * 4)
    assert float(aux[StepCounter.ATTN_KEPT_BYTES]) == 0  # XLA's forms


def test_the_step_moves_the_bias_by_each_layers_own_load():
    """``TrainState.buffers``: a row a layer under its run's key, the
    step returns the bias moved by its own loads (the rule applied to the
    loss function's aux by hand gives the same), and the rules shard
    the buffer as they would the parameter: whole."""
    c = km.kda_mla_moe_tiny(**F32, experts_held=tuple(range(8)))
    batch = batch_of(c, rows=2)
    loss_fn = km.make_loss_fn(c, head_chunk=32)
    assert isinstance(loss_fn.step_buffers, StepBuffers)
    result = accelerate(
        km.make_init_fn(c), loss_fn, optax.adam(1e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=1, fsdp=1),
                          rule_set="kda_mla_moe", remat_policy=""),
        devices=jax.devices()[:1])
    state = result.init_fn(jax.random.PRNGKey(0))

    def bias(s, r):
        return s.buffers["layers"][str(r)]["moe"]["router"]["bias"]

    assert bias(state, 0).shape == (1, 2, 32) and bias(state, 1).shape == (
        1, 1, 32) and bias(state, 1).dtype == jnp.float32
    assert not any(np.asarray(bias(state, r)).any() for r in range(3))
    _, aux = loss_fn(state.params, batch, None, state.buffers)
    load = aux[km.ROUTER_LOAD]
    state, metrics = result.train_step(state, result.shard_batch(batch),
                                       jax.random.PRNGKey(1))
    for r in range(3):
        np.testing.assert_allclose(bias(state, r), moe.selection_bias_update(
            jnp.zeros_like(load[str(r)]), load[str(r)], c.router_bias_rate),
            atol=1e-9)
    # two layers of a run saw different tokens' hidden states
    assert np.abs(np.asarray(load["0"][0, 0] - load["0"][0, 1])).sum() > 0
    assert 0 < float(metrics["router_bias_abs"]) <= c.router_bias_rate
    assert km.ROUTER_LOAD not in metrics
    spec = result.state_sharding.buffers["layers"]["1"]["moe"]["router"][
        "bias"].spec
    assert all(axis is None for axis in spec)


def test_a_part_runs_under_its_scope():
    c = km.kda_mla_moe_tiny(**F32, **KERNELS,
                            experts_held=tuple(range(8)),
                            expert_row_factor=8.0)
    batch = batch_of(c)
    params = km.init(jax.random.PRNGKey(0), c)
    loss_fn = km.make_loss_fn(c)
    text = jax.jit(jax.grad(loss_fn, has_aux=True)).lower(
        params, batch, None).as_text(debug_info=True)
    for scope in (DeviceScope.KDA, DeviceScope.MLA,
                  DeviceScope.ATTN_GATE, DeviceScope.MOE_ROUTER,
                  DeviceScope.MOE_GROUPS, DeviceScope.MOE_EXPERTS,
                  DeviceScope.FFN):
        # a scope's own name, or inside a transform's: ``jvp(kda)/``
        assert f"{scope}/" in text or f"{scope})/" in text, scope
    # on the kernels a chunk is prepared inside ``kda_rule_fwd`` and
    # prepared again and differentiated inside ``kda_rule_bwd``: XLA
    # runs nothing of the rule under ``kda_chunk``, forward (PR 63) or
    # backward (PR 64); on the scan the two steps' preparation is there
    assert "kda_rule_bwd" in text and "kda_chunk" not in text
    forward = jax.jit(loss_fn).lower(params, batch, None).as_text(
        debug_info=True)
    assert "kda_rule_fwd" in forward and "kda_chunk" not in forward
    plain = km.make_loss_fn(km.kda_mla_moe_tiny(
        **F32, experts_held=tuple(range(8)), expert_row_factor=8.0))
    assert "kda_chunk/" in jax.jit(plain).lower(params, batch, None).as_text(
        debug_info=True)
    assert {DeviceScope.KDA, DeviceScope.KDA_CHUNK} <= set(DeviceScope.ALL)
    assert StepCounter.KDA_LOG_DECAY_MEAN in StepCounter.ALL


def test_rule_set_is_registered_and_names_every_leaf():
    assert RULE_SETS["kda_mla_moe"] is kda_mla_moe_rules
    shapes = jax.eval_shape(km.make_init_fn(km.KdaMlaMoeConfig(
        num_layers=7, first_k_dense=1, experts_held=tuple(range(32)))),
        jax.random.PRNGKey(0))
    sizes = {"data": 1, "fsdp": 2, "tensor": 2}
    rules = kda_mla_moe_rules()
    for path, leaf in _flatten_with_paths(shapes):
        spec = tuple(rules.spec_for(path, leaf.shape, sizes))
        # never a stacked axis: a run's two, the dense stack's one
        lead = 2 if path.startswith("layers/") else (
            1 if path.startswith("dense_layers/") else 0)
        assert spec[:lead] == (None,) * lead, (path, spec)
        if path.endswith("scale") or "router/" in path:
            assert all(s is None for s in spec), (path, spec)
        elif "experts/" in path:
            assert spec[lead] is None and "fsdp" in spec and (
                "tensor" not in spec), (path, spec)
        elif "kv_a_proj/" in path:
            assert spec[lead:] == ("fsdp", None), (path, spec)
        elif "_proj/" in path:
            assert "fsdp" in spec and "tensor" in spec, (path, spec)
        elif "_conv/" in path:
            assert spec[lead:] == (None, "tensor"), (path, spec)
        elif path.endswith(("a_log", "dt_bias")):
            assert spec[lead:] == ("tensor",), (path, spec)
        elif leaf.size > 1e6:
            assert "fsdp" in spec, (path, spec)


def test_sharded_on_virtual_devices_gives_the_single_device_loss():
    """``fsdp=2`` on the CPU's virtual devices under the
    ``kda_mla_moe`` rules, the ``kda_*``, flash and grouped-matmul
    kernels under ``shard_map``: the first step's loss is the
    single-device loss, a kernel lands where its rule puts it, and the
    loss falls."""
    c = km.kda_mla_moe_tiny(**F32, **KERNELS,
                            experts_held=tuple(range(8)),
                            expert_row_factor=8.0)
    batch = batch_of(c, rows=4)
    loss_fn = km.make_loss_fn(c, head_chunk=16)
    result = accelerate(
        km.make_init_fn(c), loss_fn, optax.adam(3e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2),
                          rule_set="kda_mla_moe", remat_policy=""),
        devices=jax.devices()[:4])
    state = result.init_fn(jax.random.PRNGKey(0))
    alone, _ = loss_fn(jax.device_get(state.params), batch, None,
                       jax.device_get(state.buffers))
    mixer = state.params["layers"]["0"]["mixer"]
    assert tuple(mixer["f_proj"]["kernel"].sharding.spec)[:3] == (
        None, None, "fsdp")
    assert tuple(mixer["o_proj"]["kernel"].sharding.spec)[3] == "fsdp"
    assert tuple(state.params["layers"]["1"]["mixer"]["kv_a_proj"][
        "kernel"].sharding.spec)[:3] == (None, None, "fsdp")
    assert tuple(state.params["dense_layers"]["mixer"]["f_proj"][
        "kernel"].sharding.spec)[:2] == (None, "fsdp")
    sharded = result.shard_batch(batch)
    losses = []
    for i in range(6):
        state, metrics = result.train_step(state, sharded,
                                           jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    assert abs(losses[0] - float(alone)) < 1e-5
    assert np.isfinite(losses).all() and losses[-1] < losses[0] * 0.99
    assert -0.2 < float(metrics[StepCounter.KDA_LOG_DECAY_MEAN]) < 0.0
    assert float(metrics[StepCounter.MOE_ROWS_DROPPED]) == 0
