"""``models/mla_moe.py``: the layer plan, the sizes, the kernel path
against the XLA path, the held set and its counters, and the rule set
on virtual devices."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import mla_moe
from dlrover_tpu.ops import moe
from dlrover_tpu.ops.remat import apply_remat
from dlrover_tpu.parallel.accelerate import accelerate
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.sharding_rules import (
    _flatten_with_paths,
    mla_moe_rules,
)
from dlrover_tpu.parallel.strategy import RULE_SETS, Strategy
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)
KERNELS = dict(use_kernels=True, flash_block_q=32, flash_block_k=32)


def batch_of(config, rows=2, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def test_layer_plan_is_dense_then_experts():
    c = mla_moe.MlaMoeConfig()
    plan = mla_moe.layer_plan(c)
    assert plan == ["dense"] + ["moe"] * 60
    assert mla_moe.layer_kinds(c) == {"dense": 1, "moe": 60}
    assert mla_moe.layer_kinds(mla_moe.mla_moe_tiny(
        num_layers=5, first_k_dense=2)) == {"dense": 2, "moe": 3}
    with pytest.raises(ValueError, match="at least one expert layer"):
        mla_moe.layer_plan(mla_moe.mla_moe_tiny(num_layers=1))


def test_param_count_at_the_published_sizes():
    """A.X-K1 whole: 61 x 187.1M of latent attention, a dense FFN of
    396.4M, 60 x (193 experts of 44.0M and a router of 1.4M), a table
    and a head of 1,174.4M each: 519B, as its card says."""
    count = mla_moe.param_count(mla_moe.MlaMoeConfig())
    mla = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
           + 64 * 128 * 7168)
    moe = 7168 * 192 + 193 * 3 * 7168 * 2048
    norms = 61 * (2 * 7168 + 1536 + 512) + 7168
    assert count == (61 * mla + 3 * 7168 * 18432 + 60 * moe
                     + 2 * 163840 * 7168 + norms)
    assert 518e9 < count < 520e9


def test_a_held_set_holds_its_experts_weights_alone():
    whole = mla_moe.mla_moe_tiny()
    cut = mla_moe.mla_moe_tiny(experts_held=tuple(range(8, 16)))
    shapes = jax.eval_shape(mla_moe.make_init_fn(cut), jax.random.PRNGKey(0))
    moe = shapes["moe_layers"]["moe"]
    assert moe["experts"]["gate"]["kernel"].shape == (2, 8, 64, 32)
    assert moe["router"]["kernel"].shape == (2, 64, 24)  # whole
    one = 3 * 64 * 32
    assert (mla_moe.param_count(whole) - mla_moe.param_count(cut)
            == 2 * 16 * one)
    assert cut.held == tuple(range(8, 16)) and len(whole.held) == 24
    with pytest.raises(ValueError, match="experts_held"):
        mla_moe.init(jax.random.PRNGKey(0),
                     mla_moe.mla_moe_tiny(experts_held=(3, 3, 30)))


@pytest.mark.parametrize("held", [(), tuple(range(8))],
                         ids=["all-held", "8-of-24"])
def test_kernel_path_equals_the_xla_path(held):
    """The Pallas kernels (latent flash, bounded grouped matmuls) in the
    interpreter against XLA's dense attention and einsum experts: the
    loss, the counters and every gradient."""
    xla = mla_moe.mla_moe_tiny(experts_held=held, **F32)
    kernels = dataclasses.replace(xla, **KERNELS)
    params = mla_moe.init(jax.random.PRNGKey(0), xla)
    batch = batch_of(xla)
    out = {}
    for name, c in (("xla", xla), ("kernels", kernels)):
        loss_fn = mla_moe.make_loss_fn(c, head_chunk=16)
        out[name] = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, None)
    (a, aux_a), grad_a = out["xla"]
    (b, aux_b), grad_b = out["kernels"]
    assert abs(float(a) - float(b)) < 1e-5
    # what the router sent is counted alike; only the kernel path has
    # a row buffer, and says at which rung of its ladder each layer ran
    buffered = float(aux_b.pop(StepCounter.MOE_ROWS_BUFFERED))
    assert float(aux_a.pop(StepCounter.MOE_ROWS_BUFFERED)) == 0
    # and only the kernels name an output for the three layers'
    # checkpoints to keep: out [2, 4, 64, 16] and lse, float32
    assert float(aux_b.pop(StepCounter.ATTN_KEPT_BYTES)) == 3 * 34_816
    assert float(aux_a.pop(StepCounter.ATTN_KEPT_BYTES)) == 0
    assert {k: float(v) for k, v in aux_a.items()} == {
        k: float(v) for k, v in aux_b.items()}
    ladder = moe.held_row_ladder(
        2 * xla.max_seq_len, xla.num_experts_per_tok, xla.n_routed_experts,
        len(xla.held), xla.expert_row_factor, xla.expert_block_t)
    assert buffered in {a + b for a in ladder for b in ladder}
    for x, y in zip(jax.tree.leaves(grad_a), jax.tree.leaves(grad_b)):
        assert float(jnp.abs(x - y).max()) < 1e-4 * float(
            jnp.abs(x).max()) + 1e-7


def test_the_counters_count_the_held_experts_rows():
    c = mla_moe.mla_moe_tiny(experts_held=tuple(range(8)), **F32)
    params = mla_moe.init(jax.random.PRNGKey(0), c)
    batch = batch_of(c)
    _, aux = mla_moe.make_loss_fn(c)(params, batch, None)
    # no window layer and no delta-rule or Mamba-2 layer in a latent
    # model: their counters are never here; a learned selection of keys
    # (whose kept bytes stand in place of ``attn_kept_bytes``) and a
    # group limit count theirs where the model has them
    # (test_mla_moe_dsa.py),
    # as noise heads, a band and a bias the step moves do theirs
    # (test_mla_moe_gdla.py)
    ours = set(StepCounter.ALL) - {StepCounter.ATTN_BAND_TILES,
                                   StepCounter.ATTN_BAND_TILES_UNMASKED,
                                   StepCounter.DIFF_LAMBDA_MEAN,
                                   StepCounter.ROUTER_BIAS_ABS,
                                   StepCounter.GDN_NEG_EIG,
                                   StepCounter.SSD_DT_MEAN,
                                   StepCounter.KDA_LOG_DECAY_MEAN} - {
        name for name in StepCounter.ALL
        if name.startswith(("dsa_", "moe_group_", "loop_"))}
    # a plain residual and no prediction module: the rows' counters alone
    assert set(aux) == ours - {
        StepCounter.HC_RES_DEFECT, StepCounter.HC_KERNEL_PASSES,
        StepCounter.MTP_LOSS}
    _, more = mla_moe.make_loss_fn(dataclasses.replace(
        c, hc_mult=2, mtp_layers=1))(mla_moe.init(
            jax.random.PRNGKey(0), dataclasses.replace(
                c, hc_mult=2, mtp_layers=1)), batch, None)
    assert set(more) == ours
    # XLA's dense forms name nothing for a checkpoint to keep
    assert float(aux[StepCounter.ATTN_KEPT_BYTES]) == 0
    held = float(aux[StepCounter.MOE_ROWS_HELD])
    # 2 x 64 tokens, 4 of 24 experts each, 2 expert layers, a third
    # of the experts held: 341 rows if routing were uniform
    assert 150 < held < 600
    assert held / 8 / 2 <= float(aux[StepCounter.MOE_ROWS_MAX]) / 2 <= 128
    assert float(aux[StepCounter.MOE_ROWS_DROPPED]) == 0


@functools.lru_cache(maxsize=None)
def _trained(policy):
    """(config, (loss, aux), gradients, the gradient program's jaxpr
    text) of a latent toy with a prediction module (a dense layer, two
    expert layers and the module's: three scans) on the interpreter's
    kernels under ``policy``: one trace gives the text and the program
    that ran."""
    config = mla_moe.mla_moe_tiny(
        experts_held=tuple(range(8)), mtp_layers=1, remat_policy=policy,
        **F32, **KERNELS)
    args = (mla_moe.init(jax.random.PRNGKey(0), config),
            batch_of(config, seed=13), None)
    traced = jax.jit(jax.value_and_grad(
        mla_moe.make_loss_fn(config, head_chunk=16), has_aux=True)).trace(
            *args)
    return (config,) + traced.lower().compile()(*args) + (
        str(traced.jaxpr),)


@pytest.mark.parametrize("policy", ["full", "dots_saveable", "none"])
def test_a_latent_layers_checkpoint_keeps_out_and_lse(policy, monkeypatch):
    """Under every policy the loss and every gradient are bit for bit
    what the layers and the prediction module give with nothing kept
    (``apply_remat`` as the parent called it); the aux counts ``out``
    and ``lse`` a layer and module; and under ``"full"`` the gradient
    program calls ``flash_mla_fwd`` once a scan where the parent's
    calls it twice."""
    _, (loss, aux), grad, kept = _trained(policy)
    layer = 2 * 4 * 64 * (16 * 4 + 4)  # out [2, 4, 64, 16] and lse, float32
    assert float(aux[StepCounter.ATTN_KEPT_BYTES]) == (
        0 if policy == "none" else (3 + 1) * layer)
    monkeypatch.setattr(mla_moe, "apply_remat", lambda fn, policy, keep: (
        apply_remat(fn, policy)))
    _, (loss_w, aux_w), grad_w, replayed = _trained.__wrapped__(policy)
    assert float(loss) == float(loss_w)
    assert float(aux[StepCounter.MTP_LOSS]) == float(
        aux_w[StepCounter.MTP_LOSS])
    jax.tree.map(np.testing.assert_array_equal, grad, grad_w)
    # dots_saveable keeps the projections' products and replays the
    # kernel between them as "full" does
    replays = 0 if policy == "none" else 3
    assert (kept.count("name=flash_mla_fwd"),
            replayed.count("name=flash_mla_fwd")) == (3, 3 + replays)
    assert kept.count("name=flash_mla_bwd") == 3


def test_a_layer_with_an_indexer_keeps_what_it_kept(monkeypatch):
    """Which names a checkpoint keeps follows the op its layer calls:
    the selected attention's and its indexer's loss's with an indexer,
    as before; the latent flash kernel's without, the prediction
    module's checkpoint as the layers'."""
    kept = []
    monkeypatch.setattr(mla_moe, "apply_remat", lambda fn, policy, keep: (
        kept.append(keep) or apply_remat(fn, policy, keep=keep)))
    sparse = mla_moe.mla_moe_tiny(
        index_n_heads=4, index_head_dim=16, index_topk=24, index_block_q=32,
        index_block_k=32, sparse_block_q=32, **F32)
    plain = mla_moe.mla_moe_tiny(mtp_layers=1, **F32)
    for c, names, checkpoints in (
            (sparse, ("dsa_attn_out", "dsa_attn_lse", "dsa_index_dqi",
                      "dsa_index_dki", "dsa_index_dw"), 2),
            (plain, ("flash_attn_out", "flash_attn_lse"), 3)):
        del kept[:]
        jax.eval_shape(mla_moe.make_loss_fn(c, head_chunk=16),
                       jax.eval_shape(lambda c=c: mla_moe.init(
                           jax.random.PRNGKey(0), c)), batch_of(c), None)
        assert kept == [names] * checkpoints, c


def test_what_the_cells_latent_layers_keep():
    """``ATTN_KEPT_BYTES`` at the three committed configurations, by
    arithmetic: a layer's and a prediction module's ``out`` [B, H, S,
    128] in bf16 and ``lse`` [B, H, S] in float32 (ISSUE 58); nothing
    where there is no remat."""
    from chipbench.families.mla_moe import job as axk1
    from chipbench.families.mla_moe_gdla import job as motif3
    from chipbench.families.mla_moe_hc import job as xing4

    for job, name, calls, step in (
            (axk1, "a.x-k1-ep24-1chip", 5, 170_393_600),
            (xing4, "xing4.0-29b-a4b-ep4-1chip", 8, 545_259_520),
            (motif3, "motif-3-beta-1chip", 6, 1_022_361_600)):
        with open(os.path.join(REPO, "chipbench", "configs",
                               name + ".json")) as f:
            model = json.load(f)
        c, a = job.model_config(model), model["assumed"]
        assert c.remat_policy == "full" and c.use_kernels, name
        assert not c.index_n_heads and c.num_layers + c.mtp_layers == calls
        rows = a["batch"] * c.num_heads * a["seq_len"]
        layer = rows * (c.v_head_dim * jnp.dtype(c.compute_dtype).itemsize
                        + 4)
        assert calls * layer == step, name
        assert float(jnp.float32(step)) == step  # exact as counted
    c, (_, aux), _, _ = _trained("none")
    assert c.use_kernels and float(aux[StepCounter.ATTN_KEPT_BYTES]) == 0


def test_a_dropped_row_is_counted():
    """A row buffer a twentieth of what uniform routing needs: rows
    fall past it, the counter says how many, and the loss is that of
    the rows that were computed."""
    c = mla_moe.mla_moe_tiny(experts_held=tuple(range(8)),
                             expert_row_factor=0.05, **F32, **KERNELS)
    params = mla_moe.init(jax.random.PRNGKey(0), c)
    loss, aux = mla_moe.make_loss_fn(c)(params, batch_of(c), None)
    assert float(aux[StepCounter.MOE_ROWS_DROPPED]) > 0
    assert np.isfinite(float(loss))


def test_the_parts_carry_their_scopes():
    """Every part's operations sit under its ``named_scope`` in the
    lowered program, the kernels under theirs."""
    c = mla_moe.mla_moe_tiny(experts_held=tuple(range(8)), **F32, **KERNELS)
    params = mla_moe.init(jax.random.PRNGKey(0), c)
    text = jax.jit(mla_moe.make_loss_fn(c)).lower(
        params, batch_of(c), None).as_text(debug_info=True)
    for scope in (DeviceScope.MLA, DeviceScope.MOE_ROUTER,
                  DeviceScope.MOE_SHARED, DeviceScope.MOE_EXPERTS,
                  DeviceScope.FFN):
        assert f"/{scope}/" in text, scope


def test_fused_head_equals_the_plain_head():
    c = mla_moe.mla_moe_tiny(**F32)
    params = mla_moe.init(jax.random.PRNGKey(0), c)
    batch = batch_of(c)
    plain, _ = mla_moe.make_loss_fn(c)(params, batch, None)
    fused, _ = mla_moe.make_loss_fn(c, head_chunk=16)(params, batch, None)
    assert abs(float(plain) - float(fused)) < 1e-5
    logits = mla_moe.apply(params, batch["input_ids"], c)
    assert logits.shape == (2, 64, 256) and logits.dtype == jnp.float32


def test_rule_set_is_registered_and_names_every_leaf():
    assert RULE_SETS["mla_moe"] is mla_moe_rules
    shapes = jax.eval_shape(mla_moe.make_init_fn(mla_moe.MlaMoeConfig(
        num_layers=5, experts_held=tuple(range(8)), vocab_size=20480)),
        jax.random.PRNGKey(0))
    sizes = {"data": 1, "fsdp": 2, "tensor": 2}
    rules = mla_moe_rules()
    for path, leaf in _flatten_with_paths(shapes):
        spec = rules.spec_for(path, leaf.shape, sizes)
        if path.split("/")[0] in ("dense_layers", "moe_layers"):
            assert spec[0] is None, (path, spec)  # never the stacked axis
        if "experts/" in path:  # whole on the axes the kernel reads
            assert "tensor" not in spec and spec[1] is None, (path, spec)
            assert "fsdp" in spec, (path, spec)
        elif "router" in path or path.endswith("scale"):
            assert all(s is None for s in spec), (path, spec)
        elif leaf.size > 1e6:
            assert "fsdp" in spec, (path, spec)


def test_trains_sharded_on_virtual_devices():
    """fsdp x tensor on the CPU's virtual devices under the ``mla_moe``
    rules, the latent kernels under ``shard_map``: the loss falls, and
    a kernel lands where its rule puts it."""
    c = mla_moe.mla_moe_tiny(experts_held=tuple(range(8)), **F32, **KERNELS)
    batch = batch_of(c, rows=4)
    result = accelerate(
        mla_moe.make_init_fn(c), mla_moe.make_loss_fn(c, head_chunk=16),
        optax.adam(3e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                          rule_set="mla_moe", remat_policy=""))
    state = result.init_fn(jax.random.PRNGKey(0))
    layer = state.params["moe_layers"]
    assert tuple(layer["attn"]["q_b_proj"]["kernel"].sharding.spec) == (
        None, "fsdp", "tensor")
    assert tuple(layer["moe"]["experts"]["down"]["kernel"].sharding.spec
                 ) == (None, None, None, "fsdp")
    sharded = result.shard_batch(batch)
    losses = []
    for i in range(8):
        state, metrics = result.train_step(state, sharded,
                                           jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] * 0.95
    assert float(metrics[StepCounter.MOE_ROWS_DROPPED]) == 0


def test_init_fn_carries_the_layer_kinds():
    init_fn = mla_moe.make_init_fn(mla_moe.mla_moe_tiny())
    assert init_fn.layer_kinds == {"dense": 1, "moe": 2}


def test_the_example_reuses_the_llama_examples_step_lines():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples"))
    import train_llama
    import train_mla_moe

    assert train_mla_moe.StepLines is train_llama.StepLines
    assert train_mla_moe.synthetic_batches is train_llama.synthetic_batches
