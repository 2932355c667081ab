"""``models/gqa_moe.py``: the layer plan from the two lists, the sizes,
the module against the family's plain reference (XLA path and the
kernels in the interpreter), the kinds of layer, the router's input,
the shares of the held experts, and the rule set on virtual devices."""

import copy
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.families.gqa_moe import job, reference  # noqa: E402
from dlrover_tpu.models import gqa_moe  # noqa: E402
from dlrover_tpu.models.common import rms_norm  # noqa: E402
from dlrover_tpu.ops import moe  # noqa: E402
from dlrover_tpu.ops.flash_attention import (  # noqa: E402
    band_walk,
    window_tiles,
)
from dlrover_tpu.ops.remat import apply_remat  # noqa: E402
from dlrover_tpu.parallel.accelerate import accelerate  # noqa: E402
from dlrover_tpu.parallel.mesh import MeshPlan  # noqa: E402
from dlrover_tpu.parallel.sharding_rules import (  # noqa: E402
    _flatten_with_paths,
    gqa_moe_rules,
)
from dlrover_tpu.parallel.strategy import RULE_SETS, Strategy  # noqa: E402
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter  # noqa: E402

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)
KERNELS = dict(use_kernels=True, flash_block_q=32, flash_block_k=32)
HELD = tuple(range(8))


def batch_of(config, rows=1, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def toy():
    """The family's toy configuration (two periods of a full and a
    window layer, 8 of 24 experts held, float32): what the reference
    reads."""
    with open(os.path.join(REPO, "tests", "chipbench",
                           "tiny_gqa_moe.json")) as f:
        return json.load(f)


def perturbed(config):
    """Initial weights with the norm scales moved off 1, so that a
    dropped norm would show."""
    return jax.jit(lambda key: jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                              a.shape, a.dtype),
        gqa_moe.init(key, config)))(jax.random.PRNGKey(3))


def test_the_layer_plan_is_one_period_of_the_two_lists():
    c = gqa_moe.GqaMoeConfig()
    assert gqa_moe.layer_plan(c) == [(0, 0), (1, 1), (1, 1), (1, 1)]
    assert gqa_moe.layer_kinds(c) == {"attn_full": 13, "attn_window": 39}
    cut = dataclasses.replace(c, num_layers=12)
    assert gqa_moe.layer_kinds(cut) == {"attn_full": 3, "attn_window": 9}
    # the lists need not agree: a windowed layer without rotary
    mixed = gqa_moe.gqa_moe_tiny(num_layers=6, window_layout=(0, 1, 1) * 2,
                                 rope_layout=(0, 0, 1) * 2)
    assert gqa_moe.layer_plan(mixed) == [(0, 0), (1, 0), (1, 1)]
    assert gqa_moe.make_init_fn(mixed).layer_kinds == {
        "attn_full": 2, "attn_window": 4}


@pytest.mark.parametrize("depth", [3, 6, 50])
def test_a_depth_that_is_no_whole_number_of_periods_is_refused(depth):
    with pytest.raises(ValueError, match="no whole number of periods"):
        gqa_moe.layer_plan(dataclasses.replace(
            gqa_moe.GqaMoeConfig(), num_layers=depth))


def test_lists_that_cannot_name_every_layer_are_refused():
    with pytest.raises(ValueError, match="equally long"):
        gqa_moe.layer_plan(gqa_moe.gqa_moe_tiny(rope_layout=(0, 1)))
    with pytest.raises(ValueError, match="at least as long as the depth"):
        gqa_moe.layer_plan(gqa_moe.gqa_moe_tiny(num_layers=10))
    with pytest.raises(ValueError, match="experts_held"):
        gqa_moe.init(jax.random.PRNGKey(0),
                     gqa_moe.gqa_moe_tiny(experts_held=(3, 3, 30)))


def test_param_count_at_the_published_sizes():
    """SmallThinker whole: 52 x (21.0M of attention, a router of 0.16M,
    64 experts of 5.9M, two norms) and a table and a head of 389M
    each: 21.5B, as its name says."""
    count = gqa_moe.param_count(gqa_moe.GqaMoeConfig())
    layer = (2 * 2560 * (28 + 4) * 128 + 2560 * 64
             + 64 * 3 * 2560 * 768 + 2 * 2560)
    assert count == 52 * layer + 2 * 151936 * 2560 + 2560
    assert 21.4e9 < count < 21.6e9
    # a held set holds its experts' weights alone, behind a whole router
    cut = gqa_moe.gqa_moe_tiny(experts_held=HELD)
    shapes = jax.eval_shape(gqa_moe.make_init_fn(cut), jax.random.PRNGKey(0))
    assert sorted(shapes["layers"]) == ["0", "1"]  # positions of a period
    moe_at = shapes["layers"]["1"]["moe"]  # [periods, held, in, out]
    assert moe_at["experts"]["gate"]["kernel"].shape == (2, 8, 64, 32)
    assert moe_at["router"]["kernel"].shape == (2, 64, 16)
    assert (gqa_moe.param_count(gqa_moe.gqa_moe_tiny())
            - gqa_moe.param_count(cut)) == 4 * 8 * 3 * 64 * 32


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_the_module_agrees_with_the_familys_reference(path):
    """Loss and every gradient against ``chipbench/families/gqa_moe/
    reference.py`` (float32, dense masked softmax a head, the held
    experts as a loop) on seeded weights: XLA's dense attention and
    einsum experts, and the Pallas kernels (plain and windowed flash,
    bounded grouped matmuls) in the interpreter."""
    model = toy()
    config = job.model_config(model, use_kernels=path == "kernels",
                              flash_block_q=32, flash_block_k=32)
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    loss_fn = gqa_moe.make_loss_fn(config, head_chunk=32)

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
    flat = jax.tree_util.tree_leaves_with_path(grad)
    assert len(flat) == 2 * 10 + 3
    for (where, a), b in zip(flat, jax.tree.leaves(grad_want)):
        limit = 1e-4 * float(jnp.abs(b).max()) + 1e-7
        assert float(jnp.abs(a - b).max()) < limit, jax.tree_util.keystr(
            where)
        assert float(jnp.abs(b).max()) > 0, jax.tree_util.keystr(where)


@functools.lru_cache(maxsize=None)
def _trained(policy):
    """(config, weights, batch, (loss, aux), gradients) of the toy on
    the interpreter's kernels under ``policy``."""
    config = job.model_config(toy(), use_kernels=True, remat_policy=policy,
                              flash_block_q=32, flash_block_k=32)
    params = perturbed(config)
    batch = batch_of(config, seed=13)
    return (config, params, batch) + jax.jit(jax.value_and_grad(
        gqa_moe.make_loss_fn(config, head_chunk=32), has_aux=True))(
            params, batch, None)


@pytest.mark.parametrize("policy", ["full", "none", "dots_saveable"])
def test_a_full_and_a_window_layers_checkpoint_keeps_out_and_lse(
        policy, monkeypatch):
    """Under every policy the loss and the gradients are the program's
    with no remat; under ``"full"`` they are bit for bit what the layers
    give with nothing kept (the parent's program), and the gradient
    program calls ``flash_fwd`` and ``flash_win_fwd`` once a layer where
    that one calls each twice (a period's two layers are one scan
    body)."""
    config, params, batch, (loss, aux), grad = _trained(policy)
    layer = 4 * 64 * (16 * 4 + 4)  # out [1, 4, 64, 16] and lse, float32
    assert float(aux[StepCounter.ATTN_KEPT_BYTES]) == (
        0 if policy == "none" else 4 * layer)
    (loss_p, _), grad_p = _trained("none")[3:]
    assert float(loss) == pytest.approx(float(loss_p), abs=2e-5)
    for (where, a), b in zip(jax.tree_util.tree_leaves_with_path(grad),
                             jax.tree.leaves(grad_p)):
        limit = 2e-4 * float(jnp.abs(b).max()) + 1e-7
        assert float(jnp.abs(a - b).max()) < limit, jax.tree_util.keystr(
            where)
    if policy != "full":
        return

    def text():
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda p: gqa_moe.make_loss_fn(config, head_chunk=32)(
                p, batch, None), has_aux=True))(params))

    kept = text()
    # ``apply_hidden`` as the parent built it: a full and a window
    # layer's checkpoint saves what its policy says and nothing more
    monkeypatch.setattr(gqa_moe, "apply_remat", lambda fn, policy, keep: (
        apply_remat(fn, policy)))
    (loss_w, _), grad_w = _trained.__wrapped__("full")[3:]
    assert float(loss) == float(loss_w)
    jax.tree.map(np.testing.assert_array_equal, grad, grad_w)
    replayed = text()
    for kernel, ours, parents in (
            ("flash_fwd", 1, 2), ("flash_win_fwd", 1, 2),
            ("flash_dkv", 1, 1), ("flash_dq", 1, 1), ("flash_win_bwd", 1, 1)):
        assert (kept.count(f"name={kernel}\n"),
                replayed.count(f"name={kernel}\n")) == (ours, parents), kernel


def test_what_the_cells_full_and_window_layers_keep():
    """``ATTN_KEPT_BYTES`` at the committed configuration, by
    arithmetic: 12 layers of ``out`` [1, 28, 16384, 128] in bf16 and
    ``lse`` [1, 28, 16384] in float32; nothing where XLA's dense forms
    run (they name nothing) or where there is no remat."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "smallthinker-21b-a3b-ep4-1chip.json")) as f:
        model = json.load(f)
    c = job.model_config(model)
    a = model["assumed"]
    assert c.remat_policy == "full" and c.use_kernels
    rows = a["batch"] * c.num_heads * a["seq_len"]
    layer = rows * c.head_dim * jnp.dtype(c.compute_dtype).itemsize + rows * 4
    assert layer == 117_440_512 + 1_835_008 == 119_275_520
    assert gqa_moe.layer_kinds(c) == {"attn_full": 3, "attn_window": 9}
    assert 12 * layer == 1_431_306_240
    assert float(jnp.float32(12 * layer)) == 12 * layer  # exact as counted
    tiny = gqa_moe.gqa_moe_tiny(experts_held=HELD, **F32)
    ids = batch_of(tiny)["input_ids"]
    for changed in (dict(use_kernels=False), dict(remat_policy="none",
                                                  **KERNELS)):
        c = dataclasses.replace(tiny, **changed)
        _, stats = gqa_moe.apply_hidden(
            gqa_moe.init(jax.random.PRNGKey(0), c), ids, c)
        assert float(stats[StepCounter.ATTN_KEPT_BYTES]) == 0, changed


def hidden(config, params, batch):
    return gqa_moe.apply_hidden(params, batch["input_ids"], config)[0]


def test_the_layer_kinds_follow_the_two_lists():
    """A stack whose lists are all 0 is full attention without
    positions: bit for bit what a stack of window layers gives whose
    window is as long as the row and whose rotary list is all 0. And
    each list is felt: a shorter window moves the result, and so does
    rotary on the same layers."""
    full = gqa_moe.gqa_moe_tiny(window_layout=(0,) * 4, rope_layout=(0,) * 4,
                                experts_held=HELD, **F32)
    params = perturbed(full)
    batch = batch_of(full)
    want = hidden(full, params, batch)
    whole_row = dataclasses.replace(full, window_layout=(1,) * 4,
                                    sliding_window=full.max_seq_len)
    assert bool(jnp.all(hidden(whole_row, params, batch) == want))
    windowed = dataclasses.replace(whole_row, sliding_window=16)
    rotary = dataclasses.replace(full, rope_layout=(1,) * 4)
    for other in (windowed, rotary):
        moved = hidden(other, params, batch)
        assert float(jnp.abs(moved - want).max()) > 0.05
    # rotary turns position 0 by nothing, and the first token sees
    # itself alone: its state is the same with and without positions
    assert bool(jnp.all(hidden(rotary, params, batch)[:, 0] == want[:, 0]))


def test_the_router_reads_the_attentions_input():
    """One layer by hand: the routing the layer used is that of ``u =
    RMSNorm_in(x)``; fed ``z = RMSNorm_post(x')`` the router selects
    other experts, and the reference told to do so parts from the
    program."""
    c = gqa_moe.gqa_moe_tiny(num_layers=1, window_layout=(0,),
                             rope_layout=(0,), experts_held=HELD, **F32)
    params = perturbed(c)
    batch = batch_of(c)
    p = jax.tree.map(lambda a: a[0], params["layers"]["0"])
    x = params["embed_tokens"]["embedding"][batch["input_ids"]]
    u = rms_norm(x, p["input_norm"]["scale"], c.rms_norm_eps)
    z = rms_norm(x + gqa_moe._attention(u, p["attn"], c, False, None),
                 p["post_norm"]["scale"], c.rms_norm_eps)
    from_u, _ = gqa_moe.route(u, p["moe"], c)
    from_z, _ = gqa_moe.route(z, p["moe"], c)
    assert float(jnp.mean(jnp.sort(from_u) != jnp.sort(from_z))) > 0.2
    _, stats = gqa_moe.apply_hidden(params, batch["input_ids"], c)
    assert float(stats["rows_held"]) == int(np.isin(from_u, HELD).sum())
    assert float(stats["rows_held"]) != int(np.isin(from_z, HELD).sum())


def test_the_reference_that_routes_from_z_parts_from_the_program(
        monkeypatch):
    model = toy()
    config = job.model_config(model)
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    program = hidden(config, params, batch)[0]

    def apart():
        plain = []
        job.reference_loss_of(model, config, params, batch["input_ids"][0],
                              batch["labels"][0], hidden=plain)
        return job.hidden_error(program, plain[0])

    assert apart() < 1e-5
    monkeypatch.setattr(reference, "router_input", lambda u, z: z)
    assert apart() > 0.05


def test_the_four_shares_add_up_to_the_whole_layer():
    """8 experts over 4 shares of 2 under the whole router: the parts
    the four held sets give (the program's ``held_expert_ffn`` fed the
    softmax top-k routing, ReGLU) sum to the uncut reference's expert
    layer. There is no shared expert to count once."""
    model = toy()
    model["deployment"]["published_moe_num_primary_experts"] = 8
    whole = copy.deepcopy(model)
    whole["deployment"]["experts_held"] = list(range(8))
    key = jax.random.split(jax.random.PRNGKey(7), 6)
    d, f = model["hidden_size"], model["moe_ffn_hidden_size"]
    every = {"w_gate": jax.random.normal(key[0], (8, d, f)) * 0.2,
             "w_up": jax.random.normal(key[1], (8, d, f)) * 0.2,
             "w_down": jax.random.normal(key[2], (8, f, d)) * 0.2}
    w_router = jax.random.normal(key[3], (d, 8))
    u = jax.random.normal(key[4], (64, d))  # what the router reads
    z = jax.random.normal(key[5], (64, d))  # what the experts read
    k = model["moe_num_active_primary_experts"]
    with jax.default_matmul_precision("highest"):
        top_i, gate = reference.route(u, w_router, whole)
        want = reference.expert_layer(z, every, top_i, gate, whole)
        got_i, got_w, _ = moe.topk_softmax_routing(u @ w_router, k)
        assert bool(jnp.all(got_i == top_i))
        total = jnp.zeros_like(want)
        for share in range(4):
            held = (2 * share, 2 * share + 1)
            mine = jax.tree.map(lambda a: a[2 * share:2 * share + 2], every)
            part = copy.deepcopy(model)
            part["deployment"]["experts_held"] = list(held)
            plain = reference.expert_layer(z, mine, top_i, gate, part)
            program, stats = moe.held_expert_ffn(
                {"gate": {"kernel": mine["w_gate"]},
                 "up": {"kernel": mine["w_up"]},
                 "down": {"kernel": mine["w_down"]}},
                z, got_i, got_w, held,
                moe.held_row_bound(64, k, 8, 2, 4.0, 8), 8, True,
                jax.nn.relu)
            assert float(jnp.abs(program - plain).max()) < 1e-4
            assert float(stats["rows_dropped"]) == 0
            total = total + program
    assert float(jnp.abs(total - want).max()) < 1e-4
    assert float(jnp.abs(want).max()) > 0.1  # the experts count


def test_a_dropped_row_is_counted():
    c = gqa_moe.gqa_moe_tiny(experts_held=HELD, expert_row_factor=0.05,
                             **F32, **KERNELS)
    params = gqa_moe.init(jax.random.PRNGKey(0), c)
    loss, aux = gqa_moe.make_loss_fn(c)(params, batch_of(c, rows=2), None)
    assert set(aux) == set(StepCounter.ALL) - {
        StepCounter.HC_RES_DEFECT, StepCounter.HC_KERNEL_PASSES,
        StepCounter.MTP_LOSS, StepCounter.GDN_NEG_EIG,
        StepCounter.SSD_DT_MEAN, StepCounter.KDA_LOG_DECAY_MEAN,
        StepCounter.LOOP_EXIT_ENTROPY, StepCounter.LOOP_EXIT_MEAN_PASS,
        StepCounter.LOOP_LOSS_FIRST, StepCounter.LOOP_LOSS_LAST,
        # the latent model's differential switches (test_mla_moe_gdla.py)
        StepCounter.DIFF_LAMBDA_MEAN, StepCounter.ROUTER_BIAS_ABS} - {
        # a model with sparse layers counts these (test_gqa_moe_dsa.py),
        # a group-limited router its reach (test_mla_moe_dsa.py)
        name for name in StepCounter.ALL
        if name.startswith(("dsa_", "moe_group_"))}
    assert float(aux[StepCounter.MOE_ROWS_DROPPED]) > 0
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("window,block,by_hand", [
    # squares of 16 over 64 tokens: a band of two, both edges
    (16, 16, (7, 0)),
    # a window of the whole row: the causal half, four on the diagonal
    (64, 16, (10, 6)),
    # tiles of 8 x 16 where the window does not fill a tile of 16
    (12, 16, None),
    (32, 32, None),
])
def test_the_loss_counts_the_bands_tiles(window, block, by_hand):
    """``attn_band_tiles`` and ``attn_band_tiles_unmasked`` in the
    aux: what ``band_walk`` says of the forward's tiles, a call a
    row, head and window layer; XLA's dense attention visits none."""
    c = gqa_moe.gqa_moe_tiny(experts_held=HELD, sliding_window=window,
                             window_block=block, **F32, **KERNELS)
    params = gqa_moe.init(jax.random.PRNGKey(0), c)
    batch = batch_of(c, rows=2)
    _, aux = gqa_moe.make_loss_fn(c)(params, batch, None)
    walk = band_walk(c.max_seq_len, window,
                     *window_tiles(c.max_seq_len, window, block)[0])
    calls = 2 * c.num_heads * gqa_moe.layer_kinds(c)[DeviceScope.ATTN_WINDOW]
    assert calls == 2 * 4 * 2
    assert (aux[StepCounter.ATTN_BAND_TILES],
            aux[StepCounter.ATTN_BAND_TILES_UNMASKED]) == (
        calls * walk.tiles, calls * walk.unmasked)
    if by_hand:
        assert (walk.tiles, walk.unmasked) == by_hand
    dense = dataclasses.replace(c, use_kernels=False)
    assert StepCounter.ATTN_BAND_TILES not in gqa_moe.make_loss_fn(dense)(
        params, batch, None)[1]


def test_a_window_layer_runs_the_tiles_the_rule_chose():
    """The two window kernels by name in a window layer's program, on
    the grids ``band_walk`` gives for ``window_tiles``' answer: the
    backward is the one kernel, the group's heads outside the k tiles."""
    c = gqa_moe.gqa_moe_tiny(experts_held=HELD, sliding_window=32,
                             window_block=16, **F32, **KERNELS)
    params = gqa_moe.init(jax.random.PRNGKey(0), c)
    batch = batch_of(c)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: gqa_moe.make_loss_fn(c)(p, batch, None)[0]))(params))
    fwd, bwd = window_tiles(c.max_seq_len, 32, 16)
    assert (fwd, bwd) == ((16, 16), (16, 16))
    walk = band_walk(c.max_seq_len, 32, 16, 16)
    h, kv, blocks = c.num_heads, c.num_kv_heads, c.max_seq_len // 16
    for name, grid in (
            ("flash_win_fwd", (1, h, blocks, walk.k_steps)),
            ("flash_win_bwd", (1, kv, h // kv, blocks, walk.q_steps))):
        assert f"name={name}" in text, name
        assert f"grid={grid}" in text, (name, grid)
    assert "flash_win_dkv" not in text and "flash_win_dq" not in text


def test_the_parts_carry_their_scopes_and_the_router_stands_first():
    """Every part's operations sit under its ``named_scope`` in the
    lowered program, and in a layer's text the router's come before
    its attention's: nothing of the attention feeds them."""
    c = gqa_moe.gqa_moe_tiny(experts_held=HELD, **F32, **KERNELS)
    params = gqa_moe.init(jax.random.PRNGKey(0), c)
    text = jax.jit(lambda p, ids: gqa_moe.apply_hidden(p, ids, c)).lower(
        params, batch_of(c)["input_ids"]).as_text(debug_info=True)
    for scope in (DeviceScope.ATTN_FULL, DeviceScope.ATTN_WINDOW,
                  DeviceScope.MOE_ROUTER, DeviceScope.MOE_EXPERTS):
        assert f"/{scope}/" in text, scope
    assert 0 < text.index(f"/{DeviceScope.MOE_ROUTER}/") < text.index(
        f"/{DeviceScope.ATTN_FULL}/") < text.index(
            f"/{DeviceScope.MOE_EXPERTS}/")


def test_rule_set_is_registered_and_names_every_leaf():
    assert RULE_SETS["gqa_moe"] is gqa_moe_rules
    shapes = jax.eval_shape(gqa_moe.make_init_fn(gqa_moe.GqaMoeConfig(
        num_layers=8, experts_held=tuple(range(16)), vocab_size=37984)),
        jax.random.PRNGKey(0))
    sizes = {"data": 1, "fsdp": 2, "tensor": 2}
    rules = gqa_moe_rules()
    for path, leaf in _flatten_with_paths(shapes):
        spec = rules.spec_for(path, leaf.shape, sizes)
        if path.startswith("layers/"):  # never the stacked axis
            assert spec[0] is None, (path, spec)
        if "experts/" in path:  # whole on the axes the kernel reads
            assert "tensor" not in spec and spec[1] is None, (path, spec)
            assert "fsdp" in spec, (path, spec)
        elif "router" in path or path.endswith("scale"):
            assert all(s is None for s in spec), (path, spec)
        elif "_proj/" in path:
            assert "fsdp" in spec and "tensor" in spec, (path, spec)
        elif leaf.size > 1e6:
            assert "fsdp" in spec, (path, spec)


def test_sharded_on_virtual_devices_gives_the_single_device_loss():
    """fsdp x tensor on the CPU's virtual devices under the ``gqa_moe``
    rules, both kinds of flash kernel under ``shard_map``: the first
    step's loss is the single-device loss, a kernel lands where its
    rule puts it, and the loss falls."""
    c = gqa_moe.gqa_moe_tiny(experts_held=HELD, **F32, **KERNELS)
    batch = batch_of(c, rows=4)
    loss_fn = gqa_moe.make_loss_fn(c, head_chunk=16)
    result = accelerate(
        gqa_moe.make_init_fn(c), loss_fn, optax.adam(3e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                          rule_set="gqa_moe", remat_policy=""))
    state = result.init_fn(jax.random.PRNGKey(0))
    alone, _ = loss_fn(jax.device_get(state.params), batch, None)
    layers = state.params["layers"]["1"]
    assert tuple(layers["attn"]["q_proj"]["kernel"].sharding.spec) == (
        None, "fsdp", "tensor")
    assert tuple(layers["moe"]["experts"]["down"]["kernel"].sharding.spec
                 ) == (None, None, None, "fsdp")
    sharded = result.shard_batch(batch)
    losses = []
    for i in range(6):
        state, metrics = result.train_step(state, sharded,
                                           jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    assert abs(losses[0] - float(alone)) < 1e-5
    assert np.isfinite(losses).all() and losses[-1] < losses[0] * 0.97
    assert float(metrics[StepCounter.MOE_ROWS_DROPPED]) == 0
