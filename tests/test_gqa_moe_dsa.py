"""``models/gqa_moe.py`` with its sparse switches (Keye-VL-2.0's
language model's block) at a toy size on the CPU: the model against
``chipbench/families/gqa_moe_dsa/reference.py`` on seeded weights (loss,
the indexer's loss, hidden states, every gradient, on XLA's dense forms
and on the Pallas kernels in the interpreter); the two disjoint
gradient paths; what a sparse layer's checkpoint keeps, and a full or
window layer's; the three-axis rotary with unequal rows; the defaults,
which are SmallThinker's; the shares of the experts.
"""

import dataclasses
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench.families.gqa_moe_dsa import job, reference  # noqa: E402
from dlrover_tpu.models import gqa_moe  # noqa: E402
from dlrover_tpu.ops import (  # noqa: E402
    flash_attention,
    moe,
    sparse_attention,
)
from dlrover_tpu.ops.remat import apply_remat  # noqa: E402
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter  # noqa: E402

# an image's tokens keep the temporal position and count rows, columns
POS = np.stack([np.minimum(np.arange(64), 20),
                np.arange(64) // 8, np.arange(64) % 8])


def toy():
    """The family's toy configuration (two sparse layers, 24 of a row's
    64 keys, 8 of 24 experts held, float32): what the reference reads."""
    with open(os.path.join(REPO, "tests", "chipbench",
                           "tiny_gqa_moe_dsa.json")) as f:
        return json.load(f)


def batch_of(config, rows=1, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def perturbed(config):
    """Initial weights with the norm scales moved off 1, so that a
    dropped norm would show."""
    return jax.jit(lambda key: jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                              a.shape, a.dtype),
        gqa_moe.init(key, config)))(jax.random.PRNGKey(3))


def is_index(path):
    return "'index'" in jax.tree_util.keystr(path)


@pytest.mark.parametrize("positions", [None, POS], ids=["text", "image"])
@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_the_module_agrees_with_the_familys_reference(path, positions):
    """Loss, the indexer's loss and every gradient against the
    reference (float32, dense scores in query blocks, the selection by
    a sort; its ``stop_gradient``s are the issue's) on seeded weights."""
    model = toy()
    config = job.model_config(model, use_kernels=path == "kernels")
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    if positions is not None:
        batch["position_ids"] = jnp.asarray(positions)[None]
    loss_fn = gqa_moe.make_loss_fn(config, head_chunk=32)

    def ref(p):
        lm, kl, _ = reference.loss(
            model, batch["input_ids"][0], batch["labels"][0],
            p["embed_tokens"]["embedding"], job.reference_layers(p, config),
            p["norm"]["scale"], p["lm_head"]["kernel"], pos=positions)
        return lm + kl, kl

    (got, aux), grad = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, None)
    (want, want_kl), grad_want = jax.value_and_grad(ref, has_aux=True)(
        params)
    assert abs(float(got) - float(want)) < 2e-5
    assert float(aux[StepCounter.DSA_INDEX_KL]) == pytest.approx(
        float(want_kl), rel=1e-4)
    assert float(want_kl) > 0.05  # the indexer's loss counts
    assert float(aux[StepCounter.MOE_ROWS_DROPPED]) == 0
    assert float(aux[StepCounter.DSA_PAIRS_SELECTED]) == 2 * (300 + 40 * 24)
    assert float(aux[StepCounter.DSA_PAIRS_CAUSAL]) == 2 * 2080
    flat = jax.tree_util.tree_leaves_with_path(grad)
    assert len(flat) == 15 + 3  # one stack of layers, table, norm, head
    for (where, a), b in zip(flat, jax.tree.leaves(grad_want)):
        limit = 2e-4 * float(jnp.abs(b).max()) + 1e-7
        assert float(jnp.abs(a - b).max()) < limit, jax.tree_util.keystr(
            where)
        assert float(jnp.abs(b).max()) > 0, jax.tree_util.keystr(where)


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_two_disjoint_gradient_paths_in_one_loss(path):
    """The indexer's three matrices get their gradient from the
    indexer's loss alone and every other leaf from the language-model
    loss alone: the indexer's input and the probabilities it is trained
    towards are detached, and the selection has no gradient."""
    config = job.model_config(toy(), use_kernels=path == "kernels")
    params = perturbed(config)
    batch = batch_of(config, seed=12)
    total = gqa_moe.make_loss_fn(config, head_chunk=32)
    lm_only = gqa_moe.make_loss_fn(
        dataclasses.replace(config, index_loss_weight=0.0), head_chunk=32)
    from_lm = jax.grad(lambda p: lm_only(p, batch, None)[0])(params)
    from_kl = jax.grad(lambda p: total(p, batch, None)[1][
        StepCounter.DSA_INDEX_KL])(params)
    both = jax.grad(lambda p: total(p, batch, None)[0])(params)
    seen = {True: 0, False: 0}
    for (where, lm), kl, whole in zip(
            jax.tree_util.tree_leaves_with_path(from_lm),
            jax.tree.leaves(from_kl), jax.tree.leaves(both)):
        mine, other = (kl, lm) if is_index(where) else (lm, kl)
        assert not np.asarray(other).any(), jax.tree_util.keystr(where)
        assert np.abs(np.asarray(mine)).max() > 0, jax.tree_util.keystr(
            where)
        np.testing.assert_allclose(whole, mine, rtol=1e-5, atol=1e-7)
        seen[is_index(where)] += 1
    assert seen == {True: 3, False: 15}


def test_rot3_with_unequal_rows():
    """Pair ``i`` turns by the position row of its section: against the
    rotation written out pair by pair; equal rows are plain rotary."""
    c = gqa_moe.gqa_moe_tiny(rope_sections=(2, 3, 3), rope_theta=1e4)
    pos = gqa_moe._position_rows(POS, 1, 64)
    cos, sin = gqa_moe._section_tables(pos, c)
    assert cos.shape == (1, 1, 64, 8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 16))
    got = np.asarray(gqa_moe._rotate(x, cos, sin))
    row = [0, 0, 1, 1, 1, 2, 2, 2]
    for i in range(8):
        angle = POS[row[i]] * 1e4 ** (-i / 8)
        a, b = np.asarray(x[..., i]), np.asarray(x[..., i + 8])
        np.testing.assert_allclose(
            got[..., i], a * np.cos(angle) - b * np.sin(angle), atol=1e-5)
        np.testing.assert_allclose(
            got[..., i + 8], b * np.cos(angle) + a * np.sin(angle),
            atol=1e-5)
    text = gqa_moe._section_tables(gqa_moe._position_rows(None, 1, 64), c)
    plain = gqa_moe._rotary_tables(64, c)
    for ours, theirs in zip(text, plain):
        np.testing.assert_array_equal(ours[0, 0], theirs)
    # a batch's own rows, and what is refused
    both = gqa_moe._position_rows(np.stack([POS, POS + 1]), 2, 64)
    assert gqa_moe._section_tables(both, c)[0].shape == (2, 1, 64, 8)
    with pytest.raises(ValueError, match="positions"):
        gqa_moe._position_rows(POS[:2], 1, 64)
    with pytest.raises(ValueError, match="rope_sections"):
        gqa_moe._section_tables(pos, dataclasses.replace(
            c, rope_sections=(2, 3, 4)))


def test_the_defaults_are_smallthinkers():
    """No switch set: SmallThinker's tree of parameters, its plan, its
    kinds, its counters, and not one operation under a sparse scope;
    and the switches spelled out at their defaults are the same
    numbers, bit for bit."""
    c = gqa_moe.gqa_moe_tiny()
    spelled = gqa_moe.gqa_moe_tiny(
        router_input="attn_input", expert_activation="relu", qk_norm=False,
        rope_sections=(), index_loss_weight=1.0, sparse_topk=2048)
    assert (c.router_input, c.expert_activation, c.qk_norm, c.rope_sections,
            c.has_sparse) == ("attn_input", "relu", False, (), False)
    params = gqa_moe.init(jax.random.PRNGKey(0), c)
    assert len(jax.tree.leaves(params)) == 2 * 10 + 3
    assert "index" not in params["layers"]["0"]["attn"]
    assert gqa_moe.layer_plan(c) == [(0, 0), (1, 1)]
    assert gqa_moe.layer_kinds(c) == {"attn_full": 2, "attn_window": 2}
    batch = batch_of(c)
    (loss, aux), grad = jax.value_and_grad(
        gqa_moe.make_loss_fn(c), has_aux=True)(params, batch, None)
    (loss_s, _), grad_s = jax.value_and_grad(
        gqa_moe.make_loss_fn(spelled), has_aux=True)(params, batch, None)
    assert float(loss) == float(loss_s)
    jax.tree.map(np.testing.assert_array_equal, grad, grad_s)
    assert set(aux) == {"moe_rows_held", "moe_rows_max", "moe_rows_dropped",
                        "moe_rows_buffered", "attn_kept_bytes"}
    assert float(aux["attn_kept_bytes"]) == 0  # XLA's forms name nothing
    text = jax.jit(gqa_moe.make_loss_fn(c)).lower(
        params, batch, None).as_text(debug_info=True)
    assert "dsa_" not in text and "attn_sparse" not in text
    # each switch alone moves the loss
    for switch in (dict(router_input="post_norm"),
                   dict(expert_activation="silu"),
                   dict(rope_sections=(2, 3, 3), rope_theta=2e4)):
        moved = gqa_moe.make_loss_fn(gqa_moe.gqa_moe_tiny(**switch))(
            params, batch, None)[0]
        assert float(moved) != float(loss), switch


def test_a_third_kind_beside_full_and_window():
    """One period of a full, a window and a sparse layer: three scopes,
    three kinds on ``trainer_ready``, the indexer on the sparse
    position alone, and the XLA forms and the kernels agree."""
    kinds = dict(window_layout=(0, 1, 2) * 2, rope_layout=(0, 1, 1) * 2,
                 num_layers=6, sparse_topk=24, index_heads=4,
                 index_head_dim=8, index_block_q=32, index_block_k=32,
                 sparse_block_q=32, param_dtype=jnp.float32,
                 compute_dtype=jnp.float32)
    c = gqa_moe.gqa_moe_tiny(**kinds)
    assert gqa_moe.layer_plan(c) == [(0, 0), (1, 1), (2, 1)]
    assert gqa_moe.make_init_fn(c).layer_kinds == {
        "attn_full": 2, "attn_window": 2, "attn_sparse": 2}
    params = gqa_moe.init(jax.random.PRNGKey(0), c)
    assert [("index" in params["layers"][j]["attn"]) for j in "012"] == [
        False, False, True]
    batch = batch_of(c)
    loss, aux = gqa_moe.make_loss_fn(c)(params, batch, None)
    kernels = gqa_moe.gqa_moe_tiny(
        use_kernels=True, flash_block_q=32, flash_block_k=32, **kinds)
    loss_k, aux_k = gqa_moe.make_loss_fn(kernels)(params, batch, None)
    assert float(loss) == pytest.approx(float(loss_k), rel=1e-5)
    assert float(aux[StepCounter.DSA_PAIRS_SELECTED]) == float(
        aux_k[StepCounter.DSA_PAIRS_SELECTED]) == 2 * (300 + 40 * 24)
    assert float(aux[StepCounter.DSA_TILES_VISITED]) == 0 < float(
        aux_k[StepCounter.DSA_TILES_VISITED])
    text = jax.jit(lambda p, ids: gqa_moe.apply_hidden(p, ids, kernels)
                   ).lower(params, batch["input_ids"]).as_text(
                       debug_info=True)
    for scope in (DeviceScope.ATTN_FULL, DeviceScope.ATTN_WINDOW,
                  DeviceScope.ATTN_SPARSE, DeviceScope.DSA_INDEX):
        assert f"/{scope}/" in text, scope
    for kernel in ("dsa_index_select", "dsa_attn_fwd", "dsa_index_kl"):
        assert kernel in text, kernel
    # the loss's one kernel, under no older name
    assert "dsa_index_kl_fwd" not in text and "dsa_index_kl_bwd" not in text
    with pytest.raises(ValueError, match="window_layout"):
        gqa_moe.layer_plan(gqa_moe.gqa_moe_tiny(window_layout=(0, 3) * 4))
    with pytest.raises(ValueError, match="router_input"):
        gqa_moe.init(jax.random.PRNGKey(0),
                     gqa_moe.gqa_moe_tiny(router_input="nowhere"))


def _calls(text, kernel):
    """Call sites of a kernel's shared ``jax.jit`` in a lowered module
    (a second lowering of the callee is ``@<kernel>_<n>``)."""
    return len(re.findall(rf"call @{kernel}(_\d+)?\(", text))


@functools.lru_cache(maxsize=None)
def _trained(policy):
    """(config, weights, batch, (loss, aux), gradients) of the toy on
    the interpreter's kernels under ``policy``."""
    config = job.model_config(toy(), use_kernels=True, remat_policy=policy)
    params = perturbed(config)
    batch = batch_of(config, seed=13)
    return (config, params, batch) + jax.jit(jax.value_and_grad(
        gqa_moe.make_loss_fn(config, head_chunk=32), has_aux=True))(
            params, batch, None)


@pytest.mark.parametrize("policy", ["full", "none", "dots_saveable"])
def test_a_sparse_layers_checkpoint_keeps_out_and_lse(policy, monkeypatch):
    """Under every policy the loss, the indexer's loss and the gradients
    are the program's with no remat; under ``"full"`` they are bit for
    bit what the layers give with nothing kept (the parent's program),
    and the gradient program calls ``dsa_attn_fwd`` and the indexer's
    loss's one kernel once where that one calls each twice (the two
    layers are one scan body), the selection still twice."""
    config, params, batch, (loss, aux), grad = _trained(policy)
    layer = sparse_attention.kept_bytes(1, 4, 64, 16, jnp.float32)
    assert layer == 4 * 64 * (16 * 4 + 4)
    assert float(aux[StepCounter.DSA_ATTN_KEPT_BYTES]) == (
        0 if policy == "none" else 2 * layer)
    # the three gradients of the indexer's loss: 4 heads of 8
    index = sparse_attention.index_kept_bytes(1, 4, 64, 8, jnp.float32)
    assert index == 64 * (4 * 8 + 8 + 4) * 4
    assert float(aux[StepCounter.DSA_INDEX_KEPT_BYTES]) == (
        0 if policy == "none" else 2 * index)
    (loss_p, aux_p), grad_p = _trained("none")[3:]
    assert float(loss) == pytest.approx(float(loss_p), abs=2e-5)
    assert float(aux[StepCounter.DSA_INDEX_KL]) == pytest.approx(
        float(aux_p[StepCounter.DSA_INDEX_KL]), rel=1e-4)
    for (where, a), b in zip(jax.tree_util.tree_leaves_with_path(grad),
                             jax.tree.leaves(grad_p)):
        limit = 2e-4 * float(jnp.abs(b).max()) + 1e-7
        assert float(jnp.abs(a - b).max()) < limit, jax.tree_util.keystr(
            where)
    if policy != "full":
        return

    def text():
        # the value and the aux beside the gradients, as a train step
        # asks: the forward pass then owes the indexer's loss's value
        # whatever is kept
        return jax.jit(jax.value_and_grad(
            lambda p: gqa_moe.make_loss_fn(config, head_chunk=32)(
                p, batch, None), has_aux=True)).lower(params).as_text()

    kept = text()
    # ``apply_hidden`` as the parent built it: every layer's checkpoint
    # saves what its policy says and nothing more
    monkeypatch.setattr(gqa_moe, "apply_remat", lambda fn, policy, keep: (
        apply_remat(fn, policy)))
    (loss_w, aux_w), grad_w = _trained.__wrapped__("full")[3:]
    assert float(loss) == float(loss_w)
    assert float(aux[StepCounter.DSA_INDEX_KL]) == float(
        aux_w[StepCounter.DSA_INDEX_KL])
    jax.tree.map(np.testing.assert_array_equal, grad, grad_w)
    replayed = text()
    for kernel, ours, parents in (("dsa_attn_fwd", 1, 2),
                                  ("dsa_index_kl", 1, 2),
                                  ("dsa_index_select", 2, 2),
                                  ("dsa_attn_bwd", 1, 1)):
        assert (_calls(kept, kernel), _calls(replayed, kernel)) == (
            ours, parents), kernel
    # value and gradient out of the one kernel: no older name is left
    for text in (kept, replayed):
        assert "dsa_index_kl_fwd" not in text
        assert "dsa_index_kl_bwd" not in text


def test_every_kind_of_layer_keeps_its_kernels_out_and_lse(monkeypatch):
    """One period of a full, a window and a sparse layer under
    ``"full"``: the full and the window layer's checkpoints are given
    the flash ops' names and hold ``out`` and ``lse`` alone of what they
    computed, the sparse layer's the selected attention's two and the
    three gradients of the indexer's loss; each kind's kept bytes are
    counted under its own name."""
    from jax._src.ad_checkpoint import saved_residuals

    c = gqa_moe.gqa_moe_tiny(
        window_layout=(0, 1, 2) * 2, rope_layout=(0, 1, 1) * 2,
        num_layers=6, sparse_topk=24, index_heads=4, index_head_dim=8,
        index_block_q=32, index_block_k=32, sparse_block_q=32,
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
        use_kernels=True, flash_block_q=32, flash_block_k=32)
    assert c.remat_policy == "full"
    params = gqa_moe.init(jax.random.PRNGKey(0), c)
    built = []

    def recorded(fn, policy, keep):
        built.append((keep, apply_remat(fn, policy, keep=keep)))
        return built[-1][1]

    monkeypatch.setattr(gqa_moe, "apply_remat", recorded)
    ids = batch_of(c)["input_ids"]
    _, stats = gqa_moe.apply_hidden(params, ids, c)
    assert [keep for keep, _ in built] == [
        flash_attention.KEPT_NAMES, flash_attention.KEPT_NAMES,
        sparse_attention.KEPT_NAMES + sparse_attention.INDEX_KEPT_NAMES]
    assert flash_attention.KEPT_NAMES == ("flash_attn_out", "flash_attn_lse")
    # two full and two window layers; two sparse ones
    assert float(stats[StepCounter.ATTN_KEPT_BYTES]) == (
        4 * 4 * 64 * (16 * 4 + 4))
    assert float(stats[StepCounter.DSA_ATTN_KEPT_BYTES]) == (
        2 * sparse_attention.kept_bytes(1, 4, 64, 16, jnp.float32))
    assert float(stats[StepCounter.DSA_INDEX_KEPT_BYTES]) == (
        2 * sparse_attention.index_kept_bytes(1, 4, 64, 8, jnp.float32))
    x = jnp.zeros((1, 64, c.hidden_size), jnp.float32)
    kept = []
    for j, (_, layer) in enumerate(built):
        p = jax.tree.map(lambda a: a[0], params["layers"][str(j)])
        # what a layer computed and its checkpoint holds on to (the
        # rest are its arguments and the rotary tables it closes over)
        kept.append([value.shape for value, why in saved_residuals(
            layer, x, p) if why.startswith(("output of", "named"))])
    out_and_lse = [(1, 4, 64, 16), (1, 4, 64)]
    assert kept == [out_and_lse, out_and_lse, out_and_lse + [
        (1, 4, 64, 8), (1, 64, 8), (1, 64, 4)]]


def test_what_the_cells_sparse_layers_keep():
    """``DSA_ATTN_KEPT_BYTES`` at the committed configuration, by
    arithmetic: 8 layers of ``out`` [1, 32, 16384, 128] in bf16 and
    ``lse`` [1, 32, 16384] in float32; nothing where XLA's dense forms
    run (they name nothing) or where there is no remat."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "keye-vl-2.0-30b-a3b-ep4-1chip.json")) as f:
        model = json.load(f)
    c = job.model_config(model)
    a = model["assumed"]
    assert c.remat_policy == "full" and c.use_kernels
    layer = sparse_attention.kept_bytes(
        a["batch"], c.num_heads, a["seq_len"], c.head_dim, c.compute_dtype)
    assert layer == 134_217_728 + 2_097_152 == 136_314_880
    assert gqa_moe.layer_kinds(c)[DeviceScope.ATTN_SPARSE] * layer == (
        8 * 136_314_880)
    assert float(jnp.float32(8 * layer)) == 8 * layer  # exact as counted
    # and of the indexer's loss ``dqi`` [1, 16, 16384, 64], ``dki`` [1,
    # 16384, 64] and ``dw`` [1, 16384, 16] in bf16
    index = sparse_attention.index_kept_bytes(
        a["batch"], c.index_heads, a["seq_len"], c.index_head_dim,
        c.compute_dtype)
    assert index == 33_554_432 + 2_097_152 + 524_288 == 36_175_872
    assert float(jnp.float32(8 * index)) == 8 * index
    toy_c = job.model_config(toy(), use_kernels=False)
    _, stats = gqa_moe.apply_hidden(
        perturbed(toy_c), batch_of(toy_c)["input_ids"], toy_c)
    assert float(stats[StepCounter.DSA_ATTN_KEPT_BYTES]) == 0
    assert float(stats[StepCounter.DSA_INDEX_KEPT_BYTES]) == 0


def test_apply_layers_is_apply_hidden_a_layer_at_a_time():
    config = job.model_config(toy())
    params = perturbed(config)
    batch = batch_of(config, rows=2)
    whole, stats = gqa_moe.apply_hidden(params, batch["input_ids"], config)
    *layers, last = gqa_moe.apply_layers(params, batch["input_ids"], config)
    np.testing.assert_allclose(last, whole, atol=1e-5)
    assert len(layers) == 2
    for chose in layers:
        assert chose["selected"].shape == (2, 64, 64)
        assert chose["experts"].shape == (2 * 64, 3)
        assert int(chose["selected"].sum()) == 2 * (300 + 40 * 24)
    assert sum(float(c[StepCounter.DSA_INDEX_KL]) for c in layers) == (
        pytest.approx(float(stats[StepCounter.DSA_INDEX_KL]), rel=1e-5))


def test_the_four_shares_add_up_to_the_whole_layer():
    """8 experts over 4 shares of 2 under the whole router, which reads
    ``z``: the parts the four held sets give (the program's
    ``held_expert_ffn`` fed the softmax top-k routing, SwiGLU) sum to
    the uncut reference's expert layer. There is no shared expert to
    count once."""
    model = toy()
    key = jax.random.split(jax.random.PRNGKey(7), 5)
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    every = {"w_gate": jax.random.normal(key[0], (8, d, f)) * 0.2,
             "w_up": jax.random.normal(key[1], (8, d, f)) * 0.2,
             "w_down": jax.random.normal(key[2], (8, f, d)) * 0.2}
    w_router = jax.random.normal(key[3], (d, 8))
    z = jax.random.normal(key[4], (64, d))  # router and experts read it
    k = model["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        top_i, gate = reference.route(z, w_router, model)
        want = reference.expert_layer(z, every, top_i, gate, range(8))
        got_i, got_w, _ = moe.topk_softmax_routing(z @ w_router, k)
        assert bool(jnp.all(got_i == top_i))
        total = jnp.zeros_like(want)
        for share in range(4):
            held = (2 * share, 2 * share + 1)
            mine = jax.tree.map(lambda a: a[2 * share:2 * share + 2], every)
            plain = reference.expert_layer(z, mine, top_i, gate, held)
            program, stats = moe.held_expert_ffn(
                {"gate": {"kernel": mine["w_gate"]},
                 "up": {"kernel": mine["w_up"]},
                 "down": {"kernel": mine["w_down"]}},
                z, got_i, got_w, held,
                moe.held_row_bound(64, k, 8, 2, 4.0, 8), 8, True,
                jax.nn.silu)
            assert float(jnp.abs(program - plain).max()) < 1e-4
            assert float(stats["rows_dropped"]) == 0
            total = total + program
    assert float(jnp.abs(total - want).max()) < 1e-4
    assert float(jnp.abs(want).max()) > 0.1  # the experts count
