"""``models/mla_moe.py`` with its sparse switches (A.X-K2's block) at a
toy size on the CPU: the model against ``chipbench/families/
mla_moe_dsa/reference.py`` on seeded weights (loss, the indexer's loss,
every gradient, on XLA's dense forms and on the Pallas kernels in the
interpreter); the two disjoint gradient paths; what a layer's
checkpoint keeps (the selected attention's output and logsumexp, the
indexer's loss's three gradients); the defaults, which are A.X-K1's
and Xing4.0's; the group-limited router against a table made by hand;
the shares of the experts.
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

from chipbench.families.mla_moe_dsa import job, reference  # noqa: E402
from dlrover_tpu.models import mla_moe  # noqa: E402
from dlrover_tpu.ops import moe, sparse_attention  # noqa: E402
from dlrover_tpu.ops.remat import apply_remat  # noqa: E402
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter  # noqa: E402


def toy():
    """The family's toy configuration (a dense and two expert layers,
    24 of a row's 64 keys, 2 of 4 groups, 6 of 24 experts held,
    float32): what the reference reads."""
    with open(os.path.join(REPO, "tests", "chipbench",
                           "tiny_mla_moe_dsa.json")) as f:
        return json.load(f)


def batch_of(config, rows=1, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def perturbed(config):
    """Initial weights with the norm scales moved off 1 and the
    indexer's key norm's bias off 0, so that a dropped norm would
    show."""
    return jax.jit(lambda key: jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                              a.shape, a.dtype),
        mla_moe.init(key, config)))(jax.random.PRNGKey(3))


def is_index(path):
    return "'index'" in jax.tree_util.keystr(path)


def is_bias(path):
    return jax.tree_util.keystr(path).endswith("['router']['bias']")


def reference_of(model, config, batch):
    def ref(p):
        lm, kl, _ = reference.loss(
            model, batch["input_ids"][0], batch["labels"][0],
            p["embed_tokens"]["embedding"], job.reference_layers(p, config),
            p["norm"], p["lm_head"]["kernel"])
        return lm + kl, kl

    return ref


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_the_module_agrees_with_the_familys_reference(path):
    """Loss, the indexer's loss and every gradient against the
    reference (float32, dense scores in query blocks, the selection by
    a sort, the groups by hand; its ``stop_gradient``s are the
    issue's) on seeded weights."""
    model = toy()
    config = job.model_config(model, use_kernels=path == "kernels")
    params = perturbed(config)
    batch = batch_of(config, seed=11)
    loss_fn = mla_moe.make_loss_fn(config, head_chunk=32)
    (got, aux), grad = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, None)
    (want, want_kl), grad_want = jax.value_and_grad(
        reference_of(model, config, batch), has_aux=True)(params)
    assert abs(float(got) - float(want)) < 2e-5
    assert float(aux[StepCounter.DSA_INDEX_KL]) == pytest.approx(
        float(want_kl), rel=1e-4)
    assert float(want_kl) > 0.05  # the indexer's loss counts
    assert float(aux[StepCounter.MOE_ROWS_DROPPED]) == 0
    # three layers of 24 * 25 / 2 + 40 * 24 selected of 64 * 65 / 2
    assert float(aux[StepCounter.DSA_PAIRS_SELECTED]) == 3 * (300 + 40 * 24)
    assert float(aux[StepCounter.DSA_PAIRS_CAUSAL]) == 3 * 2080
    assert float(aux[StepCounter.MOE_GROUP_TOKENS]) == 2 * 64
    assert 0 < float(aux[StepCounter.MOE_GROUP_REACH]) < 2 * 64
    flat = jax.tree_util.tree_leaves_with_path(grad)
    for (where, a), b in zip(flat, jax.tree.leaves(grad_want)):
        name = jax.tree_util.keystr(where)
        limit = 2e-4 * float(jnp.abs(b).max()) + 1e-7
        assert float(jnp.abs(a - b).max()) < limit, name
        # the selection bias moves the choice alone: no gradient
        assert (float(jnp.abs(b).max()) > 0) != is_bias(where), name


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_two_disjoint_gradient_paths_in_one_loss(path):
    """The indexer's four leaves get their gradient from the indexer's
    loss alone and every other leaf from the language-model loss alone:
    the indexer's inputs (the normed hidden state and the query latent)
    and the probabilities it is trained towards are detached, and the
    selection has no gradient."""
    config = job.model_config(toy(), use_kernels=path == "kernels")
    params = perturbed(config)
    batch = batch_of(config, seed=12)
    total = mla_moe.make_loss_fn(config, head_chunk=32)
    lm_only = mla_moe.make_loss_fn(
        dataclasses.replace(config, index_loss_weight=0.0), head_chunk=32)
    from_lm = jax.grad(lambda p: lm_only(p, batch, None)[0])(params)
    from_kl = jax.grad(lambda p: total(p, batch, None)[1][
        StepCounter.DSA_INDEX_KL])(params)
    both = jax.grad(lambda p: total(p, batch, None)[0])(params)
    seen = {True: 0, False: 0}
    for (where, lm), kl, whole in zip(
            jax.tree_util.tree_leaves_with_path(from_lm),
            jax.tree.leaves(from_kl), jax.tree.leaves(both)):
        if is_bias(where):
            assert not np.asarray(whole).any()
            continue
        mine, other = (kl, lm) if is_index(where) else (lm, kl)
        assert not np.asarray(other).any(), jax.tree_util.keystr(where)
        assert np.abs(np.asarray(mine)).max() > 0, jax.tree_util.keystr(
            where)
        np.testing.assert_allclose(whole, mine, rtol=1e-5, atol=1e-7)
        seen[is_index(where)] += 1
    # two stacks of layers: five indexer leaves each (the key norm has
    # a scale and a bias)
    assert seen[True] == 2 * 5 and seen[False] > 30


def _calls(text, kernel):
    """Call sites of a kernel's shared ``jax.jit`` in a lowered module
    (a second lowering of the callee is ``@<kernel>_<n>``)."""
    return len(re.findall(rf"call @{kernel}(_\d+)?\(", text))


def test_a_layers_checkpoint_keeps_out_and_lse():
    """The replay of either scan's layer leaves ``dsa_attn_fwd`` out
    and makes the selection again; the aux counts the bytes kept."""
    config = job.model_config(toy(), use_kernels=True)
    params = perturbed(config)
    batch = batch_of(config)
    loss_fn = mla_moe.make_loss_fn(config, head_chunk=32)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, batch, None)[0])).lower(
        params).as_text()
    # a call in the forward scan and none in the backward's, for the
    # dense layers' scan and the expert layers' alike
    assert _calls(text, "dsa_attn_fwd") == 2
    assert _calls(text, "dsa_index_select") == 4
    assert _calls(text, "dsa_attn_bwd") == 2
    _, aux = loss_fn(params, batch, None)
    c = config
    assert float(aux[StepCounter.DSA_ATTN_KEPT_BYTES]) == (
        c.num_layers * 1 * c.num_heads * 64 * (c.v_head_dim * 4 + 4))
    none = dataclasses.replace(config, remat_policy="none")
    _, aux = mla_moe.make_loss_fn(none, head_chunk=32)(params, batch, None)
    assert float(aux[StepCounter.DSA_ATTN_KEPT_BYTES]) == 0


@functools.lru_cache(maxsize=None)
def _trained(policy):
    """(config, weights, batch, (loss, aux), gradients) of the toy on
    the interpreter's kernels under ``policy``."""
    config = job.model_config(toy(), use_kernels=True, remat_policy=policy)
    params = perturbed(config)
    batch = batch_of(config, seed=13)
    return (config, params, batch) + jax.jit(jax.value_and_grad(
        mla_moe.make_loss_fn(config, head_chunk=32), has_aux=True))(
            params, batch, None)


@pytest.mark.parametrize("policy", ["full", "none", "dots_saveable"])
def test_a_layers_checkpoint_keeps_the_index_losss_gradients(
        policy, monkeypatch):
    """The indexer's loss is one kernel, run once a layer in the
    forward pass: under every policy the loss, the indexer's loss and
    the gradients are the program's with no remat; under ``"full"`` they
    are bit for bit what the layers give with nothing kept, where the
    replay runs the kernel again (twice a scan body, two scans); the aux
    counts the three kept gradients' bytes."""
    config, params, batch, (loss, aux), grad = _trained(policy)
    c = config
    layer = sparse_attention.index_kept_bytes(
        1, c.index_n_heads, 64, c.index_head_dim, jnp.float32)
    assert layer == 64 * 4 * (
        c.index_n_heads * c.index_head_dim + c.index_head_dim
        + c.index_n_heads)
    assert float(aux[StepCounter.DSA_INDEX_KEPT_BYTES]) == (
        0 if policy == "none" else c.num_layers * layer)
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
            lambda p: mla_moe.make_loss_fn(config, head_chunk=32)(
                p, batch, None), has_aux=True)).lower(params).as_text()

    kept = text()
    # ``apply_hidden`` as the parent built it: a layer's checkpoint
    # keeps the selected attention's two names and nothing of the loss
    monkeypatch.setattr(mla_moe, "apply_remat", lambda fn, policy, keep: (
        apply_remat(fn, policy, keep=sparse_attention.KEPT_NAMES)))
    (loss_w, aux_w), grad_w = _trained.__wrapped__("full")[3:]
    assert float(loss) == float(loss_w)
    assert float(aux[StepCounter.DSA_INDEX_KL]) == float(
        aux_w[StepCounter.DSA_INDEX_KL])
    jax.tree.map(np.testing.assert_array_equal, grad, grad_w)
    replayed = text()
    for kernel, ours, parents in (("dsa_index_kl", 2, 4),
                                  ("dsa_attn_fwd", 2, 2),
                                  ("dsa_index_select", 4, 4),
                                  ("dsa_attn_bwd", 2, 2)):
        assert (_calls(kept, kernel), _calls(replayed, kernel)) == (
            ours, parents), kernel
    # value and gradient out of the one kernel: no older name is left
    for text in (kept, replayed):
        assert "dsa_index_kl_fwd" not in text
        assert "dsa_index_kl_bwd" not in text


def test_apply_layers_is_apply_hidden_a_layer_at_a_time():
    config = job.model_config(toy())
    params = perturbed(config)
    batch = batch_of(config, rows=1)
    whole, _, stats = mla_moe.apply_hidden(params, batch["input_ids"],
                                           config)
    *layers, last = mla_moe.apply_layers(params, batch["input_ids"], config)
    np.testing.assert_allclose(last, whole, atol=1e-5)
    assert len(layers) == 3
    assert "experts" not in layers[0]  # the dense layer routes nothing
    for chose in layers:
        assert chose["selected"].shape == (1, 64, 64)
        assert int(chose["selected"].sum()) == 300 + 40 * 24
    for chose in layers[1:]:
        assert chose["experts"].shape == (64, 4)
        assert chose["groups"].shape == (64, 4)
        assert bool(jnp.all(chose["groups"].sum(axis=1) == 2))
        # every selected expert lies in a kept group
        kept = jnp.take_along_axis(chose["groups"], chose["experts"] // 6,
                                   axis=1)
        assert bool(jnp.all(kept))
    assert float(stats["group_tokens"]) == 2 * 64


XING4 = dict(hc_mult=2, mtp_layers=1, router_bias=True)


@pytest.mark.parametrize("family", [{}, XING4], ids=["axk1", "xing4"])
def test_the_defaults_are_the_block_without_the_switches(family):
    """At the switches' defaults the parameters have no new leaf and
    the traced loss and gradient no new scope, kernel or counter: the
    program of A.X-K1's and of Xing4.0's toy is what it was."""
    c = mla_moe.mla_moe_tiny(experts_held=tuple(range(8)), **family)
    assert (c.index_n_heads, c.attn_output_gate, c.gated_norm_rank,
            c.n_group, c.topk_group) == (0, False, 0, 1, 1)
    params = mla_moe.init(jax.random.PRNGKey(0), c)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    for new in ("index", "g_proj", "gate_a", "gate_b"):
        assert not [n for n in names if f"'{new}'" in n], new
    batch = batch_of(c)
    loss_fn = mla_moe.make_loss_fn(c)
    text = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, None), has_aux=True)).lower(
            params).as_text(debug_info=True)
    for new in (DeviceScope.DSA_INDEX, DeviceScope.ATTN_SPARSE,
                DeviceScope.ATTN_GATE, DeviceScope.GATED_NORM,
                DeviceScope.MOE_GROUPS, "dsa_", "checkpoint_name"):
        assert new not in text, new
    _, aux = loss_fn(params, batch, None)
    assert not [k for k in aux if k.startswith(("dsa_", "moe_group"))]


def test_an_indexer_that_keeps_every_key_is_dense_attention():
    """``index_topk`` at the row's length selects every causal key:
    the language-model loss is the dense latent attention's on the same
    weights, and its gradients too."""
    dense = mla_moe.mla_moe_tiny(
        experts_held=tuple(range(8)), param_dtype=jnp.float32,
        compute_dtype=jnp.float32)
    sparse = dataclasses.replace(dense, index_n_heads=2, index_head_dim=16,
                                 index_topk=64, index_loss_weight=0.0)
    params = mla_moe.init(jax.random.PRNGKey(0), sparse)
    batch = batch_of(dense)
    got, grad = jax.value_and_grad(
        lambda p: mla_moe.make_loss_fn(sparse)(p, batch, None)[0])(params)
    want, grad_want = jax.value_and_grad(
        lambda p: mla_moe.make_loss_fn(dense)(p, batch, None)[0])(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for (where, a), b in zip(jax.tree_util.tree_leaves_with_path(grad),
                             jax.tree.leaves(grad_want)):
        if not is_index(where):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7,
                                       err_msg=jax.tree_util.keystr(where))


def test_the_sparse_switches_refuse_streams_and_a_prediction_module():
    for more in (dict(hc_mult=2), dict(mtp_layers=1)):
        with pytest.raises(ValueError, match="indexer"):
            mla_moe.init(jax.random.PRNGKey(0), mla_moe.mla_moe_tiny(
                index_n_heads=2, **more))


# -- the group-limited router -------------------------------------------------


def _logit(score):
    return float(np.log(score / (1 - score)))


def test_group_limited_routing_against_a_table_made_by_hand():
    """8 experts in 4 groups of 2, 2 groups kept, top-3. Token 0's best
    expert (7, score 0.9) lies in group 3, whose two scores add up to
    less than two other groups': the token loses it. Token 1's two best
    groups hold its three best experts and nothing is lost. Token 2 has
    the bias move a group in."""
    scores = np.array([
        # g0          g1          g2          g3
        [0.60, 0.55, 0.50, 0.52, 0.10, 0.12, 0.90, 0.05],
        [0.80, 0.70, 0.10, 0.20, 0.75, 0.30, 0.15, 0.05],
        [0.40, 0.40, 0.41, 0.41, 0.10, 0.10, 0.05, 0.05],
    ])
    logits = jnp.asarray(np.vectorize(_logit)(scores), jnp.float32)
    top_i, top_w, s, groups = moe.group_limited_routing(
        logits, 3, n_group=4, topk_group=2, renormalise=True, scale=2.5)
    np.testing.assert_allclose(s, scores, atol=1e-6)
    # marks: token 0: 1.15, 1.02, 0.22, 0.95 -> groups 0 and 1
    np.testing.assert_array_equal(groups[0], [True, True, False, False])
    assert sorted(top_i[0].tolist()) == [0, 1, 3]  # not 6, the best
    np.testing.assert_allclose(
        sorted(top_w[0].tolist()),
        sorted(2.5 * np.array([0.60, 0.55, 0.52]) / 1.67), rtol=1e-5)
    # token 1: marks 1.5, 0.3, 1.05, 0.2 -> groups 0 and 2
    np.testing.assert_array_equal(groups[1], [True, False, True, False])
    assert sorted(top_i[1].tolist()) == [0, 1, 4]
    # plain top-3 of all 8 would have taken expert 6 for token 0
    plain_i, _, _ = moe.sigmoid_topk_routing(logits, 3, True, 2.5)
    assert 6 in plain_i[0].tolist() and 6 not in top_i[0].tolist()
    # the bias: +0.02 on group 2's two experts does not lift it over
    # groups 0 and 1 (0.24 against 0.8 and 0.82), +0.4 does, and the
    # weights stay the unbiased scores'
    bias = jnp.zeros(8).at[4:6].set(0.4)
    top_i, top_w, _, groups = moe.group_limited_routing(
        logits, 3, 4, 2, True, 1.0, bias)
    np.testing.assert_array_equal(groups[2], [False, True, True, False])
    assert sorted(top_i[2].tolist()) == [2, 4, 5]
    np.testing.assert_allclose(sorted(top_w[2].tolist()), sorted(
        np.array([0.41, 0.10, 0.10]) / 0.61), rtol=1e-5)
    with pytest.raises(ValueError, match="groups"):
        moe.top_groups(jnp.zeros((2, 9)), 4, 2)


def test_the_router_agrees_with_the_references():
    model = toy()
    key = jax.random.split(jax.random.PRNGKey(5), 2)
    scores = jax.nn.sigmoid(jax.random.normal(key[0], (128, 24)))
    bias = 0.05 * jax.random.normal(key[1], (24,))
    logits = jnp.log(scores / (1 - scores))
    top_i, top_w, _, groups = moe.group_limited_routing(
        logits, 4, 4, 2, True, 2.5, bias)
    want_i, want_groups = reference.route(jax.nn.sigmoid(logits), bias,
                                          model)
    np.testing.assert_array_equal(np.sort(top_i, 1), np.sort(want_i, 1))
    np.testing.assert_array_equal(groups, want_groups)
    np.testing.assert_allclose(
        top_w, reference.gates_of(jax.nn.sigmoid(logits), top_i, model),
        rtol=1e-5)


def test_all_the_shares_add_up_to_the_whole_layer():
    """24 experts over 4 shares of 6, a share a group, under the whole
    group-limited router: the routed parts the four held sets give (the
    program's ``held_expert_ffn`` fed ``group_limited_routing``) plus
    the shared expert ONCE sum to the uncut reference's expert layer;
    and a share whose group a token does not keep gets no row of it."""
    model = toy()
    uncut = dict(model, deployment=dict(
        model["deployment"], experts_held=list(range(24))))
    key = jax.random.split(jax.random.PRNGKey(7), 9)
    d, f = model["hidden_size"], model["moe_intermediate_size"]

    def swiglu(k0, lead):
        return {"w_gate": jax.random.normal(key[k0], lead + (d, f)) * 0.2,
                "w_up": jax.random.normal(key[k0 + 1], lead + (d, f)) * 0.2,
                "w_down": jax.random.normal(key[k0 + 2], lead + (f, d)) * 0.2}

    every, shared = swiglu(0, (24,)), swiglu(3, ())
    w_router = jax.random.normal(key[6], (d, 24))
    bias = 0.01 * jax.random.normal(key[7], (24,))
    z = jax.random.normal(key[8], (64, d))
    k = model["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        want, top_i, groups = reference.expert_layer(
            z, {"w_router": w_router, "router_bias": bias, "shared": shared,
                "experts": every}, uncut)
        got_i, got_w, _, got_groups = moe.group_limited_routing(
            z @ w_router, k, 4, 2, True, 2.5, bias)
        assert bool(jnp.all(jnp.sort(got_i, 1) == jnp.sort(top_i, 1)))
        assert bool(jnp.all(got_groups == groups))
        total = reference.swiglu(z, shared)  # once, whatever the shares
        for share in range(4):
            held = tuple(range(6 * share, 6 * share + 6))
            mine = jax.tree.map(lambda a: a[6 * share:6 * share + 6], every)
            program, stats = moe.held_expert_ffn(
                {"gate": {"kernel": mine["w_gate"]},
                 "up": {"kernel": mine["w_up"]},
                 "down": {"kernel": mine["w_down"]}},
                z, got_i, got_w, held,
                moe.held_row_bound(64, k, 24, 6, 4.0, 8), 8, True)
            assert float(stats["rows_dropped"]) == 0
            # a token that does not keep this share's group sends it
            # nothing
            outside = ~np.asarray(groups[:, share])
            assert outside.any() and not np.asarray(
                program)[outside].any()
            total = total + program
    assert float(jnp.abs(total - want).max()) < 1e-4
    assert float(jnp.abs(want - reference.swiglu(z, shared)).max()) > 0.1


# -- under a mesh -------------------------------------------------------------


def test_the_new_leaves_under_the_mla_moe_rules():
    """``fsdp=2 x tensor=2`` on the CPU's virtual devices: the output
    gate is a column beside ``o_proj``'s rows, the indexer is whole on
    ``tensor`` and shards its projections' input axis over ``fsdp``,
    its key norm, the gated norms' factors and the selection bias are
    whole; and the step trains under the mesh (XLA's dense forms: the
    sparse kernels run on one chip's rows), the bias left as it was."""
    import optax

    from dlrover_tpu.parallel.accelerate import accelerate
    from dlrover_tpu.parallel.mesh import MeshPlan
    from dlrover_tpu.parallel.sharding_rules import (
        _flatten_with_paths,
        mla_moe_rules,
    )
    from dlrover_tpu.parallel.strategy import Strategy

    config = job.model_config(toy())
    shapes = jax.eval_shape(mla_moe.make_init_fn(config),
                            jax.random.PRNGKey(0))
    sizes = {"data": 1, "fsdp": 2, "tensor": 2}
    rules = mla_moe_rules()
    spec = {path: rules.spec_for(path, leaf.shape, sizes)
            for path, leaf in _flatten_with_paths(shapes)}
    for stack in ("dense_layers", "moe_layers"):
        attn = f"{stack}/attn"
        assert tuple(spec[f"{attn}/g_proj/kernel"]) == (
            None, "fsdp", "tensor")
        assert tuple(spec[f"{attn}/o_proj/kernel"]) == (
            None, "tensor", "fsdp")
        for leaf in ("q_proj", "k_proj", "w_proj"):
            assert tuple(spec[f"{attn}/index/{leaf}/kernel"]) == (
                None, "fsdp", None), leaf
        for leaf in ("scale", "bias"):
            assert not any(spec[f"{attn}/index/k_norm/{leaf}"])
        for norm in ("input_norm", "post_norm"):
            for leaf in ("scale", "gate_a", "gate_b"):
                assert not any(spec[f"{stack}/{norm}/{leaf}"]), (norm, leaf)
    for leaf in ("scale", "gate_a", "gate_b"):
        assert not any(spec[f"norm/{leaf}"])
    assert not any(spec["moe_layers/moe/router/bias"])

    ids = np.random.default_rng(0).integers(0, 512, (4, 65)).astype(np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    result = accelerate(
        mla_moe.make_init_fn(config),
        mla_moe.make_loss_fn(config, head_chunk=16), optax.adam(3e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                          rule_set="mla_moe", remat_policy=""))
    state = result.init_fn(jax.random.PRNGKey(0))
    layer = state.params["moe_layers"]
    assert tuple(layer["attn"]["g_proj"]["kernel"].sharding.spec) == (
        None, "fsdp", "tensor")
    assert tuple(layer["attn"]["index"]["q_proj"]["kernel"].sharding.spec
                 ) == (None, "fsdp", None)
    bias = np.asarray(layer["moe"]["router"]["bias"])
    sharded = result.shard_batch(batch)
    losses, kls = [], []
    for i in range(6):
        state, metrics = result.train_step(state, sharded,
                                           jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
        kls.append(float(metrics[StepCounter.DSA_INDEX_KL]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    # the indexer's loss is in every step's metrics (its target, the
    # attention's own probabilities, moves as the model trains)
    assert np.isfinite(kls).all() and min(kls) > 0
    assert float(metrics[StepCounter.MOE_GROUP_TOKENS]) == 2 * 4 * 64
    # no gradient reaches the selection bias, so the optimizer leaves it
    assert np.array_equal(bias, np.asarray(
        state.params["moe_layers"]["moe"]["router"]["bias"]))
