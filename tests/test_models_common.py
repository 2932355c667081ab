"""The frame the period-stacked decoders share (``models/common.py``:
``period_of``, ``stacked_init``, ``layer_slot``, ``scan_periods``,
``causal_conv``, ``norm_init``, ``make_init_fn``) and the head with the
loss (``models/losses.lm_head_loss``), each alone on toy layers. The
models' own tests hold the same code through each module."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from dlrover_tpu.models import common, losses

A, B = "a", "b"


@pytest.mark.parametrize("one, depth", [
    ((A,), 7), ((A, B), 6), ((A, A, A, B), 8),
    ((A,) * 5 + (B,) + (A,) * 4, 20)])
def test_the_period_is_the_smallest_at_which_the_list_repeats(one, depth):
    kinds = one * (40 // len(one))
    assert common.period_of(kinds, depth, "layer_types") == len(one)
    # tuples of several lists, zipped, are kinds like any other
    pairs = list(zip(kinds, kinds))
    assert common.period_of(pairs, depth, "two lists") == len(one)


def test_a_list_that_does_not_repeat_is_its_own_period():
    assert common.period_of((A, A, B), 3, "layer_types") == 3
    # and one cut inside a period repeats at that period all the same
    assert common.period_of((A, B, B, A, B), 3, "layer_types") == 3


@pytest.mark.parametrize("kinds, depth, words", [
    ((A, B) * 4, 9, "at least as long as the depth"),
    ((A, B) * 4, 0, "at least as long as the depth"),
    ((A, B) * 4, 3, "no whole number of periods"),
    ((A, A, A, B) * 8, 10, "repeats every 4 layers")])
def test_a_depth_the_list_cannot_give_is_refused(kinds, depth, words):
    with pytest.raises(ValueError, match=words) as refusal:
        common.period_of(kinds, depth, "the_list")
    assert "the_list" in str(refusal.value)


def toy_init(lead, width):
    """``layer_init`` of two toy kinds with different trees."""

    def layer_init(key, kind):
        tree = {"w": jax.random.normal(key, lead + (width, width)) * 0.3}
        if kind == B:
            tree["shift"] = jax.random.normal(
                jax.random.fold_in(key, 1), lead + (width,))
        return tree

    return layer_init


def toy_layer(kind):
    def layer(x, p):
        y = jnp.tanh(x @ p["w"])
        if kind == B:
            y = y + p["shift"]
        return x + y, {"mean": jnp.mean(y), "count": jnp.float32(kind == B)}

    return layer


@pytest.mark.parametrize("plan", [(A,), (A, B), (A, A, B, A)])
def test_stacked_init_keys_the_layers_by_their_place_in_the_period(plan):
    periods, width = 3, 4
    key = jax.random.PRNGKey(5)
    stacked = common.stacked_init(key, plan, toy_init((periods,), width))
    assert sorted(stacked) == sorted(str(j) for j in range(len(plan)))
    keys = jax.random.split(key, len(plan))
    for j, kind in enumerate(plan):
        # position j's own key, the kind's own tree, the periods leading
        want = toy_init((periods,), width)(keys[j], kind)
        assert sorted(stacked[str(j)]) == sorted(want)
        for name, leaf in want.items():
            assert leaf.shape[0] == periods
            np.testing.assert_array_equal(stacked[str(j)][name], leaf)


@pytest.mark.parametrize("period, depth", [(1, 3), (2, 6), (4, 8), (10, 20)])
def test_layer_slot_finds_every_layer_once(period, depth):
    slots = [common.layer_slot(index, period) for index in range(depth)]
    assert len(set(slots)) == depth
    assert {key for key, _ in slots} == {str(j) for j in range(period)}
    assert {at for _, at in slots} == set(range(depth // period))
    # in the scan's order: a period's layers, then the next period's
    assert slots == [(str(j), at) for at in range(depth // period)
                     for j in range(period)]


def by_hand(layers, x, stacked, periods):
    """The stack as a plain loop over ``layer_slot``: (x, the layers'
    outs summed a period, stacked)."""
    sums = []
    for at in range(periods):
        outs = []
        for j, layer in enumerate(layers):
            key, place = common.layer_slot(at * len(layers) + j, len(layers))
            x, out = layer(x, jax.tree.map(lambda a: a[place], stacked[key]))
            outs.append(out)
        sums.append(jax.tree.map(lambda *a: sum(a), *outs))
    return x, jax.tree.map(lambda *a: jnp.stack(a), *sums)


@pytest.mark.parametrize("plan", [(A,), (A, B), (B, A, A, B)])
@pytest.mark.parametrize("remat", [False, True])
def test_scan_periods_is_the_plain_loop(plan, remat):
    periods, width = 3, 8
    stacked = common.stacked_init(jax.random.PRNGKey(0), plan,
                                  toy_init((periods,), width))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, width))
    layers = [toy_layer(kind) for kind in plan]
    scanned = [jax.checkpoint(layer) for layer in layers] if remat else layers

    got_x, got = jax.jit(
        lambda x, p: common.scan_periods(scanned, x, p))(x, stacked)
    want_x, want = jax.jit(
        lambda x, p: by_hand(layers, x, p, periods))(x, stacked)
    np.testing.assert_array_equal(got_x, want_x)
    assert sorted(got) == ["count", "mean"]
    for name in got:
        assert got[name].shape == (periods,)
        np.testing.assert_array_equal(got[name], want[name])
    assert float(got["count"].sum()) == periods * plan.count(B)

    def loss(run):
        def of(x, p):
            out, sums = run(x, p)
            return jnp.sum(jnp.sin(out)) + jnp.sum(sums["mean"])
        return jax.jit(jax.grad(of, argnums=(0, 1)))

    got_g = loss(lambda x, p: common.scan_periods(scanned, x, p))(x, stacked)
    want_g = loss(lambda x, p: by_hand(layers, x, p, periods))(x, stacked)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-6)


def head_operands(seq, tied):
    rows, d, vocab = 2, 16, 40
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    hidden = jax.random.normal(k[0], (rows, seq, d))
    labels = jax.random.randint(k[1], (rows, seq), 0, vocab)
    labels = labels.at[:, -3:].set(losses.IGNORE_INDEX)
    shape = (vocab, d) if tied else (d, vocab)
    return hidden, jax.random.normal(k[2], shape) * 0.2, labels


@pytest.mark.parametrize("tied", [False, True])
@pytest.mark.parametrize("head_chunk", [8, 7, 24, 100])
def test_the_fused_head_gives_the_unfused_loss(head_chunk, tied):
    """``head_chunk`` 8 divides the row of 24, 7 does not (the largest
    divisor under it is taken), 24 is one chunk, 100 is over the row."""
    hidden, weight, labels = head_operands(24, tied)

    def loss(head_chunk):
        def of(hidden, weight):
            return losses.lm_head_loss(
                hidden, weight.T if tied else weight, labels, head_chunk)
        return jax.jit(jax.value_and_grad(of, argnums=(0, 1)))(hidden,
                                                                weight)

    (plain, plain_g), (fused, fused_g) = loss(0), loss(head_chunk)
    assert abs(float(plain) - float(fused)) < 1e-5
    for a, b in zip(plain_g, fused_g):
        assert a.shape == b.shape  # a tied table's gradient is a table
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
    # and the unfused one is the cross entropy of the whole logits
    head = weight.T if tied else weight
    assert abs(float(plain) - float(losses.masked_lm_loss(
        hidden @ head, labels))) < 1e-6


def test_the_head_is_read_in_the_hidden_states_dtype():
    hidden, head, labels = head_operands(16, tied=False)
    low = hidden.astype(jnp.bfloat16)
    for head_chunk in (0, 8):
        got = losses.lm_head_loss(low, head, labels, head_chunk)
        want = losses.masked_lm_loss(
            (low @ head.astype(jnp.bfloat16)).astype(jnp.float32), labels)
        assert got.dtype == jnp.float32
        assert abs(float(got) - float(want)) < 1e-5


def vocabulary_wide(jaxpr, vocab, names, around=(), stack=""):
    """The equations of ``jaxpr`` called one of ``names``, nested ones
    too, with ``vocab`` among an operand's or a result's sizes: the
    primitives around each and its whole name stack."""
    found = []
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if eqn.primitive.name in names and any(
                vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            found.append((around, here))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += vocabulary_wide(sub, vocab, names,
                                     around + (eqn.primitive.name,), here)
    return found


def toy_step(module, vocab, **overrides):
    """The jaxpr of a toy model's loss and gradients, the head fused
    over two chunks of 16."""
    config = getattr(module, module.__name__.rsplit(".", 1)[1] + "_tiny")(
        vocab_size=vocab, **overrides)
    params = jax.eval_shape(module.make_init_fn(config),
                            jax.random.PRNGKey(0))
    batch = {key: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for key in ("input_ids", "labels")}
    return jax.make_jaxpr(jax.value_and_grad(
        module.make_loss_fn(config, head_chunk=16), has_aux=True))(
            params, batch, jax.random.PRNGKey(0)).jaxpr


def masked_rows(labels):
    return labels.at[0].set(losses.IGNORE_INDEX)


def all_masked(labels):
    return jnp.full_like(labels, losses.IGNORE_INDEX)


@pytest.mark.parametrize("cotangent", [1.0, 0.3 / 2])
@pytest.mark.parametrize("masking", [None, masked_rows, all_masked])
@pytest.mark.parametrize("tied", [False, True])
@pytest.mark.parametrize("head_chunk", [8, 7])
def test_the_one_pass_head_is_the_checkpointed_scan(head_chunk, tied,
                                                    masking, cotangent):
    """Value and gradients of ``one_pass_lm_head_loss`` against
    ``chunked_lm_head_loss`` in float32: a chunk that divides the row of
    24 and one that does not, a head and a table's transpose, labels
    with a tail, a whole row or everything masked, and the cotangent of
    a prediction module's pass (``mtp_loss_weight / mtp_layers``)."""
    hidden, weight, labels = head_operands(24, tied)
    if masking:
        labels = masking(labels)

    def step(head_loss):
        def of(hidden, weight):
            return cotangent * head_loss(
                hidden, weight.T if tied else weight, labels, head_chunk)
        return jax.jit(jax.value_and_grad(of, argnums=(0, 1)))(hidden,
                                                                weight)

    (want, want_g), (got, got_g) = (
        step(losses.chunked_lm_head_loss), step(losses.one_pass_lm_head_loss))
    assert abs(float(want) - float(got)) < 1e-6
    for w, g in zip(want_g, got_g):
        assert w.shape == g.shape and w.dtype == g.dtype
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-7)
    if masking is all_masked:
        assert float(got) == 0.0 and not any(g.any() for g in got_g)


@pytest.mark.parametrize("tied", [False, True])
@pytest.mark.parametrize("head_chunk", [8, 7, 100])
def test_the_primal_is_the_forward_rules_value(head_chunk, tied):
    """With no gradient asked (``eval_step``) the value alone is made,
    by the forward rule's operations in the forward rule's order: run
    an operation at a time the two are equal bit for bit, and compiled
    (the two programs are fused apart, and a row's sum of exponentials
    may come out an ulp off) to float32's last digits."""
    hidden, weight, labels = head_operands(24, tied)

    def of(hidden, weight):
        return losses.one_pass_lm_head_loss(
            hidden, weight.T if tied else weight, labels, head_chunk)

    with_grad = jax.value_and_grad(of, argnums=(0, 1))
    with jax.disable_jit():
        alone, (both, _) = of(hidden, weight), with_grad(hidden, weight)
    assert alone.dtype == both.dtype == jnp.float32
    assert float(alone) == float(both)
    compiled = jax.jit(of)(hidden, weight), jax.jit(with_grad)(
        hidden, weight)[0]
    for value in compiled:
        assert abs(float(value) - float(alone)) < 1e-6
    assert not vocabulary_wide(jax.make_jaxpr(of)(hidden, weight).jaxpr,
                               40, ("mul",))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_one_pass_head_keeps_the_two_gradients_alone(dtype):
    """What the backward pass is left: ``dx`` like ``hidden`` and ``dW``
    in the head's dtype, nothing of the logits (the checkpointed scan
    keeps its inputs and makes the logits again)."""
    from jax._src.ad_checkpoint import saved_residuals

    hidden, head, labels = head_operands(24, tied=False)
    hidden = hidden.astype(dtype)
    kept = saved_residuals(
        lambda x, w: losses.lm_head_loss(x, w, labels, 8), hidden, head)
    assert sorted((a.shape, a.dtype) for a, _ in kept) == sorted(
        [(hidden.shape, hidden.dtype), (head.shape, head.dtype)])
    assert not any("argument" in where for _, where in kept)


@pytest.mark.parametrize("name, passes, overrides", [
    ("sambay", 1, {}), ("gqa_moe", 1, {}), ("delta_hybrid", 1, {}),
    ("ssd_hybrid", 1, {}), ("mla_moe", 1, {}),
    ("mla_moe", 2, {"mtp_layers": 1})])
def test_a_step_multiplies_by_the_head_three_times_a_chunk(name, passes,
                                                           overrides):
    """The five modules that call ``lm_head_loss``: a pass of the head
    is one scan whose body holds the logits' product and the two
    gradients' (the checkpointed scan has four in two bodies), no
    checkpoint around it, and all of it, the backward rule's scaling
    too, under the scope ``head_loss`` (what ``head_loss_ms`` sums)."""
    jaxpr = toy_step(importlib.import_module("dlrover_tpu.models." + name),
                     250, **overrides)
    products = vocabulary_wide(jaxpr, 250, ("dot_general",))
    assert [around for around, _ in products] == [("scan",)] * 3 * passes
    scaled = vocabulary_wide(jaxpr, 250, ("mul",))
    assert sum(not around for around, _ in scaled) >= passes
    for _, stack in products + scaled:
        assert "head_loss" in stack, stack


def test_llamas_step_keeps_the_checkpointed_scan(monkeypatch):
    """``models/llama.py`` calls ``chunked_lm_head_loss`` itself, and
    its program stays what the elastic cell was bounded on
    (``ROADMAP.md`` S12): the forward body's product, and the replayed
    one and the two gradients' inside the backward body's checkpoint."""
    from dlrover_tpu.models import llama

    def refuse(*args):
        raise AssertionError("llama.py took the one pass")

    monkeypatch.setattr(losses, "one_pass_lm_head_loss", refuse)
    products = vocabulary_wide(toy_step(llama, 250), 250, ("dot_general",))
    assert [around for around, _ in products] == [
        ("scan",)] + [("scan", "remat2")] * 3
    assert sum("rematted_computation" in stack for _, stack in products) == 1
    for _, stack in products:
        assert "head_loss" in stack, stack


@pytest.mark.parametrize("tied", [False, True])
def test_a_head_sharded_over_the_vocabulary_gives_the_same_gradients(tied):
    """A 2x2 mesh of the virtual CPU devices, the rows over one axis and
    the head's vocabulary over the other: the scan's carry and the
    chunks' softmax are partitioned by the compiler, and the loss and
    both gradients are the single device's."""
    hidden, weight, labels = head_operands(24, tied)

    def of(hidden, weight):
        return 0.5 * losses.one_pass_lm_head_loss(
            hidden, weight.T if tied else weight, labels, 8)

    step = jax.jit(jax.value_and_grad(of, argnums=(0, 1)))
    want, want_g = step(hidden, weight)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "fsdp"))
    got, got_g = step(
        jax.device_put(hidden, NamedSharding(mesh, P("data"))),
        jax.device_put(weight, NamedSharding(
            mesh, P("fsdp", None) if tied else P(None, "fsdp"))))
    assert abs(float(want) - float(got)) < 1e-6
    for w, g in zip(want_g, got_g):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-7)


@pytest.mark.parametrize("taps", [1, 2, 4])
@pytest.mark.parametrize("bias", [0.0, "channel"])
def test_causal_conv_is_numpys_a_channel(taps, bias):
    rows, seq, channels = 2, 11, 3
    k = jax.random.split(jax.random.PRNGKey(taps), 3)
    u = np.array(jax.random.normal(k[0], (rows, seq, channels)))
    kernel = np.asarray(jax.random.normal(k[1], (taps, channels)))
    if bias == "channel":
        bias = np.asarray(jax.random.normal(k[2], (channels,)))
    got = np.asarray(common.causal_conv(jnp.asarray(u), jnp.asarray(kernel),
                                        jnp.asarray(bias)))
    assert got.shape == u.shape
    for row in range(rows):
        for ch in range(channels):
            # out[t] = sum_k kernel[k] u[t - (K - 1) + k]: numpy's
            # convolution of the reversed filter, cut to the row
            want = np.convolve(u[row, :, ch], kernel[::-1, ch])[:seq]
            np.testing.assert_allclose(
                got[row, :, ch], want + np.broadcast_to(bias, (channels,))[ch],
                rtol=0, atol=1e-5)
    # causal: a later token moves nothing before it
    u[:, 6:] += 1.0
    moved = np.asarray(common.causal_conv(
        jnp.asarray(u), jnp.asarray(kernel), jnp.asarray(bias)))
    np.testing.assert_array_equal(moved[:, :6], got[:, :6])


@pytest.mark.parametrize("lead", [(), (3,)])
@pytest.mark.parametrize("bias", [False, True])
def test_norm_init_is_the_identity_norm(lead, bias):
    p = common.norm_init(lead, 8, jnp.float32, bias=bias)
    assert sorted(p) == (["bias", "scale"] if bias else ["scale"])
    assert p["scale"].shape == lead + (8,)
    assert float(p["scale"].min()) == 1.0
    if bias:
        assert p["bias"].shape == lead + (8,) and not p["bias"].any()


def test_make_init_fn_carries_the_layers_by_kind():
    def init(rng, config):
        return {"w": jax.random.normal(rng, (config, config))}

    init_fn = common.make_init_fn(init, 4, {"attn_full": 2})
    assert init_fn.layer_kinds == {"attn_full": 2}
    assert init_fn(jax.random.PRNGKey(0))["w"].shape == (4, 4)
    assert common.param_count(init_fn) == 16
