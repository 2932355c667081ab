"""The frame the period-stacked decoders share (``models/common.py``:
``period_of``, ``stacked_init``, ``layer_slot``, ``scan_periods``,
``causal_conv``, ``norm_init``, ``make_init_fn``) and the head with the
loss (``models/losses.lm_head_loss``), each alone on toy layers. The
models' own tests hold the same code through each module."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
