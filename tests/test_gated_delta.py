"""``ops/gated_delta.py``: the chunked form of the gated delta rule, as
a ``lax.scan`` over chunks and through the ``gdn_fwd`` / ``gdn_bwd``
kernels in the Pallas interpreter, against the recurrence token by
token. Toy sizes, float32, on the CPU."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.gated_delta import (
    _unit_lower_inverse,
    chain_tiles,
    gated_delta_rule,
    gated_delta_rule_auto,
    gated_delta_rule_reference,
)

# Everything below is float32 on both sides, so the two differ by the
# order of float32 sums alone: the chunked form adds a chunk's writes
# through a triangular inverse and a [dk, dv] product where the
# recurrence adds them one by one. Measured: 1e-6 of the largest entry
# at 256 tokens in the outputs and in every gradient; 2e-5 leaves room
# for a chunk of 128 (twice the terms a sum) and a decay near 1 (every
# term kept). A wrong mask, ratio or sign reads 1e-2 to 1.
TOL = 2e-5

HEADS, DK, DV = 2, 16, 32


def operands(seed, batch, seq, beta_at, decay, heads=HEADS):
    """q and k at length 1 (q over sqrt(dk)), as the model hands them
    over; ``beta`` around ``beta_at`` within (0, 2); the log-decay a
    token around ``-decay``."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(key):
        u = jax.random.normal(key, (batch, seq, heads, DK))
        return u / jnp.linalg.norm(u, axis=-1, keepdims=True)

    q, key = unit(k[0]) / math.sqrt(DK), unit(k[1])
    v = jax.random.normal(k[2], (batch, seq, heads, DV))
    g = -decay * jax.nn.softplus(jax.random.normal(k[3], (batch, seq, heads)))
    spread = min(beta_at, 2.0 - beta_at)
    beta = beta_at + spread * 0.9 * jnp.tanh(
        jax.random.normal(k[4], (batch, seq, heads)))
    weight = jax.random.normal(k[5], (batch, seq, heads, DV))
    return (q, key, v, g, beta), weight


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def scalar(fn, weight):
    """A loss that feels the outputs and the final state."""

    def loss(*args):
        o, final = fn(*args)
        return (o * weight).sum() + 0.1 * (final ** 2).sum()

    return loss


CASES = [
    # (seq, chunk, beta around, decay a token)
    pytest.param(64, 64, 0.5, 0.1, id="one-chunk-of-64"),
    pytest.param(256, 64, 0.5, 0.1, id="four-chunks-of-64"),
    pytest.param(128, 128, 0.5, 0.1, id="one-chunk-of-128"),
    pytest.param(256, 128, 0.5, 0.1, id="two-chunks-of-128"),
    pytest.param(256, 64, 1.5, 0.1, id="beta-over-1"),
    pytest.param(256, 128, 1.0, 0.1, id="beta-both-sides-of-1"),
    pytest.param(256, 64, 1.0, 2.0, id="strong-decay"),
    pytest.param(256, 64, 1.0, 1e-3, id="weak-decay"),
    pytest.param(200, 64, 1.0, 0.1, id="a-row-padded-to-its-chunk"),
]


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scan-over-chunks", "kernels-interpreted"])
@pytest.mark.parametrize("seq,chunk,beta_at,decay", CASES)
def test_forward_is_the_recurrence(seq, chunk, beta_at, decay, kernels):
    args, _ = operands(seq + chunk, 2, seq, beta_at, decay)
    want_o, want_final = gated_delta_rule_reference(*args)
    o, final = gated_delta_rule(*args, use_kernels=kernels, chunk=chunk,
                                heads_per_program=1 + kernels)
    assert o.shape == want_o.shape and final.shape == (2, HEADS, DK, DV)
    assert rel(o, want_o) < TOL and rel(final, want_final) < TOL


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scan-over-chunks", "kernels-interpreted"])
@pytest.mark.parametrize("seq,chunk,beta_at,decay", CASES)
def test_all_five_gradients_are_the_recurrences(seq, chunk, beta_at, decay,
                                                kernels):
    args, weight = operands(seq + chunk + 1, 2, seq, beta_at, decay)
    want = jax.grad(scalar(gated_delta_rule_reference, weight),
                    argnums=range(5))(*args)
    got = jax.jit(jax.grad(scalar(
        lambda *a: gated_delta_rule(*a, use_kernels=kernels, chunk=chunk),
        weight), argnums=range(5)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert rel(a, b) < TOL, (name, rel(a, b))


def test_the_kernels_give_what_the_scan_over_chunks_gives():
    """The two chains run the same three lines a chunk on the same
    prepared operands: outputs and gradients agree far inside the
    tolerance to the recurrence."""
    args, weight = operands(7, 2, 256, 1.0, 0.1)

    def run(kernels):
        return jax.jit(jax.value_and_grad(scalar(
            lambda *a: gated_delta_rule(*a, use_kernels=kernels), weight),
            argnums=range(5)))(*args)

    (loss_a, grads_a), (loss_b, grads_b) = run(True), run(False)
    assert abs(float(loss_a - loss_b)) < 1e-5 * abs(float(loss_b))
    for a, b in zip(grads_a, grads_b):
        assert rel(a, b) < 2e-6


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scan-over-chunks", "kernels-interpreted"])
def test_a_row_split_in_two_with_the_state_handed_over(kernels):
    """The first half's final state as the second half's initial one
    gives the whole row's outputs, final state and, through the handed
    state, gradients."""
    args, weight = operands(11, 1, 256, 1.0, 0.1)
    run = lambda *a, **kw: gated_delta_rule(  # noqa: E731
        *a, use_kernels=kernels, **kw)

    def halves(*a):
        first = [t[:, :128] for t in a]
        second = [t[:, 128:] for t in a]
        o1, state = run(*first)
        o2, final = run(*second, initial_state=state)
        return jnp.concatenate([o1, o2], axis=1), final

    o, final = halves(*args)
    want_o, want_final = run(*args)
    assert rel(o, want_o) < TOL and rel(final, want_final) < TOL
    got = jax.grad(scalar(halves, weight), argnums=range(5))(*args)
    want = jax.grad(scalar(run, weight), argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert rel(a, b) < TOL


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scan-over-chunks", "kernels-interpreted"])
def test_beta_zero_leaves_the_state_a_pure_decay(kernels):
    """Nothing is erased and nothing written: the state a row starts
    from comes out scaled by the row's whole decay, and a token answers
    from it."""
    (q, k, v, g, _), _ = operands(13, 1, 128, 1.0, 0.05)
    start = jax.random.normal(jax.random.PRNGKey(5), (1, HEADS, DK, DV))
    o, final = gated_delta_rule(q, k, v, g, jnp.zeros_like(g),
                                initial_state=start, use_kernels=kernels)
    kept = jnp.exp(jnp.cumsum(g, axis=1))  # [B, S, H]
    assert rel(final, kept[:, -1][..., None, None] * start) < 1e-6
    want_o = jnp.einsum("bshk,bhkv->bshv", q, start) * kept[..., None]
    assert rel(o, want_o) < TOL


def test_without_the_erase_the_rule_is_plain_linear_attention():
    """Orthogonal keys a chunk see nothing of each other: ``A`` is zero
    and the rule is decayed linear attention, which a cumulative sum
    gives."""
    seq = DK  # one key a direction
    eye = jnp.eye(DK)[None, :, None, :]  # [1, S, 1, dk]
    k = jnp.broadcast_to(eye, (1, seq, 1, DK))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, seq, 1, DV))
    q = jnp.ones((1, seq, 1, DK))
    g = jnp.zeros((1, seq, 1))
    beta = jnp.ones((1, seq, 1))
    o, _ = gated_delta_rule(q, k, v, g, beta, use_kernels=False)
    assert rel(o, jnp.cumsum(v, axis=1)) < 1e-6


@pytest.mark.parametrize("size", [2, 16, 64, 128])
def test_the_doubling_inverse_is_the_inverse(size):
    """Block substitution by doubling against ``numpy.linalg.inv`` in
    float64, on the rule's own matrix at its hardest: ``beta`` up to 2,
    no decay, keys of 4 dimensions so that many are nearly the same
    (there the powers of ``A`` grow like ``2^n`` and a Neumann series
    over the whole chunk cancels catastrophically, while ``T`` stays
    bounded); and its gradient against autodiff through the doubling."""
    from dlrover_tpu.ops.gated_delta import _doubling_inverse

    rng = np.random.RandomState(size)
    k = rng.randn(3, size, 4)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = rng.uniform(0.0, 2.0, (3, size, 1))
    a = np.tril(beta * (k @ k.transpose(0, 2, 1)), -1).astype(np.float32)
    got = np.asarray(_unit_lower_inverse(jnp.asarray(a)))
    want = np.linalg.inv(np.eye(size) + a.astype(np.float64))
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())
    assert np.abs(np.triu(got, 1)).max() == 0.0
    weight = jnp.asarray(rng.randn(3, size, size), jnp.float32)
    mask = jnp.tril(jnp.ones((size, size), bool), -1)
    grad = lambda f: jax.grad(lambda x: (  # noqa: E731
        f(jnp.where(mask, x, 0.0)) * weight).sum())(jnp.asarray(a))
    assert rel(grad(_unit_lower_inverse), grad(_doubling_inverse)) < 1e-4


def test_under_a_mesh_the_op_gives_the_single_device_result():
    """Batch over ``fsdp`` and heads over ``tensor`` on a 2 x 2 mesh of
    the CPU's virtual devices: a head's recurrence needs nothing of
    another's, so outputs and gradients are the single device's."""
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs four devices")
    args, weight = operands(17, 2, 128, 1.0, 0.1, heads=4)
    loss = lambda *a: (gated_delta_rule_auto(  # noqa: E731
        *a, use_kernels=True) * weight).sum()
    want_o = gated_delta_rule(*args)[0]
    want = jax.grad(loss, argnums=range(5))(*args)  # no mesh: plain call
    mesh = Mesh(np.asarray(devices[:4]).reshape(1, 2, 2),
                ("data", "fsdp", "tensor"))
    with jax.sharding.set_mesh(mesh):
        got_o = jax.jit(gated_delta_rule_auto)(*args)
        got = jax.jit(jax.grad(loss, argnums=range(5)))(*args)
    assert rel(got_o, want_o) < 1e-6
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5


def test_a_chunk_that_is_no_power_of_two_is_refused():
    args, _ = operands(1, 1, 96, 1.0, 0.1)
    with pytest.raises(ValueError, match="power of two"):
        gated_delta_rule(*args, chunk=96)
    with pytest.raises(ValueError, match="divide"):
        gated_delta_rule(*args, heads_per_program=3)


@pytest.mark.parametrize("seq,heads,want", [
    (8192, 30, (64, 10)),  # the benchmark's cell
    (8192, 10, (64, 10)),  # one of its three head groups
    (8192, 15, (64, 5)),  # its heads over two chips
    (64, 4, (64, 4)), (96, 4, (32, 4)), (8, 2, (8, 2)),
])
def test_the_tiles_follow_the_shape(seq, heads, want):
    assert chain_tiles(seq, heads) == want


def test_the_head_groups_follow_the_shape():
    """The smallest divisor of the heads at which a group's backward
    holds a gigabyte or less: three for the benchmark's layer, one at a
    toy size, and never more groups than heads."""
    from dlrover_tpu.ops.gated_delta import head_groups

    assert head_groups(1, 8192, 30, 96, 192) == 3
    assert head_groups(1, 8192, 10, 96, 192) == 1
    assert head_groups(2, 8192, 30, 96, 192) == 5
    assert head_groups(2, 256, 4, 16, 32) == 1
    assert head_groups(64, 8192, 2, 96, 192) == 2


def test_the_grouped_op_is_the_op(monkeypatch):
    """Heads in groups, one after another, each its own checkpoint:
    the outputs and gradients of the op on all heads at once."""
    from dlrover_tpu.ops import gated_delta

    args, weight = operands(19, 2, 128, 1.0, 0.1, heads=4)
    loss = lambda fn: (lambda *a: (fn(*a) * weight).sum())  # noqa: E731
    whole = lambda *a: gated_delta_rule(*a)[0]  # noqa: E731
    want = jax.value_and_grad(loss(whole), argnums=range(5))(*args)
    monkeypatch.setattr(gated_delta, "_GROUP_BYTES", 1 << 20)
    assert gated_delta.head_groups(2, 128, 4, DK, DV) == 2
    got = jax.jit(jax.value_and_grad(loss(
        gated_delta.gated_delta_rule_grouped), argnums=range(5)))(*args)
    assert abs(float(got[0] - want[0])) < 1e-5 * abs(float(want[0]))
    for a, b in zip(got[1], want[1]):
        assert rel(a, b) < 1e-6
