"""``ops/kda.py``: the chunked form of the delta rule under a diagonal
decay (Kimi delta attention), as a ``lax.scan`` over chunks and through
the ``kda_fwd`` / ``kda_bwd`` kernels in the Pallas interpreter,
against the recurrence token by token; the forward pass's one kernel
(``kda_rule_fwd``: a chunk prepared in VMEM and chained there) against
the recurrence and against those two steps; the backward's two
(``kda_rule_starts`` and ``kda_rule_bwd``: a chunk prepared again in
VMEM and its preparation differentiated there by hand) against autodiff
of the scan over chunks and of the recurrence. Toy sizes, float32, on
the CPU."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import kda as kda_op
from dlrover_tpu.ops import trace_once
from dlrover_tpu.ops.gated_delta import gated_delta_rule
from dlrover_tpu.ops.kda import (
    chain_tiles,
    kda,
    kda_auto,
    kda_backward,
    kda_forward,
    kda_grouped,
    kda_reference,
)

# Float32 on both sides, so the two differ by the order of float32 sums
# and by the sub-chunk's two factors: a pair inside a sub-chunk is the
# product of a factor down to e^-75 and one up to e^75 at the gate's
# bound, each rounded once. Measured: 2e-6 of the largest entry at 256
# tokens in the outputs and in every gradient, 8e-6 at the bound; 5e-5
# leaves room for a chunk of 128. A wrong mask, ratio, reference row or
# sign reads 1e-2 to 1.
TOL = 5e-5

HEADS, DK, DV = 2, 16, 32
BOUND = -5.0


def operands(seed, batch, seq, gate_at, heads=HEADS, beta_at=0.5):
    """q and k at length 1 (q over sqrt(dk)), as the model hands them
    over; ``beta`` in (0, 1); the log decay a token and key channel
    ``BOUND * sigmoid(.)`` around ``BOUND * sigmoid(gate_at)``."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(key):
        u = jax.random.normal(key, (batch, seq, heads, DK))
        return u / jnp.linalg.norm(u, axis=-1, keepdims=True)

    q, key = unit(k[0]) / math.sqrt(DK), unit(k[1])
    v = jax.random.normal(k[2], (batch, seq, heads, DV))
    g = BOUND * jax.nn.sigmoid(
        gate_at + 2.0 * jax.random.normal(k[3], (batch, seq, heads, DK)))
    beta = jax.nn.sigmoid(
        math.log(beta_at / (1 - beta_at))
        + jax.random.normal(k[4], (batch, seq, heads)))
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
    # (seq, chunk, the gate's pre-activation around)
    pytest.param(64, 64, -3.0, id="one-chunk-of-64"),
    pytest.param(256, 64, -3.0, id="four-chunks-of-64"),
    pytest.param(128, 128, -3.0, id="one-chunk-of-128"),
    pytest.param(256, 64, 0.0, id="the-gate-at-half-its-bound"),
    pytest.param(256, 64, 4.0, id="the-gate-near-its-bound"),
    pytest.param(256, 64, -8.0, id="hardly-any-decay"),
    pytest.param(200, 64, -3.0, id="a-row-padded-to-its-chunk"),
    pytest.param(32, 8, -3.0, id="a-chunk-under-a-sub-chunk"),
]


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scan-over-chunks", "kernels-interpreted"])
@pytest.mark.parametrize("seq,chunk,gate_at", CASES)
def test_forward_is_the_recurrence(seq, chunk, gate_at, kernels):
    args, _ = operands(seq + chunk, 2, seq, gate_at)
    want_o, want_final = kda_reference(*args)
    o, final = kda(*args, use_kernels=kernels, chunk=chunk,
                   heads_per_program=1 + kernels)
    assert o.shape == want_o.shape and final.shape == (2, HEADS, DK, DV)
    assert rel(o, want_o) < TOL and rel(final, want_final) < TOL


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scan-over-chunks", "kernels-interpreted"])
@pytest.mark.parametrize("seq,chunk,gate_at", CASES)
def test_all_five_gradients_are_the_recurrences(seq, chunk, gate_at, kernels):
    args, weight = operands(seq + chunk + 1, 2, seq, gate_at)
    want = jax.grad(scalar(kda_reference, weight), argnums=range(5))(*args)
    got = jax.jit(jax.grad(scalar(
        lambda *a: kda(*a, use_kernels=kernels, chunk=chunk), weight),
        argnums=range(5)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert rel(a, b) < TOL, (name, rel(a, b))


def test_the_kernels_give_what_the_scan_over_chunks_gives():
    """The two chains run the same three lines a chunk on the same
    prepared operands: outputs and gradients agree far inside the
    tolerance to the recurrence."""
    args, weight = operands(7, 2, 256, -2.0)

    def run(kernels):
        return jax.jit(jax.value_and_grad(scalar(
            lambda *a: kda(*a, use_kernels=kernels), weight),
            argnums=range(5)))(*args)

    (loss_a, grads_a), (loss_b, grads_b) = run(True), run(False)
    assert abs(float(loss_a - loss_b)) < 1e-5 * abs(float(loss_b))
    for a, b in zip(grads_a, grads_b):
        assert rel(a, b) < 2e-6


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scan-over-chunks", "kernels-interpreted"])
def test_a_gate_at_its_bound_on_a_whole_sub_chunk_stays_finite(kernels):
    """``g = -5`` on every token and channel of a sub-chunk (and of the
    whole row): a pair inside the sub-chunk is ``e^-75 x e^75``, which
    float32 holds; values and gradients are finite and the
    recurrence's (the gate's own gradient to 2e-4: at the bound a
    token's trace is e^-5 of the one before, the gradient is 1e-5 of the
    other rows' and 6e-5 of it is the order of the sums)."""
    (q, k, v, g, beta), weight = operands(3, 1, 128, 0.0)
    for at in (g.at[:, 16:32].set(BOUND), jnp.full_like(g, BOUND)):
        args = (q, k, v, at, beta)
        want_o, want_final = kda_reference(*args)
        o, final = kda(*args, use_kernels=kernels)
        assert bool(jnp.isfinite(o).all() and jnp.isfinite(final).all())
        assert rel(o, want_o) < TOL
        want = jax.grad(scalar(kda_reference, weight),
                        argnums=range(5))(*args)
        got = jax.grad(scalar(lambda *a: kda(*a, use_kernels=kernels),
                              weight), argnums=range(5))(*args)
        for name, a, b in zip("q k v g beta".split(), got, want):
            assert bool(jnp.isfinite(a).all()), name
            assert rel(a, b) < (2e-4 if name == "g" else TOL), (
                name, rel(a, b))


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scan-over-chunks", "kernels-interpreted"])
def test_a_decay_equal_over_a_heads_channels_is_the_scalar_rule(kernels):
    """With ``g`` the same on every key channel of a head the diagonal
    decay is the scalar one: ``ops.gated_delta``'s outputs, final state
    and gradients (``g``'s summed over the channels)."""
    (q, k, v, g, beta), weight = operands(23, 2, 256, -2.0)
    g1 = g[..., 0]
    wide = lambda t: jnp.broadcast_to(t[..., None], g.shape)  # noqa: E731
    want_o, want_final = gated_delta_rule(q, k, v, g1, beta,
                                          use_kernels=False)
    o, final = kda(q, k, v, wide(g1), beta, use_kernels=kernels)
    assert rel(o, want_o) < TOL and rel(final, want_final) < TOL
    want = jax.grad(scalar(lambda *a: gated_delta_rule(
        *a, use_kernels=False), weight), argnums=range(5))(q, k, v, g1, beta)
    got = jax.grad(scalar(lambda q, k, v, g1, beta: kda(
        q, k, v, wide(g1), beta, use_kernels=kernels), weight),
        argnums=range(5))(q, k, v, g1, beta)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert rel(a, b) < TOL, (name, rel(a, b))


def test_a_vector_decay_is_not_its_mean():
    """The control of the one above: the decay's mean over a head's
    channels in place of the vector reads far past the tolerance."""
    (q, k, v, g, beta), _ = operands(29, 1, 128, 0.0)
    o, _ = kda(q, k, v, g, beta, use_kernels=False)
    mean = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    other, _ = kda(q, k, v, mean, beta, use_kernels=False)
    assert rel(other, o) > 1e-2


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["scan-over-chunks", "kernels-interpreted"])
def test_a_row_split_in_two_with_the_state_handed_over(kernels):
    args, weight = operands(11, 1, 256, -2.0)
    run = lambda *a, **kw: kda(*a, use_kernels=kernels, **kw)  # noqa: E731

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
def test_beta_zero_leaves_the_state_a_decay_by_the_row(kernels):
    """Nothing is erased and nothing written: the state a row starts
    from comes out with each of its ``dk`` rows scaled by that
    channel's whole decay."""
    (q, k, v, g, beta), _ = operands(13, 1, 128, -7.0)
    start = jax.random.normal(jax.random.PRNGKey(5), (1, HEADS, DK, DV))
    o, final = kda(q, k, v, g, jnp.zeros_like(beta), initial_state=start,
                   use_kernels=kernels)
    kept = jnp.exp(jnp.cumsum(g, axis=1))  # [B, S, H, dk]
    assert rel(final, kept[:, -1][..., None] * start) < 1e-6
    want_o = jnp.einsum("bshk,bhkv->bshv", q * kept, start)
    assert rel(o, want_o) < TOL


def test_under_a_mesh_the_op_gives_the_single_device_result():
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs four devices")
    args, weight = operands(17, 2, 128, -2.0, heads=4)
    loss = lambda *a: (kda_auto(*a, use_kernels=True) * weight).sum()  # noqa: E731
    want_o = kda_auto(*args)  # no mesh: plain call
    want = jax.grad(loss, argnums=range(5))(*args)
    mesh = Mesh(np.asarray(devices[:4]).reshape(1, 2, 2),
                ("data", "fsdp", "tensor"))
    with jax.sharding.set_mesh(mesh):
        got_o = jax.jit(kda_auto)(*args)
        got = jax.jit(jax.grad(loss, argnums=range(5)))(*args)
    assert rel(got_o, want_o) < 1e-6
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5


def test_a_chunk_that_is_no_power_of_two_is_refused():
    args, _ = operands(1, 1, 96, -2.0)
    with pytest.raises(ValueError, match="power of two"):
        kda(*args, chunk=96)
    with pytest.raises(ValueError, match="divide"):
        kda(*args, heads_per_program=3)


@pytest.mark.parametrize("seq,heads,want", [
    (8192, 32, (64, 8)),  # the benchmark's cell
    (8192, 2, (64, 2)),  # one of its head groups
    (64, 4, (64, 4)), (96, 4, (32, 4)), (8, 2, (8, 2)),
])
def test_the_tiles_follow_the_shape(seq, heads, want):
    assert chain_tiles(seq, heads) == want


def test_the_op_the_layer_calls_is_the_op():
    """``kda_grouped``: on the kernels the forward kernel's output and
    the backward kernels' gradients, on the scan the two steps' own."""
    args, weight = operands(19, 2, 128, -2.0, heads=4)
    loss = lambda fn: (lambda *a: (fn(*a) * weight).sum())  # noqa: E731
    whole = lambda *a: kda(*a, use_kernels=False)[0]  # noqa: E731
    assert rel(kda_grouped(*args), kda_forward(*args)[0]) < 1e-6
    got = jax.jit(jax.grad(loss(kda_grouped), argnums=range(5)))(*args)
    for a, b in zip(got, kda_backward(*args, weight)):
        assert rel(a, b) < 1e-6
    scan = lambda *a: kda_grouped(*a, use_kernels=False)  # noqa: E731
    assert rel(scan(*args), whole(*args)) < 1e-6
    assert rel(kda_grouped(*args), whole(*args)) < TOL
    want = jax.grad(loss(whole), argnums=range(5))(*args)
    for a, b in zip(jax.grad(loss(scan), argnums=range(5))(*args), want):
        assert rel(a, b) < 1e-6


# -- the forward pass's one kernel --------------------------------------------


@pytest.mark.parametrize("heads_per_program", [1, 2])
@pytest.mark.parametrize("seq,chunk,gate_at", CASES)
def test_the_forward_kernel_is_the_recurrence(seq, chunk, gate_at,
                                              heads_per_program):
    args, _ = operands(seq + chunk, 2, seq, gate_at)
    want_o, want_final = kda_reference(*args)
    o, final = kda_forward(*args, chunk=chunk,
                           heads_per_program=heads_per_program)
    assert o.shape == want_o.shape and final.shape == (2, HEADS, DK, DV)
    assert o.dtype == want_o.dtype and final.dtype == jnp.float32
    assert rel(o, want_o) < TOL and rel(final, want_final) < TOL


@pytest.mark.parametrize("gate_at", [-3.0, 0.0, 4.0])
def test_the_forward_kernel_is_the_two_steps_forward(gate_at):
    """The kernel prepares a chunk by ``_prepare``'s formulas and chains
    it by ``_chain_step``: from a state handed over, outputs and final
    state are the two steps' to 2e-6 where the gate lies on a grid of
    1/64, whose sums float32 holds exactly in whatever order (measured
    1.6e-7). With the gate as drawn the two differ by that order alone
    (``cumsum`` there, a triangular product here: 1.2e-6 to 5.0e-6, an
    ulp of a sum of 16 to 300 under the exponential), which is what
    either is from the recurrence."""
    (q, k, v, g, beta), _ = operands(7, 2, 256, gate_at)
    start = jax.random.normal(jax.random.PRNGKey(5), (2, HEADS, DK, DV))
    for gate, tol in ((jnp.round(64 * g) / 64, 2e-6), (g, 1e-5)):
        want_o, want_final = kda(q, k, v, gate, beta, initial_state=start)
        o, final = kda_forward(q, k, v, gate, beta, initial_state=start)
        assert rel(o, want_o) < tol and rel(final, want_final) < tol


def test_the_forward_kernel_hands_the_state_over():
    args, _ = operands(11, 1, 256, -2.0)
    o1, state = kda_forward(*(t[:, :128] for t in args))
    o2, final = kda_forward(*(t[:, 128:] for t in args),
                            initial_state=state)
    want_o, want_final = kda_reference(*args)
    assert rel(jnp.concatenate([o1, o2], axis=1), want_o) < TOL
    assert rel(final, want_final) < TOL
    whole_o, whole_final = kda_forward(*args)
    assert rel(jnp.concatenate([o1, o2], axis=1), whole_o) < 1e-6
    assert rel(final, whole_final) < 1e-6


def test_the_forward_kernel_at_the_gates_bound_stays_finite():
    """``g = -5`` on a whole sub-chunk and on the whole row: the pairs
    inside a sub-chunk are ``e^-75 x e^75`` in the kernel's VMEM as in
    ``_prepare``: finite, and the recurrence's."""
    (q, k, v, g, beta), _ = operands(3, 1, 128, 0.0)
    for at in (g.at[:, 16:32].set(BOUND), jnp.full_like(g, BOUND)):
        want_o, want_final = kda_reference(q, k, v, at, beta)
        o, final = kda_forward(q, k, v, at, beta)
        assert bool(jnp.isfinite(o).all() and jnp.isfinite(final).all())
        assert rel(o, want_o) < TOL and rel(final, want_final) < TOL


# -- the backward's two kernels -------------------------------------------------


def of_output(fn, weight):
    """A loss that feels the output alone, as a layer's does."""
    return lambda *args: (fn(*args) * weight).sum()


@pytest.mark.parametrize("heads_per_program", [1, 2])
@pytest.mark.parametrize("seq,chunk,gate_at", CASES)
def test_the_backward_kernels_are_the_scans_autodiff(seq, chunk, gate_at,
                                                     heads_per_program):
    """``kda_backward`` from a gradient of the output against autodiff
    of the float32 chunked form, whose formulas and split the kernels
    differentiate by hand: the two differ by the order of float32 sums
    and by the rounding of the six-piece products (measured 2e-7 to
    7e-6, the gate's 2e-5 near its bound)."""
    args, weight = operands(seq + chunk + 1, 2, seq, gate_at)
    want = jax.grad(of_output(
        lambda *a: kda(*a, use_kernels=False, chunk=chunk)[0], weight),
        argnums=range(5))(*args)
    got = kda_backward(*args, weight, chunk=chunk,
                       heads_per_program=heads_per_program)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert rel(a, b) < TOL, (name, rel(a, b))


def _several_chunks():
    return operands(31, 2, 256, -3.0)


def _padded():
    return operands(37, 2, 200, 0.0)


def _bound_on_a_sub_chunk():
    (q, k, v, g, beta), weight = operands(3, 1, 128, 0.0)
    return (q, k, v, g.at[:, 16:32].set(BOUND), beta), weight


def _bound_on_the_row():
    (q, k, v, g, beta), weight = operands(3, 1, 128, 0.0)
    return (q, k, v, jnp.full_like(g, BOUND), beta), weight


def _beta_zero():  # a chunk that writes nothing and hands the state on
    (q, k, v, g, beta), weight = operands(13, 1, 256, -3.0)
    return (q, k, v, g, beta.at[:, 64:128].set(0.0)), weight


def _equal_decay():
    (q, k, v, g, beta), weight = operands(23, 2, 256, -2.0)
    return (q, k, v, jnp.broadcast_to(g[..., :1], g.shape), beta), weight


def _two_head_blocks():  # chain_tiles: eight heads a program
    return operands(41, 1, 128, -2.0, heads=16)


GRADIENT_CASES = [
    pytest.param(_several_chunks, id="a-row-of-several-chunks"),
    pytest.param(_padded, id="a-row-padded-to-its-chunk"),
    pytest.param(_bound_on_a_sub_chunk,
                 id="the-gate-at-its-bound-on-a-sub-chunk"),
    pytest.param(_bound_on_the_row, id="the-gate-at-its-bound-on-the-row"),
    pytest.param(_beta_zero, id="beta-zero-on-a-chunk"),
    pytest.param(_equal_decay, id="a-decay-equal-over-a-heads-channels"),
    pytest.param(_two_head_blocks, id="two-head-blocks-a-layer"),
]


@pytest.mark.parametrize("oracle", ["scan-over-chunks", "recurrence"])
@pytest.mark.parametrize("case", GRADIENT_CASES)
def test_the_five_gradients_of_the_op_the_layer_calls(case, oracle):
    """``kda_grouped`` on the kernels (forward ``kda_rule_fwd``,
    backward ``kda_rule_starts`` and ``kda_rule_bwd``) under
    ``jax.grad``: finite, and autodiff's of the float32 chunked form
    and of the recurrence token by token, to the tolerance of the
    forward's tests. The gate's own where the WHOLE row sits at its
    bound: to 5e-3 of its largest entry, which is there 5e-3 where the
    other cases' is 0.5 to 0.9 and the queries' 9: a token's trace is
    e^-5 of the one before, and what is left of the gate's gradient is
    the difference of the pairs' row and column sums, a hundred times
    its size and each rounded at 1e-7 (measured 1.8e-3 of it, 1e-5 of
    the keys' beside it; autodiff adds and subtracts the SAME rounded
    value and reads 6e-5). ``beta``'s own on a chunk where ``beta`` is
    0 is no zero: it is what a first write would gain. With sixteen
    heads a layer is two head blocks, each with a block of its own for
    ``beta``'s."""
    args, weight = case()
    plain = {"scan-over-chunks": lambda *a: kda(*a, use_kernels=False)[0],
             "recurrence": lambda *a: kda_reference(*a)[0]}[oracle]
    want = jax.grad(of_output(plain, weight), argnums=range(5))(*args)
    got = jax.jit(jax.grad(of_output(kda_grouped, weight),
                           argnums=range(5)))(*args)
    gate = 5e-3 if bool((args[3] == BOUND).all()) else TOL
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(b).max()) > 0, name
        assert rel(a, b) < (gate if name == "g" else TOL), (name, rel(a, b))


def test_the_backward_kernels_under_an_equal_decay_are_the_scalar_rules():
    """With ``g`` the same on every key channel of a head the five
    gradients are ``ops.gated_delta``'s (``g``'s summed over the
    channels)."""
    (q, k, v, g, beta), weight = operands(23, 2, 256, -2.0)
    g1 = g[..., 0]
    wide = lambda t: jnp.broadcast_to(t[..., None], g.shape)  # noqa: E731
    want = jax.grad(of_output(lambda *a: gated_delta_rule(
        *a, use_kernels=False)[0], weight), argnums=range(5))(
            q, k, v, g1, beta)
    got = jax.grad(of_output(lambda q, k, v, g1, beta: kda_grouped(
        q, k, v, wide(g1), beta), weight), argnums=range(5))(
            q, k, v, g1, beta)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert rel(a, b) < TOL, (name, rel(a, b))


@pytest.mark.parametrize("gate_at", [-3.0, 0.0, 4.0])
def test_the_gradients_on_the_kernels_are_the_two_steps(gate_at):
    """``kda_grouped``'s backward on the kernels against the derivative
    of the two steps it took the place of (the preparation in XLA by
    autodiff, ``kda_fwd`` and ``kda_bwd`` interpreted): the same
    formulas at the same precisions in another order, to 2e-5 of the
    largest entry (measured 1e-6 to 7e-6; they were equal to the bit
    while the backward WAS those two steps, PR 63)."""
    args, weight = operands(19, 2, 128, gate_at, heads=4)
    want = jax.jit(jax.grad(of_output(lambda *a: kda(*a)[0], weight),
                            argnums=range(5)))(*args)
    got = jax.jit(jax.grad(of_output(kda_grouped, weight),
                           argnums=range(5)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert rel(a, b) < 2e-5, (name, rel(a, b))


@pytest.mark.parametrize("gate_at", [-3.0, 0.0, 4.0])
def test_the_backward_kernels_in_bf16_round_where_the_two_steps_do(gate_at):
    """q, k and v in bf16 as a layer hands them over (the gate and
    ``beta`` float32): the kernels' gradients come in their operands'
    dtypes and are as far from autodiff of the float32 chunked form on
    the same rounded operands as the two steps' are, bf16's rounding of
    the prepared operands (measured 2.6e-3 to 6.4e-3 of the largest
    entry where the two steps read 3.1e-3 to 7.0e-3)."""
    (q, k, v, g, beta), weight = operands(5, 2, 256, gate_at)
    low = [t.astype(jnp.bfloat16) for t in (q, k, v)]
    want = jax.grad(of_output(
        lambda *a: kda(*a, use_kernels=False)[0], weight),
        argnums=range(5))(*(t.astype(jnp.float32) for t in low), g, beta)
    got = kda_backward(*low, g, beta, weight.astype(jnp.bfloat16))
    assert [t.dtype for t in got] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert rel(a.astype(jnp.float32), b) < 1.5e-2, (name, rel(a, b))


def test_a_wrong_term_of_the_backward_is_seen(monkeypatch):
    """The control of the tolerances above: the pairs' columns left out
    of the keys' gradient (one of its four terms) reads far past
    them."""
    args, weight = operands(31, 1, 128, -3.0)
    want = kda_backward(*args, weight)
    real = kda_op._chunk_pairs_bwd

    def without_columns(*a):
        rows, columns = real(*a)
        return rows, jnp.zeros_like(columns)

    monkeypatch.setattr(kda_op, "_chunk_pairs_bwd", without_columns)
    # a kernel is traced once a process: this one into a cache of its own
    monkeypatch.setattr(trace_once, "_SHARED", {})
    got = kda_backward(*args, weight)
    assert rel(got[1], want[1]) > 1e-2
    for n in (0, 2):  # the queries' and the values' do not read them
        assert rel(got[n], want[n]) < 1e-6


def test_the_forward_pass_on_the_kernels_is_the_one_kernel():
    """What the layer runs, by the kernels' call sites in the jaxpr:
    forward the ``kda_rule_fwd`` kernel alone (nothing is prepared in
    XLA for it); the derivative adds the states pass and the backward
    pass, one each, and neither ``kda_fwd`` nor ``kda_bwd``; on the
    scan no kernel."""
    args, weight = operands(1, 1, 64, -2.0)

    def sites(fn):  # a site is named twice: its jit and its pallas_call
        names = re.findall(r"name=\s*(kda_\w+)",
                           str(jax.make_jaxpr(fn)(*args)))
        return {n: names.count(n) // 2 for n in names if n != "kda_out"}

    assert sites(kda_grouped) == {"kda_rule_fwd": 1}
    grad = jax.grad(lambda *a: (kda_grouped(*a) * weight).sum(),
                    argnums=range(5))
    assert sites(grad) == {"kda_rule_fwd": 1, "kda_rule_starts": 1,
                           "kda_rule_bwd": 1}
    assert sites(lambda *a: kda_grouped(*a, use_kernels=False)) == {}
