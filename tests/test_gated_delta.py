"""``ops/gated_delta.py``: the chunked form of the gated delta rule, as
a ``lax.scan`` over chunks, through the ``gdn_fwd`` / ``gdn_bwd``
kernels of the two steps and through the whole rule's three kernels
(``gdn_rule_fwd``, ``gdn_rule_starts``, ``gdn_rule_bwd``: what a layer
calls) in the Pallas interpreter, against the recurrence token by
token. Toy sizes, float32, on the CPU."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gated_delta as gdn_op
from dlrover_tpu.ops import trace_once
from dlrover_tpu.ops.gated_delta import (
    KEPT_NAMES,
    _unit_lower_inverse,
    chain_tiles,
    gated_delta_rule,
    gated_delta_rule_auto,
    gated_delta_rule_grouped,
    gated_delta_rule_reference,
    gdn_backward,
    gdn_forward,
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
    want_o = gated_delta_rule_auto(*args)  # no mesh: the plain call
    want = jax.grad(loss, argnums=range(5))(*args)
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
    (8192, 10, (64, 10)),  # its heads over three chips
    (8192, 15, (64, 5)),  # its heads over two chips
    (64, 4, (64, 4)), (96, 4, (32, 4)), (8, 2, (8, 2)),
])
def test_the_tiles_follow_the_shape(seq, heads, want):
    assert chain_tiles(seq, heads) == want


# -- the whole rule as three kernels, what a layer calls ----------------------


def of_output(fn, weight):
    """A loss that feels the output alone, as a layer's does."""
    return lambda *args: (fn(*args) * weight).sum()


def scan_over_chunks(*args, **kw):
    return gated_delta_rule(*args, use_kernels=False, **kw)[0]


@pytest.mark.parametrize("heads_per_program", [1, 2])
@pytest.mark.parametrize("seq,chunk,beta_at,decay", CASES)
def test_the_forward_kernel_is_the_recurrence(seq, chunk, beta_at, decay,
                                              heads_per_program):
    """``gdn_rule_fwd`` alone, two head blocks of one head and one of
    two (keys of 16, values of 32: no lane tile either): outputs and
    final state."""
    args, _ = operands(seq + chunk, 2, seq, beta_at, decay)
    want_o, want_final = gated_delta_rule_reference(*args)
    o, final = gdn_forward(*args, chunk=chunk,
                           heads_per_program=heads_per_program)
    assert o.shape == want_o.shape and final.shape == (2, HEADS, DK, DV)
    assert o.dtype == want_o.dtype and final.dtype == jnp.float32
    assert rel(o, want_o) < TOL and rel(final, want_final) < TOL


@pytest.mark.parametrize("seq,chunk,beta_at,decay", CASES)
def test_the_backward_kernels_are_the_scans_autodiff(seq, chunk, beta_at,
                                                     decay):
    """``gdn_backward`` (``gdn_rule_starts`` and ``gdn_rule_bwd``) from
    a gradient of the output against autodiff of the float32 chunked
    form, whose formulas the kernels differentiate by hand: the two
    differ by the order of float32 sums and by the rounding of the
    inverse's six-piece products (measured 3e-7 to 6e-6). The heads a
    program alternate over the cases: one (two head blocks, each with
    its own block of ``g``'s and ``beta``'s gradients) and two."""
    args, weight = operands(seq + chunk + 1, 2, seq, beta_at, decay)
    want = jax.grad(of_output(
        lambda *a: scan_over_chunks(*a, chunk=chunk), weight),
        argnums=range(5))(*args)
    got = gdn_backward(*args, weight, chunk=chunk,
                       heads_per_program=1 + (seq + chunk) // 64 % 2)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert rel(a, b) < TOL, (name, rel(a, b))


def _beta_at(value):
    (q, k, v, g, beta), weight = operands(13, 1, 256, 1.0, 0.1)
    return (q, k, v, g, jnp.full_like(beta, value)), weight


def _beta_zero_on_a_chunk():  # it writes nothing and hands the state on
    (q, k, v, g, beta), weight = operands(13, 1, 256, 1.0, 0.1)
    return (q, k, v, g, beta.at[:, 64:128].set(0.0)), weight


GRADIENT_CASES = [
    pytest.param(lambda: operands(31, 2, 256, 1.0, 0.1),
                 id="a-row-of-several-chunks"),
    pytest.param(lambda: operands(37, 2, 200, 1.0, 0.1),
                 id="a-row-padded-to-its-chunk"),
    pytest.param(_beta_zero_on_a_chunk, id="beta-zero-on-a-chunk"),
    pytest.param(lambda: _beta_at(1.0), id="beta-one"),
    pytest.param(lambda: _beta_at(1.99), id="beta-near-two"),
    pytest.param(lambda: operands(43, 1, 256, 1.0, 2.0),
                 id="strong-decay"),
    pytest.param(lambda: operands(47, 1, 256, 1.0, 1e-3), id="weak-decay"),
    # chain_tiles: six heads a program
    pytest.param(lambda: operands(41, 1, 64, 1.0, 0.1, heads=12),
                 id="two-head-blocks-a-layer"),
]


@pytest.mark.parametrize("oracle", ["scan-over-chunks", "recurrence"])
@pytest.mark.parametrize("case", GRADIENT_CASES)
def test_the_five_gradients_of_the_op_the_layer_calls(case, oracle):
    """``gated_delta_rule_grouped`` on the kernels (forward
    ``gdn_rule_fwd``, backward ``gdn_rule_starts`` and
    ``gdn_rule_bwd``) under ``jax.grad``: finite, and autodiff's of the
    float32 chunked form and of the recurrence token by token, to the
    tolerance of the forward's tests. ``beta``'s own on a chunk where
    ``beta`` is 0 is no zero: it is what a first write would gain. With
    twelve heads a layer is two head blocks."""
    args, weight = case()
    plain = {"scan-over-chunks": scan_over_chunks,
             "recurrence": lambda *a: gated_delta_rule_reference(*a)[0]}[
        oracle]
    want = jax.grad(of_output(plain, weight), argnums=range(5))(*args)
    got = jax.jit(jax.grad(of_output(gated_delta_rule_grouped, weight),
                           argnums=range(5)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(b).max()) > 0, name
        assert rel(a, b) < TOL, (name, rel(a, b))


def test_the_op_the_layer_calls_is_the_op():
    """``gated_delta_rule_grouped``: on the kernels the forward
    kernel's output and the backward kernels' gradients, on the scan
    the two steps' own."""
    args, weight = operands(19, 2, 128, 1.0, 0.1, heads=4)
    assert rel(gated_delta_rule_grouped(*args), gdn_forward(*args)[0]) < 1e-6
    got = jax.jit(jax.grad(of_output(gated_delta_rule_grouped, weight),
                           argnums=range(5)))(*args)
    for a, b in zip(got, gdn_backward(*args, weight)):
        assert rel(a, b) < 1e-6
    scan = lambda *a: gated_delta_rule_grouped(  # noqa: E731
        *a, use_kernels=False)
    assert rel(scan(*args), scan_over_chunks(*args)) < 1e-6
    assert rel(gated_delta_rule_grouped(*args), scan_over_chunks(*args)) < TOL
    want = jax.grad(of_output(scan_over_chunks, weight),
                    argnums=range(5))(*args)
    for a, b in zip(jax.grad(of_output(scan, weight),
                             argnums=range(5))(*args), want):
        assert rel(a, b) < 1e-6


def test_the_forward_kernel_hands_the_state_over():
    """From a state handed in, outputs and final state are the two
    steps' (the same formulas in another order of float32 sums), and a
    row in two halves is the row."""
    (q, k, v, g, beta), _ = operands(7, 2, 256, 1.0, 0.1)
    start = jax.random.normal(jax.random.PRNGKey(5), (2, HEADS, DK, DV))
    want_o, want_final = gated_delta_rule(q, k, v, g, beta,
                                          initial_state=start)
    o, final = gdn_forward(q, k, v, g, beta, initial_state=start)
    assert rel(o, want_o) < 1e-5 and rel(final, want_final) < 1e-5
    args = (q, k, v, g, beta)
    o1, state = gdn_forward(*(t[:, :128] for t in args))
    o2, final = gdn_forward(*(t[:, 128:] for t in args),
                            initial_state=state)
    whole_o, whole_final = gdn_forward(*args)
    assert rel(jnp.concatenate([o1, o2], axis=1), whole_o) < 1e-6
    assert rel(final, whole_final) < 1e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_a_chunk_prepared_in_the_kernel_rounds_where_the_two_steps_do(dtype):
    """``_rule_chunk``, what a grid step computes in VMEM, on one chunk
    of four heads against ``_prepare``: the six operands of the chain
    in the inputs' dtype (the decay float32), from float32 sums, ratios,
    ``beta`` and inverse. In float32 the two differ by the order of the
    sums of ``g`` and by the inverse's six-piece products (measured
    under 2e-6 of the largest entry); in bf16 they round THE SAME
    float32 values but for that, so all but a few entries in a thousand
    are equal to the bit and none differs by more than an ulp of bf16
    (2^-8 of the entry's own size)."""
    (q, k, v, g, beta), _ = operands(3, 1, 64, 1.0, 0.1, heads=4)
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    by_head = lambda t: jnp.moveaxis(t, 2, 1)  # noqa: E731  [B, H, C, .]
    want = gdn_op._prepare(*(by_head(t)[:, :, None] for t in (q, k, v)),
                           *(by_head(t)[:, :, None] for t in (g, beta)))
    ch = gdn_op._rule_chunk(*(by_head(t)[0] for t in (q, k, v)),
                            *(by_head(t)[0, :, :, None] for t in (g, beta)))
    got = (ch.qg, ch.kd, ch.w, ch.ubar, ch.p, ch.decay[:, 0, 0])
    for name, a, b in zip("Qg Kd W Ubar P decay".split(), got, want):
        b = b[0, :, 0]
        assert a.dtype == b.dtype == (
            jnp.float32 if name == "decay" else dtype), name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if dtype == jnp.float32 or name == "decay":
            assert rel(a, b) < 2e-6, (name, rel(a, b))
            continue
        assert float(jnp.mean(a == b)) > 0.99, (name, jnp.mean(a == b))
        assert bool((jnp.abs(a - b) <= 2.0 ** -7 * jnp.abs(b)).all()), name


def test_the_backward_kernels_in_bf16_round_where_the_two_steps_do():
    """q, k and v in bf16 as a layer hands them over (``g`` and
    ``beta`` float32): the kernels' gradients come in their operands'
    dtypes and are as far from autodiff of the float32 chunked form on
    the same rounded operands as the two steps' are, bf16's rounding of
    the prepared operands (measured 3e-3 to 6e-3 of the largest entry
    for either)."""
    (q, k, v, g, beta), weight = operands(5, 2, 256, 1.0, 0.1)
    low = [t.astype(jnp.bfloat16) for t in (q, k, v)]
    want = jax.grad(of_output(scan_over_chunks, weight), argnums=range(5))(
        *(t.astype(jnp.float32) for t in low), g, beta)
    two_steps = jax.grad(of_output(
        lambda *a: gated_delta_rule(*a)[0].astype(jnp.float32), weight),
        argnums=range(5))(*low, g, beta)
    got = gdn_backward(*low, g, beta, weight.astype(jnp.bfloat16))
    assert [t.dtype for t in got] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    for name, a, b, c in zip("q k v g beta".split(), got, want, two_steps):
        ours, theirs = (rel(t.astype(jnp.float32), b) for t in (a, c))
        assert ours < 1.5e-2 and ours < 2 * theirs, (name, ours, theirs)


def test_a_wrong_term_of_the_backward_is_seen(monkeypatch):
    """The control of the tolerances above: what a chunk's last sum of
    ``g`` feels of the chunk's decay and of ``Kd`` (two of the terms of
    ``g``'s gradient) left out reads far past them, and the four other
    gradients, which do not read them, are what they were."""
    args, weight = operands(31, 1, 128, 1.0, 0.1)
    want = gdn_backward(*args, weight)
    monkeypatch.setattr(gdn_op, "_sum_of_tiles",
                        lambda x: jnp.zeros((x.shape[0], 1, 1), x.dtype))
    # a kernel is traced once a process: this one into a cache of its own
    monkeypatch.setattr(trace_once, "_SHARED", {})
    got = gdn_backward(*args, weight)
    assert rel(got[3], want[3]) > 1e-2
    for n in (0, 1, 2, 4):
        assert rel(got[n], want[n]) < 1e-6


def test_the_forward_pass_on_the_kernels_is_the_one_kernel():
    """What the layer runs, by the kernels' call sites in the jaxpr:
    forward the ``gdn_rule_fwd`` kernel alone (nothing is prepared in
    XLA for it); the derivative adds the states pass and the backward
    pass, one each, and neither ``gdn_fwd`` nor ``gdn_bwd``; on the
    scan no kernel. The output carries ``KEPT_NAMES``."""
    args, weight = operands(1, 1, 64, 1.0, 0.1)

    def sites(fn):  # a site is named twice: its jit and its pallas_call
        names = re.findall(r"name=\s*(gdn_\w+)",
                           str(jax.make_jaxpr(fn)(*args)))
        return {n: names.count(n) // 2 for n in names if n not in KEPT_NAMES}

    assert sites(gated_delta_rule_grouped) == {"gdn_rule_fwd": 1}
    assert f"name={KEPT_NAMES[0]}" in str(
        jax.make_jaxpr(gated_delta_rule_grouped)(*args))
    grad = jax.grad(of_output(gated_delta_rule_grouped, weight),
                    argnums=range(5))
    assert sites(grad) == {"gdn_rule_fwd": 1, "gdn_rule_starts": 1,
                           "gdn_rule_bwd": 1}
    assert sites(lambda *a: gated_delta_rule_grouped(
        *a, use_kernels=False)) == {}


def test_a_checkpoint_that_keeps_the_output_runs_the_forward_once():
    """A layer's checkpoint under ``keep=KEPT_NAMES``: the op's
    residuals are its inputs, so with the output kept the replay reads
    nothing of the forward kernel and the gradient program has it once;
    with nothing kept, twice."""
    from dlrover_tpu.ops.remat import apply_remat

    args, weight = operands(1, 1, 64, 1.0, 0.1)

    def forwards(keep):
        layer = apply_remat(
            lambda *a: jnp.tanh(gated_delta_rule_grouped(*a)), "full",
            keep=keep)
        text = jax.jit(jax.value_and_grad(
            of_output(layer, weight), argnums=range(5))).lower(
            *args).as_text()
        return len(re.findall(r"call @gdn_rule_fwd", text))

    assert (forwards(()), forwards(KEPT_NAMES)) == (2, 1)
