"""``ops/ssd.py``: the state-space dual of a Mamba-2 layer. The kernels
(in the Pallas interpreter) and the chunked form in ``jax.numpy``
against the recurrence token by token, values and every gradient, with
a head whose decay underflows inside a chunk and one that barely
decays; the refusals; the op under a mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import ssd, trace_once

HEADS, P, N, CHUNK = 4, 16, 32, 64
# a gradient against the recurrence's, over the largest entry; ``dA``
# is the sum over a row of the differences of two running sums of the
# chunk's pairs, and in float32 carries their rounding (against a
# float64 recurrence the token-by-token float32 one reads 1e-6 there
# and both chunked forms 1e-5 to 2e-4 by the seed)
GRAD_TOL = {"A": 1e-3}


def operands(seed, seq, groups=1, batch=1, heads=HEADS):
    """Head 0 decays by ``exp(-2)`` a token (``exp(-128)`` over a chunk
    of 64 is 0 in float32, and ``exp(128)`` no float32 at all), head 1
    by ``exp(-1e-5)``; the others as an initialisation gives them."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (batch, seq, heads, P))
    dt = jnp.exp(jax.random.uniform(k[1], (batch, seq, heads))
                 * np.log(100.0) + np.log(1e-3))
    a = -jax.random.uniform(k[2], (heads,), minval=1.0, maxval=16.0)
    dt = dt.at[..., 0].set(1.0).at[..., 1].set(1e-3)
    a = a.at[0].set(-2.0).at[1].set(-1e-2)
    b = jax.random.normal(k[3], (batch, seq, groups, N))
    c = jax.random.normal(k[4], (batch, seq, groups, N))
    d = jax.random.normal(k[5], (heads,))
    weight = jax.random.normal(k[6], (batch, seq, heads, P))
    return (x, dt, a, b, c, d), weight


def rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


FORMS = {
    "kernels": lambda *a: ssd.ssd(*a, chunk=CHUNK, interpret=True),
    "chunked": lambda *a: ssd.ssd_chunked(*a, chunk=CHUNK),
}


@pytest.mark.parametrize("chunks", [2, 4])
@pytest.mark.parametrize("form", FORMS)
def test_forward_is_the_recurrence(form, chunks):
    args, _ = operands(chunks, chunks * CHUNK)
    got, want = FORMS[form](*args), ssd.ssd_reference(*args)
    assert got.shape == want.shape and bool(jnp.isfinite(got).all())
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("chunks", [2, 4])
@pytest.mark.parametrize("form", FORMS)
def test_all_six_gradients_are_the_recurrences(form, chunks):
    args, weight = operands(10 + chunks, chunks * CHUNK)

    def loss(fn):
        return lambda *a: (fn(*a) * weight).sum()

    want = jax.grad(loss(ssd.ssd_reference), argnums=range(6))(*args)
    got = jax.grad(loss(FORMS[form]), argnums=range(6))(*args)
    for name, g, w in zip("x dt A B C D".split(), got, want):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        assert rel(g, w) < GRAD_TOL.get(name, 1e-4), (name, rel(g, w))


def test_the_fast_head_forgets_and_the_slow_head_remembers():
    """What the two made-up heads are for: after a chunk the first
    one's state holds nothing of the chunk's first token, the second
    one's nearly all of it."""
    (x, dt, a, b, c, d), _ = operands(3, 2 * CHUNK)
    moved = x.at[0, 0].add(1.0)
    delta = (ssd.ssd(moved, dt, a, b, c, d, chunk=CHUNK, interpret=True)
             - ssd.ssd(x, dt, a, b, c, d, chunk=CHUNK, interpret=True))
    late = jnp.abs(delta[0, CHUNK:]).max(axis=(0, 2))  # a head
    assert float(late[0]) == 0.0 and float(late[1]) > 1e-3


def test_two_groups_through_the_chunked_form():
    args, weight = operands(5, 2 * CHUNK, groups=2)
    assert rel(ssd.ssd_chunked(*args, chunk=CHUNK),
               ssd.ssd_reference(*args)) < 1e-5
    want = jax.grad(lambda *a: (ssd.ssd_reference(*a) * weight).sum(),
                    argnums=(3, 4))(*args)
    got = jax.grad(lambda *a: (ssd.ssd_chunked(*a, chunk=CHUNK)
                               * weight).sum(), argnums=(3, 4))(*args)
    for g, w in zip(got, want):
        assert rel(g, w) < 1e-4


def test_bf16_operands_keep_a_float32_recurrence():
    """In bf16 the kernels round what enters a product and nothing
    that is carried: against the recurrence on the same rounded
    operands the output is within a few bf16 steps."""
    args, _ = operands(6, 4 * CHUNK)
    x, dt, a, b, c, d = args
    low = (x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
           c.astype(jnp.bfloat16), d)
    got = ssd.ssd(*low, chunk=CHUNK, interpret=True)
    assert got.dtype == jnp.bfloat16
    assert rel(got.astype(jnp.float32), ssd.ssd_reference(*low)) < 2e-2


def test_heads_side_by_side_and_head_blocks(monkeypatch):
    """Sixteen heads in blocks of eight: two grid steps along the head
    axis, each of one lane tile of eight heads of 16."""
    args, weight = operands(7, 2 * CHUNK, heads=16)
    assert ssd._fit_heads(8, 16) == 8 and ssd._fit_heads(8, 4) == 4
    assert ssd._fit_heads(16, 64) == 16 and ssd._fit_heads(12, 64) == 8
    assert ssd._tile_heads(64, 8) == 2 and ssd._tile_heads(16, 8) == 8
    assert ssd._tile_heads(128, 8) == 1 and ssd._tile_heads(16, 4) == 4
    monkeypatch.setattr(ssd, "HEADS_PER_PROGRAM", 8)
    run = lambda *a: ssd.ssd(*a, chunk=CHUNK, interpret=True)  # noqa: E731
    assert rel(run(*args), ssd.ssd_reference(*args)) < 1e-5
    want = jax.grad(lambda *a: (ssd.ssd_reference(*a) * weight).sum(),
                    argnums=range(6))(*args)
    got = jax.grad(lambda *a: (run(*a) * weight).sum(),
                   argnums=range(6))(*args)
    for name, g, w in zip("x dt A B C D".split(), got, want):
        assert rel(g, w) < GRAD_TOL.get(name, 1e-4), name


def test_what_the_op_refuses():
    (x, dt, a, b, c, d), _ = operands(8, 2 * CHUNK, groups=2)
    with pytest.raises(ValueError, match="one group"):
        ssd.ssd(x, dt, a, b, c, d, chunk=CHUNK, interpret=True)
    with pytest.raises(ValueError, match="one group"):
        ssd.ssd_auto(x, dt, a, b, c, d, chunk=CHUNK, interpret=True)
    one = (b[:, :, :1], c[:, :, :1])
    for fn in (ssd.ssd, ssd.ssd_chunked):
        with pytest.raises(ValueError, match="no whole number of chunks"):
            fn(x, dt, a, *one, d, chunk=48)
    with pytest.raises(ValueError, match="do not divide"):
        ssd.ssd_reference(x, dt, a, jnp.tile(b, (1, 1, 2, 1))[:, :, :3],
                          jnp.tile(c, (1, 1, 2, 1))[:, :, :3], d)
    with pytest.raises(ValueError, match=r"\[B, S, H, P\]"):
        ssd.ssd_chunked(x, dt[..., :2], a, *one, d, chunk=CHUNK)
    with pytest.raises(ValueError, match="multiple of 8"):
        ssd.ssd(x[:, :36], dt[:, :36], a, one[0][:, :36], one[1][:, :36], d,
                chunk=12, interpret=True)


def test_one_jit_a_kernel_and_shape():
    """Two calls of one shape share a ``jax.jit`` (``shared_call``): the
    kernel's body is traced once a process."""
    args, _ = operands(9, 2 * CHUNK)
    ssd.ssd(*args, chunk=CHUNK, interpret=True)
    held = len(trace_once._SHARED)
    ssd.ssd(*args, chunk=CHUNK, interpret=True)
    jax.grad(lambda x: ssd.ssd(x, *args[1:], chunk=CHUNK,
                               interpret=True).sum())(args[0])
    assert held <= len(trace_once._SHARED) <= held + 1  # the backward's, once
    assert {key[0] for key in trace_once._SHARED
            if key[0].startswith("ssd")} == {"ssd_fwd", "ssd_bwd"}


def test_under_a_mesh_the_op_gives_the_single_device_result():
    """Batch over ``fsdp`` and heads over ``tensor`` on a 2 x 2 mesh of
    the CPU's virtual devices: B and C are whole on every shard and
    their gradients summed over it, so outputs and gradients are the
    single device's."""
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs four devices")
    args, weight = operands(17, 2 * CHUNK, batch=2, heads=16)
    run = lambda *a: ssd.ssd_auto(*a, chunk=CHUNK)  # noqa: E731
    loss = lambda *a: (run(*a) * weight).sum()  # noqa: E731
    want_y = run(*args)  # no mesh: the plain call
    want = jax.grad(loss, argnums=range(6))(*args)
    mesh = Mesh(np.asarray(devices[:4]).reshape(1, 2, 2),
                ("data", "fsdp", "tensor"))
    with jax.sharding.set_mesh(mesh):
        got_y = jax.jit(run)(*args)
        got = jax.jit(jax.grad(loss, argnums=range(6)))(*args)
    assert rel(got_y, want_y) < 1e-6
    for name, g, w in zip("x dt A B C D".split(), got, want):
        assert rel(g, w) < GRAD_TOL.get(name, 1e-5), name
