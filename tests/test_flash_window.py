"""The sliding-window flash kernels and a value width of its own
(``ops/flash_attention.py``), in the interpreter, against
``ops/attention_ref.py`` under the band mask."""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.flash_attention import (
    _band_blocks,
    flash_attention,
    flash_attention_auto,
    flash_attention_window,
)

SEQ, BLOCK = 128, 32


def qkv(heads=4, kv_heads=2, dim=16, value_dim=32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (2, heads, SEQ, dim)),
            jax.random.normal(k[1], (2, kv_heads, SEQ, dim)),
            jax.random.normal(k[2], (2, kv_heads, SEQ, value_dim)),
            jax.random.normal(k[3], (2, heads, SEQ, value_dim)))


def band_reference(q, k, v, window):
    t = jnp.arange(SEQ)
    visible = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < window)
    bias = jnp.where(visible, 0.0, jnp.finfo(jnp.float32).min)
    return mha_reference(q, k, v, causal=False, bias=bias)


# one key; less than a block; the block size; a block and a bit; two
# blocks; the whole row (the band's first block is then before the row
# for every q block but the last)
WINDOWS = [1, 7, BLOCK, BLOCK + 1, 2 * BLOCK, SEQ]


@pytest.fixture(scope="module", params=WINDOWS,
                ids=[f"window-{w}" for w in WINDOWS])
def windowed(request):
    window = request.param
    q, k, v, weight = qkv()

    def loss(f):
        return lambda q, k, v: (f(q, k, v) * weight).sum()

    kernel = lambda q, k, v: flash_attention_window(  # noqa: E731
        q, k, v, window, None, BLOCK, True)
    reference = lambda q, k, v: band_reference(q, k, v, window)  # noqa: E731
    return ((kernel(q, k, v), reference(q, k, v)),
            jax.grad(loss(kernel), (0, 1, 2))(q, k, v),
            jax.grad(loss(reference), (0, 1, 2))(q, k, v))


def test_windowed_forward_matches_the_band_mask(windowed):
    (got, want), _, _ = windowed
    assert got.shape == want.shape  # [B, H, S, value_dim]
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("arg", range(3), ids=["dq", "dk", "dv"])
def test_windowed_backward_matches_the_band_mask(windowed, arg):
    """dq comes from the dQ kernel, dk and dv from the dKV kernel."""
    _, got, want = windowed
    assert float(jnp.abs(got[arg] - want[arg]).max()) < 5e-5


@pytest.mark.parametrize("window,block,seq,want", [
    (512, 512, 8192, 2), (512, 256, 8192, 3), (1, 512, 8192, 1),
    (513, 512, 8192, 2), (514, 512, 8192, 3), (8192, 512, 8192, 16),
    (512, 8192, 8192, 1)])
def test_the_grid_covers_the_bands_blocks_only(window, block, seq, want):
    assert _band_blocks(window, block, seq) == want


def test_the_windowed_grid_is_in_the_lowered_call():
    """2 of 8 k blocks a q block at a window of one block: the
    pallas_call's grid, read from the lowered program."""
    q, k, v, _ = qkv()
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention_window(
        q, k, v, 16, None, 16, True))(q, k, v)
    grids = [eqn.params["grid_mapping"].grid
             for eqn in jaxpr.jaxpr.eqns[0].params["call_jaxpr"].eqns
             if eqn.primitive.name == "pallas_call"]
    assert grids == [(2, 4, SEQ // 16, 2)], grids


def test_no_window_is_todays_kernel_bitwise():
    """``window=None`` takes the path it took: the same call, output
    and gradients bitwise those of ``flash_attention``."""
    q, k, v, weight = qkv(value_dim=16)
    plain = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, True, None, BLOCK, 2 * BLOCK, True)
    routed = lambda q, k, v: flash_attention_auto(  # noqa: E731
        q, k, v, causal=True, block_q=BLOCK, block_k=2 * BLOCK,
        interpret=True, window=None)
    assert (plain(q, k, v) == routed(q, k, v)).all()
    for a, b in zip(
            jax.grad(lambda *x: (plain(*x) * weight).sum(), (0, 1, 2))(
                q, k, v),
            jax.grad(lambda *x: (routed(*x) * weight).sum(), (0, 1, 2))(
                q, k, v)):
        assert (a == b).all()
    names = [eqn.params["name"] for eqn in jax.make_jaxpr(plain)(
        q, k, v).jaxpr.eqns[0].params["call_jaxpr"].eqns
        if eqn.primitive.name == "pallas_call"]
    assert names == ["flash_fwd"]


@pytest.mark.parametrize("window", [None, 48], ids=["full", "window"])
def test_heads_of_64_with_values_of_128(window):
    """The differential attention's shape: query and key heads of 64,
    value heads of 128, two query heads a key head."""
    q, k, v, weight = qkv(heads=4, kv_heads=2, dim=64, value_dim=128,
                          seed=5)

    def kernel(q, k, v):
        return flash_attention_auto(q, k, v, causal=True, block_q=BLOCK,
                                    block_k=BLOCK, interpret=True,
                                    window=window)

    reference = lambda q, k, v: band_reference(  # noqa: E731
        q, k, v, window or SEQ)
    assert kernel(q, k, v).shape == (2, 4, SEQ, 128)
    assert float(jnp.abs(kernel(q, k, v) - reference(q, k, v)).max()) < 2e-5
    for a, b in zip(
            jax.grad(lambda *x: (kernel(*x) * weight).sum(), (0, 1, 2))(
                q, k, v),
            jax.grad(lambda *x: (reference(*x) * weight).sum(), (0, 1, 2))(
                q, k, v)):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 1e-4


def test_seven_query_heads_a_kv_head_of_128_and_a_window_off_the_blocks():
    """SmallThinker's head shape: 28 query heads over 4 KV heads of 128
    (the dKV kernel sums seven query heads into a KV head's block),
    under a window that is no multiple of the block, so the band's
    first block is cut inside."""
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(k[0], (1, 28, SEQ, 128))
    kk, v = (jax.random.normal(k[i], (1, 4, SEQ, 128)) for i in (1, 2))
    weight = jax.random.normal(k[3], (1, 28, SEQ, 128))
    window = BLOCK + 9
    assert window % BLOCK and _band_blocks(window, BLOCK, SEQ) == 3

    def loss(f):
        return lambda q, k, v: (f(q, k, v) * weight).sum()

    kernel = lambda q, k, v: flash_attention_window(  # noqa: E731
        q, k, v, window, None, BLOCK, True)
    reference = lambda q, k, v: band_reference(q, k, v, window)  # noqa: E731
    assert float(jnp.abs(kernel(q, kk, v)
                         - reference(q, kk, v)).max()) < 2e-5
    got = jax.grad(loss(kernel), (0, 1, 2))(q, kk, v)
    want = jax.grad(loss(reference), (0, 1, 2))(q, kk, v)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 2e-4


@pytest.mark.parametrize("bad", ["zero", "unequal-blocks"])
def test_a_window_that_cannot_be_walked_is_refused(bad):
    from dlrover_tpu.ops.flash_attention import _flash_forward

    q, k, v, _ = qkv()
    with pytest.raises(ValueError, match="window|square"):
        if bad == "zero":
            flash_attention_window(q, k, v, 0, None, BLOCK, True)
        else:
            _flash_forward(q, k, v, scale=1.0, causal=True, block_q=32,
                           block_k=64, interpret=True, window=8)
