"""The grouped and the windowed latent flash kernels
(``ops.flash_attention.flash_attention_mla_grouped``,
``flash_mla_*`` with fewer key/value heads than query heads and
``flash_mla_win_*``) in the interpreter against a dense reference:
forward and every gradient, at rows that are and are not multiples of
the window and the tiles; ``G == H`` without a window is the present
path bit for bit; a scan's per-layer choice of kind
(``flash_attention_mla_by_kind``) is the kind's own call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import flash_attention as fa


def _operands(seed, batch, heads, groups, seq, dn=16, dr=8, dv=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = lambda h, d: (batch, h, seq, d)  # noqa: E731
    return (jax.random.normal(k[0], shape(heads, dn)),
            jax.random.normal(k[1], shape(heads, dr)),
            jax.random.normal(k[2], shape(groups, dn)),
            jax.random.normal(k[3], shape(1, dr)),
            jax.random.normal(k[4], shape(groups, dv)))


def dense(qn, qr, kn, kr, v, scale, window):
    """Every head's own masked softmax over its group's keys."""
    rep = qn.shape[1] // kn.shape[1]
    kn, v = jnp.repeat(kn, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = (jnp.einsum("bhqd,bhkd->bhqk", qn, kn)
         + jnp.einsum("bhqd,bkd->bhqk", qr, kr[:, 0])) * scale
    seq = qn.shape[2]
    t, u = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    seen = u <= t
    if window:
        seen = seen & (u > t - window)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


CASES = [
    # heads, groups, seq, window, block_q, block_k
    (10, 2, 64, 0, 16, 32),   # five query heads a KV head, causal
    (4, 4, 64, 16, 16, 16),   # a window of one tile, G == H
    (10, 2, 64, 16, 16, 16),  # both
    (6, 2, 96, 20, 32, 32),   # the row no multiple of the window
    (6, 3, 72, 40, 32, 32),   # tiles fitted to the row (24), window > tile
    (5, 1, 48, 7, 16, 16),    # a window shorter than a tile
    (4, 2, 64, 64, 16, 32),   # the window is the row: plain causal
]


@pytest.mark.parametrize("heads,groups,seq,window,bq,bk", CASES)
def test_grouped_kernels_match_the_dense_reference(heads, groups, seq,
                                                   window, bq, bk):
    ops = _operands(seq + heads, 2, heads, groups, seq)
    scale = 24 ** -0.5
    weight = jax.random.normal(jax.random.PRNGKey(7),
                               (2, heads, seq, ops[4].shape[-1]))

    def kernel(*a):
        return fa.flash_attention_mla_grouped(*a, scale, window, bq, bk,
                                              True)

    np.testing.assert_allclose(kernel(*ops), dense(*ops, scale, window),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight),
                   argnums=range(5))(*ops)
    want = jax.grad(lambda *a: jnp.sum(dense(*a, scale, window) * weight),
                    argnums=range(5))(*ops)
    for name, g, w in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"),
                          got, want):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4,
                                   err_msg=name)


def test_one_head_a_group_without_a_window_is_the_present_path():
    ops = _operands(3, 2, 4, 4, 64)
    weight = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 64, 16))

    def run(f):
        return jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) * weight), argnums=range(5))(*ops)

    auto = run(lambda *a: fa.flash_attention_mla_auto(
        *a, None, 16, 32, True))
    present = run(lambda *a: fa.flash_attention_mla(*a, None, 16, 32, True))
    for a, b in zip(jax.tree.leaves(auto), jax.tree.leaves(present)):
        assert jnp.array_equal(a, b)
    # and the grouped kernels at one head a group compute the same sums
    grouped = run(lambda *a: fa.flash_attention_mla_grouped(
        *a, None, 0, 16, 32, True))
    for a, b in zip(jax.tree.leaves(grouped), jax.tree.leaves(present)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_the_auto_wrapper_lowers_the_present_call_when_nothing_is_new():
    """``G == H`` and no window: the traced program holds the parent's
    kernels under the parent's names and nothing of the grouped path."""
    ops = _operands(3, 1, 4, 4, 64)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(fa.flash_attention_mla_auto(
        *a, None, 16, 32, True)), argnums=range(5))).lower(*ops).as_text()
    assert "flash_mla_win" not in text


@pytest.mark.parametrize("windowed", [0, 1])
def test_a_layers_kind_chosen_in_the_scan_is_that_kinds_call(windowed):
    ops = _operands(11, 1, 6, 2, 64)
    weight = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 64, 16))

    def run(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) * weight), argnums=range(5)))(*ops)

    chosen = run(lambda *a: fa.flash_attention_mla_auto(
        *a, None, 16, 32, True, window=16, window_block=16,
        windowed=jnp.int32(windowed)))
    own = run(lambda *a: fa.flash_attention_mla_grouped(
        *a, None, 16 * windowed, *((16, 16) if windowed else (16, 32)),
        True))
    for a, b in zip(jax.tree.leaves(chosen), jax.tree.leaves(own)):
        assert jnp.array_equal(a, b)


def test_shapes_that_are_no_groups_are_refused():
    qn, qr, kn, kr, v = _operands(0, 1, 6, 4, 32)
    with pytest.raises(ValueError, match="divides"):
        fa.flash_attention_mla_grouped(qn, qr, kn, kr, v, None, 0, 16, 16,
                                       True)


def test_the_bands_tile_counters_are_the_walks():
    walk = fa.band_walk(8192, 128, 128, 128)
    assert fa.mla_band_tile_counters(80, 8192, 128) == {
        "attn_band_tiles": 80 * walk.tiles,
        "attn_band_tiles_unmasked": 80 * walk.unmasked}
    # a q block sees its own tile and the one before it
    assert walk.tiles == 2 * 64 - 1 and walk.k_steps == 2


@pytest.mark.parametrize("op,forward", [
    ("flash_attention_mla_grouped", "flash_mla_fwd"),
    ("flash_attention_mla_grouped-window", "flash_mla_win_fwd"),
    ("flash_attention_mla_by_kind", "flash_mla_win_fwd")])
def test_a_checkpoint_that_keeps_the_names_has_one_forward_kernel(
        op, forward):
    """Around a grouped latent op a checkpoint given ``KEPT_NAMES``
    holds ``out`` and ``lse4`` as residuals and its gradient program has
    each forward kernel once (by kind: once a branch of the forward's
    one switch, whose two results carry the names); one given nothing
    holds its arguments alone, has every forward kernel again in its
    replay, and gives the same bits."""
    from hlo_checks import kept_names_spare_the_forward

    ops = _operands(13, 1, 6, 2, 32)
    weight = jax.random.normal(jax.random.PRNGKey(4), (1, 6, 32, 16))
    if op == "flash_attention_mla_by_kind":
        call = lambda *a: fa.flash_attention_mla_by_kind(  # noqa: E731
            jnp.int32(1), *a, None, 16, (16, 32), 16, True)
    else:
        window = 16 * op.endswith("window")
        call = lambda *a: fa.flash_attention_mla_grouped(  # noqa: E731
            *a, None, window, 16, 16 if window else 32, True)

    kept_names_spare_the_forward(
        lambda *a: (jnp.sin(call(*a)) * weight).sum(), ops, fa.KEPT_NAMES,
        forward, [(1, 6, 32, 16), (1, 6, 1, 32)])
