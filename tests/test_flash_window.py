"""The sliding-window flash kernels and a value width of its own
(``ops/flash_attention.py``), in the interpreter, against
``ops/attention_ref.py`` under the band mask."""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.flash_attention import (
    _flash_window,
    band_walk,
    flash_attention,
    flash_attention_auto,
    flash_attention_window,
    window_tiles,
)

SEQ, BLOCK = 128, 32
# (block_q, block_k): square, a k tile of two q blocks, a q block of two
TILES = {"square": (32, 32), "wide": (32, 64), "tall": (64, 32)}


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


def at_tiles(window, tiles):
    """The window kernels at ``tiles`` forward and backward."""
    return lambda q, k, v: _flash_window(  # noqa: E731
        q, k, v, window, None, tiles, tiles, True)


def grads(f, weight, *args):
    return jax.grad(lambda *a: (f(*a) * weight).sum(), (0, 1, 2))(*args)


# one key; shorter than any tile; a whole number of every shape's tiles,
# one key less and one more (the far tile all but empty, all but full);
# the whole row (no far tile at all)
WINDOWS = [1, 7, 2 * BLOCK - 1, 2 * BLOCK, 2 * BLOCK + 1, SEQ]


@pytest.fixture(scope="module", params=[
    pytest.param((w, t), id=f"window-{w}-{t}")
    for t in TILES for w in WINDOWS])
def windowed(request):
    window, tiles = request.param
    q, k, v, weight = qkv()
    kernel = at_tiles(window, TILES[tiles])
    reference = lambda q, k, v: band_reference(q, k, v, window)  # noqa: E731
    return ((kernel(q, k, v), reference(q, k, v)),
            grads(kernel, weight, q, k, v),
            grads(reference, weight, q, k, v))


def test_windowed_forward_matches_the_band_mask(windowed):
    (got, want), _, _ = windowed
    assert got.shape == want.shape  # [B, H, S, value_dim]
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("arg", range(3), ids=["dq", "dk", "dv"])
def test_windowed_backward_matches_the_band_mask(windowed, arg):
    """dq comes from the dQ kernel, dk and dv from the dKV kernel."""
    _, got, want = windowed
    assert float(jnp.abs(got[arg] - want[arg]).max()) < 5e-5


def test_every_kind_of_tile_is_on_those_grids():
    """Between them the windows above put every kind on a grid: wholly
    visible, diagonal, far, and both at once where the window is
    shorter than a tile."""
    seen = set()
    for tiles in TILES.values():
        for window in WINDOWS:
            seen |= set(band_walk(SEQ, window, *tiles).kinds)
    assert seen == {(False, False), (True, False), (False, True),
                    (True, True)}
    assert band_walk(SEQ, SEQ, 32, 32).kinds == (
        (False, False), (True, False))


@pytest.mark.parametrize("tiles", TILES)
def test_a_window_of_the_whole_row_is_the_causal_kernel_bitwise(tiles):
    """No far tile, so no window comparison anywhere: the causal
    kernels' tiles in their order, the same bits out and back."""
    q, k, v, weight = qkv()
    bq, bk = TILES[tiles]
    band = at_tiles(SEQ, (bq, bk))
    causal = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, True, None, bq, bk, True)
    assert (band(q, k, v) == causal(q, k, v)).all()
    for a, b in zip(grads(band, weight, q, k, v),
                    grads(causal, weight, q, k, v)):
        assert (a == b).all()


def brute_walk(seq, window, bq, bk):
    """(tiles with a visible pair, tiles with every pair visible, the
    most k tiles a q block touches, the most q blocks a k tile)."""
    visible = [[j <= t and t - j < window for j in range(seq)]
               for t in range(seq)]
    touched = {}
    for i in range(seq // bq):
        for j in range(seq // bk):
            pairs = [visible[t][c] for t in range(i * bq, (i + 1) * bq)
                     for c in range(j * bk, (j + 1) * bk)]
            if any(pairs):
                touched[i, j] = all(pairs)
    by_q = [sum(1 for (i, _) in touched if i == b)
            for b in range(seq // bq)]
    by_k = [sum(1 for (_, j) in touched if j == b)
            for b in range(seq // bk)]
    return (len(touched), sum(touched.values()), max(by_q), max(by_k))


@pytest.mark.parametrize("bq,bk", [(8, 8), (8, 16), (16, 8), (8, 32),
                                   (32, 8), (16, 16), (64, 64)])
def test_the_walk_counts_what_a_brute_force_count_finds(bq, bk):
    """Every tile the walk visits holds a visible pair, it misses none,
    and the tiles it calls unmasked are the wholly visible ones; a band
    is contiguous in both directions, so the grids' extents are the
    fullest q block's and k tile's counts."""
    for seq in (64, 96):
        if seq % bq or seq % bk:
            continue
        for window in (1, 2, 7, 8, 9, 15, 16, 17, 24, 33, seq - 1, seq,
                       seq + 5):
            walk = band_walk(seq, window, bq, bk)
            assert (walk.tiles, walk.unmasked, walk.k_steps,
                    walk.q_steps) == brute_walk(seq, window, bq, bk), (
                seq, window)


@pytest.mark.parametrize("window,bq,bk,seq,want", [
    (512, 512, 512, 8192, 2), (512, 256, 256, 8192, 3),
    (1, 512, 512, 8192, 1), (513, 512, 512, 8192, 2),
    (514, 512, 512, 8192, 3), (8192, 512, 512, 8192, 16),
    (512, 8192, 8192, 8192, 1),
    # SmallThinker's band: 9 squares of 512, 5 tiles of 1024
    (4096, 512, 512, 16384, 9), (4096, 512, 1024, 16384, 5),
    (4096, 1024, 512, 16384, 10), (4096, 1024, 1024, 16384, 5)])
def test_the_grid_covers_the_bands_blocks_only(window, bq, bk, seq, want):
    assert band_walk(seq, window, bq, bk).k_steps == want


def test_the_share_of_unmasked_tiles_at_the_two_cells_shapes():
    """SmallThinker: 7 of a q block's 9 squares lie inside the band, 3
    of its 5 tiles of 512 x 1024 (over the row the same shares, the
    bands cut by the row's start having lost far tiles and inside tiles
    in that proportion); Phi-4-mini-flash's band of two squares is both
    edges."""
    square = band_walk(16384, 4096, 512, 512)
    assert (square.k_steps, square.q_steps) == (9, 9)
    assert (square.tiles, square.unmasked) == (252, 196)  # 7 / 9
    wide = band_walk(16384, 4096, 512, 1024)
    assert (wide.k_steps, wide.q_steps) == (5, 10)
    assert (wide.tiles, wide.unmasked) == (140, 84)  # 3 / 5
    assert wide.kinds == ((False, False), (False, True), (True, False))
    phi = band_walk(8192, 512, 512, 512)
    assert (phi.tiles, phi.unmasked) == (2 * 16 - 1, 0)
    assert phi.kinds == ((False, True), (True, False))


@pytest.mark.parametrize("tiles,grids", [
    # 2 of 8 k blocks a q block at a window of one block
    ((16, 16), [(2, 4, 8, 2), (2, 2, 8, 2, 2), (2, 4, 8, 2)]),
    # a k tile of two q blocks: the band of q block i is tiles
    # (i - 1) // 2 and i // 2; a k tile is seen by three q blocks
    ((16, 32), [(2, 4, 8, 2), (2, 2, 4, 2, 3), (2, 4, 8, 2)]),
    # a q block of two k tiles sees three; a k tile two q blocks
    ((32, 16), [(2, 4, 4, 3), (2, 2, 8, 2, 2), (2, 4, 4, 3)]),
], ids=["square", "wide", "tall"])
def test_the_windowed_grid_is_in_the_lowered_call(tiles, grids):
    """The three pallas_calls' grids, read from the traced program:
    the band's tiles and no others (forward, dKV, dQ)."""
    q, k, v, weight = qkv()
    text = str(jax.make_jaxpr(
        lambda *a: grads(at_tiles(16, tiles), weight, *a))(q, k, v))
    for name, grid in zip(("flash_win_fwd", "flash_win_dkv",
                           "flash_win_dq"), grids):
        assert f"name={name}" in text
        assert text.count(f"grid={grid}") >= 1, (name, grid)
    walk = band_walk(SEQ, 16, *tiles)
    assert (walk.k_steps, walk.q_steps) == (grids[0][3], grids[1][4])


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


@pytest.mark.parametrize("window,tiles", [
    (None, "square"), (48, "square"), (48, "wide"), (48, "tall")])
def test_heads_of_64_with_values_of_128(window, tiles):
    """The differential attention's shape: query and key heads of 64,
    value heads of 128, two query heads a key head."""
    q, k, v, weight = qkv(heads=4, kv_heads=2, dim=64, value_dim=128,
                          seed=5)

    def kernel(q, k, v):
        if window:
            return at_tiles(window, TILES[tiles])(q, k, v)
        return flash_attention_auto(q, k, v, causal=True, block_q=BLOCK,
                                    block_k=BLOCK, interpret=True)

    reference = lambda q, k, v: band_reference(  # noqa: E731
        q, k, v, window or SEQ)
    assert kernel(q, k, v).shape == (2, 4, SEQ, 128)
    assert float(jnp.abs(kernel(q, k, v) - reference(q, k, v)).max()) < 2e-5
    for a, b in zip(grads(kernel, weight, q, k, v),
                    grads(reference, weight, q, k, v)):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 1e-4


@pytest.mark.parametrize("tiles", TILES)
def test_seven_query_heads_a_kv_head_of_128_and_a_window_off_the_blocks(
        tiles):
    """SmallThinker's head shape: 28 query heads over 4 KV heads of 128
    (the dKV kernel sums seven query heads into a KV head's block),
    under a window that is no multiple of a tile's side, so the band's
    far tile is cut inside."""
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(k[0], (1, 28, SEQ, 128))
    kk, v = (jax.random.normal(k[i], (1, 4, SEQ, 128)) for i in (1, 2))
    weight = jax.random.normal(k[3], (1, 28, SEQ, 128))
    window = BLOCK + 9
    assert band_walk(SEQ, window, BLOCK, BLOCK).k_steps == 3
    kernel = at_tiles(window, TILES[tiles])
    reference = lambda q, k, v: band_reference(q, k, v, window)  # noqa: E731
    assert float(jnp.abs(kernel(q, kk, v)
                         - reference(q, kk, v)).max()) < 2e-5
    for a, b in zip(grads(kernel, weight, q, kk, v),
                    grads(reference, weight, q, kk, v)):
        assert float(jnp.abs(a - b).max()) < 2e-4


def test_the_entry_point_takes_the_tiles_the_shapes_pay_for():
    """``flash_attention_window`` asks ``window_tiles``, which sees the
    row, the window and the limit on a side and nothing else: the
    kernels it runs are those of ``_flash_window`` at that answer."""
    q, k, v, weight = qkv()
    fwd, bwd = window_tiles(SEQ, BLOCK, 2 * BLOCK)
    assert (fwd, bwd) == ((BLOCK, 2 * BLOCK), (BLOCK, BLOCK))
    entry = lambda q, k, v: flash_attention_window(  # noqa: E731
        q, k, v, BLOCK, None, 2 * BLOCK, True)
    direct = lambda q, k, v: _flash_window(  # noqa: E731
        q, k, v, BLOCK, None, fwd, bwd, True)
    assert (entry(q, k, v) == direct(q, k, v)).all()
    for a, b in zip(grads(entry, weight, q, k, v),
                    grads(direct, weight, q, k, v)):
        assert (a == b).all()


@pytest.mark.parametrize("seq,window,block,forward,backward", [
    # SmallThinker's window layers and Phi-4-mini-flash's, as measured
    (16384, 4096, 1024, (1024, 1024), (1024, 1024)),
    (8192, 512, 1024, (512, 1024), (512, 512)),
    # the window fills a tile but the band is not two wide
    (16384, 1024, 1024, (1024, 1024), (512, 512)),
    (16384, 2047, 1024, (1024, 1024), (512, 512)),
    (16384, 2048, 1024, (1024, 1024), (1024, 1024)),
    # a limit the caller lowered, a row shorter than the limit, a side
    # that cannot be halved and stay a multiple of 8
    (16384, 4096, 512, (512, 512), (512, 512)),
    (512, 128, 1024, (256, 512), (256, 256)),
    (64, 16, 8, (8, 8), (8, 8)),
])
def test_the_rule_for_the_tiles(seq, window, block, forward, backward):
    assert window_tiles(seq, window, block) == (forward, backward)
    for bq, bk in (forward, backward):
        assert seq % bq == 0 and seq % bk == 0 and max(bq, bk) <= block


def test_a_window_of_no_key_is_refused():
    q, k, v, _ = qkv()
    with pytest.raises(ValueError, match="window"):
        flash_attention_window(q, k, v, 0, None, BLOCK, True)
