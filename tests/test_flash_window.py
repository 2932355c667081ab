"""The sliding-window flash kernels and a value width of its own
(``ops/flash_attention.py``), in the interpreter, against
``ops/attention_ref.py`` under the band mask."""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.ops import flash_attention as fa
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
    """All three come from the one backward kernel (rows of this
    length are under its budget)."""
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


@pytest.fixture
def two_kernels(monkeypatch):
    """The backward of a row whose state is over the budget: the dKV
    and the dQ kernel."""
    monkeypatch.setattr(fa, "_WIN_ROW_STATE_BUDGET_BYTES", 0)


# (forward, dKV, dQ, the one backward kernel)
GRIDS = {
    # 2 of 8 k blocks a q block at a window of one block
    "square": ((16, 16), [(2, 4, 8, 2), (2, 2, 8, 2, 2), (2, 4, 8, 2),
                          (2, 2, 2, 8, 2)]),
    # a k tile of two q blocks: the band of q block i is tiles
    # (i - 1) // 2 and i // 2; a k tile is seen by three q blocks
    "wide": ((16, 32), [(2, 4, 8, 2), (2, 2, 4, 2, 3), (2, 4, 8, 2),
                        (2, 2, 2, 4, 3)]),
    # a q block of two k tiles sees three; a k tile two q blocks
    "tall": ((32, 16), [(2, 4, 4, 3), (2, 2, 8, 2, 2), (2, 4, 4, 3),
                        (2, 2, 2, 8, 2)]),
}


def lowered_calls(tiles):
    q, k, v, weight = qkv()
    return str(jax.make_jaxpr(
        lambda *a: grads(at_tiles(16, tiles), weight, *a))(q, k, v))


@pytest.mark.parametrize("shape", GRIDS)
def test_the_windowed_grid_is_in_the_lowered_call(shape):
    """The two pallas_calls' grids, read from the traced program: the
    band's tiles and no others. The backward's is the dKV kernel's
    with the group's heads outside the k tiles."""
    tiles, (forward, dkv, _, backward) = GRIDS[shape]
    text = lowered_calls(tiles)
    for name, grid in (("flash_win_fwd", forward),
                       ("flash_win_bwd", backward)):
        assert f"name={name}" in text
        assert text.count(f"grid={grid}") >= 1, (name, grid)
    assert "flash_win_dkv" not in text and "flash_win_dq" not in text
    assert text.count("pallas_call") == 2
    assert backward == (dkv[0], dkv[1], dkv[3], dkv[2], dkv[4])
    walk = band_walk(SEQ, 16, *tiles)
    assert (walk.k_steps, walk.q_steps) == (forward[3], backward[4])


@pytest.mark.parametrize("shape", GRIDS)
def test_the_two_kernels_grids_are_in_the_lowered_call(two_kernels, shape):
    """Over the budget: the three pallas_calls' grids (forward, dKV,
    dQ), the band's tiles and no others."""
    tiles, grids = GRIDS[shape]
    text = lowered_calls(tiles)
    for name, grid in zip(("flash_win_fwd", "flash_win_dkv",
                           "flash_win_dq"), grids):
        assert f"name={name}" in text
        assert text.count(f"grid={grid}") >= 1, (name, grid)
    assert "flash_win_bwd" not in text
    walk = band_walk(SEQ, 16, *tiles)
    assert (walk.k_steps, walk.q_steps) == (grids[0][3], grids[1][4])


# shorter than a tile (every tile an edge, some both at once); one
# tile's side; off the blocks; several tiles and a key
BACKWARD_WINDOWS = [7, BLOCK, BLOCK + 9, 2 * BLOCK + 1]


def backward_operands(group, dim, value_dim, window):
    """(q, k, v, out, lse, do) of one batch row: the residuals are the
    dense band's, whatever tiles the backward then takes."""
    kv_heads = 1 if group == 7 else 2
    k = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(k[0], (1, group * kv_heads, SEQ, dim))
    kk = jax.random.normal(k[1], (1, kv_heads, SEQ, dim))
    v = jax.random.normal(k[2], (1, kv_heads, SEQ, value_dim))
    do = jax.random.normal(k[3], (1, group * kv_heads, SEQ, value_dim))
    t = jnp.arange(SEQ)
    visible = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < window)
    scores = jnp.einsum("bhsd,bhtd->bhst", q, jnp.repeat(kk, group, 1),
                        precision="highest") * dim ** -0.5
    scores = jnp.where(visible, scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    out = jnp.einsum("bhst,bhte->bhse", jnp.exp(scores - lse[..., None]),
                     jnp.repeat(v, group, 1), precision="highest")
    return q, kk, v, out, lse, do


@pytest.mark.parametrize("tiles", TILES)
@pytest.mark.parametrize("group,dim,value_dim", [
    (1, 64, 128), (2, 128, 128), (7, 64, 128), (7, 128, 128)])
@pytest.mark.parametrize("window", BACKWARD_WINDOWS)
def test_the_one_kernel_is_the_two_kernels_bitwise(
        window, group, dim, value_dim, tiles):
    """dK and dV sum a group's heads and then the q blocks of a k tile,
    dQ the k tiles in ascending order, as the dKV and the dQ kernel do:
    the same bits in all three gradients, whatever the tiles' kinds."""
    q, k, v, out, lse, do = backward_operands(group, dim, value_dim, window)
    bq, bk = TILES[tiles]
    one = fa._flash_window_backward_one_call(
        q, k, v, out, lse, do, window, dim ** -0.5, bq, bk, True)
    two = fa._flash_backward(
        q, k, v, out, lse, do, jnp.zeros_like(lse), causal=True, scale=None,
        block_q=bq, block_k=bk, interpret=True, window=window)
    for name, a, b in zip(("dq", "dk", "dv"), one, two):
        assert a.shape == b.shape and (a == b).all(), name
        assert float(jnp.abs(a).max()) > 0.1, name


def test_those_backward_windows_put_every_kind_of_tile_on_a_grid():
    seen = set()
    for tiles in TILES.values():
        for window in BACKWARD_WINDOWS:
            seen |= set(band_walk(SEQ, window, *tiles).kinds)
    assert seen == {(False, False), (True, False), (False, True),
                    (True, True)}


@pytest.mark.parametrize("arg", range(3), ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("window,tiles", [
    (7, "square"), (2 * BLOCK + 1, "wide"), (BLOCK + 9, "tall")])
def test_the_two_kernels_match_the_band_mask(two_kernels, window, tiles,
                                             arg):
    """The pair that rows over the budget keep, against dense XLA."""
    q, k, v, weight = qkv()
    got = grads(at_tiles(window, TILES[tiles]), weight, q, k, v)
    want = grads(lambda q, k, v: band_reference(q, k, v, window), weight,
                 q, k, v)
    assert float(jnp.abs(got[arg] - want[arg]).max()) < 5e-5


def backward_kernels(heads, kv_heads, seq, dim, value_dim, window,
                     dtype=jnp.bfloat16):
    """The names of the backward's pallas_calls at a shape, from the
    traced program (abstract operands: nothing runs)."""
    shapes = (jax.ShapeDtypeStruct((1, heads, seq, dim), dtype),
              jax.ShapeDtypeStruct((1, kv_heads, seq, dim), dtype),
              jax.ShapeDtypeStruct((1, kv_heads, seq, value_dim), dtype))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention_window(
            q, k, v, window, None, 1024, False).astype(jnp.float32).sum(),
        (0, 1, 2)))(*shapes)
    return [name for name in ("flash_win_bwd", "flash_win_dkv",
                              "flash_win_dq")
            if f"name={name}" in str(jaxpr)]


@pytest.mark.parametrize("shape,names", [
    # the two cells' window layers: SmallThinker's, Phi-4-mini-flash's
    ((28, 4, 16384, 128, 128, 4096), ["flash_win_bwd"]),
    ((20, 10, 8192, 64, 128, 512), ["flash_win_bwd"]),
    # twice SmallThinker's row: 96 MiB of accumulators and output rows
    ((28, 4, 32768, 128, 128, 4096), ["flash_win_dkv", "flash_win_dq"]),
    # the same row in float32: 64 MiB
    ((28, 4, 16384, 128, 128, 4096, jnp.float32),
     ["flash_win_dkv", "flash_win_dq"]),
    # a minor axis of 64 takes 128 lanes: 16,384 x 64 is at the budget
    ((20, 10, 16384, 64, 128, 512), ["flash_win_bwd"]),
    ((20, 10, 16384 + 1024, 64, 128, 512),
     ["flash_win_dkv", "flash_win_dq"]),
], ids=["smallthinker", "phi4flash", "row-32768", "float32", "at-the-budget",
        "a-tile-over"])
def test_the_backward_is_chosen_from_the_rows_state(shape, names):
    """The one kernel wherever its whole-row state fits the budget, by
    the shapes alone: row, widths (in lanes) and item size."""
    assert backward_kernels(*shape) == names


def test_the_rows_state_in_bytes():
    """Three float32 accumulators and three double-buffered output
    rows in the operands' type, each width padded to 128 lanes."""
    mib = 1024 * 1024
    assert fa._win_row_state_bytes(16384, 128, 128, 2) == 48 * mib
    assert fa._win_row_state_bytes(8192, 64, 128, 2) == 24 * mib
    assert fa._win_row_state_bytes(8192, 128, 128, 4) == 36 * mib
    assert fa._WIN_ROW_STATE_BUDGET_BYTES == 48 * mib
    assert fa._WIN_VMEM_LIMIT_BYTES == 2 * fa._WIN_ROW_STATE_BUDGET_BYTES


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
    (the backward kernel sums seven query heads into a KV head's row),
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


def test_a_checkpoint_that_keeps_the_names_has_one_forward_kernel():
    """Around the window op, a checkpoint given ``KEPT_NAMES`` holds
    ``out`` and ``lse`` as residuals and its gradient program runs
    ``flash_win_fwd`` once; one given nothing (every caller but
    ``models/gqa_moe.py``) holds its arguments alone, runs the kernel
    again in its replay, and gives the same bits."""
    from jax._src.ad_checkpoint import saved_residuals

    from dlrover_tpu.ops.remat import apply_remat

    q, k, v, weight = qkv()

    def f(q, k, v):
        return (flash_attention_window(q, k, v, 40, None, BLOCK, True)
                * weight).sum()

    got = {}
    for keep, forwards, kept in (((), 2, []), (fa.KEPT_NAMES, 1, [
            (2, 4, SEQ, 32), (2, 4, SEQ)])):
        g = apply_remat(f, "full", keep=keep)
        assert [value.shape for value, why in saved_residuals(g, q, k, v)
                if why.startswith(("output of", "named"))] == kept
        grad = jax.grad(g, (0, 1, 2))
        text = str(jax.make_jaxpr(grad)(q, k, v))
        assert text.count("name=flash_win_fwd") == forwards
        assert text.count("name=flash_win_bwd") == 1
        got[keep] = grad(q, k, v)
    for a, b, c in zip(got[()], got[fa.KEPT_NAMES],
                       jax.grad(f, (0, 1, 2))(q, k, v)):
        assert bool(jnp.all(a == b)) and bool(jnp.all(a == c))
