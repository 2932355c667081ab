"""Gated experts of which this chip holds a set (``ops/moe.py``:
``sigmoid_topk_routing``, ``topk_softmax_routing``, ``held_expert_ffn``
with either activation of its gate over
``grouped_matmul`` with ``num_tiles``) against the einsum oracle, in the
interpreter: skewed routing, an expert with no rows, rows past the
bound counted, the tiles past the last group skipped, and the ladder
of row counts: each rung the full bound's result bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import moe
from dlrover_tpu.ops.grouped_matmul import grouped_matmul
from dlrover_tpu.ops.remat import apply_remat

TOKENS, HIDDEN, WIDTH, EXPERTS, TOP_K, TILE = 64, 32, 48, 24, 4, 8
HELD = (8, 9, 10, 11, 12, 13, 14, 15)  # the second share of three


def layer(seed=1, held=HELD, skew=True):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    experts = {
        name: {"kernel": jax.random.normal(k[i], (len(held),) + shape)
               * 0.2}
        for i, (name, shape) in enumerate((
            ("gate", (HIDDEN, WIDTH)), ("up", (HIDDEN, WIDTH)),
            ("down", (WIDTH, HIDDEN))))}
    x = jax.random.normal(k[3], (TOKENS, HIDDEN))
    logits = jax.random.normal(k[4], (TOKENS, EXPERTS))
    if skew:  # every token wants expert 9, none wants expert 12
        logits = logits.at[:, 9].add(4.0).at[:, 12].add(-50.0)
    return experts, x, logits


def test_routing_is_sigmoid_top_k_renormalised_and_scaled():
    _, _, logits = layer()
    top_i, top_w, scores = moe.sigmoid_topk_routing(logits, TOP_K, True, 2.5)
    want = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    assert np.allclose(scores, want, atol=1e-6)
    order = np.argsort(-want, axis=1)[:, :TOP_K]
    assert (np.sort(top_i, axis=1) == np.sort(order, axis=1)).all()
    picked = np.take_along_axis(want, np.asarray(top_i), axis=1)
    assert np.allclose(top_w, 2.5 * picked / picked.sum(1, keepdims=True),
                       atol=1e-6)
    # the weights of a token add up to the scale, whatever its scores
    assert np.allclose(np.asarray(top_w).sum(1), 2.5, atol=1e-5)
    _, plain_w, _ = moe.sigmoid_topk_routing(logits, TOP_K, False, 1.0)
    assert np.allclose(plain_w, picked, atol=1e-6)


@pytest.mark.parametrize("renormalise", [True, False],
                         ids=["renormalised", "of-all"])
def test_routing_is_softmax_over_all_top_k(renormalise):
    """``topk_softmax_routing`` against softmax over all the experts,
    top-k, and the selected probabilities over their sum (which is the
    softmax of the selected logits alone) or as they are."""
    _, _, logits = layer()
    top_i, top_w, scores = moe.topk_softmax_routing(logits, TOP_K,
                                                    renormalise)
    wide = np.asarray(logits, np.float64)
    want = np.exp(wide - wide.max(1, keepdims=True))
    want /= want.sum(1, keepdims=True)
    assert np.allclose(scores, want, atol=1e-6)
    assert scores.dtype == top_w.dtype == jnp.float32
    assert top_i.dtype == jnp.int32 and top_i.shape == (TOKENS, TOP_K)
    order = np.argsort(-want, axis=1)[:, :TOP_K]
    assert (np.asarray(top_i) == order).all()  # largest first
    picked = np.take_along_axis(want, order, axis=1)
    if renormalise:
        picked = picked / picked.sum(1, keepdims=True)
        assert np.allclose(np.asarray(top_w).sum(1), 1.0, atol=1e-6)
    else:  # every token wants expert 9: the rest share what is left
        assert (np.asarray(top_w).sum(1) < 1.0).all()
    assert np.allclose(top_w, picked, atol=1e-6)
    # bf16 logits are scored in float32 all the same
    _, low_w, _ = moe.topk_softmax_routing(logits.astype(jnp.bfloat16),
                                           TOP_K, renormalise)
    assert low_w.dtype == jnp.float32


def test_the_balance_term_is_one_under_uniform_routing():
    scores = jnp.full((2 * 48, EXPERTS), 0.5)
    # token t selects experts t, t+1, .. (mod E): every expert as often
    top_i = (jnp.arange(96)[:, None] + jnp.arange(TOP_K)) % EXPERTS
    assert abs(float(moe.sequence_balance_loss(scores, top_i, 2)) - 1) < 1e-6
    # all selections on four experts that also score highest: above 1
    skew = scores.at[:, :TOP_K].set(0.9)
    top_i = jnp.broadcast_to(jnp.arange(TOP_K), (96, TOP_K))
    assert float(moe.sequence_balance_loss(skew, top_i, 2)) > 1.5


def test_the_row_bound_is_whole_tiles_of_a_multiple_of_the_expectation():
    # 8192 tokens, top-8 of 192, 8 held: 2,731 rows expected
    assert moe.held_row_bound(8192, 8, 192, 8, 1.0) == (22 + 8) * 128
    assert moe.held_row_bound(8192, 8, 192, 8, 4.0) == (86 + 8) * 128
    # never more than every assignment
    assert moe.held_row_bound(64, 4, 24, 24, 4.0, 8) == (32 + 24) * 8


@pytest.fixture(scope="module")
def routed():
    experts, x, logits = layer()
    top_i, top_w, _ = moe.sigmoid_topk_routing(logits, TOP_K, True, 2.5)
    return experts, x, top_i, top_w


# the gate's activation: SwiGLU as the two latent models have it (the
# default, and named), ReGLU as ``models/gqa_moe.py`` asks for it
ACTIVATIONS = pytest.mark.parametrize("activation", [
    (), (jax.nn.silu,), (jax.nn.relu,)], ids=["default", "silu", "relu"])


@ACTIVATIONS
def test_held_experts_match_the_oracle_under_skew(routed, activation):
    experts, x, top_i, top_w = routed
    bound = moe.held_row_bound(TOKENS, TOP_K, EXPERTS, len(HELD), 4.0, TILE)
    out, stats = moe.held_expert_ffn(experts, x, top_i, top_w, HELD, bound,
                                     TILE, True, *activation)
    want = moe.held_expert_ffn_reference(experts, x, top_i, top_w, HELD,
                                         *activation)
    if activation == (jax.nn.relu,):  # and ReGLU is not SwiGLU
        assert float(jnp.abs(want - moe.held_expert_ffn_reference(
            experts, x, top_i, top_w, HELD)).max()) > 0.1
    assert float(jnp.abs(out - want).max()) < 1e-5
    per_expert = [(np.asarray(top_i) == e).sum() for e in HELD]
    assert per_expert[1] == TOKENS and per_expert[4] == 0  # 9 and 12
    assert float(stats["rows_held"]) == sum(per_expert)
    assert float(stats["rows_max"]) == TOKENS
    assert float(stats["rows_dropped"]) == 0


@ACTIVATIONS
def test_held_experts_gradients_match_the_oracle(routed, activation):
    """Through the three grouped matmuls, forward, dx and dW, and the
    gate's activation transposed by ``jax.vjp``; the expert with no
    rows gets a gradient of zeros, not garbage."""
    experts, x, top_i, top_w = routed
    bound = moe.held_row_bound(TOKENS, TOP_K, EXPERTS, len(HELD), 4.0, TILE)
    kernel = lambda e, x, w: (moe.held_expert_ffn(  # noqa: E731
        e, x, top_i, w, HELD, bound, TILE, True, *activation)[0] ** 2).sum()
    oracle = lambda e, x, w: (moe.held_expert_ffn_reference(  # noqa: E731
        e, x, top_i, w, HELD, *activation) ** 2).sum()
    got = jax.grad(kernel, (0, 1, 2))(experts, x, top_w)
    want = jax.grad(oracle, (0, 1, 2))(experts, x, top_w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(a - b).max()) < 2e-4
    assert float(jnp.abs(got[0]["gate"]["kernel"][4]).max()) == 0.0


def test_what_the_other_experts_add_is_left_out(routed):
    """Only the held experts' terms: a token none of whose selections
    is held gets zeros; another held set gives another part."""
    experts, x, top_i, top_w = routed
    bound = moe.held_row_bound(TOKENS, TOP_K, EXPERTS, 8, 4.0, TILE)
    elsewhere = tuple(range(16, 24))
    out, stats = moe.held_expert_ffn(experts, x, top_i, top_w, elsewhere,
                                     bound, TILE, True)
    want = moe.held_expert_ffn_reference(experts, x, top_i, top_w,
                                         elsewhere)
    assert float(jnp.abs(out - want).max()) < 1e-5
    none_held = ~np.isin(np.asarray(top_i), elsewhere).any(axis=1)
    assert none_held.any() and float(
        jnp.abs(out[np.flatnonzero(none_held)]).max()) == 0.0
    assert float(stats["rows_held"]) == np.isin(
        np.asarray(top_i), elsewhere).sum()


def test_rows_past_the_bound_are_counted(routed):
    """A buffer of one tile an expert: the skewed expert's 64 rows do
    not fit, and the counter says how many assignments were left out."""
    experts, x, top_i, top_w = routed
    held_rows = int(np.isin(np.asarray(top_i), HELD).sum())
    bound = len(HELD) * TILE
    out, stats = moe.held_expert_ffn(experts, x, top_i, top_w, HELD, bound,
                                     TILE, True)
    dropped = float(stats["rows_dropped"])
    assert 0 < dropped < held_rows
    assert float(stats["rows_held"]) == held_rows  # routed, not computed
    assert bool(jnp.isfinite(out).all())
    with pytest.raises(ValueError, match="whole tiles"):
        moe.held_expert_ffn(experts, x, top_i, top_w, HELD, bound - 1,
                            TILE, True)


def test_tiles_past_the_last_group_are_skipped():
    """``grouped_matmul`` told how many tiles are real equals itself on
    those tiles alone and writes zeros, not products, past them; rows
    there get no gradient and give none."""
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(k[0], (6 * TILE, HIDDEN))  # garbage tail too
    w = jax.random.normal(k[1], (3, HIDDEN, WIDTH))
    tile_expert = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
    real = jnp.asarray([4], jnp.int32)
    got = grouped_matmul(x, w, tile_expert, TILE, 512, True, real)
    want = grouped_matmul(x, w, tile_expert, TILE, 512, True)
    assert (got[:4 * TILE] == want[:4 * TILE]).all()
    assert float(jnp.abs(got[4 * TILE:]).max()) == 0.0
    f = lambda x, w: (grouped_matmul(  # noqa: E731
        x, w, tile_expert, TILE, 512, True, real) ** 2).sum()
    g = lambda x, w: (grouped_matmul(  # noqa: E731
        x[:4 * TILE], w, tile_expert[:4], TILE, 512, True) ** 2).sum()
    dx, dw = jax.grad(f, (0, 1))(x, w)
    dx_want, dw_want = jax.grad(g, (0, 1))(x, w)
    assert float(jnp.abs(dx[:4 * TILE] - dx_want[:4 * TILE]).max()) < 1e-4
    assert float(jnp.abs(dx[4 * TILE:]).max()) == 0.0
    assert float(jnp.abs(dw - dw_want).max()) < 1e-4


def test_every_expert_held_is_the_whole_layer():
    experts, x, logits = layer(held=tuple(range(EXPERTS)), skew=False)
    top_i, top_w, _ = moe.sigmoid_topk_routing(logits, TOP_K, True, 1.0)
    held = tuple(range(EXPERTS))
    bound = moe.held_row_bound(TOKENS, TOP_K, EXPERTS, EXPERTS, 4.0, TILE)
    out, stats = moe.held_expert_ffn(experts, x, top_i, top_w, held, bound,
                                     TILE, True)
    want = moe.held_expert_ffn_reference(experts, x, top_i, top_w, held)
    assert float(jnp.abs(out - want).max()) < 1e-5
    assert float(stats["rows_held"]) == TOKENS * TOP_K


# -- the ladder of row counts ------------------------------------------------

LADDER = (10 * TILE, 20 * TILE, 40 * TILE)  # three rungs, to test each


@pytest.mark.parametrize("shape", [
    (8192, 8, 192, 8, 4.0, 128), (8192, 8, 192, 8, 2.0, 128),
    (8192, 8, 192, 8, 0.5, 128), (TOKENS, TOP_K, EXPERTS, 8, 4.0, TILE),
    (TOKENS, TOP_K, EXPERTS, EXPERTS, 4.0, TILE), (4096, 2, 64, 1, 16.0, 128),
], ids=["axk1", "factor-2", "factor-under-1", "toy", "all-held", "one-held"])
def test_the_ladder_is_whole_tiles_under_the_bound(shape):
    """Ascending, in whole tiles, ending at ``held_row_bound``, each
    rung about half the next, none under what uniform routing sends
    with a tile a held expert (so none under ``held`` tiles)."""
    tokens, top_k, experts, held, factor, tile = shape
    ladder = moe.held_row_ladder(*shape)
    floor = moe.held_row_bound(tokens, top_k, experts, held,
                               min(factor, 1.0), tile)
    assert ladder[-1] == moe.held_row_bound(*shape)
    assert list(ladder) == sorted(set(ladder))
    assert all(n % tile == 0 and n >= held * tile for n in ladder)
    assert ladder[0] >= floor
    for small, large in zip(ladder, ladder[1:]):
        assert 0 <= 2 * small - large < 2 * tile
    # the next halving would be under the floor
    assert -(-ladder[0] // (2 * tile)) * tile < floor


def test_the_ladder_at_the_benchmarks_shapes():
    # 4 x 2,731 rows and a tile an expert, and its half; a quarter
    # (3,072) would be under the uniform 2,731 + 8 tiles
    assert moe.held_row_ladder(8192, 8, 192, 8, 4.0, 128) == (6016, 12032)
    assert moe.held_row_ladder(8192, 8, 192, 8, 0.5, 128) == (
        moe.held_row_bound(8192, 8, 192, 8, 0.5, 128),)
    assert moe.held_row_ladder(TOKENS, TOP_K, EXPERTS, 8, 4.0, TILE) == (
        20 * TILE, 40 * TILE)


def routing_ending_at(tiles, extra=0, seed=3):
    """Selections (``top_i``, ``top_w``) whose groups for the 8 held
    experts end at ``tiles`` tiles exactly: the first gets all but
    seven of them, three rows short of full, one gets no row (and keeps
    its tile), the others up to a tile; ``extra`` rows more go to the
    last, whose tile was full. The other selections go to experts held
    elsewhere, some beyond the table; a token may select one twice."""
    counts = [(tiles - 7) * TILE - 3, 5, 1, TILE, 0, 7, 2, TILE + extra]
    chosen = np.repeat(np.asarray(HELD), counts)
    rest = np.resize(np.asarray([2, 17, 23, 30]),
                     TOKENS * TOP_K - len(chosen))
    flat = np.random.RandomState(seed).permutation(
        np.concatenate([chosen, rest]))
    top_i = jnp.asarray(flat.reshape(TOKENS, TOP_K), jnp.int32)
    top_w = jax.random.uniform(jax.random.PRNGKey(seed), (TOKENS, TOP_K),
                               minval=0.1, maxval=1.0)
    return top_i, top_w, counts


def value_and_grads(fn, experts, x, top_i, top_w):
    """(output, stats, gradients by the three kernels, ``x`` and
    ``top_w``) of ``fn(experts, x, top_i, top_w)`` under a loss that
    weighs every output element differently."""
    def loss(experts, x, top_w):
        out, stats = fn(experts, x, top_i, top_w)
        weigh = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
            out.shape)
        return (out.astype(jnp.float32) ** 2 * weigh).sum(), (out, stats)

    (_, (out, stats)), grads = jax.value_and_grad(
        loss, (0, 1, 2), has_aux=True)(experts, x, top_w)
    return out, stats, grads


def same_bits(a, b):
    return all(x.dtype == y.dtype and (np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def cast_layer(dtype):
    experts, x, _ = layer(skew=False)
    return jax.tree.map(lambda a: a.astype(dtype), (experts, x))


def close(got, want, f32_tol, dtype):
    """Within the file's float32 tolerance; in bf16, where the oracle
    rounds at other places, within a few roundings (2**-8 each) of the
    largest element."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        tol = f32_tol if dtype == jnp.float32 else (
            8 * 2.0 ** -8 * float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) <= tol


DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["f32", "bf16"])


@DTYPES
@pytest.mark.parametrize("rung", [0, 1, 2])
def test_every_rung_is_the_full_bound_bit_for_bit(rung, dtype):
    """Routing whose groups end exactly at a rung runs at that rung,
    and output and every gradient (the three kernels, ``x``,
    ``top_w``) equal the full bound's bitwise: the rows past the rung
    are pad rows. And both are the oracle's."""
    experts, x = cast_layer(dtype)
    top_i, top_w, counts = routing_ending_at((10, 20, 30)[rung])
    on = lambda rows: value_and_grads(  # noqa: E731
        lambda e, x, i, w: moe.held_expert_ffn(e, x, i, w, HELD, rows,
                                               TILE, True),
        experts, x, top_i, top_w)
    out, stats, grads = on(LADDER)
    full_out, full_stats, full_grads = on(LADDER[-1])
    assert float(stats["rows_buffered"]) == LADDER[rung]
    assert float(full_stats["rows_buffered"]) == LADDER[-1]
    assert same_bits(out, full_out) and same_bits(grads, full_grads)
    assert float(stats["rows_held"]) == sum(counts)
    assert float(stats["rows_max"]) == max(counts)
    assert float(stats["rows_dropped"]) == 0
    want_out, _, want_grads = value_and_grads(
        lambda e, x, i, w: (moe.held_expert_ffn_reference(e, x, i, w, HELD),
                            None), experts, x, top_i, top_w)
    close(out, want_out, 1e-5, dtype)
    close(grads, want_grads, 2e-4, dtype)
    assert float(jnp.abs(grads[0]["gate"]["kernel"][4]).max()) == 0.0


@DTYPES
@pytest.mark.parametrize("rung", [0, 1])
def test_a_row_past_a_rung_takes_the_next_and_drops_nothing(rung, dtype):
    experts, x = cast_layer(dtype)
    top_i, top_w, counts = routing_ending_at((10, 20)[rung], extra=1)
    out, stats = moe.held_expert_ffn(experts, x, top_i, top_w, HELD, LADDER,
                                     TILE, True)
    assert float(stats["rows_buffered"]) == LADDER[rung + 1]
    assert float(stats["rows_held"]) == sum(counts)
    assert float(stats["rows_dropped"]) == 0
    full, _ = moe.held_expert_ffn(experts, x, top_i, top_w, HELD, LADDER[-1],
                                  TILE, True)
    assert same_bits(out, full)
    close(out, moe.held_expert_ffn_reference(experts, x, top_i, top_w, HELD),
          1e-5, dtype)


@DTYPES
def test_rows_past_the_full_bound_are_counted_as_without_a_ladder(
        routed, dtype):
    """The skewed routing against a bound of two tiles an expert: the
    clamp bites, so the last rung runs, and what is computed and what
    is counted as left out are a single bound's."""
    experts, x, top_i, top_w = routed
    experts, x = jax.tree.map(lambda a: a.astype(dtype), (experts, x))
    bound = 2 * len(HELD) * TILE
    out, stats = moe.held_expert_ffn(experts, x, top_i, top_w, HELD,
                                     (bound // 2, bound), TILE, True)
    alone, alone_stats = moe.held_expert_ffn(experts, x, top_i, top_w, HELD,
                                             bound, TILE, True)
    assert float(stats["rows_dropped"]) > 0
    assert {k: float(v) for k, v in stats.items()} == {
        k: float(v) for k, v in alone_stats.items()}
    assert float(stats["rows_buffered"]) == bound
    assert same_bits(out, alone)


@pytest.mark.parametrize("rows", [(TILE, 16 * TILE), (9 * TILE + 1, 16 * TILE),
                                  (16 * TILE, 8 * TILE), (8 * TILE, 8 * TILE)],
                         ids=["under-held-tiles", "part-tile", "descending",
                              "twice"])
def test_a_ladder_that_is_not_one_is_refused(routed, rows):
    experts, x, top_i, top_w = routed
    with pytest.raises(ValueError, match="ascending whole tiles"):
        moe.held_expert_ffn(experts, x, top_i, top_w, HELD, rows, TILE, True)


@DTYPES
def test_the_ladder_under_grad_in_a_remat_layer_scan(dtype):
    """The model's own nesting: ``jax.grad`` of a ``lax.scan`` over
    layers, each under ``apply_remat``, the branch inside. Layers that
    take different rungs give the single bound's loss and gradients."""
    experts, x = cast_layer(dtype)
    stacked = jax.tree.map(lambda a: jnp.stack([a, a * 0.5, -a]), experts)
    routing = [routing_ending_at(t, seed=t) for t in (10, 30, 20)]
    top_i = jnp.stack([r[0] for r in routing])
    top_w = jnp.stack([r[1] for r in routing])

    def run(rows):
        def block(x, p):
            experts, top_i, top_w = p
            y, stats = moe.held_expert_ffn(experts, x, top_i, top_w, HELD,
                                           rows, TILE, True)
            return x + y, stats["rows_buffered"]

        def loss(stacked, x, top_w):
            x, buffered = jax.lax.scan(apply_remat(block, "full"), x,
                                       (stacked, top_i, top_w))
            return (x.astype(jnp.float32) ** 2).sum(), buffered

        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
            stacked, x, top_w)

    (value, buffered), grads = run(LADDER)
    (full_value, _), full_grads = run(LADDER[-1])
    assert [float(b) for b in buffered] == [LADDER[0], LADDER[2], LADDER[1]]
    assert same_bits(value, full_value)
    # one compiled program a ladder: XLA may sum a row's products in
    # another order at another row count, so to a few roundings, not to a bit
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(full_grads)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(a - b).max()) <= (
            16 * float(jnp.finfo(dtype).eps) * float(jnp.abs(b).max()))
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
               and float(jnp.abs(g.astype(jnp.float32)).max()) > 0
               for g in jax.tree.leaves(grads))
