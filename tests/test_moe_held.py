"""Gated experts of which this chip holds a set (``ops/moe.py``:
``sigmoid_topk_routing``, ``held_expert_ffn`` over
``grouped_matmul`` with ``num_tiles``) against the einsum oracle, in the
interpreter: skewed routing, an expert with no rows, rows past the
bound counted, the tiles past the last group skipped."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import moe
from dlrover_tpu.ops.grouped_matmul import grouped_matmul

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


def test_held_experts_match_the_oracle_under_skew(routed):
    experts, x, top_i, top_w = routed
    bound = moe.held_row_bound(TOKENS, TOP_K, EXPERTS, len(HELD), 4.0, TILE)
    out, stats = moe.held_expert_ffn(experts, x, top_i, top_w, HELD, bound,
                                     TILE, True)
    want = moe.held_expert_ffn_reference(experts, x, top_i, top_w, HELD)
    assert float(jnp.abs(out - want).max()) < 1e-5
    per_expert = [(np.asarray(top_i) == e).sum() for e in HELD]
    assert per_expert[1] == TOKENS and per_expert[4] == 0  # 9 and 12
    assert float(stats["rows_held"]) == sum(per_expert)
    assert float(stats["rows_max"]) == TOKENS
    assert float(stats["rows_dropped"]) == 0


def test_held_experts_gradients_match_the_oracle(routed):
    """Through the three grouped matmuls, forward, dx and dW; the
    expert with no rows gets a gradient of zeros, not garbage."""
    experts, x, top_i, top_w = routed
    bound = moe.held_row_bound(TOKENS, TOP_K, EXPERTS, len(HELD), 4.0, TILE)
    kernel = lambda e, x, w: (moe.held_expert_ffn(  # noqa: E731
        e, x, top_i, w, HELD, bound, TILE, True)[0] ** 2).sum()
    oracle = lambda e, x, w: (moe.held_expert_ffn_reference(  # noqa: E731
        e, x, top_i, w, HELD) ** 2).sum()
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
