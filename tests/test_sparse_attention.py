"""``ops/sparse_attention.py`` at tiny sizes on the CPU: the indexer's
scores, the exact selection and its tie rule, the forward and both
gradients of ``selected_attention`` and of ``index_kl`` against dense
float32 ``jax.numpy`` written here from the equations, and the Pallas
kernels (interpreter) against the XLA forms.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import sparse_attention as sa
from dlrover_tpu.ops import trace_once

B, H, HK, T, D, J, E, K = 2, 4, 2, 96, 16, 4, 8, 20


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def operands(seed=0, whole=False, seq=T):
    r = np.random.RandomState(seed)
    rn = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)  # noqa: E731
    if whole:  # small whole numbers: every score exact, many ties
        ri = lambda lo, hi, *s: jnp.asarray(  # noqa: E731
            r.randint(lo, hi, s), jnp.float32)
        index = (ri(-2, 3, B, J, seq, E), ri(-2, 3, B, seq, E),
                 ri(-1, 3, B, seq, J))
    else:
        index = (rn(B, J, seq, E), rn(B, seq, E), rn(B, seq, J))
    return (rn(B, H, seq, D), rn(B, HK, seq, D), rn(B, HK, seq, D)), index


def dense_scores(qi, ki, w):
    """I[t, s] from the equation, a head at a time."""
    r = jnp.maximum(jnp.einsum("bjte,bse->bjts", qi, ki), 0.0)
    return jnp.einsum("btj,bjts->bts", w, r) / np.sqrt(J * E)


def brute_selection(scores, topk):
    """Each query's ``topk`` causal keys of largest score, ties to the
    lower position, by sorting (score, position) pairs in Python."""
    scores = np.asarray(scores)
    keep = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            order = sorted(range(t + 1), key=lambda s: (-scores[b, t, s], s))
            keep[b, t, order[:topk]] = True
    return keep


def dense_attention(q, k, v, keep):
    group = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhtd,bhsd->bhts", q, jnp.repeat(k, group, 1)) / np.sqrt(
        q.shape[-1])
    s = jnp.where(keep[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return (jnp.einsum("bhts,bhsd->bhtd", p, jnp.repeat(v, group, 1)),
            jax.nn.logsumexp(s, axis=-1), p)


def dense_kl(qi, ki, w, q, k, keep):
    """mean-free: a row's KL(pbar || softmax_S(I))."""
    *_, p = dense_attention(q, k, k, keep)
    pbar = jax.lax.stop_gradient(jnp.mean(p, axis=1))
    log_soft = jax.nn.log_softmax(
        jnp.where(keep, dense_scores(qi, ki, w), -jnp.inf), axis=-1)
    log_pbar = jnp.log(jnp.where(pbar > 0, pbar, 1.0))
    return jnp.sum(jnp.where(keep & (pbar > 0),
                             pbar * (log_pbar - log_soft), 0.0), axis=-1)


def close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), (
        np.abs(a - b).max(), np.abs(b).max())


def test_the_scores_are_the_equations():
    _, (qi, ki, w) = operands()
    close(sa.index_scores(qi, ki, w), dense_scores(qi, ki, w))
    assert sa.index_scale(16, 64) == 1 / 32


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["xla", "kernels"])
@pytest.mark.parametrize("whole", [True, False], ids=["ties", "floats"])
def test_exactly_the_topk_causal_keys_and_ties_to_the_lower_position(
        use_kernels, whole):
    _, (qi, ki, w) = operands(1, whole)
    chosen = sa.select_topk(qi, ki, w, K, use_kernels=use_kernels,
                            block_q=32, block_k=32)
    keep = np.asarray(sa.dense_mask(chosen.mask)) != 0
    assert (keep.sum(-1) == np.minimum(np.arange(T) + 1, K)).all()
    assert not np.triu(keep, 1).any()
    scores = dense_scores(qi, ki, w)
    if whole:  # exact arithmetic: the sets are the brute force's
        assert len(np.unique(np.asarray(scores))) < 200  # ties abound
        assert (keep == brute_selection(scores, K)).all()
    else:
        assert (keep == brute_selection(scores, K)).mean() > 0.999
    close(chosen.lse, jax.nn.logsumexp(
        jnp.where(keep, scores, -jnp.inf), axis=-1))


def test_the_kernel_and_the_xla_form_select_the_same_keys():
    _, (qi, ki, w) = operands(2, whole=True)
    ours = sa.select_topk(qi, ki, w, K, block_q=32, block_k=32)
    theirs = sa.select_topk(qi, ki, w, K, use_kernels=False)
    assert ours.mask.shape == (B, 3, T, 32)
    assert theirs.mask.shape == (B, 1, T, T)
    np.testing.assert_array_equal(sa.dense_mask(ours.mask),
                                  sa.dense_mask(theirs.mask))


@pytest.mark.parametrize("topk", [1, T, 4 * T])
def test_the_ends_of_the_range(topk):
    """One key a query (the best), and a ``topk`` the row never
    reaches: every causal key."""
    _, (qi, ki, w) = operands(3)
    for use_kernels in (False, True):
        keep = np.asarray(sa.dense_mask(sa.select_topk(
            qi, ki, w, topk, use_kernels=use_kernels, block_q=32,
            block_k=32).mask)) != 0
        if topk == 1:
            best = np.argmax(np.where(
                np.tril(np.ones((T, T), bool)),
                np.asarray(dense_scores(qi, ki, w)), -np.inf), axis=-1)
            assert (keep.sum(-1) == 1).all()
            assert (np.argmax(keep, -1) == best).all()
        else:
            assert (keep == np.tril(np.ones((T, T), bool))).all()


def test_the_selection_has_no_gradient():
    _, (qi, ki, w) = operands(4)
    for use_kernels in (False, True):
        g = jax.grad(lambda qi: jnp.sum(sa.select_topk(
            qi, ki, w, K, use_kernels=use_kernels, block_q=32,
            block_k=32).lse))(qi)
        assert not np.asarray(g).any()


def backward_kernels():
    """The names of the selected attention's backward kernels traced so
    far (``shared_call``'s keys lead with the kernel's name)."""
    return {key[0] for key in trace_once._SHARED} & {
        "dsa_attn_bwd", "dsa_attn_dkv", "dsa_attn_dq"}


@pytest.mark.parametrize("path", ["xla", "kernels", "kernels-pair"])
def test_selected_attention_forward_and_gradients(path, monkeypatch):
    use_kernels = path != "xla"
    if path == "kernels-pair":
        # rows whose state is over the budget keep ``dsa_attn_dkv`` and
        # ``dsa_attn_dq``: here every row is, by a budget of nothing
        monkeypatch.setattr(sa, "_ATTN_ROW_STATE_BUDGET_BYTES", 0)
    monkeypatch.setattr(trace_once, "_SHARED", {})
    (q, k, v), (qi, ki, w) = operands(5)
    chosen = sa.select_topk(qi, ki, w, K, use_kernels=use_kernels,
                            block_q=32, block_k=32)
    keep = sa.dense_mask(chosen.mask) != 0

    def ours(q, k, v):
        out, lse = sa.selected_attention(q, k, v, chosen,
                                         use_kernels=use_kernels, block_q=32)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(jnp.sin(lse)), (out, lse)

    def plain(q, k, v):
        out, lse, _ = dense_attention(q, k, v, keep)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(jnp.sin(lse)), (out, lse)

    (_, (out, lse)), grads = jax.value_and_grad(
        ours, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, (out_p, lse_p)), grads_p = jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    close(out, out_p)
    close(lse, lse_p)
    for a, b in zip(grads, grads_p):
        close(a, b, 5e-5)
    assert backward_kernels() == {
        "xla": set(), "kernels": {"dsa_attn_bwd"},
        "kernels-pair": {"dsa_attn_dkv", "dsa_attn_dq"}}[path]


def recency_selection(seq, topk):
    """An indexer that prefers recent keys: with ``topk`` under a tile,
    the far causal tiles hold no selected pair."""
    pos = jnp.arange(seq, dtype=jnp.float32)
    qi = jnp.ones((B, 1, seq, 1))
    ki = jnp.broadcast_to(pos[None, :, None], (B, seq, 1))
    w = jnp.ones((B, seq, 1))
    return sa.select_topk(qi, ki, w, topk, block_q=32, block_k=32)


@pytest.mark.parametrize("block_q", [32, 16, 64],
                         ids=["square", "half", "twice"])
@pytest.mark.parametrize("selection", ["learned", "recency"])
def test_the_one_backward_kernel_is_the_pair_bit_for_bit(
        selection, block_q, monkeypatch):
    """dQ, dK and dV of ``dsa_attn_bwd`` against ``dsa_attn_dkv`` and
    ``dsa_attn_dq`` at two query heads a KV head, a cotangent on the
    logsumexp too, query blocks of, under and over the key tile of 32,
    and where tiles are skipped."""
    seq = 128
    (q, k, v), (qi, ki, w) = operands(11, seq=seq)
    assert q.shape[1] == 2 * k.shape[1]
    chosen = (recency_selection(seq, 8) if selection == "recency" else
              sa.select_topk(qi, ki, w, K, block_q=32, block_k=32))
    skipped = sa.selection_counters(chosen, H, block_q)["dsa_tiles_skipped"]
    assert (float(skipped) > 0) == (selection == "recency")

    def grads():
        def loss(q, k, v):
            out, lse = sa.selected_attention(q, k, v, chosen,
                                             block_q=block_q)
            return jnp.sum(out * jnp.cos(out)) + jnp.sum(jnp.sin(lse))

        monkeypatch.setattr(trace_once, "_SHARED", {})
        result = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return result, backward_kernels()

    one, ran = grads()
    assert ran == {"dsa_attn_bwd"}
    monkeypatch.setattr(sa, "_ATTN_ROW_STATE_BUDGET_BYTES", 0)
    pair, ran = grads()
    assert ran == {"dsa_attn_dkv", "dsa_attn_dq"}
    for a, b in zip(one, pair):
        assert np.abs(np.asarray(b)).max() > 1e-3
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("seq, fits", [(8192, True), (16384, True),
                                       (32768, False)])
def test_the_backward_is_one_kernel_where_the_rows_state_fits(seq, fits):
    """The arithmetic alone: a query head's dQ and a KV head's dK and
    dV in float32 and their bf16 output blocks twice, 48 MiB at rows of
    16,384 and widths of 128, the budget."""
    state = sa._win_row_state_bytes(seq, 128, 128, 2)
    assert state == (4 + 2 * 2) * seq * 3 * 128 == seq * 3072
    assert sa._ATTN_ROW_STATE_BUDGET_BYTES == 48 * 1024 * 1024
    assert (state <= sa._ATTN_ROW_STATE_BUDGET_BYTES) == fits
    # a width under the lanes occupies them all
    assert sa._win_row_state_bytes(seq, 64, 16, 2) == state


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["xla", "kernels"])
def test_index_kl_forward_and_gradients(use_kernels):
    (q, k, v), (qi, ki, w) = operands(6)
    chosen = sa.select_topk(qi, ki, w, K, use_kernels=use_kernels,
                            block_q=32, block_k=32)
    keep = sa.dense_mask(chosen.mask) != 0
    _, lse, _ = dense_attention(q, k, v, keep)
    # uneven rows' weights, and a cotangent on the scalar that is not 1
    weight = jnp.broadcast_to(jnp.arange(T, dtype=jnp.float32) / T, (B, T))

    def ours(qi, ki, w, q, k):
        return 1.7 * sa.index_kl(
            qi, ki, w, q, k, lse, chosen, use_kernels=use_kernels,
            block_q=32, weight=weight)

    def plain(qi, ki, w, q, k):
        return 1.7 * jnp.sum(weight * dense_kl(qi, ki, w, q, k, keep))

    value, grads = jax.value_and_grad(ours, argnums=(0, 1, 2, 3, 4))(
        qi, ki, w, q, k)
    value_p, grads_p = jax.value_and_grad(plain, argnums=(0, 1, 2, 3, 4))(
        qi, ki, w, q, k)
    assert value.shape == () and value.dtype == jnp.float32
    assert float(value) == pytest.approx(float(value_p), rel=1e-5)
    assert float(value) > 0
    for a, b in zip(grads[:3], grads_p[:3]):
        assert np.abs(np.asarray(b)).max() > 1e-3
        close(a, b, 5e-5)
    # the main attention's q and k are data to the indexer's loss
    for a in grads[3:]:
        assert not np.asarray(a).any()


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["xla", "kernels"])
def test_the_default_weight_is_the_mean_and_the_weights_are_data(use_kernels):
    (q, k, v), (qi, ki, w) = operands(6)
    chosen = sa.select_topk(qi, ki, w, K, block_q=32, block_k=32)
    keep = sa.dense_mask(chosen.mask) != 0
    _, lse, _ = dense_attention(q, k, v, keep)
    how = dict(use_kernels=use_kernels, block_q=32)
    mean = sa.index_kl(qi, ki, w, q, k, lse, chosen, **how)
    assert float(mean) == pytest.approx(
        float(jnp.mean(dense_kl(qi, ki, w, q, k, keep))), rel=1e-5)
    even = jnp.full((B, T), 1.0 / (B * T))
    assert float(sa.index_kl(qi, ki, w, q, k, lse, chosen, weight=even,
                             **how)) == float(mean)
    # a row's weight gets no gradient
    to_weight = jax.grad(lambda a: sa.index_kl(
        qi, ki, w, q, k, lse, chosen, weight=a, **how))(even)
    assert not np.asarray(to_weight).any()


def test_the_primal_is_the_forward_rules_value_bit_for_bit():
    """No gradient asked (``eval_step``, ``apply_layers``): the same
    kernel body with its gradient half off, the same value; and one
    kernel name for both."""
    (q, k, v), (qi, ki, w) = operands(6)
    chosen = sa.select_topk(qi, ki, w, K, block_q=32, block_k=32)
    _, lse = sa.selected_attention(q, k, v, chosen, block_q=32)
    weight = jnp.broadcast_to(jnp.arange(T, dtype=jnp.float32) / T, (B, T))

    def loss(qi):
        return sa.index_kl(qi, ki, w, q, k, lse, chosen, block_q=32,
                           weight=weight)

    primal = loss(qi)
    ruled, _ = jax.value_and_grad(loss)(qi)
    assert float(primal) == float(ruled) > 0
    mine = {key for key in trace_once._SHARED
            if key[0].startswith("dsa_index_kl")}
    assert {key[0] for key in mine} == {"dsa_index_kl"}
    # the gradient half is static: on in the rule, off in the primal
    assert {key[2][-1] for key in mine} == {False, True}
    text = jax.jit(jax.grad(loss)).lower(qi).as_text()
    assert "dsa_index_kl_fwd" not in text and "dsa_index_kl_bwd" not in text


def test_what_the_forward_rule_keeps():
    """The three gradients are the rule's residuals under their names,
    in their operands' dtypes (the key head's too, summed in float32
    inside the kernel), and ``index_kept_bytes`` is their size."""
    from jax._src.ad_checkpoint import saved_residuals

    (q, k, v), (qi, ki, w) = operands(6)
    chosen = sa.select_topk(qi, ki, w, K, block_q=32, block_k=32)
    _, lse = sa.selected_attention(q, k, v, chosen, block_q=32)
    qi, ki, w = (a.astype(jnp.bfloat16) for a in (qi, ki, w))

    def loss(qi, ki, w):
        return sa.index_kl(qi, ki, w, q, k, lse, chosen, block_q=32)

    kept = [(value.shape, value.dtype) for value, why in saved_residuals(
        jax.checkpoint(
            loss, policy=jax.checkpoint_policies.save_only_these_names(
                *sa.INDEX_KEPT_NAMES)), qi, ki, w)
        if why.startswith("named")]
    assert kept == [((B, J, T, E), jnp.bfloat16), ((B, T, E), jnp.bfloat16),
                    ((B, T, J), jnp.bfloat16)]
    assert sa.index_kept_bytes(B, J, T, E, jnp.bfloat16) == sum(
        2 * int(np.prod(shape)) for shape, _ in kept)
    # keye's layer and axk2's, by arithmetic (ISSUE 52)
    assert sa.index_kept_bytes(1, 16, 16384, 64, jnp.bfloat16) == (
        33_554_432 + 2_097_152 + 524_288)
    assert sa.index_kept_bytes(1, 64, 8192, 128, jnp.bfloat16) == (
        134_217_728 + 2_097_152 + 1_048_576)


def test_the_kl_is_zero_where_the_indexer_is_the_attention():
    """One query head whose scores the indexer reproduces exactly: the
    two distributions over the selected keys are one."""
    r = np.random.RandomState(7)
    x = jnp.asarray(r.randn(1, 1, 32, 8), jnp.float32)
    y = jnp.abs(jnp.asarray(r.randn(1, 32, 8), jnp.float32))
    x = jnp.abs(x)  # relu(q . k) = q . k
    w = jnp.full((1, 32, 1), np.sqrt(8.0))  # undo the two scales
    chosen = sa.select_topk(x, y, w, 8, use_kernels=False)
    q, k = x * np.sqrt(8.0), y[:, None]
    _, lse = sa.selected_attention(q, k, k, chosen, use_kernels=False)
    for use_kernels in (False, True):
        # every row's KL is at least 0: their sum is 0 where each is
        kl = sa.index_kl(x, y, w, q, k, lse, chosen,
                         use_kernels=use_kernels, block_q=32,
                         weight=jnp.ones((1, 32)))
        assert abs(float(kl)) < 32 * 1e-5


def test_a_tile_with_no_selected_pair_is_skipped_and_counted():
    """An indexer that prefers recent keys: with ``topk`` under a tile,
    the far causal tiles hold no selected pair; the kernels skip them
    and the result is the dense one."""
    seq, topk = 128, 8
    (q, k, v), _ = operands(8, seq=seq)
    chosen = recency_selection(seq, topk)
    keep = np.asarray(sa.dense_mask(chosen.mask)) != 0
    t = np.arange(seq)
    assert (keep == ((t[None] <= t[:, None])
                     & (t[None] > t[:, None] - topk))).all()
    counters = sa.selection_counters(chosen, H, 32)
    # of the 10 causal tiles a row the diagonal and the one before it
    # hold a selected pair: 4 + 3 visited, 3 skipped
    assert float(counters["dsa_tiles_visited"]) == B * H * 7
    assert float(counters["dsa_tiles_skipped"]) == B * H * 3
    assert float(counters["dsa_pairs_selected"]) == B * (36 + 120 * 8)
    assert float(counters["dsa_pairs_causal"]) == B * seq * (seq + 1) // 2
    # a query's selected keys a key tile: what the flags are sums of
    np.testing.assert_array_equal(
        chosen.counts, keep.reshape(B, seq, 4, 32).sum(-1).transpose(0, 2, 1))
    out, lse = sa.selected_attention(q, k, v, chosen, block_q=32)
    out_p, lse_p, _ = dense_attention(q, k, v, jnp.asarray(keep))
    close(out, out_p)
    close(lse, lse_p)


def test_rows_the_tiles_do_not_divide_take_the_largest_divisor_or_fail():
    (q, k, v), (qi, ki, w) = operands(9, seq=80)
    chosen = sa.select_topk(qi, ki, w, K, block_q=48, block_k=48)
    assert chosen.mask.shape == (B, 2, 80, 40)  # 40 divides 80, 48 not
    plain = sa.select_topk(qi, ki, w, K, use_kernels=False)
    np.testing.assert_array_equal(sa.dense_mask(chosen.mask),
                                  sa.dense_mask(plain.mask))
    out, _ = sa.selected_attention(q, k, v, chosen, block_q=48)
    out_p, _ = sa.selected_attention(q, k, v, plain, use_kernels=False)
    close(out, out_p)
    # on the chip a tile rides the lanes: whole 128s or the whole row
    with pytest.raises(ValueError, match="multiple of 128"):
        sa.select_topk(qi, ki, w, K, block_q=40, block_k=40,
                       interpret=False)
    # the largest divisor of 80 within 32 is 20, no whole sublanes
    with pytest.raises(ValueError, match="no legal block tiling"):
        sa.select_topk(qi, ki, w, K, block_q=32, block_k=32)


def test_a_kernel_is_traced_once_a_process():
    """Two layers' calls with the same shapes share one jitted callee
    (``shared_call``): the second adds no entry."""
    (q, k, v), (qi, ki, w) = operands(10, seq=64)
    chosen = sa.select_topk(qi, ki, w, K, block_q=32, block_k=32)

    def grad(q, chosen):
        return jax.grad(lambda q: jnp.sum(sa.selected_attention(
            q, k, v, chosen, block_q=32)[0]))(q)

    grad(q, chosen)
    once = dict(trace_once._SHARED)
    assert {"dsa_index_select", "dsa_attn_fwd", "dsa_attn_bwd"} <= {
        key[0] for key in once}
    again = sa.select_topk(qi + 1, ki, w, K, block_q=32, block_k=32)
    grad(q + 1, again)
    assert trace_once._SHARED == once


# -- the latent layout --------------------------------------------------------

DN, DR, DV = 16, 8, 12  # nope and rope score parts, narrower values


def latent_operands(seed=9):
    r = np.random.RandomState(seed)
    rn = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)  # noqa: E731
    return (rn(B, H, T, DN), rn(B, H, T, DR), rn(B, H, T, DN),
            rn(B, 1, T, DR), rn(B, H, T, DV))


def two_part_scores(q_nope, q_rope, k_nope, k_rope, scale):
    """``q_nope_h . k_nope_h + q_rope_h . k_rope``: the rotary key ONE
    head for all, written from the equation."""
    return (jnp.einsum("bhtd,bhsd->bhts", q_nope, k_nope)
            + jnp.einsum("bhtd,bsd->bhts", q_rope, k_rope[:, 0])) * scale


def two_part_attention(q_nope, q_rope, k_nope, k_rope, v, keep, scale):
    s = jnp.where(keep[:, None],
                  two_part_scores(q_nope, q_rope, k_nope, k_rope, scale),
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return (jnp.einsum("bhts,bhsd->bhtd", p, v),
            jax.nn.logsumexp(s, axis=-1), p)


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["xla", "kernels"])
def test_latent_selected_attention_forward_and_gradients(use_kernels):
    """Two-part scores, one KV head a query head, values narrower than
    the scores: outputs, logsumexp and the five gradients (the shared
    rotary key's the sum over the heads) against dense ``jax.numpy``,
    and against ``selected_attention_reference`` on the parts side by
    side."""
    parts = latent_operands()
    _, (qi, ki, w) = operands(5)
    chosen = sa.select_topk(qi, ki, w, K, use_kernels=use_kernels,
                            block_q=32, block_k=32)
    keep = sa.dense_mask(chosen.mask) != 0
    scale = 0.3

    def ours(*parts):
        out, lse = sa.selected_attention_latent(
            *parts, chosen, scale, use_kernels=use_kernels, block_q=32)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(jnp.sin(lse)), (out, lse)

    def plain(*parts):
        out, lse, _ = two_part_attention(*parts, keep, scale)
        return jnp.sum(out * jnp.cos(out)) + jnp.sum(jnp.sin(lse)), (out, lse)

    every = tuple(range(5))
    (_, (out, lse)), grads = jax.value_and_grad(
        ours, argnums=every, has_aux=True)(*parts)
    (_, (out_p, lse_p)), grads_p = jax.value_and_grad(
        plain, argnums=every, has_aux=True)(*parts)
    assert out.shape == (B, H, T, DV) and grads[3].shape == (B, 1, T, DR)
    close(out, out_p)
    close(lse, lse_p)
    for a, b in zip(grads, grads_p):
        assert np.abs(np.asarray(b)).max() > 1e-3
        close(a, b, 5e-5)
    q, k = sa.latent_operands(*parts[:4])
    assert q.shape == k.shape == (B, H, T, DN + DR)
    out_r, lse_r = sa.selected_attention_reference(
        q, k, parts[4], chosen.mask, scale)
    close(out, out_r)
    close(lse, lse_r)


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["xla", "kernels"])
def test_latent_index_kl_forward_and_gradients(use_kernels):
    """The indexer's loss against the head-mean of two-part
    probabilities: its value, its gradient to the indexer's three
    operands, and none to the attention's four."""
    parts = latent_operands(10)
    _, (qi, ki, w) = operands(6)
    chosen = sa.select_topk(qi, ki, w, K, use_kernels=use_kernels,
                            block_q=32, block_k=32)
    keep = sa.dense_mask(chosen.mask) != 0
    scale = 0.3
    _, lse, p = two_part_attention(*parts, keep, scale)
    # uneven rows' weights, and a cotangent on the scalar that is not 1
    weight = jnp.broadcast_to(jnp.arange(T, dtype=jnp.float32) / T, (B, T))

    def ours(qi, ki, w, *main):
        return 0.6 * sa.index_kl_latent(
            qi, ki, w, *main, lse, chosen, scale, use_kernels=use_kernels,
            block_q=32, weight=weight)

    def plain(qi, ki, w):
        pbar = jnp.mean(p, axis=1)
        log_soft = jax.nn.log_softmax(
            jnp.where(keep, dense_scores(qi, ki, w), -jnp.inf), axis=-1)
        log_pbar = jnp.log(jnp.where(pbar > 0, pbar, 1.0))
        return 0.6 * jnp.sum(weight * jnp.sum(jnp.where(
            keep & (pbar > 0), pbar * (log_pbar - log_soft), 0.0), axis=-1))

    value, grads = jax.value_and_grad(ours, argnums=tuple(range(7)))(
        qi, ki, w, *parts[:4])
    value_p, grads_p = jax.value_and_grad(plain, argnums=(0, 1, 2))(
        qi, ki, w)
    assert float(value) == pytest.approx(float(value_p), rel=1e-5)
    assert float(value) > 0
    for a, b in zip(grads[:3], grads_p):
        assert np.abs(np.asarray(b)).max() > 1e-3
        close(a, b, 5e-5)
    for a in grads[3:]:
        assert not np.asarray(a).any()
