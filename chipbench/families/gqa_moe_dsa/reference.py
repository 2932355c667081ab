"""A plain reference for the language model of Keye-VL-2.0-30B-A3B (the
model's public ``config.json`` as the ``model-configs`` catalog quotes
it, and ISSUE 48's equations from it). Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: the
indexer's scores and the attention as dense ``[rows, seq]`` arrays a
block of query rows, the selection by a sort, the experts as a loop
over the experts held here; no kernel, no batching, no sharding,
nothing imported from the program.

Layer, pre-norm residual, RMSNorm with eps ``rms_norm_eps`` and float32
statistics, no bias (``u``, ``z`` [T, hidden]; ``g(h)`` the KV head of
query head ``h``)::

    u = RMSNorm_in(x)
    q_h = rot3(RMSNorm_q(u W_q)_h)                 32 heads of 128
    k_g = rot3(RMSNorm_k(u W_k)_g),  v_g = (u W_v)_g    4 KV heads
        RMSNorm_q / _k: over each head's 128, one learned scale each
        rot3: rotate-half pairs (i, i + 64); pair i turns by
        pos[a(i)] * theta^(-i/64), a(i) = 0, 1, 2 by mrope_section
    qI_j = rot(u W_qI)_j  16 heads of 64;  kI = rot(u W_kI)  ONE head;
    w = u W_w  16 wide
        rot: plain rotary over pos[0] on all 32 pairs, the same theta
    I[t, s] = (16 * 64)^-1/2 sum_j w[t, j] relu(qI_j[t] . kI[s]),  s <= t
    S_t = every s <= t where t < topk, else the topk keys s <= t of
          largest I[t, s], ties to the lower s
    a_h[t] = sum_{s in S_t} softmax_{s in S_t}(q_h[t] . k_g(h)[s]
                                               / sqrt(128)) v_g(h)[s]
    x' = x + concat_h(a_h) W_o
    z = RMSNorm_post(x');  r = z W_r  (all 128 logits)
    top = the 8 largest;  p = softmax over those 8 (norm_topk_prob)
    y = sum_{e in top, e held here} p_e W_down_e (silu(W_gate_e z)
                                                  * (W_up_e z))
    x'' = x' + y

then the final RMSNorm and the untied head. The loss is ``L_LM + sum
over layers of L_I``, ``L_I = mean_t KL(pbar[t, .] || softmax_{S_t}(I[t,
.]))``, ``pbar`` the mean over the 32 query heads of the attention's
probabilities. The indexer reads ``stop_gradient(u)`` and ``pbar`` is
under ``stop_gradient`` too (the selection, a sort, has no gradient of
its own): JAX's differentiation of this file's functions then gives
the indexer's three matrices their gradient from ``L_I`` alone and
every other leaf its gradient from ``L_LM`` alone, which is what
``tests/test_gqa_moe_dsa.py`` holds the program's gradients to.

The experts held here are ``deployment.experts_held`` of
``deployment.published_num_experts``; what the others would add is left
out (the chip's share of a layer, ``model-configs`` section 4). The
vision tower is not computed: the configuration is the language model,
and on text the three position rows are equal.

It runs one layer at a time, so that it fits beside the training state
of a chip: the caller hands the layers over as an iterator of
dictionaries. Scores are computed ``ROW_BLOCK`` query rows at a time.
``layer``'s ``given`` hands it the selection and the expert choice of
another computation (the program's), so that a pair or an expert whose
score lies at the boundary does not count as an error of the
arithmetic.

Departures from the published code, none in the mathematics: weight
matrices are [in, out] (``x @ w``); what the config leaves open (the
norms on q and k, the indexer's input, rotary and scale, the loss) is
the configuration file's ``assumed``.
"""

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 256  # query rows scored at a time


def mm(a, b):
    """Every matrix product of the reference goes through here (float32
    at the highest precision; a test that shows what lower precision
    would do replaces it)."""
    return jnp.matmul(a, b)


def act(x):
    """The gate's activation: the experts are SwiGLU."""
    return jax.nn.silu(x)


def index_act(x):
    """The indexer's activation on a head's scores."""
    return jax.nn.relu(x)


def softmax_dtype():
    """The precision the attention's softmax runs in."""
    return jnp.float32


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def sections(model):
    """The position row of each rotary pair of a main head."""
    rows = []
    for row, count in enumerate(model["rope_scaling"]["mrope_section"]):
        rows += [row] * count
    return jnp.asarray(rows)


def rotary_tables(pos_of_pair, dim, theta):
    """cos and sin [seq, dim/2] from each pair's own positions
    ``pos_of_pair`` [seq, dim/2]: pair ``i`` turns at
    ``theta^(-2i/dim)``."""
    inv_freq = theta ** (
        -2.0 * jnp.arange(dim // 2, dtype=jnp.float32) / dim)
    angles = pos_of_pair * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """``x`` [seq, heads, d]; pair ``i`` is (x[..., i], x[..., i + d/2])."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def select(scores, start, topk):
    """``scores`` [rows, seq] of queries ``start ..``: the boolean
    selection, a stable sort by descending score (ties to the lower
    position) cut at ``topk``, causal keys only."""
    rows, seq = scores.shape
    t = start + jnp.arange(rows)[:, None]
    s = jnp.arange(seq)[None, :]
    causal = s <= t
    if topk >= seq:
        return causal
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                        stable=True)
    last = order[:, topk - 1:topk]  # where the topk-th of the sort sits
    least = jnp.take_along_axis(scores, last, axis=-1)
    chosen = (scores > least) | ((scores == least) & (s <= last))
    return causal & ((t < topk) | chosen)


def attention(u, w, model, pos, given=None):
    """``u`` [seq, hidden], already normed; ``pos`` [3, seq]. Gives
    (what the attention adds to the residual, ``W_o`` applied, the
    indexer's loss, the selection [seq, seq] bool); ``given`` is a
    selection to attend under."""
    seq = u.shape[0]
    heads, kv_heads, hd = (model["num_attention_heads"],
                           model["num_key_value_heads"], model["head_dim"])
    sa, eps = model["sa_config"], model["rms_norm_eps"]
    ih, ihd, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                     sa["topk"])
    theta = model["rope_theta"]
    q = rms_norm(mm(u, w["wq"]).reshape(seq, heads, hd), w["q_norm"], eps)
    k = rms_norm(mm(u, w["wk"]).reshape(seq, kv_heads, hd), w["k_norm"],
                 eps)
    v = mm(u, w["wv"]).reshape(seq, kv_heads, hd)
    pos = jnp.asarray(pos, jnp.float32)
    cos, sin = rotary_tables(pos[sections(model)].T, hd, theta)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    u = jax.lax.stop_gradient(u)  # the indexer trains on its own loss
    cos_i, sin_i = rotary_tables(pos[0][:, None], ihd, theta)
    qi = rotate(mm(u, w["index_wq"]).reshape(seq, ih, ihd), cos_i, sin_i)
    ki = rotate(mm(u, w["index_wk"]).reshape(seq, 1, ihd), cos_i,
                sin_i)[:, 0]
    wi = mm(u, w["index_ww"])
    block = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    serves = heads // kv_heads
    k_rep = jnp.repeat(k, serves, axis=1).transpose(1, 2, 0)  # [h, hd, seq]
    v_rep = jnp.repeat(v, serves, axis=1).transpose(1, 0, 2)  # [h, seq, hd]

    def rows(args):
        start, chosen = args
        cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, start, block)
        per_head = index_act(jnp.einsum("tje,se->tjs", cut(qi), ki,
                                        precision="highest"))
        scores = jnp.einsum("tj,tjs->ts", cut(wi), per_head,
                            precision="highest") / math.sqrt(ih * ihd)
        keep = select(scores, start, topk) if chosen is None else chosen
        logits = mm(cut(q).transpose(1, 0, 2), k_rep) / math.sqrt(hd)
        probs = jax.nn.softmax(
            jnp.where(keep[None], logits, -jnp.inf).astype(softmax_dtype()),
            axis=-1).astype(jnp.float32)  # [h, rows, seq]
        out = mm(probs, v_rep).transpose(1, 0, 2).reshape(block, heads * hd)
        pbar = jax.lax.stop_gradient(jnp.mean(probs, axis=0))
        log_soft = jax.nn.log_softmax(
            jnp.where(keep, scores, -jnp.inf), axis=-1)
        kl = jnp.sum(jnp.where(
            keep & (pbar > 0.0),
            pbar * (jnp.log(jnp.where(pbar > 0.0, pbar, 1.0)) - log_soft),
            0.0), axis=-1)
        return out, kl, keep

    starts = jnp.arange(0, seq, block)
    if given is None:
        out, kl, keep = jax.lax.map(lambda s: rows((s, None)), starts)
    else:
        out, kl, keep = jax.lax.map(
            rows, (starts, given.reshape(seq // block, block, seq)))
    return (mm(out.reshape(seq, heads * hd), w["wo"]),
            jnp.mean(kl.reshape(seq)), keep.reshape(seq, seq))


def route(z, w_router, model):
    """(selected experts [seq, k], their weights [seq, k]) from the
    router's input ``z`` [seq, hidden]."""
    logits = mm(z, w_router)
    top_l, top_i = jax.lax.top_k(logits, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        return top_i, jax.nn.softmax(top_l, axis=-1)
    return top_i, jnp.take_along_axis(
        jax.nn.softmax(logits, axis=-1), top_i, axis=-1)


def gates_of(z, w_router, top_i, model):
    """The weights of the experts ``top_i`` that another computation
    selected, from this one's logits."""
    top_l = jnp.take_along_axis(mm(z, w_router), top_i, axis=-1)
    if not model["norm_topk_prob"]:
        raise ValueError("given experts are weighed by a softmax over "
                         "the selected logits")
    return jax.nn.softmax(top_l, axis=-1)


def swiglu(z, w):
    return mm(act(mm(z, w["w_gate"])) * mm(z, w["w_up"]), w["w_down"])


def expert_layer(z, w, top_i, gate, held):
    """What the experts ``held`` give ``z`` [seq, hidden] under the
    routing ``(top_i, gate)``; ``w`` holds them stacked in that order."""
    out = jnp.zeros_like(z)
    for slot, expert in enumerate(held):
        g = jnp.sum(jnp.where(top_i == expert, gate, 0.0), axis=-1)
        mine = jax.tree.map(lambda a: a[slot], w)
        out = out + g[:, None] * swiglu(z, mine)
    return out


def layer(x, w, model, pos, given=None):
    """One layer of ``x`` [seq, hidden]: (its output, the indexer's
    loss, the selection [seq, seq], the selected experts [seq, k]).
    ``given``: (a selection, selected experts) to compute under."""
    eps = model["rms_norm_eps"]
    u = rms_norm(x, w["input_norm"], eps)
    a, kl, keep = attention(u, w["attn"], model, pos,
                            None if given is None else given[0])
    x = x + a
    z = rms_norm(x, w["post_norm"], eps)
    if given is None:
        top_i, gate = route(z, w["w_router"], model)
    else:
        top_i = given[1]
        gate = gates_of(z, w["w_router"], top_i, model)
    y = expert_layer(z, w["experts"], top_i, gate,
                     model["deployment"]["experts_held"])
    return x + y, kl, keep, top_i


def head_loss(h, head, labels):
    """Mean cross entropy of ``labels`` under logits ``h @ head``."""
    logits = mm(h, head)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss(model, ids, labels, table, layers, final_norm, head, pos=None):
    """``(L_LM, sum over layers of L_I, final normed hidden states [seq,
    hidden])`` of ``labels`` [seq] given ``ids`` [seq]; the training
    loss is ``L_LM + assumed.index_loss_weight * sum L_I``. ``table``
    [vocab, hidden]; ``head`` [hidden, vocab]; ``pos`` [3, seq] (None:
    text, 0..seq-1 on every row); ``layers`` yields one dictionary a
    layer, in order: ``input_norm``, ``attn`` (``wq``, ``wk``, ``wv``,
    ``wo``, ``q_norm``, ``k_norm``, ``index_wq``, ``index_wk``,
    ``index_ww``), ``post_norm``, ``w_router`` and ``experts``
    (``w_gate``, ``w_up``, ``w_down`` with the held experts stacked in
    ``experts_held``'s order); every array is cast to float32 here.
    (``job.py`` steps ``layer`` itself, beside the program's layers.)"""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    depth, seq = model["num_hidden_layers"], len(ids)
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(seq), (3, seq))
    with jax.default_matmul_precision("highest"):
        run = jax.jit(lambda x, w, pos: layer(x, w, model, pos))
        h = jnp.asarray(table[ids], jnp.float32)
        kl_sum, count = 0.0, 0
        for w in layers:
            h, kl, _, _ = run(h, f32(w), pos)
            kl_sum, count = kl_sum + kl, count + 1
        assert count == depth, f"{count} layers were handed over"
        h = jax.jit(lambda x, s: rms_norm(x, s, model["rms_norm_eps"]))(
            h, f32(final_norm))
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        lm = jax.jit(head_loss)(h, f32(head), jnp.asarray(labels))
    return lm, kl_sum, h
