"""A plain reference for Xing4.0-29B-A4B's decoder (the model's public
``config.json``): DeepSeek-V3's block (multi-head latent attention,
leading dense layers, then one shared plus routed gated experts under a
sigmoid router whose selection takes a per-expert bias), a residual of
``hc_mult`` streams mixed by manifold-constrained hyper-connections
(DeepSeek, arXiv:2512.24880), and one multi-token-prediction module
(DeepSeek-V3, arXiv:2412.19437 section 2.2), as ISSUE 36 wrote the
equations down. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: attention as a dense
masked softmax a head, the experts as a loop over the experts held
here, the hyper-connection's 4 x 4 mappings a token as ``[seq, n, n]``
arrays; no kernel, no sorting, no batching, no sharding, nothing
imported from the program or from another family.

Streams ``X`` [seq, n, C], ``n = hc_mult``. Entry: every stream is the
token's embedding. Exit: the streams are summed, then the final
RMSNorm and the head. A layer is two sublayers ``F``, ``MLA(RMSNorm(
.))`` and ``FFN(RMSNorm(.))``, each behind its own hyper-connection
(``connect``)::

    u      = RMSNorm_nC(vec(X))                                eps hc_eps
    H_pre  = sigmoid(a_pre * (u phi_pre) + b_pre)                  R^n
    H_post = 2 sigmoid(a_post * (u phi_post) + b_post)             R^n
    M_0    = exp(clip(a_res * mat(u phi_res) + b_res, lo, hi))     R^{n x n}
    M_t    = rows_normalised(columns_normalised(M_{t-1}))   t = 1..iters
    X'[i]  = sum_j M_iters[i, j] X[j] + H_post[i] F(sum_j H_pre[j] X[j])

with ``phi``'s columns ``[pre | post | res]``, ``res`` row-major,
``lo, hi = mhc_h_res_clamp_min, mhc_h_res_clamp_max`` and ``iters =
hc_sinkhorn_iters``.

MLA: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` in heads of ``[q_nope |
q_rope]``; ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
``[k_nope | v]`` a head ``= c_kv W_kvb``; ``q_rope`` and the one ``k_r``
(shared by every head) are rotated (``rotary_tables``: YaRN's blended
frequencies); scores ``(q_nope . k_nope + q_rope . k_r) *
softmax_scale``; causal softmax; ``out = concat(P v) W_o``.

``FFN`` in the first ``first_k_dense_replace`` layers: ``W_down (silu(
W_gate u) * W_up u)`` at ``intermediate_size``. In the others::

    s = sigmoid(u W_r)                    all published experts
    top = the num_experts_per_tok largest of s + b     (b: selection only)
    g_i = s_i / (sum_top s + 1e-20) * routed_scaling_factor
    F(u) = shared(u) + sum_{i in top and held here} g_i expert_i(u)

The experts held here are ``deployment.experts_held`` of
``deployment.published_n_routed_experts``; what the others would add is
left out (the chip's share of a layer). No balance loss.

The prediction module, on ``h_i`` (the streams summed, before the final
norm) and the row's tokens: ``h'_i = [RMSNorm(h_i) | RMSNorm(Emb(
t_{i+1}))] W_eh``, one more expert layer of the same kind with streams
entered from ``h'_i``, a norm of the final norm's form, the main
model's head, predicting ``t_{i+2}``; ``loss = L_main +
assumed.mtp_loss_weight * L_mtp``, ``L_mtp`` the mean over the
positions that have a token two ahead.

It runs one layer at a time, so that it fits beside the training state
of a chip: the caller hands the layers over as an iterator of
dictionaries, one a layer in order.

Departures from the published code, none in the mathematics:
* weight matrices are taken as [in, out] (``x @ w``);
* the rotary pairs are (i, i + d/2) and not the published code's
  interleaved (2i, 2i + 1): the same function of the weights under a
  fixed permutation of the projection's columns;
* ``n_group`` 1 and ``topk_group`` 1 make ``noaux_tc``'s group limit
  select the one group there is: it is not computed;
* attention is computed one head at a time, which bounds the [seq, seq]
  scores.
"""

import math

import jax
import jax.numpy as jnp


def mm(a, b):
    """Every matrix product of the reference goes through here (float32
    at the highest precision; a test that shows what lower precision
    would do replaces it)."""
    return jnp.matmul(a, b)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


# -- hyper-connections ------------------------------------------------------


def sinkhorn(m, iters):
    """``m`` [seq, n, n], positive: ``iters`` times, every column over
    its sum, then every row over its sum."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-2, keepdims=True)
        m = m / jnp.sum(m, axis=-1, keepdims=True)
    return m


def post_mapping(logits):
    return 2.0 * jax.nn.sigmoid(logits)


def hyper_maps(streams, w, model):
    """(H_pre [seq, n], H_post [seq, n], H_res [seq, n, n]) of the
    streams [seq, n, C]."""
    seq, n, _ = streams.shape
    u = rms_norm(streams.reshape(seq, -1), w["norm"], model["hc_eps"])
    z = mm(u, w["phi"])
    a, b = w["alpha"], w["bias"]
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = post_mapping(a[1] * z[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * z[:, 2 * n:] + b[2 * n:],
                         model["mhc_h_res_clamp_min"],
                         model["mhc_h_res_clamp_max"]))
    return h_pre, h_post, sinkhorn(m.reshape(seq, n, n),
                                   model["hc_sinkhorn_iters"])


def connect(streams, w, sublayer, model):
    """One sublayer behind its hyper-connection: (the new streams, what
    ``sublayer`` returned beside its output)."""
    h_pre, h_post, h_res = hyper_maps(streams, w, model)
    y, out = sublayer(jnp.sum(h_pre[:, :, None] * streams, axis=1))
    kept = sum(h_res[:, :, j, None] * streams[:, None, j, :]
               for j in range(streams.shape[1]))
    return kept + h_post[:, :, None] * y[:, None, :], out


# -- latent attention -------------------------------------------------------


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(model):
    r = model["rope_scaling"]
    m = yarn_mscale(r["factor"], r["mscale_all_dim"])
    return (model["qk_nope_head_dim"]
            + model["qk_rope_head_dim"]) ** -0.5 * m * m


def rotary_tables(model, seq):
    """cos and sin [seq, d/2] of the rotary part, YaRN: pair ``i`` turns
    at ``1 / theta^(2i/d)``; pairs that turn fewer than ``beta_slow``
    times over the original context turn ``factor`` times slower, those
    above ``beta_fast`` as they were, linearly blended between."""
    r, d = model["rope_scaling"], model["qk_rope_head_dim"]
    base, original = model["rope_theta"], r[
        "original_max_position_embeddings"]

    def pair_at(turns):
        return (d * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_at(r["beta_fast"])), 0)
    high = min(math.ceil(pair_at(r["beta_slow"])), d - 1)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    plain = base ** (-2.0 * i / d)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = plain / r["factor"] * ramp + plain * (1.0 - ramp)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    scale = (yarn_mscale(r["factor"], r["mscale"])
             / yarn_mscale(r["factor"], r["mscale_all_dim"]))
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def rotate(x, cos, sin):
    """``x`` [seq, d]; pair ``i`` is (x[:, i], x[:, i + d/2])."""
    half = x.shape[-1] // 2
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def latent_attention(x, w, model):
    """``x`` [seq, hidden], already normed."""
    seq = x.shape[0]
    heads, eps = model["num_attention_heads"], model["rms_norm_eps"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    rank = model["kv_lora_rank"]
    cos, sin = rotary_tables(model, seq)
    q = mm(rms_norm(mm(x, w["w_qa"]), w["q_norm"], eps),
           w["w_qb"]).reshape(seq, heads, dn + dr)
    ckv = mm(x, w["w_kva"])
    k_r = rotate(ckv[:, rank:], cos, sin)  # one head for all
    kv = mm(rms_norm(ckv[:, :rank], w["kv_norm"], eps),
            w["w_kvb"]).reshape(seq, heads, dn + dv)
    visible = jnp.tril(jnp.ones((seq, seq), bool))
    scale = softmax_scale(model)

    def one_head(q_h, kv_h):
        scores = (mm(q_h[:, :dn], kv_h[:, :dn].T)
                  + mm(rotate(q_h[:, dn:], cos, sin), k_r.T)) * scale
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf),
                               axis=-1)
        return mm(probs, kv_h[:, dn:])

    out = jax.lax.map(lambda qk: one_head(*qk),
                      (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))
    return mm(out.transpose(1, 0, 2).reshape(seq, heads * dv), w["w_o"])


# -- the FFNs ---------------------------------------------------------------


def swiglu(u, w):
    return mm(jax.nn.silu(mm(u, w["w_gate"])) * mm(u, w["w_up"]),
              w["w_down"])


def route(u, w_router, bias, model):
    """(selected experts [seq, k], their weights [seq, k]): selected by
    score plus bias, weighed by score alone."""
    scores = jax.nn.sigmoid(mm(u, w_router))
    _, top_i = jax.lax.top_k(scores + bias, model["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if model["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_i, top_s * model["routed_scaling_factor"]


def expert_layer(u, w, model):
    """(F(u), the selected experts)."""
    top_i, gate = route(u, w["w_router"], w["b_router"], model)
    out = jnp.zeros_like(u)
    if model["n_shared_experts"]:
        out = out + swiglu(u, w["shared"])
    for slot, expert in enumerate(model["deployment"]["experts_held"]):
        g = jnp.sum(jnp.where(top_i == expert, gate, 0.0), axis=-1)
        mine = jax.tree.map(lambda a: a[slot], w["experts"])
        out = out + g[:, None] * swiglu(u, mine)
    return out, top_i


def layer(streams, w, model):
    """One layer on the streams [seq, n, C]: (the new streams, the
    expert layer's selected experts or None)."""
    eps = model["rms_norm_eps"]
    streams, _ = connect(
        streams, w["hc_attn"], lambda x: (latent_attention(
            rms_norm(x, w["input_norm"], eps), w["attn"], model), None),
        model)

    def ffn(x):
        u = rms_norm(x, w["post_norm"], eps)
        if "mlp" in w:
            return swiglu(u, w["mlp"]), None
        return expert_layer(u, w["moe"], model)

    return connect(streams, w["hc_ffn"], ffn, model)


# -- the head, the prediction module, the loss ------------------------------


def head_loss(h, head, labels, count):
    """Mean cross entropy of the first ``count`` of ``labels`` under
    logits ``h @ head``."""
    logits = mm(h, head)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - picked
    return jnp.sum(jnp.where(jnp.arange(len(labels)) < count, nll, 0.0)
                   ) / count


def mtp_input(h, embedded, w, eps):
    """``[RMSNorm(h) | RMSNorm(Emb(t_{i+1}))] W_eh``."""
    return mm(jnp.concatenate([rms_norm(h, w["h_norm"], eps),
                               rms_norm(embedded, w["e_norm"], eps)],
                              axis=-1), w["w_eh"])


def enter(x, model):
    return jnp.broadcast_to(x[:, None, :],
                            (x.shape[0], model["hc_mult"], x.shape[1]))


def loss(model, ids, labels, table, layers, final_norm, head, mtp,
         selections=None, hidden=None):
    """The training loss of one row: ``ids`` [seq] are ``t_0 ..
    t_{seq-1}`` and ``labels`` [seq] ``t_1 .. t_seq``. ``table``
    [vocab, hidden]; ``head`` [hidden, vocab]; ``layers`` yields one
    dictionary a layer, in order: ``input_norm``, ``attn``,
    ``post_norm``, ``hc_attn`` and ``hc_ffn`` (``norm``, ``phi``,
    ``alpha``, ``bias``) and ``mlp`` (a dense layer) or ``moe``
    (``w_router``, ``b_router``, ``shared``, ``experts`` with the held
    experts stacked in ``experts_held``'s order); ``mtp`` the module:
    ``h_norm``, ``e_norm``, ``w_eh``, ``layer`` (an expert layer's
    dictionary), ``norm``. Every array is cast to float32 here.
    ``selections``, a list, receives every expert layer's selected
    experts (the module's last); ``hidden``, a list, the final normed
    hidden states [seq, hidden] of the main model and then of the
    module."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    eps = model["rms_norm_eps"]
    assert model["num_nextn_predict_layers"] == 1
    with jax.default_matmul_precision("highest"):
        run_layer = jax.jit(lambda s, w: layer(s, w, model))
        norm = jax.jit(lambda x, s: rms_norm(x, s, eps))
        # labels and the count are arguments: closed over, they would
        # be constants of the program, and every seed would compile anew
        nll = jax.jit(head_loss)
        table, head = f32(table), f32(head)
        labels = jnp.asarray(labels)
        seq = len(labels)

        def note(top_i):
            if selections is not None and top_i is not None:
                selections.append(top_i)

        streams = enter(table[jnp.asarray(ids)], model)
        for i, w in enumerate(layers):
            assert ("mlp" in w) == (i < model["first_k_dense_replace"])
            streams, top_i = run_layer(streams, f32(w))
            note(top_i)
        assert i == model["num_hidden_layers"] - 1, (
            f"{i + 1} layers were handed over")
        h = jnp.sum(streams, axis=1)
        main = norm(h, f32(final_norm))
        total = nll(main, head, labels, seq)

        mtp = f32(mtp)
        x = jax.jit(lambda h, e, w: mtp_input(h, e, w, eps))(
            h, table[labels], {k: mtp[k] for k in ("h_norm", "e_norm",
                                                  "w_eh")})
        streams, top_i = run_layer(enter(x, model), mtp["layer"])
        note(top_i)
        ahead = norm(jnp.sum(streams, axis=1), mtp["norm"])
        # position i predicts t_{i+2} = labels[i + 1]; the last has none
        two_ahead = jnp.concatenate([labels[1:], labels[:1]])
        total = total + model["assumed"]["mtp_loss_weight"] * nll(
            ahead, head, two_ahead, seq - 1)
        if hidden is not None:
            hidden += [main, ahead]
        return total
