"""A plain reference for Motif-3-Beta's decoder (the model's public
``config.json``, ``model_type`` ``Motif``), as ISSUE 55 wrote the
equations down from the config's keys and the public descriptions of
each part: grouped differential latent attention (GDLA), a causal band
of ``sliding_window`` keys on three layers in four, PolyNorm FFNs, one
shared and the routed experts held here behind a sigmoid router whose
selection bias the step moves from the load, a residual of four streams
mixed by manifold-constrained hyper-connections, one multi-token
prediction module. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: attention as a dense
masked softmax a head, the experts as a loop over the experts held
here; no kernel, no sorting, no batching, no sharding, nothing imported
from the program or from another family.

``u`` is a sublayer's input: the hyper-connection's mix of the streams
(``connect``; arXiv:2512.24880 as Xing4.0's reference reads it), then
RMSNorm with ``rms_norm_eps``.

GDLA (``attention_cls`` ``gdla``, ``diff_v2``), per token::

    c_q = RMSNorm(u W_qa)              q = c_q W_qb -> H heads [nope | rope]
    [c_kv | k_r] = u W_kva             c_kv = RMSNorm(c_kv)
    [k_nope | v] = c_kv W_kvb          -> G heads;  k_r: ONE rotary head
    query heads rg .. rg + r - 2 are the signal heads of KV head g,
    head rg + r - 1 its noise head                     (r = H / G)
    a_h = softmax_M((q_nope_h . k_nope_g + rot(q_rope_h) . rot(k_r))
                    * head_dim^-0.5) v_g
    lam = sigmoid(u W_lam)             one value a token and signal head
    d_(g,i) = a_(rg+i) - lam_(g,i) a_(rg+r-1)          i = 0 .. r - 2
    out = (concat(d) * sigmoid(u W_g)) W_o

``M`` is causal; in a window layer also ``t - sliding_window < s <= t``.
Published layer ``i`` is full where ``(i + 1) % sliding_window_period ==
0``. ``rot`` is the plain rotary at ``rope_theta`` and the scale the
plain ``head_dim^-0.5`` (``rope_scaling.apply_yarn_scaling`` is false).
No norm after the subtraction and no ``(1 - lam0)`` factor (V2).

FFN: ``W_down(PolyNorm(W_gate u) * W_up u)``, ``PolyNorm(z) =
polynorm_output_scale * (a_1 n(z^3) + a_2 n(z^2) + a_3 n(z) + clip(b,
+-polynorm_bias_clamp))``, ``n(z) = z / sqrt(mean(z^2) + eps)`` over
the last axis. Dense in the first ``n_dense_first_layers`` layers; in
the others::

    s = sigmoid(u W_r)                       all published experts
    top = the experts_top_k largest of s + b        (b: selection only)
    g_i = s_i / (sum_top s + 1e-20) * route_scale
    F(u) = shared(u) + sum_{i in top and held here} g_i expert_i(u)

After every optimizer step, a layer: ``n`` the tokens that selected
each published expert, ``delta = load_balance_coeff * sign(mean(n) -
n)``, ``b += delta - mean(delta)`` (``bias_update``). ``b`` takes no
gradient.

The prediction module is DeepSeek-V3's, on ``h_i`` (the streams summed,
before the final norm): ``h'_i = [RMSNorm(h_i) | RMSNorm(Emb(t_{i+1}))]
W_eh``, one more expert layer of the model's kind (a window layer),
a norm of the final norm's form, the main model's head, predicting
``t_{i+2}``; ``loss = L_main + assumed.mtp_loss_weight * L_mtp``.

It runs one layer at a time, attention a head at a time and the FFNs a
block of tokens at a time, so that it fits beside the training state of
a chip.

Departures, none in the mathematics: weight matrices are [in, out];
the rotary pairs are (i, i + d/2); which of a group's query heads is
its noise head is a fixed permutation of ``W_qb``'s columns.
"""

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 1024  # the FFNs' tokens a block


def mm(a, b):
    """Every matrix product of the reference goes through here (float32
    at the highest precision; a test that shows what lower precision
    would do replaces it)."""
    return jnp.matmul(a, b)


def f32(x):
    """Where the configuration says float32 (router scores, lambda,
    PolyNorm, the mappings, softmax, cross entropy) the value passes
    through here; the same test replaces it too."""
    return x


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


def hyper_maps(streams, w, model):
    """(H_pre [seq, n], H_post [seq, n], H_res [seq, n, n]) of the
    streams [seq, n, C]."""
    seq, n, _ = streams.shape
    a_ = model["assumed"]
    u = rms_norm(streams.reshape(seq, -1), w["norm"], a_["hc_eps"])
    z = f32(mm(u, w["phi"]))
    a, b = w["alpha"], w["bias"]
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * z[:, 2 * n:] + b[2 * n:],
                         a_["hc_clamp_min"], a_["hc_clamp_max"]))
    return h_pre, h_post, sinkhorn(m.reshape(seq, n, n),
                                   model["mhc_sinkhorn_iters"])


def connect(streams, w, sublayer, model):
    """One sublayer behind its hyper-connection: (the new streams, what
    ``sublayer`` returned beside its output)."""
    h_pre, h_post, h_res = hyper_maps(streams, w, model)
    y, out = sublayer(jnp.sum(h_pre[:, :, None] * streams, axis=1))
    kept = sum(h_res[:, :, j, None] * streams[:, None, j, :]
               for j in range(streams.shape[1]))
    return kept + h_post[:, :, None] * y[:, None, :], out


# -- grouped differential latent attention ----------------------------------


def rotary_tables(model, seq):
    """cos and sin [seq, d/2] of the plain rotary at ``rope_theta``."""
    d = model["qk_rope_head_dim"]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    angles = (jnp.arange(seq, dtype=jnp.float32)[:, None]
              * model["rope_theta"] ** (-2.0 * i / d))
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """``x`` [seq, d]; pair ``i`` is (x[:, i], x[:, i + d/2])."""
    half = x.shape[-1] // 2
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def visible(seq, window):
    """[seq, seq]: key s is seen by query t; ``window`` 0 is causal."""
    ahead = jnp.arange(seq)[:, None] - jnp.arange(seq)[None, :]
    return (ahead >= 0) & ((ahead < window) if window else True)


def gdla(x, w, model, window):
    """``x`` [seq, hidden], already normed; (output, lambda's mean)."""
    seq = x.shape[0]
    heads, groups = (model["num_attention_heads"],
                     model["num_key_value_heads"])
    assert model["num_noise_heads"] == groups and heads % groups == 0
    per = heads // groups  # a group's query heads, the last its noise
    eps, dr, dv = (model["rms_norm_eps"], model["qk_rope_head_dim"],
                   model["v_head_dim"])
    dn, rank = model["head_dim"] - dr, model["kv_lora_rank"]
    cos, sin = rotary_tables(model, seq)
    q = mm(rms_norm(mm(x, w["w_qa"]), w["q_norm"], eps),
           w["w_qb"]).reshape(seq, heads, dn + dr)
    ckv = mm(x, w["w_kva"])
    k_r = rotate(ckv[:, rank:], cos, sin)  # one head for all
    kv = mm(rms_norm(ckv[:, :rank], w["kv_norm"], eps),
            w["w_kvb"]).reshape(seq, groups, dn + dv)
    seen = visible(seq, window)
    scale = model["head_dim"] ** -0.5

    def one_head(q_h, kv_g):
        scores = f32((mm(q_h[:, :dn], kv_g[:, :dn].T)
                      + mm(rotate(q_h[:, dn:], cos, sin), k_r.T)) * scale)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm(probs, kv_g[:, dn:])

    a = jax.lax.map(
        lambda qk: one_head(*qk),
        (q.transpose(1, 0, 2),
         jnp.repeat(kv.transpose(1, 0, 2), per, axis=0))
    ).reshape(groups, per, seq, dv)
    lam = jax.nn.sigmoid(f32(mm(x, w["w_lam"])))  # [seq, G (per - 1)]
    lam_g = lam.reshape(seq, groups, per - 1).transpose(1, 2, 0)
    d = a[:, :-1] - lam_g[..., None] * a[:, -1:]
    d = d.reshape(groups * (per - 1), seq, dv).transpose(1, 0, 2)
    gate = jax.nn.sigmoid(f32(mm(x, w["w_g"])))
    return mm(d.reshape(seq, -1) * gate, w["w_o"]), jnp.mean(lam)


# -- the FFNs ---------------------------------------------------------------


def poly_norm(z, w, model):
    """``w``: ``weight`` [3], ``bias`` [1]."""
    eps = model["assumed"]["polynorm_eps"]
    z = f32(z)

    def n(t):
        return t * jax.lax.rsqrt(
            jnp.mean(t * t, axis=-1, keepdims=True) + eps)

    clamp = model["polynorm_bias_clamp"]
    return model["polynorm_output_scale"] * (
        w["weight"][0] * n(z ** 3) + w["weight"][1] * n(z ** 2)
        + w["weight"][2] * n(z) + jnp.clip(w["bias"][0], -clamp, clamp))


def poly_glu(u, w, model):
    return mm(poly_norm(mm(u, w["w_gate"]), w["act"], model)
              * mm(u, w["w_up"]), w["w_down"])


def in_blocks(f, u):
    """``f`` over ``u``'s tokens a block at a time (every FFN here is a
    function of one token's row)."""
    seq = u.shape[0]
    if seq <= TOKEN_BLOCK or seq % TOKEN_BLOCK:
        return f(u)
    return jax.lax.map(f, u.reshape(-1, TOKEN_BLOCK, u.shape[1])).reshape(
        seq, -1)


def route(u, w_router, bias, model):
    """(selected experts [seq, k], their weights [seq, k]): selected by
    score plus bias, weighed by score alone."""
    assert model["score_func"] == "sigmoid"
    scores = jax.nn.sigmoid(f32(mm(u, w_router)))
    _, top_i = jax.lax.top_k(scores + bias, model["experts_top_k"])
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if model["route_norm"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_i, top_s * model["route_scale"]


def expert_layer(u, w, model):
    """(F(u), the selected experts)."""
    top_i, gate = route(u, w["w_router"], w["b_router"], model)
    out = jnp.zeros_like(u)
    if model["num_shared_experts"]:
        out = out + in_blocks(lambda t: poly_glu(t, w["shared"], model), u)
    for slot, expert in enumerate(model["deployment"]["experts_held"]):
        g = jnp.sum(jnp.where(top_i == expert, gate, 0.0), axis=-1)
        mine = {k: w["experts"][k][slot]
                for k in ("w_gate", "w_up", "w_down")}
        mine["act"] = w["experts"]["act"]  # one for the layer's experts
        out = out + g[:, None] * poly_glu(u, mine, model)
    return out, top_i


def expert_counts(top_i, model):
    """The tokens that selected each of the published experts."""
    width = model["deployment"]["published_num_experts"]
    return jnp.sum(top_i[:, :, None] == jnp.arange(width), axis=(0, 1)
                   ).astype(jnp.float32)


def bias_update(bias, counts, model):
    """A layer's selection bias after an optimizer step."""
    delta = model["load_balance_coeff"] * jnp.sign(
        jnp.mean(counts) - counts)
    return bias + delta - jnp.mean(delta)


def window_of(model, published_index):
    """The window of the layer at ``published_index``: 0 where it is a
    full layer."""
    if not model["use_sliding_window"]:
        return 0
    assert model["sliding_window_pattern"] == "interleave"
    full = (published_index + 1) % model["sliding_window_period"] == 0
    return 0 if full else model["sliding_window"]


def layer(streams, w, model, window):
    """One layer on the streams [seq, n, C]: (the new streams, the
    expert layer's selected experts or None, lambda's mean)."""
    eps = model["rms_norm_eps"]
    streams, lam = connect(
        streams, w["hc_attn"], lambda x: gdla(
            rms_norm(x, w["input_norm"], eps), w["attn"], model, window),
        model)

    def ffn(x):
        u = rms_norm(x, w["post_norm"], eps)
        if "mlp" in w:
            return in_blocks(lambda t: poly_glu(t, w["mlp"], model),
                             u), None
        return expert_layer(u, w["moe"], model)

    streams, chosen = connect(streams, w["hc_ffn"], ffn, model)
    return streams, chosen, lam


# -- the head, the prediction module, the loss ------------------------------


def head_loss(h, head, labels, count):
    """Mean cross entropy of the first ``count`` of ``labels`` under
    logits ``h @ head``."""
    logits = f32(mm(h, head))
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
    return jnp.broadcast_to(
        x[:, None, :], (x.shape[0], model["mhc_expansion_rate"], x.shape[1]))


def loss(model, ids, labels, table, layers, final_norm, head, mtp,
         selections=None, hidden=None, lambdas=None):
    """The training loss of one row: ``ids`` [seq] are ``t_0 ..
    t_{seq-1}`` and ``labels`` [seq] ``t_1 .. t_seq``. ``table``
    [vocab, hidden]; ``head`` [hidden, vocab]; ``layers`` yields one
    dictionary a layer, in order: ``input_norm``, ``attn`` (``w_qa``,
    ``q_norm``, ``w_qb``, ``w_kva``, ``kv_norm``, ``w_kvb``, ``w_lam``,
    ``w_g``, ``w_o``), ``post_norm``, ``hc_attn`` and ``hc_ffn``
    (``norm``, ``phi``, ``alpha``, ``bias``) and ``mlp`` (a dense
    layer: ``w_gate``, ``w_up``, ``w_down``, ``act``) or ``moe``
    (``w_router``, ``b_router``, ``shared``, ``experts`` with the held
    experts stacked in ``experts_held``'s order and one ``act``);
    ``mtp`` the module: ``h_norm``, ``e_norm``, ``w_eh``, ``layer`` (an
    expert layer's dictionary), ``norm``. Every array is cast to
    float32 here. ``selections``, a list, receives every expert layer's
    selected experts (the module's last: what ``expert_counts`` and
    ``bias_update`` read); ``hidden``, a list, the final normed hidden
    states [seq, hidden] of the main model and then of the module;
    ``lambdas``, a list, every layer's mean lambda."""
    cast = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    eps = model["rms_norm_eps"]
    dep = model["deployment"]
    assert model["num_nextn_predict_layers"] == 1
    with jax.default_matmul_precision("highest"):
        run = {}

        def run_layer(streams, w, window):
            if window not in run:  # a program a kind of attention
                run[window] = jax.jit(
                    lambda s, w: layer(s, w, model, window))
            return run[window](streams, w)

        norm = jax.jit(lambda x, s: rms_norm(x, s, eps))
        # labels and the count are arguments: closed over, they would
        # be constants of the program, and every seed would compile anew
        nll = jax.jit(head_loss)
        table, head = cast(table), cast(head)
        labels = jnp.asarray(labels)
        seq = len(labels)

        def note(top_i, lam):
            if selections is not None and top_i is not None:
                selections.append(top_i)
            if lambdas is not None:
                lambdas.append(lam)

        streams = enter(table[jnp.asarray(ids)], model)
        for i, w in enumerate(layers):
            dense = i < model["n_dense_first_layers"]
            assert ("mlp" in w) == dense
            streams, top_i, lam = run_layer(
                streams, cast(w),
                window_of(model, dep["first_published_layer"] + i))
            note(top_i, lam)
        assert i == model["num_hidden_layers"] - 1, (
            f"{i + 1} layers were handed over")
        h = jnp.sum(streams, axis=1)
        main = norm(h, cast(final_norm))
        total = nll(main, head, labels, seq)

        mtp = cast(mtp)
        x = jax.jit(lambda h, e, w: mtp_input(h, e, w, eps))(
            h, table[labels], {k: mtp[k] for k in ("h_norm", "e_norm",
                                                  "w_eh")})
        streams, top_i, lam = run_layer(
            enter(x, model), mtp["layer"],
            window_of(model, dep["published_num_hidden_layers"]))
        note(top_i, lam)
        ahead = norm(jnp.sum(streams, axis=1), mtp["norm"])
        # position i predicts t_{i+2} = labels[i + 1]; the last has none
        two_ahead = jnp.concatenate([labels[1:], labels[:1]])
        total = total + model["assumed"]["mtp_loss_weight"] * nll(
            ahead, head, two_ahead, seq - 1)
        if hidden is not None:
            hidden += [main, ahead]
        return total
