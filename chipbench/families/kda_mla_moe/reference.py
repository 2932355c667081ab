"""A plain reference for Ling-3.0-flash's decoder (the model's public
``config.json`` as the ``model-configs`` catalog quotes it, and ISSUE
62's equations from it: Kimi Linear's delta attention, arXiv:2510.26692,
DeepSeek-V2's latent attention without a query latent, DeepSeek-V3's
group-limited sigmoid router). Straightforward ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``: the delta rule TOKEN
BY TOKEN, as the recurrence defines it (no chunk, no triangular
inverse, no sub-chunk), latent attention as a dense masked softmax a
head, the experts one after another over the experts held here; no kernel, no
batching, no sharding, nothing imported from the program.

Layer ``l`` of ``x`` [T, hidden], RMSNorm with eps ``rms_norm_eps``, no
bias::

    u = RMSNorm_in(x);   x' = x + mixer_l(u)
    z = RMSNorm_post(x'); x'' = x' + ffn_l(z)

``mixer_l`` is MLA where ``(l + 1) % layer_group_size == 0``, KDA
otherwise. KDA (32 heads of 128 keys and 128 values; per head)::

    q, k, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
        conv: causal, depthwise, short_conv_kernel_size taps, no bias
    q, k = q / |q|, k / |k|;  q = q / sqrt(128)
    beta = sigmoid(u W_b)
    g = kda_lower_bound * sigmoid(exp(A_log) * (u W_f + dt_bias))
        a value a token, head and key CHANNEL, in (-5, 0)
    S_0 = 0;  S'_t = Diag(exp(g_t)) S_{t-1}       S [dk, dv]
    S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T;  o_t = S_t^T q_t
    mixer = (RMSNorm_head(o) * sigmoid(u W_g)) W_o    one [128] scale

MLA (32 heads)::

    q_h = RMSNorm_q((u W_q)_h) = [q_nope 128 | q_rope 64]
    [c | k_r] = u W_kva;  c_kv = RMSNorm(c)
    [k_nope_h | v_h] = (c_kv W_kvb)_h;  k_nope_h = RMSNorm_k(k_nope_h)
    k_rope = rot(RMSNorm_r(k_r))  ONE head;  q_rope_h = rot(q_rope_h)
        rot: rotate-half pairs (i, i + 32) of 64 at rope_theta
    a_h = softmax_causal((q_nope_h . k_nope_h + q_rope_h . k_rope)
                         / sqrt(192)) v_h
    mixer = concat_h(a_h * sigmoid(u W_gate)_h) W_o    W_gate [hidden, 32]

``ffn_l`` is a SwiGLU of ``intermediate_size`` where ``l <
first_k_dense_replace``, else::

    s = sigmoid(z W_r)  (all 512);  s' = s + b
    mark_k = the two largest s' of group k (experts 64k ..)
    the 4 groups of largest mark stay; top = the 8 largest s' of their
    256 experts;  p_e = 2.5 * s_e / (sum_{top} s + 1e-20)
    ffn = shared(z) + sum_{e in top, e held here} p_e expert_e(z)

then the final RMSNorm and the untied head. The loss is the mean cross
entropy plus ``assumed.balance_loss_weight`` times the sum over the
expert layers of the sequence's balance term ``sum_i f_i P_i``. The
experts held here are ``deployment.experts_held`` of
``deployment.published_num_experts``; what the others would add is left
out (the chip's share of a layer, ``model-configs`` section 4).

Departures from the published code, none in the mathematics: weight
matrices are taken as [in, out] (``x @ w``), a convolution's filter as
[taps, channels] with the last tap on the current token; the rotary
pairs are rotate-half's, a fixed permutation of the interleaved ones.

Every mechanism is a function of this module, so that a test can swap
one for a wrong one and see the comparison fail
(``tests/chipbench/kda_mla_moe_controls.py``).
"""

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024  # query rows of one head scored at a time
KDA, MLA = "kda", "mla"


def mm(a, b):
    """Every matrix product of the reference goes through here (float32
    at the highest precision; a test that shows what lower precision
    would do replaces it)."""
    return jnp.matmul(a, b)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def kinds(model):
    """The mixer of every layer here."""
    group = model["layer_group_size"]
    return [MLA if (i + 1) % group == 0 else KDA
            for i in range(model["num_hidden_layers"])]


# -- Kimi delta attention -----------------------------------------------------


def conv(u, taps):
    """Causal depthwise convolution over the row: ``u`` [seq,
    channels], ``taps`` [width, channels]; out[t] = sum_i taps[i] *
    u[t - (width - 1) + i], nothing before the row's start."""
    width, seq = taps.shape[0], u.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((width - 1, u.shape[1]), u.dtype), u])
    return sum(taps[i] * padded[i:i + seq] for i in range(width))


def unit(u, eps):
    """A head's vector at length 1: ``u`` [..., d]."""
    return u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + eps)


def beta_of(logits):
    return jax.nn.sigmoid(logits)


def log_decay(raw, a_log, bound):
    """The bounded gate: ``raw`` [seq, heads, dk] (the projection plus
    ``dt_bias``), ``a_log`` [heads]."""
    return bound * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * raw)


def decay(g):
    """alpha [heads, dk] of a token's log decay: a value a channel."""
    return jnp.exp(g)


def head_norm(o, scale, eps):
    """RMSNorm over a head's values, one learned scale."""
    return rms_norm(o, scale, eps)


def out_gate(o, logits):
    return o * jax.nn.sigmoid(logits)


def carried(state):
    """The state a token hands to the next: float32."""
    return state


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token. ``q``, ``k``, ``g`` [seq, heads,
    dk]; ``v`` [seq, heads, dv]; ``beta`` [seq, heads]. Returns [seq,
    heads, dv]."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(state, xs):  # state [heads, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        state = decay(g_t)[:, :, None] * state
        seen = jnp.sum(k_t[:, :, None] * state, axis=1)  # S^T k
        write = b_t[:, None] * (v_t - seen)
        state = carried(state + k_t[:, :, None] * write[:, None, :])
        return state, jnp.sum(q_t[:, :, None] * state, axis=1)  # S^T q

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def kda(u, w, model):
    """``u`` [seq, hidden], normed."""
    seq = u.shape[0]
    heads, hd = model["num_attention_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]

    def mixed(name):
        return jax.nn.silu(conv(mm(u, w[f"w{name}"]),
                                w[f"conv_{name}"])).reshape(seq, heads, hd)

    q = unit(mixed("q"), eps) / math.sqrt(hd)
    k = unit(mixed("k"), eps)
    v = mixed("v")
    beta = beta_of(mm(u, w["wb"]))
    g = log_decay((mm(u, w["wf"]) + w["dt_bias"]).reshape(seq, heads, hd),
                  w["a_log"], model["kda_lower_bound"])
    o = delta_rule(q, k, v, g, beta)
    o = out_gate(head_norm(o, w["o_norm"], eps),
                 mm(u, w["wg"]).reshape(seq, heads, hd))
    return mm(o.reshape(seq, heads * hd), w["wo"])


# -- latent attention ---------------------------------------------------------


def rotary_tables(model, seq):
    d = model["qk_rope_head_dim"]
    inv_freq = 1.0 / model["rope_theta"] ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """Pair ``i`` is (x[i], x[i + d/2]); ``x`` [seq, ..., d], the
    tables [seq, d/2]."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def qk_norm(x, scale, eps):
    """RMSNorm over a head's columns, one learned scale."""
    return rms_norm(x, scale, eps)


def head_gate(a, logits):
    """``a`` [seq, heads, dv] times one sigmoid a head and token."""
    return a * jax.nn.sigmoid(logits)[:, :, None]


def latent_attention(u, w, model):
    """``u`` [seq, hidden], normed."""
    seq = u.shape[0]
    heads = model["num_attention_heads"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    rank, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    cos, sin = rotary_tables(model, seq)
    q = qk_norm(mm(u, w["wq"]).reshape(seq, heads, dn + dr), w["q_norm"],
                eps)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], cos, sin)], -1)
    ckv = mm(u, w["w_kva"])
    kv = mm(rms_norm(ckv[:, :rank], w["kv_norm"], eps), w["w_kvb"]).reshape(
        seq, heads, dn + dv)
    k_nope = qk_norm(kv[..., :dn], w["k_norm"], eps)
    k_rope = rotate(qk_norm(ckv[:, rank:], w["k_rope_norm"], eps), cos, sin)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, None, :], (seq, heads, dr))], -1)
    block = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    j = jnp.arange(seq)[None, :]

    def one_head(q_h, k_h, v_h):  # [seq, .] each

        def rows(start):
            t = start + jnp.arange(block)[:, None]
            scores = mm(jax.lax.dynamic_slice_in_dim(q_h, start, block),
                        k_h.T) / math.sqrt(dn + dr)
            probs = jax.nn.softmax(jnp.where(j <= t, scores, -jnp.inf),
                                   axis=-1)
            return mm(probs, v_h)

        return jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, dv)

    a = jax.lax.map(lambda qkv: one_head(*qkv), (
        q.transpose(1, 0, 2), k.transpose(1, 0, 2),
        kv[..., dn:].transpose(1, 0, 2))).transpose(1, 0, 2)
    return mm(head_gate(a, mm(u, w["w_gate"])).reshape(seq, heads * dv),
              w["wo"])


# -- the FFNs -----------------------------------------------------------------


def swiglu(z, w):
    return mm(jax.nn.silu(mm(z, w["w_gate"])) * mm(z, w["w_up"]),
              w["w_down"])


def gates_of(scores, top_i, model):
    """The weights of the selected experts ``top_i`` from every
    expert's unbiased ``scores``."""
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if model["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_s * model["routed_scaling_factor"]


def route(scores, bias, model):
    """The selected experts [seq, k] from every expert's ``scores``
    [seq, E] and the selection ``bias`` [E]: the groups by the sum of
    their two largest biased scores, the experts among the kept groups'
    alone."""
    seq, experts = scores.shape
    biased = scores + bias
    n_group, topk_group = model["n_group"], model["topk_group"]
    size = experts // n_group
    mark = jnp.sum(jax.lax.top_k(
        biased.reshape(seq, n_group, size), 2)[0], axis=-1)
    _, kept = jax.lax.top_k(mark, topk_group)
    groups = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    allowed = jnp.repeat(groups, size, axis=1)
    _, top_i = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf),
                             model["num_experts_per_tok"])
    return top_i


def shared_expert(z, w):
    return swiglu(z, w)


def balance(scores, top_i):
    """``sum_i f_i P_i`` of one sequence."""
    seq, experts = scores.shape
    chosen = jnp.sum(jax.nn.one_hot(top_i, experts), axis=(0, 1))
    f = chosen * experts / (seq * top_i.shape[1])
    p = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    return jnp.sum(f * p)


def expert_layer(z, w, model, held=None):
    """(F(z), the balance term, the selected experts). ``held``: the
    routed experts computed, ``deployment.experts_held`` by default;
    ``w["experts"]`` stacks them in that order."""
    if held is None:
        held = model["deployment"]["experts_held"]
    scores = jax.nn.sigmoid(mm(z, w["w_router"]))
    top_i = route(scores, w["router_bias"], model)
    gate = gates_of(scores, top_i, model)
    out = jnp.zeros_like(z)
    if model["num_shared_experts"]:
        out = out + shared_expert(z, w["shared"])
    def add(out, one):  # one held expert after another, in order
        expert, mine = one
        g = jnp.sum(jnp.where(top_i == expert, gate, 0.0), axis=-1)
        return out + g[:, None] * swiglu(z, mine), None

    out, _ = jax.lax.scan(add, out, (jnp.asarray(held), w["experts"]))
    return out, balance(scores, top_i), top_i


def head_loss(h, head, labels):
    """Mean cross entropy of ``labels`` under logits ``h @ head``."""
    logits = mm(h, head)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss(model, ids, labels, table, layers, final_norm, head, hidden=None,
         mixers=None):
    """The training loss of ``labels`` [seq] given ``ids`` [seq]: mean
    cross entropy over the vocabulary held plus the weighted balance
    terms. ``table`` [vocab, hidden]; ``head`` [hidden, vocab];
    ``layers`` yields one dictionary a layer, in order: ``input_norm``,
    ``mixer`` (a KDA layer's ``wq``, ``wk``, ``wv``, ``wf``, ``wg``,
    ``wb``, ``wo``, ``conv_q``, ``conv_k``, ``conv_v``, ``a_log``,
    ``dt_bias``, ``o_norm``; an MLA layer's ``wq``, ``w_kva``,
    ``kv_norm``, ``w_kvb``, ``w_gate``, ``wo``, ``q_norm``, ``k_norm``,
    ``k_rope_norm``), ``post_norm`` and ``mlp`` (a dense layer:
    ``w_gate``, ``w_up``, ``w_down``) or ``moe`` (``w_router``,
    ``router_bias``, ``shared``, ``experts`` with the held experts
    stacked in ``experts_held``'s order); every array is cast to
    float32 here. ``hidden``, a list, receives the final normed hidden
    states [seq, hidden]; ``mixers``, a dictionary, receives under
    ``"kda"`` and ``"mla"`` the LAST layer of each kind's ``(its
    index, what its mixer read, what its mixer gave)``."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    eps = model["rms_norm_eps"]
    dense = model["first_k_dense_replace"]
    weight = model["assumed"]["balance_loss_weight"]
    with jax.default_matmul_precision("highest"):
        # one program a kind of sublayer, not one a layer
        run = {
            "norm": jax.jit(lambda x, s: rms_norm(x, s, eps)),
            KDA: jax.jit(lambda u, w: kda(u, w, model)),
            MLA: jax.jit(lambda u, w: latent_attention(u, w, model)),
            "mlp": jax.jit(swiglu),
            "moe": jax.jit(lambda z, w: expert_layer(z, w, model)[:2]),
        }
        h = jnp.asarray(table[ids], jnp.float32)
        aux, count = 0.0, 0
        for kind, w in zip(kinds(model), layers):
            w = f32(w)
            u = run["norm"](h, w["input_norm"])
            y = run[kind](u, w["mixer"])
            if mixers is not None:
                mixers[kind] = (count, u, y)
            h = h + y
            z = run["norm"](h, w["post_norm"])
            if count < dense:
                h = h + run["mlp"](z, w["mlp"])
            else:
                y, term = run["moe"](z, w["moe"])
                h, aux = h + y, aux + term
            count += 1
        assert count == model["num_hidden_layers"], (
            f"{count} layers were handed over")
        h = run["norm"](h, f32(final_norm))
        if hidden is not None:
            hidden.append(h)
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        nll = jax.jit(head_loss)(h, f32(head), jnp.asarray(labels))
    return nll + weight * aux
