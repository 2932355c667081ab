"""A plain reference for A.X-K2's decoder (the model's public
``config.json`` as the ``model-configs`` catalog quotes it, and ISSUE
51's equations from it: A.X-K1's latent-attention block, DeepSeek-V3.2's
indexer on the query latent, DeepSeek-V3's group-limited selection, a
sigmoid gate on the attention's output and a low-rank sigmoid gate on
the layers' norms). Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the indexer's scores and
the attention as dense ``[rows, seq]`` arrays a block of query rows, the
selection by a sort, the experts as a loop over the experts held here;
no kernel, no batching, no sharding, nothing imported from the program.

One layer (``x`` [T, hidden] the residual stream; float32 throughout)::

    GN(x) = n * sigmoid((n A) B),  n = g * x / sqrt(mean(x^2) + eps)
    u = GN_in(x)
    c_q = RMSNorm(u W_qa);  q_h = (c_q W_qb)_h = [q_nope | q_rope]
    [c | k_r] = u W_kva;  c_kv = RMSNorm(c);  k_rope = rot(k_r)  ONE head
    [k_nope_h | v_h] = (c_kv W_kvb)_h;  q_rope_h = rot(q_rope_h)
        rot: rotate-half pairs (i, i + 32) of 64, YaRN's blended
        frequencies (``rotary_tables``)
    u' = stop_gradient(u), c_q' = stop_gradient(c_q)
    qI_j = (c_q' W_Iq)_j   64 heads of 128, the first 64 columns rot
    kI = LayerNorm(u' W_Ik)  ONE head of 128, scale and bias, the first
                             64 columns rot
    w = u' W_Iw            64 a token
    I[t, s] = (64 * 128)^-1/2 sum_j w[t, j] relu(qI_j[t] . kI[s]), s <= t
    S_t = every s <= t where t < topk, else the topk keys s <= t of
          largest I[t, s], ties to the lower s
    a_h[t] = sum_{s in S_t} softmax_{s in S_t}((q_nope_h[t] . k_nope_h[s]
             + q_rope_h[t] . k_rope[s]) * scale) v_h[s]
        scale = 192^-1/2 * (0.1 * mscale_all_dim * ln(factor) + 1)^2
    x' = x + (concat_h(a_h) * sigmoid(u W_g)) W_o
    z = GN_post(x')
    layer 0:  x'' = x' + W_down (silu(W_gate z) * (W_up z))   at 18432
    others:   s = sigmoid(z W_r)  (all 256);  s' = s + b
              mark_k = the two largest s' of group k (experts 32k ..)
              the 4 groups of largest mark stay; top = the 8 largest s'
              of their 128 experts
              p_e = 2.5 * s_e / (sum_{top} s + 1e-20)
              x'' = x' + shared(z) + sum_{e in top, e held here} p_e
                                     expert_e(z)

then ``GN_final`` and the untied head. The loss is ``L_LM + sum over
layers of L_I``, ``L_I = mean_t KL(pbar[t, .] || softmax_{S_t}(I[t,
.]))``, ``pbar`` the mean over the heads held here of the attention's
probabilities. The indexer reads ``stop_gradient``s and ``pbar`` is
under ``stop_gradient`` too (the selection, a sort, has no gradient of
its own): JAX's differentiation of this file's functions then gives
the indexer's four leaves their gradient from ``L_I`` alone and every
other leaf its gradient from ``L_LM`` alone, which is what
``tests/test_mla_moe_dsa.py`` holds the program's gradients to. The
selection bias ``b`` is a leaf that takes no gradient.

The heads held here are ``num_attention_heads`` (their columns of
``W_qb``, ``W_kvb``, ``W_g`` and rows of ``W_o``), the experts held
here ``deployment.experts_held`` of ``deployment.
published_n_routed_experts``; what the others would add is left out
(the chip's share of a layer, ``model-configs`` section 4).

Departures from the published code, none in the mathematics: weight
matrices are [in, out] (``x @ w``); rotary pairs are (i, i + d/2), the
published interleaved pairs under a fixed permutation of columns; the
published inference code's Hadamard rotation of ``qI`` and ``kI`` (an
orthogonal map of both: no dot product changes) and its FP8 cast of
them (an inference format) are left out. What the config leaves open
(what the gates read, which norms are gated, the loss) is the
configuration file's ``assumed``.

It runs one layer at a time, so that it fits beside the training state
of a chip: the caller hands the layers over as an iterator of
dictionaries. Scores are computed ``ROW_BLOCK`` query rows at a time.
``layer``'s ``given`` hands it the selection and the expert choice of
another computation (the program's), so that a pair or an expert whose
score lies at the boundary does not count as an error of the
arithmetic.
"""

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 128  # query rows scored at a time


def mm(a, b):
    """Every matrix product of the reference goes through here (float32
    at the highest precision; a test that shows what lower precision
    would do replaces it)."""
    return jnp.matmul(a, b)


def index_act(x):
    """The indexer's activation on a head's scores."""
    return jax.nn.relu(x)


def output_gate(logits):
    """The gate on the attention's output, from ``u W_g``."""
    return jax.nn.sigmoid(logits)


def norm_gate(logits):
    """A gated norm's gate, from ``(n A) B``."""
    return jax.nn.sigmoid(logits)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, axis=-1, keepdims=True) + eps
    ) * scale + bias


def gated_norm(x, w, eps):
    """``w``: ``scale`` and, where the norm is gated, ``gate_a`` [hidden,
    rank] and ``gate_b`` [rank, hidden]."""
    n = rms_norm(x, w["scale"], eps)
    if "gate_a" not in w:
        return n
    return n * norm_gate(mm(mm(n, w["gate_a"]), w["gate_b"]))


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(model):
    r = model["rope_parameters"]
    m = yarn_mscale(r["factor"], r["mscale_all_dim"])
    return (model["qk_nope_head_dim"]
            + model["qk_rope_head_dim"]) ** -0.5 * m * m


def rotary_tables(model, seq):
    """cos and sin [seq, d/2] of the rotary part, YaRN: pair ``i`` turns
    at ``1 / theta^(2i/d)``; pairs that turn fewer than ``beta_slow``
    times over the original context turn ``factor`` times slower, those
    above ``beta_fast`` as they were, linearly blended between."""
    r, d = model["rope_parameters"], model["qk_rope_head_dim"]
    base, original = r["rope_theta"], r["original_max_position_embeddings"]

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
    """``x`` [seq, heads, d]; pair ``i`` is (x[..., i], x[..., i + d/2])."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rotate_leading(x, width, cos, sin):
    """``x`` [seq, heads, d] with its first ``width`` columns rotated."""
    return jnp.concatenate(
        [rotate(x[..., :width], cos, sin), x[..., width:]], axis=-1)


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


def attention(u, w, model, given=None):
    """``u`` [seq, hidden], already normed. Gives (what the attention
    adds to the residual, gated and ``W_o`` applied, the indexer's loss,
    the selection [seq, seq] bool); ``given`` is a selection to attend
    under."""
    seq = u.shape[0]
    heads, eps = model["num_attention_heads"], model["rms_norm_eps"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    rank = model["kv_lora_rank"]
    ih, ihd, topk = (model["index_n_heads"], model["index_head_dim"],
                     model["index_topk"])
    cos, sin = rotary_tables(model, seq)
    c_q = rms_norm(mm(u, w["w_qa"]), w["q_norm"], eps)
    q = mm(c_q, w["w_qb"]).reshape(seq, heads, dn + dr)
    q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], cos, sin)
    ckv = mm(u, w["w_kva"])
    k_rope = rotate(ckv[:, None, rank:], cos, sin)[:, 0]  # one head for all
    kv = mm(rms_norm(ckv[:, :rank], w["kv_norm"], eps),
            w["w_kvb"]).reshape(seq, heads, dn + dv)
    # the indexer trains on its own loss: it reads both detached
    ui, cqi = jax.lax.stop_gradient(u), jax.lax.stop_gradient(c_q)
    qi = rotate_leading(mm(cqi, w["index_wq"]).reshape(seq, ih, ihd), dr,
                        cos, sin)
    ki = rotate_leading(layer_norm(
        mm(ui, w["index_wk"]), w["index_k_scale"], w["index_k_bias"],
        eps)[:, None], dr, cos, sin)[:, 0]
    wi = mm(ui, w["index_ww"])
    block = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    k_nope = kv[..., :dn].transpose(1, 2, 0)  # [h, dn, seq]
    v = kv[..., dn:].transpose(1, 0, 2)  # [h, seq, dv]
    scale = softmax_scale(model)

    def rows(args):
        start, chosen = args
        cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, start, block)
        per_head = index_act(jnp.einsum("tje,se->tjs", cut(qi), ki,
                                        precision="highest"))
        scores = jnp.einsum("tj,tjs->ts", cut(wi), per_head,
                            precision="highest") / math.sqrt(ih * ihd)
        keep = select(scores, start, topk) if chosen is None else chosen
        logits = (mm(cut(q_nope).transpose(1, 0, 2), k_nope)
                  + mm(cut(q_rope).transpose(1, 0, 2), k_rope.T)) * scale
        probs = jax.nn.softmax(jnp.where(keep[None], logits, -jnp.inf),
                               axis=-1)  # [h, rows, seq]
        out = mm(probs, v).transpose(1, 0, 2).reshape(block, heads * dv)
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
    out = out.reshape(seq, heads * dv)
    if model["attention_output_gate"]:
        out = out * output_gate(mm(u, w["w_g"]))
    return (mm(out, w["w_o"]), jnp.mean(kl.reshape(seq)),
            keep.reshape(seq, seq))


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
    """(selected experts [seq, k], the kept groups [seq, n_group] bool)
    from every expert's ``scores`` [seq, E] and the selection ``bias``
    [E]: the groups by the sum of their two largest biased scores, the
    experts among the kept groups' alone."""
    seq, experts = scores.shape
    biased = scores + jax.lax.stop_gradient(bias)
    n_group, topk_group = model["n_group"], model["topk_group"]
    if n_group <= 1:
        _, top_i = jax.lax.top_k(biased, model["num_experts_per_tok"])
        return top_i, jnp.ones((seq, 1), bool)
    size = experts // n_group
    mark = jnp.sum(jax.lax.top_k(
        biased.reshape(seq, n_group, size), 2)[0], axis=-1)
    _, kept = jax.lax.top_k(mark, topk_group)
    groups = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    allowed = jnp.repeat(groups, size, axis=1)
    _, top_i = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf),
                             model["num_experts_per_tok"])
    return top_i, groups


def expert_layer(z, w, model, given=None):
    """(F(z), the selected experts, the kept groups); ``given``: the
    experts another computation selected."""
    scores = jax.nn.sigmoid(mm(z, w["w_router"]))
    top_i, groups = route(scores, w["router_bias"], model)
    if given is not None:
        top_i = given
    gate = gates_of(scores, top_i, model)
    out = jnp.zeros_like(z)
    if model["n_shared_experts"]:
        out = out + swiglu(z, w["shared"])
    for slot, expert in enumerate(model["deployment"]["experts_held"]):
        g = jnp.sum(jnp.where(top_i == expert, gate, 0.0), axis=-1)
        mine = jax.tree.map(lambda a: a[slot], w["experts"])
        out = out + g[:, None] * swiglu(z, mine)
    return out, top_i, groups


def layer(x, w, model, given=None):
    """One layer of ``x`` [seq, hidden]: (its output, the indexer's
    loss, the selection [seq, seq], the selected experts [seq, k] and
    the kept groups [seq, n_group], both None of a dense layer).
    ``given``: (a selection, selected experts or None) to compute
    under. ``w``: ``input_norm``, ``post_norm`` (``scale``, ``gate_a``,
    ``gate_b``), ``attn`` and ``mlp`` (a dense layer) or ``moe``."""
    eps = model["rms_norm_eps"]
    u = gated_norm(x, w["input_norm"], eps)
    a, kl, keep = attention(u, w["attn"], model,
                            None if given is None else given[0])
    x = x + a
    z = gated_norm(x, w["post_norm"], eps)
    if "mlp" in w:
        return x + swiglu(z, w["mlp"]), kl, keep, None, None
    y, top_i, groups = expert_layer(z, w["moe"], model,
                                    None if given is None else given[1])
    return x + y, kl, keep, top_i, groups


def head_loss(h, head, labels):
    """Mean cross entropy of ``labels`` under logits ``h @ head``."""
    logits = mm(h, head)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss(model, ids, labels, table, layers, final_norm, head):
    """``(L_LM, sum over layers of L_I, final normed hidden states [seq,
    hidden])`` of ``labels`` [seq] given ``ids`` [seq]; the training
    loss is ``L_LM + assumed.index_loss_weight * sum L_I``. ``table``
    [vocab, hidden]; ``head`` [hidden, vocab]; ``final_norm`` a gated
    norm's dictionary; ``layers`` yields one dictionary a layer, in
    order (``layer``'s ``w``: ``attn`` holds ``w_qa``, ``q_norm``,
    ``w_qb``, ``w_kva``, ``kv_norm``, ``w_kvb``, ``w_o``, ``w_g``,
    ``index_wq``, ``index_wk``, ``index_k_scale``, ``index_k_bias``,
    ``index_ww``; ``moe`` holds ``w_router``, ``router_bias``,
    ``shared`` and ``experts`` with the held experts stacked in
    ``experts_held``'s order); every array is cast to float32 here.
    (``job.py`` steps ``layer`` itself, beside the program's layers.)"""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    depth = model["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        run = jax.jit(lambda x, w: layer(x, w, model)[:2])
        h = jnp.asarray(table[ids], jnp.float32)
        kl_sum, count = 0.0, 0
        for w in layers:
            h, kl = run(h, f32(w))
            kl_sum, count = kl_sum + kl, count + 1
        assert count == depth, f"{count} layers were handed over"
        h = jax.jit(lambda x, w: gated_norm(
            x, w, model["rms_norm_eps"]))(h, f32(final_norm))
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        lm = jax.jit(head_loss)(h, f32(head), jnp.asarray(labels))
    return lm, kl_sum, h
