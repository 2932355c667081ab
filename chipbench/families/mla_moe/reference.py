"""A plain reference for A.X-K1's decoder (the model's public
``config.json``; the block is the one DeepSeek-V2 and DeepSeek-V3
publish: multi-head latent attention, a leading dense layer, then one
shared plus routed gated experts under a sigmoid top-k router), as
ISSUE 34 wrote its equations down. Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: attention as
a dense masked softmax a head, the experts as a loop over the experts
held here; no kernel, no sorting, no batching, no sharding, nothing
imported from the program.

Every layer, pre-norm residual, RMSNorm with eps ``rms_norm_eps``, no
biases: ``x = x + MLA(RMSNorm(x)); x = x + F(RMSNorm(x))``.

MLA: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` in heads of ``[q_nope |
q_rope]``; ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
``[k_nope | v]`` a head ``= c_kv W_kvb``; ``q_rope`` and the one ``k_r``
(shared by every head) are rotated (``rotary_tables``: YaRN's blended
frequencies); scores ``(q_nope . k_nope + q_rope . k_r) *
softmax_scale``; causal softmax; ``out = concat(P v) W_o``.

``F`` in the first ``first_k_dense_replace`` layers:
``W_down (silu(W_gate u) * W_up u)`` at ``intermediate_size``. In the
others::

    s = sigmoid(u W_r)                    all published experts
    top = the num_experts_per_tok largest
    g_i = s_i / (sum_top s + 1e-20) * routed_scaling_factor
    F(u) = shared(u) + sum_{i in top and held here} g_i expert_i(u)

The experts held here are ``deployment.experts_held`` of
``deployment.published_n_routed_experts``; what the others would add is
left out (the chip's share of a layer, ``model-configs`` section 4).
``seq_aux``: the loss adds ``assumed.balance_loss_weight`` times, summed
over the expert layers, ``sum_i f_i P_i`` with ``f_i = E / (k T) *
#{t: i in top_t}`` and ``P_i = mean_t s_i / sum_j s_j``.

It runs one layer at a time, so that it fits beside the training state
of a chip: the caller hands the layers over as an iterator of
dictionaries, one a layer in order. The head is untied.

Departures from the published code, none in the mathematics:
* weight matrices are taken as [in, out] (``x @ w``);
* the rotary pairs are (i, i + d/2) and not the published code's
  interleaved (2i, 2i + 1): the same function of the weights under a
  fixed permutation of the projection's columns;
* ``topk_method: "none"`` is read as plain top-k over every expert's
  score; ``n_group`` and ``topk_group`` are not used;
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


def swiglu(u, w):
    return mm(jax.nn.silu(mm(u, w["w_gate"])) * mm(u, w["w_up"]),
              w["w_down"])


def route(u, w_router, model):
    """(selected experts [seq, k], their weights [seq, k], every
    expert's score [seq, E])."""
    scores = jax.nn.sigmoid(mm(u, w_router))
    top_s, top_i = jax.lax.top_k(scores, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_i, top_s * model["routed_scaling_factor"], scores


def balance(scores, top_i):
    """``sum_i f_i P_i`` of one sequence."""
    seq, experts = scores.shape
    chosen = jnp.sum(jax.nn.one_hot(top_i, experts), axis=(0, 1))
    f = chosen * experts / (seq * top_i.shape[1])
    p = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    return jnp.sum(f * p)


def expert_layer(u, w, model):
    """(F(u), the balance term, the selected experts)."""
    top_i, gate, scores = route(u, w["w_router"], model)
    out = jnp.zeros_like(u)
    if model["n_shared_experts"]:
        out = out + swiglu(u, w["shared"])
    for slot, expert in enumerate(model["deployment"]["experts_held"]):
        g = jnp.sum(jnp.where(top_i == expert, gate, 0.0), axis=-1)
        mine = jax.tree.map(lambda a: a[slot], w["experts"])
        out = out + g[:, None] * swiglu(u, mine)
    return out, balance(scores, top_i), top_i


def head_loss(h, head, labels):
    """Mean cross entropy of ``labels`` under logits ``h @ head``."""
    logits = mm(h, head)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss(model, ids, labels, table, layers, final_norm, head,
         selections=None, hidden=None):
    """The training loss of ``labels`` [seq] given ``ids`` [seq]: mean
    cross entropy plus the weighted balance terms. ``table`` [vocab,
    hidden]; ``head`` [hidden, vocab]; ``layers`` yields one dictionary
    a layer, in order: ``input_norm``, ``attn``, ``post_norm`` and
    ``mlp`` (a dense layer) or ``moe`` (``w_router``, ``shared``,
    ``experts`` with the held experts stacked in ``experts_held``'s
    order); every array is cast to float32 here. ``selections``, a
    list, receives every expert layer's selected experts; ``hidden``,
    a list, the final normed hidden states [seq, hidden]."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    eps = model["rms_norm_eps"]
    dense = model["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        run = {
            "norm": jax.jit(lambda x, s: rms_norm(x, s, eps)),
            "attention": jax.jit(
                lambda x, w: latent_attention(x, w, model)),
            "mlp": jax.jit(swiglu),
            "moe": jax.jit(lambda u, w: expert_layer(u, w, model)),
        }
        h = jnp.asarray(table[ids], jnp.float32)
        aux = 0.0
        for i, w in enumerate(layers):
            w = f32(w)
            h = h + run["attention"](run["norm"](h, w["input_norm"]),
                                     w["attn"])
            u = run["norm"](h, w["post_norm"])
            if i < dense:
                h = h + run["mlp"](u, w["mlp"])
            else:
                y, term, top_i = run["moe"](u, w["moe"])
                h, aux = h + y, aux + term
                if selections is not None:
                    selections.append(top_i)
        assert i == model["num_hidden_layers"] - 1, (
            f"{i + 1} layers were handed over")
        h = run["norm"](h, f32(final_norm))
        if hidden is not None:
            hidden.append(h)
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        nll = jax.jit(head_loss)(h, f32(head), jnp.asarray(labels))
        return nll + model["assumed"]["balance_loss_weight"] * aux
