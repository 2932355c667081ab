"""A plain reference for SmallThinker-21BA3B-Instruct's decoder (the
model's public ``config.json`` as the ``model-configs`` catalog quotes
it, and ISSUE 41's equations from it). Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: attention as
a dense masked softmax a head, the experts as a loop over the experts
held here; no kernel, no sorting, no batching, no sharding, nothing
imported from the program.

Layer ``l``, pre-norm residual, RMSNorm with eps ``rms_norm_eps``, no
biases, with ``w_l = sliding_window_layout[l]`` and ``r_l =
rope_layout[l]``::

    u = RMSNorm_in(x)
    q, k, v = u W_q, u W_k, u W_v      28 query, 4 KV heads of 128
    if r_l: q, k = rot(q), rot(k)      theta rope_theta, positions 0..S-1
    a = softmax(q k^T / sqrt(128) + mask_l) v   a KV head serves 7 heads
        key j visible to query t where j <= t and, if w_l,
        t - sliding_window_size < j
    x' = x + a W_o
    g = u W_r                          THE ROUTER READS u, not z
    top = the moe_num_active_primary_experts largest g
    p = softmax(g[top])                (softmax over all, top-k,
                                        renormalised: the same numbers)
    z = RMSNorm_post(x')
    y = sum_{e in top and held here} p_e W_down_e (relu(W_gate_e z)
                                                   * (W_up_e z))
    x'' = x' + y

then the final RMSNorm and the untied head. No shared expert, no dense
layer, no balance loss, no router bias: the config has none. The
experts held here are ``deployment.experts_held`` of ``deployment.
published_moe_num_primary_experts``; what the others would add is left
out (the chip's share of a layer, ``model-configs`` section 4).

It runs one layer at a time, so that it fits beside the training state
of a chip: the caller hands the layers over as an iterator of
dictionaries, one a layer in order. Attention is computed one head and
``ROW_BLOCK`` query rows at a time, which bounds the [rows, seq]
scores (28 x 16,384^2 float32 scores whole are 30 GB).

Departures from the published code, none in the mathematics:
* weight matrices are taken as [in, out] (``x @ w``);
* the rotary pairs are (i, i + d/2) (rotate-half), as the public
  modeling code has them;
* ``described_as`` names "secondary experts": the config has no key
  for them and they are not computed.
"""

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024  # query rows of one head scored at a time


def mm(a, b):
    """Every matrix product of the reference goes through here (float32
    at the highest precision; a test that shows what lower precision
    would do replaces it)."""
    return jnp.matmul(a, b)


def act(x):
    """The gate's activation: the experts are ReGLU."""
    return jax.nn.relu(x)


def router_input(u, z):
    """What the router reads, of ``u = RMSNorm_in(x)`` and ``z =
    RMSNorm_post(x')``: the attention's input."""
    del z
    return u


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary_tables(model, seq):
    """cos and sin [seq, d/2]: pair ``i`` turns at ``theta^(-2i/d)``."""
    d = model["head_dim"]
    inv_freq = model["rope_theta"] ** (
        -2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """``x`` [seq, heads, d]; pair ``i`` is (x[..., i], x[..., i + d/2])."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(u, w, model, windowed, rotary):
    """``u`` [seq, hidden], already normed; ``windowed`` and ``rotary``
    are the layer's entries of the two published lists."""
    seq = u.shape[0]
    heads, kv_heads, hd = (model["num_attention_heads"],
                           model["num_key_value_heads"], model["head_dim"])
    window = model["sliding_window_size"]
    q = mm(u, w["wq"]).reshape(seq, heads, hd)
    k = mm(u, w["wk"]).reshape(seq, kv_heads, hd)
    v = mm(u, w["wv"]).reshape(seq, kv_heads, hd)
    if rotary:
        cos, sin = rotary_tables(model, seq)
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    block = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    j = jnp.arange(seq)[None, :]

    def one_head(q_h, k_h, v_h):  # [seq, hd] each

        def rows(start):
            t = start + jnp.arange(block)[:, None]
            visible = j <= t
            if windowed:
                visible = visible & (t - window < j)
            scores = mm(jax.lax.dynamic_slice_in_dim(q_h, start, block),
                        k_h.T) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf),
                                   axis=-1)
            return mm(probs, v_h)

        return jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, hd)

    # query head h reads KV head h // (heads / kv_heads)
    serves = heads // kv_heads
    out = jax.lax.map(lambda qkv: one_head(*qkv), (
        q.transpose(1, 0, 2),
        jnp.repeat(k.transpose(1, 0, 2), serves, axis=0),
        jnp.repeat(v.transpose(1, 0, 2), serves, axis=0)))
    return mm(out.transpose(1, 0, 2).reshape(seq, heads * hd), w["wo"])


def route(r, w_router, model):
    """(selected experts [seq, k], their weights [seq, k]) from the
    router's input ``r`` [seq, hidden]."""
    if not model["moe_primary_router_apply_softmax"]:
        raise ValueError("the reference routes by a softmax over the "
                         "router's logits")
    logits = mm(r, w_router)
    top_l, top_i = jax.lax.top_k(
        logits, model["moe_num_active_primary_experts"])
    if model["norm_topk_prob"]:
        return top_i, jax.nn.softmax(top_l, axis=-1)
    return top_i, jnp.take_along_axis(
        jax.nn.softmax(logits, axis=-1), top_i, axis=-1)


def reglu(z, w):
    return mm(act(mm(z, w["w_gate"])) * mm(z, w["w_up"]), w["w_down"])


def expert_layer(z, w, top_i, gate, model):
    """What the experts held here give ``z`` [seq, hidden] under the
    routing ``(top_i, gate)``."""
    out = jnp.zeros_like(z)
    for slot, expert in enumerate(model["deployment"]["experts_held"]):
        g = jnp.sum(jnp.where(top_i == expert, gate, 0.0), axis=-1)
        mine = jax.tree.map(lambda a: a[slot], w)
        out = out + g[:, None] * reglu(z, mine)
    return out


def layer(x, w, model, windowed, rotary):
    """One layer of ``x`` [seq, hidden]: (the layer's output, the
    selected experts)."""
    eps = model["rms_norm_eps"]
    u = rms_norm(x, w["input_norm"], eps)
    x = x + attention(u, w["attn"], model, windowed, rotary)
    z = rms_norm(x, w["post_norm"], eps)
    top_i, gate = route(router_input(u, z), w["w_router"], model)
    return x + expert_layer(z, w["experts"], top_i, gate, model), top_i


def head_loss(h, head, labels):
    """Mean cross entropy of ``labels`` under logits ``h @ head``."""
    logits = mm(h, head)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss(model, ids, labels, table, layers, final_norm, head,
         selections=None, hidden=None):
    """The training loss of ``labels`` [seq] given ``ids`` [seq]: mean
    cross entropy over the vocabulary slice. ``table`` [vocab, hidden];
    ``head`` [hidden, vocab]; ``layers`` yields one dictionary a layer,
    in order: ``input_norm``, ``attn`` (``wq``, ``wk``, ``wv``,
    ``wo``), ``post_norm``, ``w_router`` and ``experts`` (``w_gate``,
    ``w_up``, ``w_down`` with the held experts stacked in
    ``experts_held``'s order); every array is cast to float32 here.
    ``selections``, a list, receives every layer's selected experts;
    ``hidden``, a list, the final normed hidden states [seq, hidden]."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    depth = model["num_hidden_layers"]
    kinds = list(zip(model["sliding_window_layout"],
                     model["rope_layout"]))[:depth]
    with jax.default_matmul_precision("highest"):
        # one program a kind of layer, not one a layer
        run = {kind: jax.jit(lambda x, w, kind=kind: layer(
            x, w, model, *kind)) for kind in set(kinds)}
        h = jnp.asarray(table[ids], jnp.float32)
        count = 0
        for kind, w in zip(kinds, layers):
            h, top_i = run[kind](h, f32(w))
            count += 1
            if selections is not None:
                selections.append(top_i)
        assert count == depth, f"{count} layers were handed over"
        h = jax.jit(lambda x, s: rms_norm(x, s, model["rms_norm_eps"]))(
            h, f32(final_norm))
        if hidden is not None:
            hidden.append(h)
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        return jax.jit(head_loss)(h, f32(head), jnp.asarray(labels))
