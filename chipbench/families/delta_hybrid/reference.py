"""A plain reference for Olmo-Hybrid-7B's decoder (the model's public
``config.json`` as the ``model-configs`` catalog quotes it, and ISSUE
43's equations from it). Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the gated delta rule TOKEN
BY TOKEN, as the recurrence defines it (no chunk, no triangular
inverse), attention as a dense masked softmax a head; no kernel, no
batching, no sharding, nothing imported from the program.

Layer ``l``, RMSNorm with eps ``rms_norm_eps``, no biases::

    x'  = x  + RMSNorm_attn(mixer_l(x))     the mixer reads x itself;
    x'' = x' + RMSNorm_ffn(W_down(silu(W_gate x') * (W_up x')))
                                            its OUTPUT is normalised

``mixer_l`` where ``layer_types[l]`` is ``full_attention`` (30 heads of
128, as many KV heads)::

    q, k, v = x W_q, x W_k, x W_v
    q, k = RMSNorm_q(q), RMSNorm_k(k)       over all 3840 columns
    a = softmax(q k^T / sqrt(128) + causal) v        NO position
    mixer = a W_o

and where it is ``linear_attention`` (30 heads, dk 96, dv 192; per
head)::

    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
        conv: causal, depthwise, linear_conv_kernel_dim taps, no bias
    q, k = q / |q|, k / |k|;  q = q / sqrt(96)
    beta = 2 sigmoid(x W_b)                 2 where linear_allow_neg_eigval
    g = -exp(A_log) softplus(x W_a + dt_bias);  alpha = exp(g)
    S_0 = 0;  S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t
    o = RMSNorm_o(o) * silu(x W_g)          one learned [192] scale
    mixer = o W_o

then the final RMSNorm and the untied head.

It runs one layer at a time, so that it fits beside the training state
of a chip: the caller hands the layers over as an iterator of
dictionaries, one a layer in order. Attention is computed one head and
``ROW_BLOCK`` query rows at a time, which bounds the [rows, seq]
scores.

Departures from the published code, none in the mathematics:
* weight matrices are taken as [in, out] (``x @ w``), a convolution's
  filter as [taps, channels] with the last tap on the current token;
* the state is held as ``S^T`` ([dk, dv] a head).

Every mechanism is a function of this module, so that a test can swap
one for a wrong one and see the comparison fail
(``tests/chipbench/delta_hybrid_controls.py``).
"""

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024  # query rows of one head scored at a time
LINEAR, FULL = "linear_attention", "full_attention"


def mm(a, b):
    """Every matrix product of the reference goes through here (float32
    at the highest precision; a test that shows what lower precision
    would do replaces it)."""
    return jnp.matmul(a, b)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def sublayer(x, f, scale, eps):
    """The family's reordered norm: ``f`` reads ``x`` itself and its
    output is normalised, then added."""
    return x + rms_norm(f(x), scale, eps)


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


def qk_norm(u, scale, eps):
    """RMSNorm over all the columns of a full layer's q or k."""
    return rms_norm(u, scale, eps)


def positions(q, k, model):
    """What a full layer does to q and k [seq, heads, d] for position:
    nothing (``rope_parameters.rope_theta`` is null)."""
    del model
    return q, k


def decay(g):
    """alpha of the log-decay ``g``."""
    return jnp.exp(g)


def target(v_t, seen):
    """What a token writes along its key: its value less what the
    decayed state already answers there (the erase)."""
    return v_t - seen


def out_gate(o, gate):
    return o * jax.nn.silu(gate)


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token. ``q``, ``k`` [seq, heads, dk];
    ``v`` [seq, heads, dv]; ``g``, ``beta`` [seq, heads]. Returns
    [seq, heads, dv]."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(state, xs):  # state [heads, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        state = decay(g_t)[:, None, None] * state
        seen = jnp.sum(k_t[:, :, None] * state, axis=1)  # S k
        write = b_t[:, None] * target(v_t, seen)
        state = state + k_t[:, :, None] * write[:, None, :]
        return state, jnp.sum(q_t[:, :, None] * state, axis=1)  # S q

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def linear_attention(x, w, model):
    """``x`` [seq, hidden] itself, not normed."""
    seq = x.shape[0]
    heads, dk, dv = (model["linear_num_value_heads"],
                     model["linear_key_head_dim"],
                     model["linear_value_head_dim"])
    if model["linear_num_key_heads"] != heads:
        raise ValueError("the reference takes as many key heads as value "
                         "heads, as the source publishes")
    eps = model["rms_norm_eps"]

    def mixed(name, width):
        return jax.nn.silu(conv(mm(x, w[f"w{name}"]),
                                w[f"conv_{name}"])).reshape(seq, heads, width)

    q = unit(mixed("q", dk), eps) / math.sqrt(dk)
    k = unit(mixed("k", dk), eps)
    v = mixed("v", dv)
    beta = jax.nn.sigmoid(mm(x, w["wb"]))
    if model["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(mm(x, w["wa"]) + w["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = out_gate(rms_norm(o, w["o_norm"], eps),
                 mm(x, w["wg"]).reshape(seq, heads, dv))
    return mm(o.reshape(seq, heads * dv), w["wo"])


def full_attention(x, w, model):
    """``x`` [seq, hidden] itself, not normed."""
    seq = x.shape[0]
    heads, kv_heads = (model["num_attention_heads"],
                       model["num_key_value_heads"])
    hd = model["assumed"]["head_dim"]
    eps = model["rms_norm_eps"]
    q = qk_norm(mm(x, w["wq"]), w["q_norm"], eps).reshape(seq, heads, hd)
    k = qk_norm(mm(x, w["wk"]), w["k_norm"], eps).reshape(seq, kv_heads, hd)
    v = mm(x, w["wv"]).reshape(seq, kv_heads, hd)
    q, k = positions(q, k, model)
    block = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    j = jnp.arange(seq)[None, :]

    def one_head(q_h, k_h, v_h):  # [seq, hd] each

        def rows(start):
            t = start + jnp.arange(block)[:, None]
            scores = mm(jax.lax.dynamic_slice_in_dim(q_h, start, block),
                        k_h.T) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(j <= t, scores, -jnp.inf),
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


def swiglu(x, w):
    return mm(jax.nn.silu(mm(x, w["w_gate"])) * mm(x, w["w_up"]),
              w["w_down"])


def layer(x, w, model, kind):
    """One layer of ``x`` [seq, hidden]."""
    eps = model["rms_norm_eps"]
    mixer = linear_attention if kind == LINEAR else full_attention
    x = sublayer(x, lambda u: mixer(u, w["mixer"], model), w["attn_norm"],
                 eps)
    return sublayer(x, lambda u: swiglu(u, w["mlp"]), w["ffn_norm"], eps)


def head_loss(h, head, labels):
    """Mean cross entropy of ``labels`` under logits ``h @ head``."""
    logits = mm(h, head)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss(model, ids, labels, table, layers, final_norm, head, hidden=None):
    """The training loss of ``labels`` [seq] given ``ids`` [seq]: mean
    cross entropy over the vocabulary held. ``table`` [vocab, hidden];
    ``head`` [hidden, vocab]; ``layers`` yields one dictionary a layer,
    in order: ``mixer`` (a linear layer's ``wq``, ``wk``, ``wv``,
    ``wg``, ``wa``, ``wb``, ``wo``, ``conv_q``, ``conv_k``, ``conv_v``,
    ``a_log``, ``dt_bias``, ``o_norm``; a full layer's ``wq``, ``wk``,
    ``wv``, ``wo``, ``q_norm``, ``k_norm``), ``attn_norm``, ``mlp``
    (``w_gate``, ``w_up``, ``w_down``) and ``ffn_norm``; every array is
    cast to float32 here. ``hidden``, a list, receives the final normed
    hidden states [seq, hidden]."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    depth = model["num_hidden_layers"]
    kinds = model["layer_types"][:depth]
    if set(kinds) - {LINEAR, FULL}:
        raise ValueError(f"layer_types holds {sorted(set(kinds))}")
    with jax.default_matmul_precision("highest"):
        # one program a kind of layer, not one a layer
        run = {kind: jax.jit(lambda x, w, kind=kind: layer(
            x, w, model, kind)) for kind in set(kinds)}
        h = jnp.asarray(table[ids], jnp.float32)
        count = 0
        for kind, w in zip(kinds, layers):
            h = run[kind](h, f32(w))
            count += 1
        assert count == depth, f"{count} layers were handed over"
        h = jax.jit(lambda x, s: rms_norm(x, s, model["rms_norm_eps"]))(
            h, f32(final_norm))
        if hidden is not None:
            hidden.append(h)
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        return jax.jit(head_loss)(h, f32(head), jnp.asarray(labels))
