"""A plain reference for Granite-4.0-H-Micro's decoder (the model's
public ``config.json`` as the ``model-configs`` catalog quotes it, and
ISSUE 57's equations from it and from the family's public modelling
code, whose Mamba layer is Mamba-2's, arXiv:2405.21060).
Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the state-space recurrence
TOKEN BY TOKEN, as it is defined (no chunk, no decay matrix), attention
as a dense masked softmax a head; no kernel, no batching, no sharding,
nothing imported from the program.

``D`` = ``hidden_size``, RMSNorm with a learned scale and eps
``rms_norm_eps``, no bias but the convolution's::

    h_0  = embedding_multiplier * E[ids]
    h'   = h  + residual_multiplier * mixer_l(RMSNorm_in(h))
    h''  = h' + residual_multiplier * MLP(RMSNorm_post(h'))
    logits = RMSNorm_f(h_L) E^T / logits_scaling        the head is E

    MLP(u) = (silu(a) * b) W_out,  [a | b] = u W_in     (one matrix of
                                   2 x shared_intermediate_size columns)

``mixer_l`` where ``layer_types[l]`` is ``attention`` (32 query heads on
8 KV heads of 64)::

    q, k, v = u W_q, u W_k, u W_v                       NO position
    a = softmax(q k^T * attention_multiplier + causal) v
    mixer = a W_o

the scale is the published 0.015625 itself, not 1 / sqrt(64). Where it
is ``mamba`` (``H`` = 64 heads of ``P`` = 64, ``G`` = 1 group, a state
of ``N`` = 128; per head unless said)::

    [z | xBC | dt_raw] = u W_in       columns H P | H P + 2 G N | H
    xBC = silu(conv(xBC) + b_conv)    causal, depthwise, mamba_d_conv taps
    [x | B | C] = xBC                 columns H P | G N | G N
    dt = softplus(dt_raw + dt_bias);   A = -exp(A_log)
    S_0 = 0;  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T     [P, N]
    y_t = S_t C_t + D_skip x_t
    o = RMSNorm_g(y * silu(z))        the gate first, the norm over all
                                      H P columns, one learned scale
    mixer = o W_out

It runs one layer at a time, so that it fits beside the training state
of a chip: the caller hands the layers over as an iterator of
dictionaries, one a layer in order. Attention is computed one head and
``ROW_BLOCK`` query rows at a time, which bounds the [rows, seq] scores,
and the head and its cross entropy ``ROW_BLOCK`` rows at a time, which
bounds the [rows, vocabulary] logits.

Departures from the published code, none in the mathematics:
* weight matrices are taken as [in, out] (``x @ w``), a convolution's
  filter as [taps, channels] with the last tap on the current token;
* the recurrence is run as defined, where the published code runs its
  chunked form.

Every mechanism is a function of this module, so that a test can swap
one for a wrong one and see the comparison fail
(``tests/chipbench/ssd_hybrid_controls.py``).
"""

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024  # query rows of one head, or rows of logits, at a time
MAMBA, ATTENTION = "mamba", "attention"


def mm(a, b):
    """Every matrix product of the reference goes through here (float32
    at the highest precision; a test that shows what lower precision
    would do replaces it)."""
    return jnp.matmul(a, b)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def embed(table, ids, model):
    return model["embedding_multiplier"] * table[ids]


def residual(x, y, model):
    """What a sublayer adds to the stream."""
    return x + model["residual_multiplier"] * y


def attention_scale(model):
    return model["attention_multiplier"]


def conv(u, taps, bias):
    """Causal depthwise convolution over the row: ``u`` [seq,
    channels], ``taps`` [width, channels]; out[t] = bias + sum_i taps[i]
    * u[t - (width - 1) + i], nothing before the row's start."""
    width, seq = taps.shape[0], u.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((width - 1, u.shape[1]), u.dtype), u])
    return bias + sum(taps[i] * padded[i:i + seq] for i in range(width))


def positions(q, k, model):
    """What an attention layer does to q and k [seq, heads, d] for
    position: nothing (``position_embedding_type`` is ``nope``)."""
    del model
    return q, k


def step_size(dt_raw, dt_bias):
    return jax.nn.softplus(dt_raw + dt_bias)


def carried(state):
    """The state as it goes from one token to the next: float32."""
    return state


def skip(y, x, d_skip):
    return y + d_skip[:, None] * x


def gated_norm(y, z, scale, eps):
    """The gate first, then the norm over all the columns."""
    return rms_norm(y * jax.nn.silu(z), scale, eps)


def recurrence(x, dt, a, b, c):
    """The state-space recurrence token by token. ``x`` [seq, H, P];
    ``dt`` [seq, H]; ``a`` [H]; ``b``, ``c`` [seq, G, N], a group's
    shared by its ``H / G`` heads. Returns [seq, H, P]."""
    heads, p = x.shape[1:]
    groups, n = b.shape[1:]

    def per_head(t):  # [G, N] -> [H, N]
        return jnp.repeat(t, heads // groups, axis=0)

    def step(state, xs):  # state [H, P, N]
        x_t, dt_t, b_t, c_t = xs
        state = carried(
            jnp.exp(dt_t * a)[:, None, None] * state
            + (dt_t[:, None] * x_t)[:, :, None] * per_head(b_t)[:, None, :])
        return state, jnp.sum(state * per_head(c_t)[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                        (x, dt, b, c))
    return y


def mamba(u, w, model):
    """``u`` [seq, hidden], normed."""
    seq = u.shape[0]
    heads, p, groups, n = (model["mamba_n_heads"], model["mamba_d_head"],
                           model["mamba_n_groups"], model["mamba_d_state"])
    inner, shared = heads * p, groups * n
    if inner != model["mamba_expand"] * model["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    zxd = mm(u, w["w_in"])
    z, xbc, dt_raw = (zxd[:, :inner], zxd[:, inner:2 * inner + 2 * shared],
                      zxd[:, 2 * inner + 2 * shared:])
    xbc = jax.nn.silu(conv(xbc, w["conv_w"], w["conv_b"]))
    x = xbc[:, :inner].reshape(seq, heads, p)
    b = xbc[:, inner:inner + shared].reshape(seq, groups, n)
    c = xbc[:, inner + shared:].reshape(seq, groups, n)
    y = recurrence(x, step_size(dt_raw, w["dt_bias"]), -jnp.exp(w["a_log"]),
                   b, c)
    y = skip(y, x, w["d_skip"]).reshape(seq, inner)
    return mm(gated_norm(y, z, w["norm"], model["rms_norm_eps"]), w["w_out"])


def attention(u, w, model):
    """``u`` [seq, hidden], normed."""
    seq = u.shape[0]
    heads, kv_heads = (model["num_attention_heads"],
                       model["num_key_value_heads"])
    hd = model["assumed"]["head_dim"]
    q = mm(u, w["wq"]).reshape(seq, heads, hd)
    k = mm(u, w["wk"]).reshape(seq, kv_heads, hd)
    v = mm(u, w["wv"]).reshape(seq, kv_heads, hd)
    q, k = positions(q, k, model)
    scale = attention_scale(model)
    block = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    j = jnp.arange(seq)[None, :]

    def one_head(q_h, k_h, v_h):  # [seq, hd] each

        def rows(start):
            t = start + jnp.arange(block)[:, None]
            scores = mm(jax.lax.dynamic_slice_in_dim(q_h, start, block),
                        k_h.T) * scale
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


def mlp(u, w, model):
    f = model["shared_intermediate_size"]
    ab = mm(u, w["w_in"])
    return mm(jax.nn.silu(ab[:, :f]) * ab[:, f:], w["w_out"])


def layer(x, w, model, kind):
    """One layer of ``x`` [seq, hidden]: (the stream after it, what its
    mixer read, what its mixer gave)."""
    eps = model["rms_norm_eps"]
    mixer = mamba if kind == MAMBA else attention
    u = rms_norm(x, w["in_norm"], eps)
    y = mixer(u, w["mixer"], model)
    x = residual(x, y, model)
    return residual(x, mlp(rms_norm(x, w["post_norm"], eps), w["mlp"], model),
                    model), u, y


def head_loss(h, table, labels, model):
    """Mean cross entropy of ``labels`` under logits ``h E^T /
    logits_scaling``, ``ROW_BLOCK`` rows at a time."""
    seq = h.shape[0]
    block = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    def rows(start):
        logits = mm(jax.lax.dynamic_slice_in_dim(h, start, block),
                    table.T) / model["logits_scaling"]
        picked = jnp.take_along_axis(
            logits, jax.lax.dynamic_slice_in_dim(labels, start, block)[
                :, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return jnp.mean(jax.lax.map(rows, jnp.arange(0, seq, block)))


def loss(model, ids, labels, table, layers, final_norm, hidden=None,
         attended=None):
    """The training loss of ``labels`` [seq] given ``ids`` [seq]: mean
    cross entropy over the vocabulary. ``table`` [vocab, hidden], the
    head too; ``layers`` yields one dictionary a layer, in order:
    ``mixer`` (a Mamba layer's ``w_in``, ``conv_w``, ``conv_b``,
    ``a_log``, ``dt_bias``, ``d_skip``, ``norm``, ``w_out``; an
    attention layer's ``wq``, ``wk``, ``wv``, ``wo``), ``in_norm``,
    ``mlp`` (``w_in``, ``w_out``) and ``post_norm``; every array is cast
    to float32 here. ``hidden``, a list, receives the final normed
    hidden states [seq, hidden]; ``attended``, a list, what the first
    attention layer's mixer read and what it gave, [seq, hidden]
    each."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    depth = model["num_hidden_layers"]
    kinds = model["layer_types"][:depth]
    if set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(f"layer_types holds {sorted(set(kinds))}")
    if (not model["tie_word_embeddings"] or model["num_local_experts"]
            or model["position_embedding_type"] != "nope"):
        raise ValueError("the reference computes a tied head, the shared "
                         "MLP alone and no position")
    with jax.default_matmul_precision("highest"):
        # one program a kind of layer, not one a layer
        run = {kind: jax.jit(lambda x, w, kind=kind: layer(
            x, w, model, kind)) for kind in set(kinds)}
        table = f32(table)
        h = jax.jit(lambda t, i: embed(t, i, model))(table, jnp.asarray(ids))
        count = 0
        for kind, w in zip(kinds, layers):
            h, u, y = run[kind](h, f32(w))
            if kind == ATTENTION and attended is not None and not attended:
                attended += [u, y]
            count += 1
        assert count == depth, f"{count} layers were handed over"
        h = jax.jit(lambda x, s: rms_norm(x, s, model["rms_norm_eps"]))(
            h, f32(final_norm))
        if hidden is not None:
            hidden.append(h)
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        return jax.jit(lambda x, t, y: head_loss(x, t, y, model))(
            h, table, jnp.asarray(labels))
