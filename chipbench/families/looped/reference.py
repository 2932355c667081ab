"""A plain reference for Ouro-2.6B's looped decoder (the model's public
``config.json`` as the ``model-configs`` catalog quotes it, and ISSUE
65's equations from it and from "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741). Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: a Python
loop over the passes and over the layers, attention as a dense masked
softmax a head, the whole logits of a block of rows; no kernel, no
scan over a stack, no sharding, nothing imported from the program.

With ``T = total_ut_steps`` passes over ``L`` layers, RMSNorm with eps
``rms_norm_eps``, no bias but the gate's::

    h = E[ids]
    for t in 1..T, with the SAME layers every time:
        for l in 1..L:
            h = h + N2_l(Attn_l(N1_l(h)))
            h = h + N4_l(MLP_l(N3_l(h)))
        h = N_f(h);  h^(t) = h            the final norm, in every pass

    Attn(u): q, k, v = u W_q, u W_k, u W_v; rotate-half rotary at
             rope_theta on all of q's and k's columns;
             softmax(q k^T / sqrt(head_dim) + causal) v; then W_o
    MLP(u) = (silu(u W_g) * (u W_u)) W_d

    lambda_t = sigmoid(h^(t) w_g + b_g)
    p_1 = lambda_1;  p_t = lambda_t prod_{j<t} (1 - lambda_j), 1 < t < T
    p_T = prod_{j<T} (1 - lambda_j)
    L_t = CE(h^(t) W_head, label)         a token
    loss = mean over tokens of [sum_t p_t L_t - beta H(p)]
    H(p) = - sum_t p_t log p_t

It runs one layer at a time, so that it fits beside the training state
of a chip: the caller hands the layers over as a function that yields
them anew for every pass. Attention is computed one head and
``ROW_BLOCK`` query rows at a time, which bounds the [rows, seq]
scores, and the head ``ROW_BLOCK`` rows at a time, which bounds the
[rows, vocab] logits.

Departures from the published code, none in the mathematics:
* weight matrices are taken as [in, out] (``x @ w``);
* ``p log p`` is taken as 0 where ``p`` is 0 (float32 can round a
  product of ``1 - lambda`` to 0; the limit is 0);
* ``early_exit_threshold`` is inference's and does nothing here: a
  training step runs every pass of every token.

Every mechanism is a function of this module, so that a test can swap
one for a wrong one and see the comparison fail
(``tests/chipbench/looped_controls.py``).
"""

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024  # rows scored, or projected to the vocabulary, at a time


def mm(a, b):
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope_theta(model):
    return float(model["rope_theta"])


def rotary(x, theta):
    """Rotate-half on all columns: ``x`` [seq, heads, hd]; pair ``i`` is
    (x[i], x[i + hd/2]) and turns by ``t * theta^(-i / (hd/2))``."""
    seq, _, hd = x.shape
    half = hd // 2
    angles = (jnp.arange(seq, dtype=jnp.float32)[:, None]
              * theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(u, w, model):
    """``u`` [seq, hidden], normed."""
    seq = u.shape[0]
    heads, kv_heads, hd = (model["num_attention_heads"],
                           model["num_key_value_heads"], model["head_dim"])
    theta = rope_theta(model)
    q = rotary(mm(u, w["wq"]).reshape(seq, heads, hd), theta)
    k = rotary(mm(u, w["wk"]).reshape(seq, kv_heads, hd), theta)
    v = mm(u, w["wv"]).reshape(seq, kv_heads, hd)
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


def swiglu(u, w):
    return mm(jax.nn.silu(mm(u, w["w_gate"])) * mm(u, w["w_up"]),
              w["w_down"])


def sublayer(x, f, in_scale, out_scale, eps):
    """The sandwich: the sublayer reads the normed ``x``, and its
    output is normed, then added."""
    return x + rms_norm(f(rms_norm(x, in_scale, eps)), out_scale, eps)


def layer(x, w, model):
    """One layer of ``x`` [seq, hidden]."""
    eps = model["rms_norm_eps"]
    x = sublayer(x, lambda u: attention(u, w["attn"], model),
                 w["input_norm"], w["attn_out_norm"], eps)
    return sublayer(x, lambda u: swiglu(u, w["mlp"]),
                    w["post_norm"], w["mlp_out_norm"], eps)


def close_pass(h, final_norm, eps):
    """What closes a pass: the one final norm. Its result is what the
    head and the gate read."""
    return rms_norm(h, final_norm, eps)


def next_input(h, state):
    """What the next pass's first layer takes in: the normed ``state``
    that the head read, not the stream ``h`` before the norm."""
    return state


def exit_distribution(lam):
    """``lam`` [T, seq], the gate's sigmoid after each pass -> ``p``
    [T, seq]: a token exits at ``t`` with ``lam_t`` if it has not
    before, and the last pass takes the rest (``lam_T`` is not
    used)."""
    passes = lam.shape[0]
    stayed, p = jnp.ones_like(lam[0]), []
    for t in range(passes - 1):
        p.append(lam[t] * stayed)
        stayed = stayed * (1.0 - lam[t])
    return jnp.stack(p + [stayed])


def entropy(p):
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                              0.0), axis=0)


def head_weights(p):
    """The weight of pass ``t``'s cross entropy, a token: ``p_t``."""
    return p


def token_nll(h, head, labels):
    """Cross entropy a token of ``labels`` [seq] under ``h @ head``,
    ``ROW_BLOCK`` rows of logits at a time (a gradient makes a block's
    logits again: kept, those of 8192 x 49152 x ``T`` are 6.4 GB)."""
    seq = h.shape[0]
    block = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    @jax.checkpoint
    def rows(start):
        logits = mm(jax.lax.dynamic_slice_in_dim(h, start, block), head)
        picked = jnp.take_along_axis(
            logits, jax.lax.dynamic_slice_in_dim(labels, start, block)[
                :, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq)


def objective(states, gate_w, gate_b, head, labels, beta):
    """``states`` [T, seq, hidden], the normed state after each pass ->
    (the loss, the ``T`` mean cross entropies ``L_t``, the mean exit
    distribution [T])."""
    lam = jax.nn.sigmoid(mm(states, gate_w)[..., 0] + gate_b)
    p = exit_distribution(lam)
    nll = jnp.stack([token_nll(h, head, labels) for h in states])
    loss = jnp.mean(jnp.sum(head_weights(p) * nll, axis=0)
                    - beta * entropy(p))
    return loss, nll.mean(axis=1), p.mean(axis=1)


def objective_gradient(model, pass_states, labels, gate, head):
    """``jax.grad`` of ``objective``'s loss with respect to the gate's
    kernel [hidden, 1] and the head [hidden, vocab], at the reference's
    own ``pass_states`` [T, seq, hidden] (``run``'s): one more head pass
    and its transpose, nothing of the stack."""
    beta = model["assumed"]["exit_entropy_beta"]
    gate_w, gate_b = (jnp.asarray(a, jnp.float32) for a in gate)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(
            lambda gw, hd, states, lb: objective(
                states, gw, gate_b, hd, lb, beta)[0], argnums=(0, 1)))(
                    gate_w, jnp.asarray(head, jnp.float32), pass_states,
                    jnp.asarray(labels))


def run(model, ids, labels, table, layers, final_norm, gate, head):
    """The stage-I training objective of ``labels`` [seq] given ``ids``
    [seq]: a dictionary of ``loss``, ``pass_losses`` [T],
    ``exit_distribution`` [T] (the mean over tokens), ``states`` (the
    last pass's normed states [seq, hidden]) and ``pass_states`` (every
    pass's, [T, seq, hidden]). ``table`` [vocab,
    hidden]; ``head`` [hidden, vocab]; ``gate`` a pair ([hidden, 1],
    [1]); ``layers()`` yields one dictionary a layer, in order, and is
    called once a pass: ``input_norm``, ``attn`` (``wq``, ``wk``,
    ``wv``, ``wo``), ``attn_out_norm``, ``post_norm``, ``mlp``
    (``w_gate``, ``w_up``, ``w_down``) and ``mlp_out_norm``; every
    array is cast to float32 here."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    depth, passes = model["num_hidden_layers"], model["total_ut_steps"]
    eps = model["rms_norm_eps"]
    beta = model["assumed"]["exit_entropy_beta"]
    with jax.default_matmul_precision("highest"):
        one_layer = jax.jit(lambda x, w: layer(x, w, model))
        close = jax.jit(lambda x, s: close_pass(x, s, eps))
        h = jnp.asarray(table[ids], jnp.float32)
        states = []
        for _ in range(passes):
            count = 0
            for w in layers():
                h = one_layer(h, f32(w))
                count += 1
            assert count == depth, f"{count} layers were handed over"
            states.append(close(h, f32(final_norm)))
            h = next_input(h, states[-1])
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        pass_states = jnp.stack(states)
        loss, pass_losses, mean_p = jax.jit(
            lambda *a: objective(*a, beta))(
                pass_states, *f32(gate), f32(head), jnp.asarray(labels))
    return {"loss": loss, "pass_losses": pass_losses,
            "exit_distribution": mean_p, "states": states[-1],
            "pass_states": pass_states}
