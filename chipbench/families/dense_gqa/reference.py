"""A plain reference for the Mistral decoder (``MistralForCausalLM`` as
the model's public ``config.json`` and the Mistral 7B paper describe
it): pre-norm blocks of RMSNorm, rotary grouped-query attention with a
causal mask, a SwiGLU feed-forward, a final RMSNorm, an untied head and
the mean next-token cross entropy. Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")`` (on a TPU a
float32 matmul otherwise runs in bf16 passes); no kernel, no cache, no
batching, no sharding, nothing imported from the program.

It runs one layer at a time, so that a model whose float32 weights do
not fit a chip can still be checked: the caller hands the layers over
as an iterator of dictionaries and may convert each as it is asked for.

Departures from the published code, none in the mathematics:
* weight matrices are taken as [in, out] (``x @ w``); the published
  checkpoint stores [out, in];
* attention is computed one key-value head at a time (its group of
  query heads together), which bounds the [heads, seq, seq] scores;
* no sliding window: v0.3's ``sliding_window`` is null.
"""

import jax
import jax.numpy as jnp

LAYER_KEYS = ("input_norm", "wq", "wk", "wv", "wo", "post_norm",
              "w_gate", "w_up", "w_down")


def rms_norm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary(x, theta):
    """x: [seq, heads, head_dim]; positions 0..seq-1."""
    seq, _, dim = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                / dim))
    freqs = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def attention(x, w, model):
    """x: [seq, hidden] -> [seq, hidden]."""
    seq = x.shape[0]
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    dim = model.get("head_dim") or model["hidden_size"] // heads
    group = heads // kv_heads
    q = rotary((x @ w["wq"]).reshape(seq, heads, dim), model["rope_theta"])
    k = rotary((x @ w["wk"]).reshape(seq, kv_heads, dim),
               model["rope_theta"])
    v = (x @ w["wv"]).reshape(seq, kv_heads, dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    outs = []
    for j in range(kv_heads):
        qj = q[:, j * group:(j + 1) * group]  # [seq, group, dim]
        scores = jnp.einsum("sgd,td->gst", qj, k[:, j]) / jnp.sqrt(
            jnp.float32(dim))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("gst,td->sgd", probs, v[:, j]))
    out = jnp.concatenate(outs, axis=1).reshape(seq, heads * dim)
    return out @ w["wo"]


def feed_forward(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def block(h, w, model):
    eps = model["rms_norm_eps"]
    h = h + attention(rms_norm(h, w["input_norm"], eps), w, model)
    return h + feed_forward(rms_norm(h, w["post_norm"], eps), w)


def next_token_loss(h, final_norm, head, labels, eps):
    logits = rms_norm(h, final_norm, eps) @ head
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logprobs, labels[:, None], axis=-1)
    return -jnp.mean(picked)


def loss(model, ids, labels, embed, layers, final_norm, head):
    """The mean cross entropy of ``labels`` [seq] given ``ids`` [seq].
    ``layers`` yields one dictionary of ``LAYER_KEYS`` a layer, in
    order; every array is cast to float32 here."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        run_block = jax.jit(lambda h, w: block(h, w, model))
        h = jnp.asarray(embed[ids], jnp.float32)
        for w in layers:
            h = run_block(h, f32(w))
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        return jax.jit(
            lambda h, n, w, y: next_token_loss(h, n, w, y,
                                               model["rms_norm_eps"])
        )(h, f32(final_norm), f32(head), jnp.asarray(labels))
