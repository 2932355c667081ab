"""A plain reference for the SambaY decoder of Phi-4-mini-flash-reasoning
(the model's public ``config.json``, and "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation", whose
largest model, with differential attention, is this one), as ISSUE 29
wrote its equations down. Straightforward ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``: the recurrence as a
``lax.scan`` over tokens, attention as a dense masked softmax; no
kernel, no chunking of the row, no batching, no sharding, nothing
imported from the program.

Every layer, 0-based index ``i`` of ``L``: ``x = x + Mix_i(LN(x));
x = x + MLP(LN(x))``, LayerNorm with scale and bias, ``MLP(u) = W2 (a *
silu(g))`` with ``[g, a] = W1 u`` in halves. A tied table, a final
LayerNorm, no embedding scale, no positional encoding. ``Mix_i``
(``kind``): even ``i`` a state-space slot, odd ``i`` an attention slot;
``i < L/2`` Mamba and window attention; ``i = L/2`` the Mamba whose scan
output is the memory ``m``; ``i = L/2 + 1`` full attention whose keys
and values are the shared KV; above, gated memory units and cross
attention to that KV.

It runs one layer at a time, so that it fits beside the training state
of a chip: the caller hands the layers over as an iterator of
dictionaries, one a layer in order, and may convert each as it is
asked for. The head runs over slices of the vocabulary.

Departures from the published code, none in the mathematics:
* weight matrices are taken as [in, out] (``x @ w``);
* the published code computes ``a_c`` in halves of the value width
  (its attention kernel wants equal widths); here a value head is 128
  wide, which is the same sums;
* which heads pair up is adjacent heads (query heads ``2p, 2p + 1`` are
  pair ``p``), as in the published differential-attention code;
* attention is computed one key pair at a time (its two query pairs
  together, a loop over the key pairs), which bounds the [pairs, seq,
  seq] scores;
* sizes the public config does not give come from the configuration
  file's ``assumed`` (the Mamba sizes, the head size, eps of the inner
  RMSNorm = ``layer_norm_eps``); projections of attention carry biases.
"""

import math

import jax
import jax.numpy as jnp


def kind(i, depth):
    """What ``Mix_i`` is."""
    half = depth // 2
    if i % 2 == 0:
        return "ssm" if i <= half else "gmu"
    if i < half:
        return "attention_window"
    return "attention_full" if i == half + 1 else "attention_cross"


def mm(a, b):
    """Every matrix product of the reference goes through here (float32
    at the highest precision; a test that shows what lower precision
    would do replaces it)."""
    return a @ b


def layer_norm(x, w, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w["scale"] + w["bias"]


def mlp(x, w):
    g, a = jnp.split(mm(x, w["w1"]), 2, axis=-1)
    return mm(a * jax.nn.silu(g), w["w2"])


def conv_causal_depthwise(u, weight, bias):
    """out[t, c] = sum_k weight[k, c] * u[t - (K - 1) + k, c] + bias[c],
    with u before the row's start taken as zero."""
    width = weight.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((width - 1, u.shape[1]), u.dtype), u])
    return bias + sum(weight[k] * padded[k:k + u.shape[0]]
                      for k in range(width))


def mamba(x, w, model):
    """x: [seq, hidden] -> (output [seq, hidden], the scan output
    before its gate [seq, d_inner])."""
    a = model["assumed"]
    rank, states = a["dt_rank"], a["d_state"]
    u, z = jnp.split(mm(x, w["w_in"]), 2, axis=-1)
    u = jax.nn.silu(conv_causal_depthwise(u, w["conv_w"], w["conv_b"]))
    rbc = mm(u, w["w_x"])
    r, b, c = (rbc[:, :rank], rbc[:, rank:rank + states],
               rbc[:, rank + states:])
    dt = jax.nn.softplus(mm(r, w["w_dt"]) + w["b_dt"])
    a_mat = -jnp.exp(w["a_log"])  # [d_inner, states]

    def token(h, xs):
        u_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t[:, None] * a_mat) * h
             + (dt_t * u_t)[:, None] * b_t[None, :])
        return h, h @ c_t + w["d"] * u_t

    _, y = jax.lax.scan(token, jnp.zeros_like(a_mat), (u, dt, b, c))
    return mm(y * jax.nn.silu(z), w["w_out"]), y


def gated_memory(x, memory, w):
    return mm(memory * jax.nn.silu(mm(x, w["w_g"])), w["w_out"])


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def lam_of(w, lam0):
    return (jnp.exp(jnp.dot(w["lq1"], w["lk1"]))
            - jnp.exp(jnp.dot(w["lq2"], w["lk2"])) + lam0)


def project_kv(x, w, model):
    """keys [seq, pairs, 2, head] and values [seq, pairs, 2 * head]."""
    head = model["assumed"]["head_dim"]
    pairs = model["num_key_value_heads"] // 2
    k = (mm(x, w["wk"]) + w["bk"]).reshape(-1, pairs, 2, head)
    v = (mm(x, w["wv"]) + w["bv"]).reshape(-1, pairs, 2 * head)
    return k, v


def differential_attention(x, w, model, lam0, kv, window=None):
    """x: [seq, hidden] against the keys and values ``kv`` (this
    layer's own, or the shared ones); ``lam0`` is ``lambda_init`` of
    the layer's index; ``window``: key j is visible to query t where
    t - window < j <= t, and where j <= t without."""
    seq = x.shape[0]
    head = model["assumed"]["head_dim"]
    pairs = model["num_attention_heads"] // 2
    keys, values = kv
    group = pairs // keys.shape[1]
    q = (mm(x, w["wq"]) + w["bq"]).reshape(seq, keys.shape[1], group, 2,
                                           head)
    t, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    visible = j <= t
    if window is not None:
        visible = visible & (t - window < j)
    lam = lam_of(w, lam0)

    def one_key_pair(qkv):
        mine, k, v = qkv  # [seq, group, 2, head], [seq, 2, head], [seq, 2 head]
        a = []
        for c in range(2):  # a_c = softmax(q_c k_c^T / sqrt(head)) v
            scores = jnp.einsum("sgd,td->gst", mine[:, :, c],
                                k[:, c]) / math.sqrt(head)
            probs = jax.nn.softmax(
                jnp.where(visible, scores, -jnp.inf), axis=-1)
            a.append(jnp.einsum("gst,te->gse", probs, v))
        diff = a[0] - lam * a[1]  # [group, seq, 2 * head]
        rms = jax.lax.rsqrt(jnp.mean(jnp.square(diff), axis=-1,
                                     keepdims=True)
                            + model["layer_norm_eps"])
        return diff * rms * w["subln"] * (1.0 - lam0)

    # one key pair after the other (a loop, so that one pair's scores
    # are all that is held): [key pairs, group, seq, 2 * head]
    out = jax.lax.map(one_key_pair, (q.transpose(1, 0, 2, 3, 4),
                                     keys.transpose(1, 0, 2, 3),
                                     values.transpose(1, 0, 2)))
    out = out.reshape(pairs, seq, 2 * head)
    out = out.transpose(1, 0, 2).reshape(seq, pairs * 2 * head)
    return mm(out, w["wo"]) + w["bo"]


def head_loss(h, table, labels, slices=8):
    """Mean cross entropy of ``labels`` under logits ``h @ table.T``,
    the vocabulary in ``slices`` parts: the running logsumexp and the
    label's own logit."""
    rows = table.shape[0]
    step = -(-rows // slices)
    lse = jnp.full(h.shape[:1], -jnp.inf)
    picked = jnp.zeros(h.shape[:1])
    for start in range(0, rows, step):
        part = jnp.asarray(table[start:start + step], jnp.float32)
        logits = mm(h, part.T)
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1))
        inside = (labels >= start) & (labels < start + part.shape[0])
        mine = jnp.take_along_axis(
            logits, jnp.clip(labels - start, 0, part.shape[0] - 1)[:, None],
            axis=-1)[:, 0]
        picked = jnp.where(inside, mine, picked)
    return jnp.mean(lse - picked)


def loss(model, ids, labels, table, layers, final_norm):
    """The mean cross entropy of ``labels`` [seq] given ``ids`` [seq].
    ``table`` is the tied [vocab, hidden] table; ``layers`` yields one
    dictionary a layer, in order: ``mix_norm``, ``mix`` (the weights of
    ``Mix_i`` under the names used above), ``mlp_norm``, ``mlp``; every
    array is cast to float32 here."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    depth, eps = model["num_hidden_layers"], model["layer_norm_eps"]
    window = model["sliding_window"]
    with jax.default_matmul_precision("highest"):
        run = {
            "ssm": jax.jit(lambda x, w: mamba(x, w, model)),
            "gmu": jax.jit(gated_memory),
            "kv": jax.jit(lambda x, w: project_kv(x, w, model)),
            "attention": jax.jit(
                lambda x, w, lam0, kv, window: differential_attention(
                    x, w, model, lam0, kv, window),
                static_argnums=4),
            "norm": jax.jit(lambda x, w: layer_norm(x, w, eps)),
            "mlp": jax.jit(mlp),
        }
        h = jnp.asarray(table[ids], jnp.float32)
        memory = shared_kv = None
        for i, w in enumerate(layers):
            w = f32(w)
            what = kind(i, depth)
            x = run["norm"](h, w["mix_norm"])
            if what == "ssm":
                mixed, y = run["ssm"](x, w["mix"])
                if i == depth // 2:
                    memory = y
            elif what == "gmu":
                mixed = run["gmu"](x, memory, w["mix"])
            elif what == "attention_cross":
                mixed = run["attention"](x, w["mix"], lambda_init(i),
                                         shared_kv, None)
            else:
                kv = run["kv"](x, w["mix"])
                if what == "attention_full":
                    shared_kv = kv
                mixed = run["attention"](
                    x, w["mix"], lambda_init(i), kv,
                    window if what == "attention_window" else None)
            h = h + mixed
            h = h + run["mlp"](run["norm"](h, w["mlp_norm"]), w["mlp"])
        assert i == depth - 1, f"{i + 1} layers were handed over"
        # labels are an argument: closed over, they would be a constant
        # of the program, and every seed would compile a new one
        return jax.jit(lambda h, n, table, y: head_loss(
            layer_norm(h, n, eps), table, y))(
            h, f32(final_norm), table, jnp.asarray(labels))
