"""What a training step of the SambaY decoder (Phi-4-mini-flash:
Mamba-1 layers, differential attention over a window, in full and
across to a shared KV, gated memory units, a gated MLP after every
mixer, a tied head) costs, from the configuration file's dictionary
alone: the published keys and ``assumed`` (the sizes the source does
not give, ``batch``, ``seq_len``). Nothing here imports JAX or the
program.

Layers by kind, at depth ``L`` (a multiple of 4): ``L/4 + 1`` Mamba,
``L/4`` window attention, 1 full attention, ``L/4 - 1`` gated memory
units, ``L/4 - 1`` cross attention.

Model FLOPs a step (forward and backward, recompute not counted):
6 x matmul parameters x tokens, and the work of the three kernels
below. Matmul parameters are every projection and the head (the tied
table, once: its use as an embedding is a gather); the depthwise conv,
the norms and the gates are elementwise and not counted.
"""


def _sizes(model):
    a = model["assumed"]
    return dict(
        d=model["hidden_size"], f=model["intermediate_size"],
        depth=model["num_hidden_layers"], vocab=model["vocab_size"],
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], hd=a["head_dim"],
        di=a["d_inner"], n=a["d_state"], r=a["dt_rank"], k=a["d_conv"],
        window=model["sliding_window"], seq=a["seq_len"], batch=a["batch"])


def layer_counts(model):
    """Layers by kind."""
    quarter = model["num_hidden_layers"] // 4
    return {"ssm": quarter + 1, "attention_window": quarter,
            "attention_full": 1, "gmu": quarter - 1,
            "attention_cross": quarter - 1}


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def _matmul_by_kind(s):
    q = s["d"] * s["heads"] * s["hd"]
    kv = s["d"] * s["kv_heads"] * s["hd"]  # keys; values are as many
    out = s["heads"] // 2 * 2 * s["hd"] * s["d"]
    return {
        "mlp": 3 * s["d"] * s["f"],
        "ssm": (2 * s["d"] * s["di"] + s["di"] * (s["r"] + 2 * s["n"])
                + s["r"] * s["di"] + s["di"] * s["d"]),
        "attention_window": q + 2 * kv + out,
        "attention_full": q + 2 * kv + out,
        "attention_cross": q + out,
        "gmu": 2 * s["d"] * s["di"],
    }


def matmul_params(model):
    """Parameters that are multiplied with every token."""
    s, per = _sizes(model), _matmul_by_kind(_sizes(model))
    return (sum(n * (per[kind] + per["mlp"])
                for kind, n in layer_counts(model).items())
            + s["d"] * s["vocab"])


def param_count(model):
    """All parameters: the matmul kernels (the tied table once), and
    what is elementwise: two LayerNorms a layer and the final one
    (scale and bias), the attention biases, four lambda vectors and the
    inner RMSNorm's scale an attention layer, the conv, the step's
    bias, ``A`` and ``D`` a Mamba layer."""
    s, counts = _sizes(model), layer_counts(model)
    d, hd = s["d"], s["hd"]
    attention = 4 * hd + 2 * hd + s["heads"] * hd + d  # q and o biases
    own_kv = 2 * s["kv_heads"] * hd  # k and v biases
    ssm = s["k"] * s["di"] + 3 * s["di"] + s["di"] * s["n"]
    elementwise = (
        4 * d * s["depth"] + 2 * d + counts["ssm"] * ssm
        + (counts["attention_window"] + 1) * (attention + own_kv)
        + counts["attention_cross"] * attention)
    return matmul_params(model) + elementwise


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (what a kernel executes beyond it, a replayed
# forward or a block computed whole and masked, is its own cost and
# lowers its share of the roofline).
#
# Differential attention is two softmax attentions a layer, each 20
# query heads of 64 against 10 key heads of 64 and 10 value heads of
# 128: a visible (query, key) pair costs 2 x 64 (QK^T) + 2 x 128 (PV)
# FLOPs a query head forward, and the backward twice that (dV, dP, dQ,
# dK).

def _pairs_causal(seq):
    return seq * (seq + 1) // 2


def _pairs_window(seq, window):
    """Visible pairs under ``t - window < j <= t``: the band only."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _attention_flops(s, pairs):
    """Forward and backward of one layer's two calls over ``pairs``
    visible pairs a row."""
    per_pair = 2 * s["hd"] + 2 * 2 * s["hd"]
    return 3 * 2 * (s["heads"] // 2) * per_pair * pairs * s["batch"]


def _attention_bytes(s, bytes_per_elem):
    """The least HBM traffic of one layer's two calls: a forward call
    reads q, k, v and writes o; the backward reads q, k, v, o, do and
    writes dq, dk, dv; each once."""
    rows = s["batch"] * s["seq"] * bytes_per_elem
    q = s["heads"] // 2 * s["hd"] * rows
    k = s["kv_heads"] // 2 * s["hd"] * rows
    v, o = 2 * k, 2 * q
    forward = q + k + v + o
    backward = (q + k + v + 2 * o) + (q + k + v)
    return 2 * (forward + backward)


def window_flops_per_step(model):
    s = _sizes(model)
    return (layer_counts(model)["attention_window"]
            * _attention_flops(s, _pairs_window(s["seq"], s["window"])))


def window_bytes_per_step(model, bytes_per_elem=2):
    return (layer_counts(model)["attention_window"]
            * _attention_bytes(_sizes(model), bytes_per_elem))


def causal_flops_per_step(model):
    """The full layer and the cross layers: the causal half."""
    s, counts = _sizes(model), layer_counts(model)
    return ((counts["attention_full"] + counts["attention_cross"])
            * _attention_flops(s, _pairs_causal(s["seq"])))


def causal_bytes_per_step(model, bytes_per_elem=2):
    counts = layer_counts(model)
    return ((counts["attention_full"] + counts["attention_cross"])
            * _attention_bytes(_sizes(model), bytes_per_elem))


# The scan, a (token, channel, state): forward dt x A, exp, x h,
# dt u x B, +, x C, + (7); the adjoint recurrence about twice that (14).
SCAN_FLOPS_PER_STATE = 21
# and a (token, channel), the least traffic: the forward reads u (2
# bytes) and dt (4: float32 by the architecture's definition) and
# writes y (2); the backward reads u, dt, dy and writes du, ddt (14).
# B and C are 1/160 of that and not counted.
SCAN_BYTES_PER_CHANNEL = 22


def scan_flops_per_step(model):
    s = _sizes(model)
    return (layer_counts(model)["ssm"] * SCAN_FLOPS_PER_STATE
            * tokens_per_step(model) * s["di"] * s["n"])


def scan_bytes_per_step(model):
    s = _sizes(model)
    return (layer_counts(model)["ssm"] * SCAN_BYTES_PER_CHANNEL
            * tokens_per_step(model) * s["di"])


def kernel_flops_per_step(model):
    """All the Mosaic kernels of a step: attention and scan."""
    return (window_flops_per_step(model) + causal_flops_per_step(model)
            + scan_flops_per_step(model))


def kernel_bytes_per_step(model, bytes_per_elem=2):
    return (window_bytes_per_step(model, bytes_per_elem)
            + causal_bytes_per_step(model, bytes_per_elem)
            + scan_bytes_per_step(model))


def model_flops_per_step(model):
    return (6 * matmul_params(model) * tokens_per_step(model)
            + kernel_flops_per_step(model))
