"""What a training step of the grouped differential latent-attention
decoder costs (Motif-3-Beta's block: four residual streams mixed by
per-token mappings around GDLA, over every causal key or over a band,
and around a dense PolyNorm FFN or one shared expert plus the routed
experts held here; one prediction module), from the configuration
file's dictionary alone: the published keys, ``deployment`` (the
router's published width, since ``num_experts`` counts the experts
held here; which published layers these are) and ``assumed``
(``batch``, ``seq_len``). Nothing here imports JAX or the program.

Model FLOPs a step (forward and backward, recompute not counted):
6 x the matmul parameters a token meets x tokens, and the attention
kernels' work. A token meets, in each layer and in the prediction
module, the latent projections (lambda's and the output gate's among
them), the two hyper-connection projections, and the layer's FFN:
dense, or router, shared expert and ``experts_top_k * held / router
width`` of a routed expert BY EXPECTATION under uniform routing; in
the module also its projection of ``2 hidden`` to ``hidden``; and the
head once for the main model and once for the module. The stream
mixes, the Sinkhorn iteration, the subtraction and PolyNorm are
elementwise work and count for nothing here: their time lowers
``step_mfu_pct``.
"""

import os

# ``run.py`` loads this file before it starts anything, and nothing
# else of a family: a checkout whose program lacks what this family
# measures (the parent of the PR that added it, with the benchmark's
# files laid over it) fails here at once, and not after the agent has
# restarted three times a worker that cannot build the model.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *3 * [".."]))
with open(os.path.join(_ROOT, "dlrover_tpu", "models", "mla_moe.py")) as _f:
    if "num_noise_heads" not in _f.read():
        raise SystemExit(
            "chipbench/families/mla_moe_gdla measures grouped "
            "differential latent attention (num_noise_heads) in "
            "dlrover_tpu/models/mla_moe.py, which this checkout does "
            "not have")


def _sizes(model):
    a, dep = model["assumed"], model["deployment"]
    heads, dr = model["num_attention_heads"], model["qk_rope_head_dim"]
    return dict(
        d=model["hidden_size"], f=model["intermediate_size"],
        fe=model["moe_intermediate_size"],
        depth=model["num_hidden_layers"],
        dense=model["n_dense_first_layers"], vocab=model["vocab_size"],
        heads=heads, groups=model["num_key_value_heads"],
        signal=heads - model["num_noise_heads"],
        rq=model["q_lora_rank"], rkv=model["kv_lora_rank"],
        dn=model["head_dim"] - dr, dr=dr, dv=model["v_head_dim"],
        held=model["num_experts"], router=dep["published_num_experts"],
        shared=model["num_shared_experts"], k=model["experts_top_k"],
        n=model["mhc_expansion_rate"],
        mtp=model["num_nextn_predict_layers"],
        window=model["sliding_window"], seq=a["seq_len"], batch=a["batch"])


def layer_counts(model):
    """Layers by kind; ``mtp`` the prediction modules, each of which
    holds one more expert layer."""
    dense = model["n_dense_first_layers"]
    return {"dense": dense, "moe": model["num_hidden_layers"] - dense,
            "mtp": model["num_nextn_predict_layers"]}


def published_layers(model):
    """The published index of every layer here, a module's last."""
    dep = model["deployment"]
    return ([dep["first_published_layer"] + i
             for i in range(model["num_hidden_layers"])]
            + [dep["published_num_hidden_layers"] + k
               for k in range(model["num_nextn_predict_layers"])])


def full_layers(model):
    """Indices here (a module's layer k at ``num_hidden_layers + k``)
    of the layers that attend to every causal key: published layer
    ``i`` where ``(i + 1) % sliding_window_period == 0``."""
    return [here for here, i in enumerate(published_layers(model))
            if not model["use_sliding_window"]
            or (i + 1) % model["sliding_window_period"] == 0]


def attention_counts(model):
    full = len(full_layers(model))
    return {"full": full, "window": len(published_layers(model)) - full}


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def _mla_params(s):
    return (s["d"] * s["rq"] + s["rq"] * s["heads"] * (s["dn"] + s["dr"])
            + s["d"] * (s["rkv"] + s["dr"])
            + s["rkv"] * s["groups"] * (s["dn"] + s["dv"])
            + s["d"] * s["signal"]  # lambda
            + 2 * s["d"] * s["signal"] * s["dv"])  # the gate and W_o


def _hc_columns(s):
    return 2 * s["n"] + s["n"] ** 2


def hc_matmul_params(model):
    """A layer's two hyper-connection projections."""
    s = _sizes(model)
    return 2 * s["n"] * s["d"] * _hc_columns(s)


def expert_params(model):
    """One expert's three matrices."""
    s = _sizes(model)
    return 3 * s["d"] * s["fe"]


def expert_layers(model):
    """Layers that hold routed experts: the model's and one a module."""
    counts = layer_counts(model)
    return counts["moe"] + counts["mtp"]


def held_rows_expected(model):
    """Assignments a step routes to the experts held here, one expert
    layer, if routing is uniform."""
    s = _sizes(model)
    return tokens_per_step(model) * s["k"] * s["held"] / s["router"]


def active_matmul_params(model):
    """Matmul parameters a token meets, the routed experts held here
    by expectation."""
    s, counts = _sizes(model), layer_counts(model)
    one = expert_params(model)
    block = _mla_params(s) + hc_matmul_params(model)
    moe = (s["d"] * s["router"] + s["shared"] * one
           + s["k"] * s["held"] / s["router"] * one)
    return ((s["depth"] + counts["mtp"]) * block
            + counts["dense"] * 3 * s["d"] * s["f"]
            + expert_layers(model) * moe
            + counts["mtp"] * 2 * s["d"] * s["d"]
            + (1 + counts["mtp"]) * s["d"] * s["vocab"])


def param_count(model):
    """All parameters held here: every matrix (the table and the head
    apiece, every held expert whole), PolyNorm's four numbers an FFN (a
    dense layer's one, an expert layer's shared expert's and its routed
    experts'), a hyper-connection's norm scale, gates and biases, and
    the norm scales (two a layer, the two latent norms a layer, the
    final one, three a module). The router's selection bias is no
    parameter: a buffer of the training state."""
    s, counts = _sizes(model), layer_counts(model)
    hc = hc_matmul_params(model) + 2 * (s["n"] * s["d"] + 3 + _hc_columns(s))
    block = (_mla_params(s) + hc + 2 * s["d"] + s["rq"] + s["rkv"])
    moe = (s["d"] * s["router"] + 2 * 4
           + (s["shared"] + s["held"]) * expert_params(model))
    return ((s["depth"] + counts["mtp"]) * block
            + counts["dense"] * (3 * s["d"] * s["f"] + 4)
            + expert_layers(model) * moe
            + counts["mtp"] * (2 * s["d"] * s["d"] + 3 * s["d"])
            + 2 * s["d"] * s["vocab"] + s["d"])


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (a replayed forward, a block computed whole and
# masked, a padded row tile are the kernel's own cost and lower its
# share of the roofline).
#
# Latent attention, in every layer and in the module: a visible (query,
# key) pair costs a query head (the noise heads are query heads) 2 x
# (128 + 64) FLOPs in the scores and 2 x 128 in PV forward, and the
# backward twice that. A full layer sees the causal half, a window
# layer its band.


def _pairs(s, window):
    if not window or window >= s["seq"]:
        return s["seq"] * (s["seq"] + 1) // 2
    return window * (window + 1) // 2 + (s["seq"] - window) * window


def _attention_flops(s, pairs):
    per_pair = 2 * (s["dn"] + s["dr"]) + 2 * s["dv"]
    return 3 * s["heads"] * per_pair * pairs * s["batch"]


def _attention_bytes(s, bytes_per_elem):
    """The least HBM traffic of one layer's calls: the forward reads
    q, k and v (a key/value head once a group, the rotary key head
    once) and writes o; the backward reads those and o, do and writes
    dq, dk, dv; each once."""
    rows = s["batch"] * s["seq"] * bytes_per_elem
    q = s["heads"] * (s["dn"] + s["dr"]) * rows
    k = (s["groups"] * s["dn"] + s["dr"]) * rows
    v = s["groups"] * s["dv"] * rows
    o = s["heads"] * s["dv"] * rows
    return (q + k + v + o) + (q + k + v + 2 * o) + (q + k + v)


def mla_win_flops_per_step(model):
    """The window layers' kernels (``flash_mla_win_*``)."""
    s = _sizes(model)
    return attention_counts(model)["window"] * _attention_flops(
        s, _pairs(s, s["window"]))


def mla_win_bytes_per_step(model, bytes_per_elem=2):
    return attention_counts(model)["window"] * _attention_bytes(
        _sizes(model), bytes_per_elem)


def mla_flops_per_step(model):
    """Every ``flash_mla_*`` kernel: the full layers' and the band's."""
    s = _sizes(model)
    return (attention_counts(model)["full"] * _attention_flops(
        s, _pairs(s, 0)) + mla_win_flops_per_step(model))


def mla_bytes_per_step(model, bytes_per_elem=2):
    return len(published_layers(model)) * _attention_bytes(
        _sizes(model), bytes_per_elem)


# The routed experts' grouped matmuls: a row of a held expert meets its
# three matrices forward and each twice backward (dx, dW).

def gmm_flops(model, rows):
    """``rows``: assignments computed by held experts, all expert
    layers of a step together (a module's among them)."""
    return 3 * 2 * expert_params(model) * rows


def gmm_bytes(model, rows, bytes_per_elem=2):
    """The least traffic: every held expert's three matrices read in
    the forward and in dx and their gradients written (three passes),
    and a row's operands and results once a matmul (in and out, three
    matmuls, three passes)."""
    s = _sizes(model)
    weights = 3 * expert_layers(model) * s["held"] * expert_params(model)
    per_row = 3 * (2 * (s["d"] + s["fe"]) + (s["fe"] + s["d"]))
    return (weights + per_row * rows) * bytes_per_elem


def kernel_flops_per_step(model):
    """All the Mosaic kernels of a step: attention, and the grouped
    matmuls at the expected rows."""
    return mla_flops_per_step(model) + gmm_flops(
        model, expert_layers(model) * held_rows_expected(model))


def kernel_bytes_per_step(model, bytes_per_elem=2):
    return (mla_bytes_per_step(model, bytes_per_elem) + gmm_bytes(
        model, expert_layers(model) * held_rows_expected(model),
        bytes_per_elem))


def model_flops_per_step(model):
    return (6 * active_matmul_params(model) * tokens_per_step(model)
            + mla_flops_per_step(model))
