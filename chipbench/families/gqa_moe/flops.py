"""What a training step of the grouped-query decoder of window and full
layers with held experts (SmallThinker's block: every layer an expert
layer, one layer of a period full and position-free, the others
windowed and rotary, an untied head) costs, from the configuration
file's dictionary alone: the published keys, ``deployment`` (the
router's published width, since ``moe_num_primary_experts`` counts the
experts held here) and ``assumed`` (``batch``, ``seq_len``). Nothing
here imports JAX or the program.

Model FLOPs a step (forward and backward, recompute not counted):
6 x the matmul parameters a token meets x tokens, and the attention
kernels' work by visible pairs: the causal half on a full layer, the
band's pairs alone on a window layer. A token meets the four
projections of its layer, the router, the head over the vocabulary
slice (the table is a gather), and of the experts held here
``moe_num_active_primary_experts * held / router width`` on average:
the count BY EXPECTATION under uniform routing (a run's own count is in
the ``profile_window`` event and feeds ``expert_gmm_roofline``).
"""

import os

# ``run.py`` loads this file before it starts anything, and nothing
# else of a family: a checkout whose program lacks the model this
# family measures (the parent of the PR that added it, with the
# benchmark's files laid over it) fails here at once, and not after
# the agent has restarted three times a worker that cannot import it.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *3 * [".."]))
if not os.path.exists(os.path.join(_ROOT, "dlrover_tpu", "models",
                                   "gqa_moe.py")):
    raise SystemExit("chipbench/families/gqa_moe measures "
                     "dlrover_tpu/models/gqa_moe.py, which this checkout "
                     "does not have")


def _sizes(model):
    a = model["assumed"]
    return dict(
        d=model["hidden_size"], fe=model["moe_ffn_hidden_size"],
        depth=model["num_hidden_layers"], vocab=model["vocab_size"],
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], hd=model["head_dim"],
        held=model["moe_num_primary_experts"],
        router=model["deployment"]["published_moe_num_primary_experts"],
        k=model["moe_num_active_primary_experts"],
        window=model["sliding_window_size"], seq=a["seq_len"],
        batch=a["batch"])


def layer_counts(model):
    """Layers by attention kind: the first ``num_hidden_layers``
    entries of the published ``sliding_window_layout``."""
    layout = model["sliding_window_layout"][:model["num_hidden_layers"]]
    return {"attn_full": layout.count(0), "attn_window": layout.count(1)}


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def _attention_params(s):
    return 2 * s["d"] * (s["heads"] + s["kv_heads"]) * s["hd"]


def expert_params(model):
    """One expert's three matrices."""
    s = _sizes(model)
    return 3 * s["d"] * s["fe"]


def held_rows_expected(model):
    """Assignments a step routes to the experts held here, one layer,
    if routing is uniform."""
    s = _sizes(model)
    return tokens_per_step(model) * s["k"] * s["held"] / s["router"]


def active_matmul_params(model):
    """Matmul parameters a token meets, the experts held here by
    expectation."""
    s = _sizes(model)
    layer = (_attention_params(s) + s["d"] * s["router"]
             + s["k"] * s["held"] / s["router"] * expert_params(model))
    return s["depth"] * layer + s["d"] * s["vocab"]


def param_count(model):
    """All parameters held here: every matrix (the table and the head
    apiece, every held expert whole) and the norm scales (two a layer,
    the final one)."""
    s = _sizes(model)
    layer = (_attention_params(s) + s["d"] * s["router"]
             + s["held"] * expert_params(model) + 2 * s["d"])
    return s["depth"] * layer + 2 * s["d"] * s["vocab"] + s["d"]


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (a replayed forward, a block computed whole and
# masked, a K/V block read once a query head, a padded row tile are the
# kernel's own cost and lower its share of the roofline).
#
# Attention: a visible (query, key) pair costs a query head 2 x 128
# FLOPs in the scores and 2 x 128 in PV forward, and the backward twice
# that (dV, dP, dQ, dK).

def _pairs_causal(seq):
    return seq * (seq + 1) // 2


def _pairs_window(seq, window):
    """Visible pairs under ``t - window < j <= t``: the band only."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _attention_flops(s, pairs):
    """Forward and backward of one layer over ``pairs`` visible pairs a
    row."""
    return 3 * s["heads"] * 4 * s["hd"] * pairs * s["batch"]


def _attention_bytes(s, bytes_per_elem):
    """The least HBM traffic of one layer's three calls: the forward
    reads q, k, v (a KV head once, for the 7 query heads it serves) and
    writes o; the backward reads q, k, v, o, do and writes dq, dk, dv;
    each once."""
    rows = s["batch"] * s["seq"] * bytes_per_elem
    q = o = s["heads"] * s["hd"] * rows
    k = v = s["kv_heads"] * s["hd"] * rows
    forward = q + k + v + o
    backward = (q + k + v + 2 * o) + (q + k + v)
    return forward + backward


def window_flops_per_step(model):
    s = _sizes(model)
    return (layer_counts(model)["attn_window"]
            * _attention_flops(s, _pairs_window(s["seq"], s["window"])))


def window_bytes_per_step(model, bytes_per_elem=2):
    return (layer_counts(model)["attn_window"]
            * _attention_bytes(_sizes(model), bytes_per_elem))


def causal_flops_per_step(model):
    """The full layers: the causal half."""
    s = _sizes(model)
    return (layer_counts(model)["attn_full"]
            * _attention_flops(s, _pairs_causal(s["seq"])))


def causal_bytes_per_step(model, bytes_per_elem=2):
    return (layer_counts(model)["attn_full"]
            * _attention_bytes(_sizes(model), bytes_per_elem))


# The experts' grouped matmuls: a row of a held expert meets its three
# matrices forward and each twice backward (dx, dW).

def gmm_flops(model, rows):
    """``rows``: assignments computed by held experts, all layers of a
    step together."""
    return 3 * 2 * expert_params(model) * rows


def gmm_bytes(model, rows, bytes_per_elem=2):
    """The least traffic: every held expert's three matrices read in
    the forward and in dx and their gradients written (three passes),
    and a row's operands and results once a matmul (in and out, three
    matmuls, three passes)."""
    s = _sizes(model)
    weights = 3 * s["depth"] * s["held"] * expert_params(model)
    per_row = 3 * (2 * (s["d"] + s["fe"]) + (s["fe"] + s["d"]))
    return (weights + per_row * rows) * bytes_per_elem


def kernel_flops_per_step(model):
    """All the Mosaic kernels of a step: both kinds of attention, and
    the grouped matmuls at the expected rows."""
    return (window_flops_per_step(model) + causal_flops_per_step(model)
            + gmm_flops(model, model["num_hidden_layers"]
                        * held_rows_expected(model)))


def kernel_bytes_per_step(model, bytes_per_elem=2):
    return (window_bytes_per_step(model, bytes_per_elem)
            + causal_bytes_per_step(model, bytes_per_elem)
            + gmm_bytes(model, model["num_hidden_layers"]
                        * held_rows_expected(model), bytes_per_elem))


def model_flops_per_step(model):
    return (6 * active_matmul_params(model) * tokens_per_step(model)
            + window_flops_per_step(model) + causal_flops_per_step(model))
