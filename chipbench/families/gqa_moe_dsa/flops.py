"""What a training step of the grouped-query decoder with learned
sparse attention and held experts (Keye-VL-2.0's language model: every
layer attends to the ``sa_config.topk`` keys an indexer selects for each
query, every layer an expert layer, an untied head) costs, from the
configuration file's dictionary alone: the published keys,
``deployment`` (the router's published width, since ``num_experts``
counts the experts held here) and ``assumed`` (``batch``, ``seq_len``).
Nothing here imports JAX or the program.

Model FLOPs a step (forward and backward, recompute not counted):
6 x the matmul parameters a token meets x tokens, the attention by
SELECTED pairs (a query's ``min(t + 1, topk)`` keys) and the indexer's
scores by CAUSAL pairs forward (every causal key is scored before one
is chosen) and by selected pairs backward (the indexer's loss is over
the selected set). A token meets the four projections of its layer,
the indexer's three, the router, the head over the vocabulary slice
(the table is a gather), and of the experts held here
``num_experts_per_tok * held / router width`` on average: the count BY
EXPECTATION under uniform routing (a run's own count is in the
``profile_window`` event and feeds ``expert_gmm_roofline``).
"""

import os

# ``run.py`` loads this file before it starts anything, and nothing
# else of a family: a checkout whose program lacks the op this family
# measures (the parent of the PR that added it, with the benchmark's
# files laid over it) fails here at once, and not after the agent has
# restarted three times a worker that cannot import it.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *3 * [".."]))
if not os.path.exists(os.path.join(_ROOT, "dlrover_tpu", "ops",
                                   "sparse_attention.py")):
    raise SystemExit("chipbench/families/gqa_moe_dsa measures "
                     "dlrover_tpu/ops/sparse_attention.py, which this "
                     "checkout does not have")


def _sizes(model):
    a, sa = model["assumed"], model["sa_config"]
    return dict(
        d=model["hidden_size"], fe=model["moe_intermediate_size"],
        depth=model["num_hidden_layers"], vocab=model["vocab_size"],
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], hd=model["head_dim"],
        held=model["num_experts"],
        router=model["deployment"]["published_num_experts"],
        k=model["num_experts_per_tok"],
        ih=sa["indexer_num_heads"], ihd=sa["indexer_head_dim"],
        ikv=sa["indexer_num_kv_heads"], topk=sa["topk"],
        seq=a["seq_len"], batch=a["batch"])


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def _attention_params(s):
    return 2 * s["d"] * (s["heads"] + s["kv_heads"]) * s["hd"]


def _indexer_params(s):
    """The indexer's query heads, its key head(s) and its per-head
    weight, all read from the hidden state."""
    return s["d"] * (s["ih"] * s["ihd"] + s["ikv"] * s["ihd"] + s["ih"])


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
    layer = (_attention_params(s) + _indexer_params(s)
             + s["d"] * s["router"]
             + s["k"] * s["held"] / s["router"] * expert_params(model))
    return s["depth"] * layer + s["d"] * s["vocab"]


def param_count(model):
    """All parameters held here: every matrix (the table and the head
    apiece, every held expert whole) and the norm scales (two a layer
    over the hidden state, one each a query and a key head of
    ``head_dim``, the final one)."""
    s = _sizes(model)
    layer = (_attention_params(s) + _indexer_params(s)
             + s["d"] * s["router"] + s["held"] * expert_params(model)
             + 2 * s["d"] + 2 * s["hd"])
    return s["depth"] * layer + 2 * s["d"] * s["vocab"] + s["d"]


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (a replayed forward, a tile computed whole and
# masked, a K/V block read once a query head, a selection made twice
# are the kernels' own cost and lower their share of the roofline).


def pairs_causal(seq):
    return seq * (seq + 1) // 2


def pairs_selected(seq, topk):
    """``sum_t min(t + 1, topk)``: the (query, key) pairs of a row and
    layer that the attention is over."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def selected_share(model):
    """Selected over causal pairs: what ``dsa_selected_share`` reads
    where the selection is right."""
    s = _sizes(model)
    return pairs_selected(s["seq"], s["topk"]) / pairs_causal(s["seq"])


def _selected(s):
    return s["depth"] * s["batch"] * pairs_selected(s["seq"], s["topk"])


def _causal(s):
    return s["depth"] * s["batch"] * pairs_causal(s["seq"])


def dsa_attn_flops_per_step(model, backward=2.5):
    """Attention over the selected pairs: a pair costs a query head
    2 x 128 FLOPs in the scores and 2 x 128 in PV forward; the backward
    is counted at 2.5 times the forward (dV, dP, dQ, dK and the scores
    again, which no backward from a saved logsumexp can do without)."""
    s = _sizes(model)
    return (1 + backward) * 4 * s["hd"] * s["heads"] * _selected(s)


def dsa_attn_bytes_per_step(model, bytes_per_elem=2):
    """The least HBM traffic: the forward reads q, k, v (a KV head
    once) and writes o; the backward reads q, k, v, o, do and writes
    dq, dk, dv; each once. The selection itself is not counted (a
    kernel that needs it in HBM pays for it)."""
    s = _sizes(model)
    rows = s["depth"] * s["batch"] * s["seq"] * bytes_per_elem
    q = o = s["heads"] * s["hd"] * rows
    k = v = s["kv_heads"] * s["hd"] * rows
    return (q + k + v + o) + (q + k + v + 2 * o) + (q + k + v)


def dsa_index_flops_per_step(model):
    """The indexer: every causal pair scored forward (2 x 64 FLOPs a
    head, 16 heads), the scores' backward over the selected pairs alone
    (twice the forward's: to the queries and to the key), and the main
    attention's scores once more over the selected pairs, every query
    head (2 x 128), for the head-mean probabilities the loss is
    against."""
    s = _sizes(model)
    score = 2 * s["ihd"] * s["ih"]
    return (score * _causal(s) + 2 * score * _selected(s)
            + 2 * s["hd"] * s["heads"] * _selected(s))


def dsa_index_bytes_per_step(model, bytes_per_elem=2):
    """The least traffic: the indexer's queries, key and weights read
    forward, read again and their gradients written backward, and the
    main q and k read once for the probabilities."""
    s = _sizes(model)
    rows = s["depth"] * s["batch"] * s["seq"] * bytes_per_elem
    index = (s["ih"] * s["ihd"] + s["ikv"] * s["ihd"] + s["ih"]) * rows
    return 3 * index + (s["heads"] + s["kv_heads"]) * s["hd"] * rows


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
    """All the Mosaic kernels of a step: the selected attention, the
    indexer, and the grouped matmuls at the expected rows."""
    return (dsa_attn_flops_per_step(model) + dsa_index_flops_per_step(model)
            + gmm_flops(model, model["num_hidden_layers"]
                        * held_rows_expected(model)))


def kernel_bytes_per_step(model, bytes_per_elem=2):
    return (dsa_attn_bytes_per_step(model, bytes_per_elem)
            + dsa_index_bytes_per_step(model, bytes_per_elem)
            + gmm_bytes(model, model["num_hidden_layers"]
                        * held_rows_expected(model), bytes_per_elem))


def model_flops_per_step(model):
    """6 x active matmul parameters x tokens, the attention over the
    selected pairs at the usual twice-the-forward backward (recompute
    not counted), and the indexer's pair work."""
    return (6 * active_matmul_params(model) * tokens_per_step(model)
            + dsa_attn_flops_per_step(model, backward=2.0)
            + dsa_index_flops_per_step(model))
