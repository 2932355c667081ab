"""What a training step of the latent-attention decoder with learned
sparse attention, gates and a group-limited router costs (A.X-K2's
block: MLA over the ``index_topk`` keys an indexer selects in every
layer, a gate on the attention's output, gated norms, a dense SwiGLU in
the leading layers, one shared expert plus the routed experts held here
in the rest, an untied head), from the configuration file's dictionary
alone: the published keys, ``deployment`` (the router's published
width, since ``n_routed_experts`` counts the experts held here) and
``assumed`` (``batch``, ``seq_len``). Nothing here imports JAX or the
program.

Model FLOPs a step (forward and backward, recompute not counted):
6 x the matmul parameters a token meets x tokens, the attention by
SELECTED pairs (a query's ``min(t + 1, topk)`` keys) and the indexer's
scores by CAUSAL pairs forward (every causal key is scored before one
is chosen) and by selected pairs backward (the indexer's loss is over
the selected set). A token meets every projection of its layer (the
latent ones, the output gate's, the indexer's three, the gated norms'
factors), the router, the shared expert, the head over the vocabulary
slice (the table is a gather), and of the routed experts held here
``num_experts_per_tok * held / router width`` on average: the count BY
EXPECTATION under uniform routing, which a group limit that chooses
its groups evenly does not move (a run's own count is in the
``profile_window`` event and feeds ``expert_gmm_roofline``).
"""

import os

# ``run.py`` loads this file before it starts anything, and nothing
# else of a family: a checkout whose program lacks what this family
# measures (the parent of the PR that added it, with the benchmark's
# files laid over it: its ``models/mla_moe.py`` has no indexer) fails
# here at once, and not after the agent has restarted three times a
# worker that cannot build its model.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *3 * [".."]))
_MODEL = os.path.join(_ROOT, "dlrover_tpu", "models", "mla_moe.py")
if not os.path.exists(_MODEL) or "index_n_heads" not in open(_MODEL).read():
    raise SystemExit("chipbench/families/mla_moe_dsa measures the sparse "
                     "switches of dlrover_tpu/models/mla_moe.py "
                     "(index_n_heads), which this checkout does not have")


def _sizes(model):
    a = model["assumed"]
    return dict(
        d=model["hidden_size"], f=model["intermediate_size"],
        fe=model["moe_intermediate_size"],
        depth=model["num_hidden_layers"],
        dense=model["first_k_dense_replace"], vocab=model["vocab_size"],
        heads=model["num_attention_heads"], rq=model["q_lora_rank"],
        rkv=model["kv_lora_rank"], dn=model["qk_nope_head_dim"],
        dr=model["qk_rope_head_dim"], dv=model["v_head_dim"],
        held=model["n_routed_experts"],
        router=model["deployment"]["published_n_routed_experts"],
        shared=model["n_shared_experts"], k=model["num_experts_per_tok"],
        ih=model["index_n_heads"], ihd=model["index_head_dim"],
        topk=model["index_topk"],
        gate=int(model["attention_output_gate"]),
        rank=model["gated_norm_rank"] if model["gated_norm"] else 0,
        seq=a["seq_len"], batch=a["batch"])


def layer_counts(model):
    """Layers by kind."""
    dense = model["first_k_dense_replace"]
    return {"dense": dense, "moe": model["num_hidden_layers"] - dense}


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def _mla_params(s):
    """The latent projections and, with the gate, its one."""
    return (s["d"] * s["rq"] + s["rq"] * s["heads"] * (s["dn"] + s["dr"])
            + s["d"] * (s["rkv"] + s["dr"])
            + s["rkv"] * s["heads"] * (s["dn"] + s["dv"])
            + s["heads"] * s["dv"] * s["d"]
            + s["gate"] * s["d"] * s["heads"] * s["dv"])


def _indexer_params(s):
    """The indexer's query heads (from the query latent), its one key
    head and its per-head weight (from the hidden state)."""
    return (s["rq"] * s["ih"] * s["ihd"] + s["d"] * s["ihd"]
            + s["d"] * s["ih"])


def _gated_norm_params(s):
    """One gated norm's two factors."""
    return 2 * s["d"] * s["rank"]


def expert_params(model):
    """One expert's three matrices."""
    s = _sizes(model)
    return 3 * s["d"] * s["fe"]


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
    moe = (s["d"] * s["router"] + s["shared"] * one
           + s["k"] * s["held"] / s["router"] * one)
    layer = _mla_params(s) + _indexer_params(s) + 2 * _gated_norm_params(s)
    return (s["depth"] * layer + counts["dense"] * 3 * s["d"] * s["f"]
            + counts["moe"] * moe + _gated_norm_params(s)
            + s["d"] * s["vocab"])


def param_count(model):
    """All parameters held here: every matrix (the table and the head
    apiece, every held expert whole), the norm scales (two a layer and
    the final one with their gates' factors, the two latent norms a
    layer, the indexer's key norm's scale and bias) and the router's
    selection bias."""
    s, counts = _sizes(model), layer_counts(model)
    moe = (s["d"] * s["router"] + s["router"]
           + (s["shared"] + s["held"]) * expert_params(model))
    gated = s["d"] + _gated_norm_params(s)
    layer = (_mla_params(s) + _indexer_params(s) + 2 * s["ihd"]
             + 2 * gated + s["rq"] + s["rkv"])
    return (s["depth"] * layer + counts["dense"] * 3 * s["d"] * s["f"]
            + counts["moe"] * moe + 2 * s["d"] * s["vocab"] + gated)


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (a replayed forward, a tile computed whole and
# masked, the shared rotary key written once a head, a selection made
# twice are the kernels' own cost and lower their share of the
# roofline).


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
    """Latent attention over the selected pairs: a pair costs a head
    held here 2 x (128 + 64) FLOPs in the scores and 2 x 128 in PV
    forward (192 + 128 a head, twice); the backward is counted at 2.5
    times the forward (dV, dP, dQ, dK and the scores again, which no
    backward from a saved logsumexp can do without)."""
    s = _sizes(model)
    per_pair = 2 * (s["dn"] + s["dr"]) + 2 * s["dv"]
    return (1 + backward) * per_pair * s["heads"] * _selected(s)


def dsa_attn_bytes_per_step(model, bytes_per_elem=2):
    """The least HBM traffic in the latent layout: the forward reads q
    (nope and rope a head), k (nope a head, the rotary key head ONCE),
    v and writes o; the backward reads those and o, do and writes dq,
    dk, dv; each once. The selection itself is not counted (a kernel
    that needs it in HBM pays for it)."""
    s = _sizes(model)
    rows = s["depth"] * s["batch"] * s["seq"] * bytes_per_elem
    q = s["heads"] * (s["dn"] + s["dr"]) * rows
    k = (s["heads"] * s["dn"] + s["dr"]) * rows
    v = o = s["heads"] * s["dv"] * rows
    return (q + k + v + o) + (q + k + v + 2 * o) + (q + k + v)


def dsa_index_flops_per_step(model):
    """The indexer: every causal pair scored forward (2 x 128 FLOPs a
    head, 64 heads: a contraction of 8192 a pair), the scores' backward
    over the selected pairs alone (twice the forward's: to the queries
    and to the key), and the main attention's scores once more over the
    selected pairs, every head held here (2 x 192), for the head-mean
    probabilities the loss is against."""
    s = _sizes(model)
    score = 2 * s["ihd"] * s["ih"]
    return (score * _causal(s) + 2 * score * _selected(s)
            + 2 * (s["dn"] + s["dr"]) * s["heads"] * _selected(s))


def dsa_index_bytes_per_step(model, bytes_per_elem=2):
    """The least traffic: the indexer's queries, key and weights read
    forward, read again and their gradients written backward, and the
    main q and k (the rotary key once) read once for the
    probabilities."""
    s = _sizes(model)
    rows = s["depth"] * s["batch"] * s["seq"] * bytes_per_elem
    index = (s["ih"] * s["ihd"] + s["ihd"] + s["ih"]) * rows
    main = (s["heads"] * (2 * s["dn"] + s["dr"]) + s["dr"]) * rows
    return 3 * index + main


# The routed experts' grouped matmuls: a row of a held expert meets its
# three matrices forward and each twice backward (dx, dW).

def gmm_flops(model, rows):
    """``rows``: assignments computed by held experts, all expert
    layers of a step together."""
    return 3 * 2 * expert_params(model) * rows


def gmm_bytes(model, rows, bytes_per_elem=2):
    """The least traffic: every held expert's three matrices read in
    the forward and in dx and their gradients written (three passes),
    and a row's operands and results once a matmul (in and out, three
    matmuls, three passes)."""
    s, counts = _sizes(model), layer_counts(model)
    weights = 3 * counts["moe"] * s["held"] * expert_params(model)
    per_row = 3 * (2 * (s["d"] + s["fe"]) + (s["fe"] + s["d"]))
    return (weights + per_row * rows) * bytes_per_elem


def kernel_flops_per_step(model):
    """All the Mosaic kernels of a step: the selected attention, the
    indexer, and the grouped matmuls at the expected rows."""
    return (dsa_attn_flops_per_step(model) + dsa_index_flops_per_step(model)
            + gmm_flops(model, layer_counts(model)["moe"]
                        * held_rows_expected(model)))


def kernel_bytes_per_step(model, bytes_per_elem=2):
    return (dsa_attn_bytes_per_step(model, bytes_per_elem)
            + dsa_index_bytes_per_step(model, bytes_per_elem)
            + gmm_bytes(model, layer_counts(model)["moe"]
                        * held_rows_expected(model), bytes_per_elem))


def model_flops_per_step(model):
    """6 x active matmul parameters x tokens, the attention over the
    selected pairs at the usual twice-the-forward backward (recompute
    not counted), and the indexer's pair work."""
    return (6 * active_matmul_params(model) * tokens_per_step(model)
            + dsa_attn_flops_per_step(model, backward=2.0)
            + dsa_index_flops_per_step(model))
