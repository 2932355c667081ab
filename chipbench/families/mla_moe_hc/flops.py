"""What a training step of the hyper-connected latent-attention decoder
with held experts and a multi-token-prediction module costs
(Xing4.0-29B-A4B's block: four residual streams mixed by per-token
mappings around MLA and around a dense SwiGLU or one shared expert plus
the routed experts held here; one prediction module: a projection, one
more expert layer, a second pass of the untied head), from the
configuration file's dictionary alone: the published keys,
``deployment`` (the router's published width, since
``n_routed_experts`` counts the experts held here) and ``assumed``
(``batch``, ``seq_len``). Nothing here imports JAX or the program.

Model FLOPs a step (forward and backward, recompute not counted):
6 x the matmul parameters a token meets x tokens, and the attention
kernels' work. A token meets, in each of the ``num_hidden_layers``
layers and in each prediction module, the latent projections, the two
hyper-connection projections (``hc_mult * hidden`` to ``2 hc_mult +
hc_mult^2``), and the layer's FFN: dense, or router, shared expert and
``num_experts_per_tok * held / router width`` of a routed expert BY
EXPECTATION under uniform routing (a run's own count is in the
``profile_window`` event and feeds ``expert_gmm_roofline``); in a
module also its projection of ``2 hidden`` to ``hidden``; and the head
once for the main model and once a module (the table is a gather). The
stream mixes and the Sinkhorn iteration are elementwise work and count
for nothing here: their time lowers ``step_mfu_pct``. A step's tokens
are the batch's, counted once, whatever the modules predict.
"""

import os

# ``run.py`` loads this file before it starts anything, and nothing
# else of a family: a checkout whose program lacks what this family
# measures (the parent of the PR that added it, with the benchmark's
# files laid over it) fails here at once, and not after the agent has
# restarted three times a worker that cannot build the model.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *3 * [".."]))
if not os.path.exists(os.path.join(_ROOT, "dlrover_tpu", "ops",
                                   "hyper_connections.py")):
    raise SystemExit("chipbench/families/mla_moe_hc measures "
                     "dlrover_tpu/ops/hyper_connections.py under "
                     "dlrover_tpu/models/mla_moe.py, which this checkout "
                     "does not have")


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
        n=model["hc_mult"], mtp=model["num_nextn_predict_layers"],
        seq=a["seq_len"], batch=a["batch"])


def layer_counts(model):
    """Layers by kind; ``mtp`` the prediction modules, each of which
    holds one more expert layer."""
    dense = model["first_k_dense_replace"]
    return {"dense": dense, "moe": model["num_hidden_layers"] - dense,
            "mtp": model["num_nextn_predict_layers"]}


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def _mla_params(s):
    return (s["d"] * s["rq"] + s["rq"] * s["heads"] * (s["dn"] + s["dr"])
            + s["d"] * (s["rkv"] + s["dr"])
            + s["rkv"] * s["heads"] * (s["dn"] + s["dv"])
            + s["heads"] * s["dv"] * s["d"])


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
    apiece, every held expert whole), the router's selection bias, a
    hyper-connection's norm scale, gates and biases, and the norm
    scales (two a layer, the two latent norms a layer, the final one,
    three a module)."""
    s, counts = _sizes(model), layer_counts(model)
    hc = hc_matmul_params(model) + 2 * (s["n"] * s["d"] + 3 + _hc_columns(s))
    block = (_mla_params(s) + hc + 2 * s["d"] + s["rq"] + s["rkv"])
    moe = (s["d"] * s["router"] + s["router"]
           + (s["shared"] + s["held"]) * expert_params(model))
    return ((s["depth"] + counts["mtp"]) * block
            + counts["dense"] * 3 * s["d"] * s["f"]
            + expert_layers(model) * moe
            + counts["mtp"] * (2 * s["d"] * s["d"] + 3 * s["d"])
            + 2 * s["d"] * s["vocab"] + s["d"])


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (a replayed forward, a block computed whole and
# masked, a padded row tile are the kernel's own cost and lower its
# share of the roofline).
#
# Latent attention, in every layer and in every module: a visible
# (query, key) pair costs a query head 2 x (128 + 64) FLOPs in the
# scores and 2 x 128 in PV forward, and the backward twice that.

def mla_flops_per_step(model):
    s = _sizes(model)
    pairs = s["seq"] * (s["seq"] + 1) // 2
    per_pair = 2 * (s["dn"] + s["dr"]) + 2 * s["dv"]
    return ((s["depth"] + s["mtp"]) * 3 * s["heads"] * per_pair * pairs
            * s["batch"])


def mla_bytes_per_step(model, bytes_per_elem=2):
    """The least HBM traffic of a block's three calls: the forward
    reads q, k (the rotary key head once), v and writes o; the backward
    reads those and o, do and writes dq, dk, dv; each once."""
    s = _sizes(model)
    rows = s["batch"] * s["seq"] * bytes_per_elem
    q = s["heads"] * (s["dn"] + s["dr"]) * rows
    k = (s["heads"] * s["dn"] + s["dr"]) * rows
    v = o = s["heads"] * s["dv"] * rows
    forward = q + k + v + o
    backward = (q + k + v + 2 * o) + (q + k + v)
    return (s["depth"] + s["mtp"]) * (forward + backward)


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
