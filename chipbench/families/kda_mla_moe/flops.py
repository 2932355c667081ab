"""What a training step of the decoder of Kimi-delta-attention and
latent-attention layers costs (Ling-3.0-flash's block: KDA on five
layers in six and MLA without a query latent on the sixth, a dense
SwiGLU FFN in the leading layers, one shared expert plus the routed
experts held here in the rest), from the configuration file's
dictionary alone: the published keys, ``deployment`` (the router's
published width, since ``num_experts`` counts the experts held here)
and ``assumed`` (``batch``, ``seq_len``). Nothing here imports JAX or
the program.

Model FLOPs a step (forward and backward, recompute not counted):
6 x the matmul parameters a token meets x tokens, the MLA layers'
attention by visible pairs, and the rule's own work. A token meets, in
a KDA layer, the q, k, v, decay-gate, output-gate and output
projections (six of ``hidden x heads x 128``) and ``beta``'s; in an MLA
layer the query, latent, expansion, head-gate and output projections;
in a dense layer its FFN's three matrices; in an expert layer the
router, the shared expert and ``num_experts_per_tok * held / router
width`` of a routed expert BY EXPECTATION under uniform routing; and
the head over the vocabulary held (the table is a gather). The
convolutions, norms and gates are elementwise and count for nothing.
"""

import os

# ``run.py`` loads this file before it starts anything, and nothing
# else of a family: a checkout whose program lacks the model this
# family measures (the parent of the PR that added it, with the
# benchmark's files laid over it) fails here at once, and not after
# the agent has restarted three times a worker that cannot import it.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *3 * [".."]))
if not os.path.exists(os.path.join(_ROOT, "dlrover_tpu", "models",
                                   "kda_mla_moe.py")):
    raise SystemExit("chipbench/families/kda_mla_moe measures "
                     "dlrover_tpu/models/kda_mla_moe.py, which this "
                     "checkout does not have")

KDA, MLA = "kda", "mla"


def _sizes(model):
    a, dep = model["assumed"], model["deployment"]
    return dict(
        d=model["hidden_size"], f=model["intermediate_size"],
        fe=model["moe_intermediate_size"],
        depth=model["num_hidden_layers"],
        dense=model["first_k_dense_replace"], vocab=model["vocab_size"],
        heads=model["num_attention_heads"], hd=model["head_dim"],
        conv=model["short_conv_kernel_size"],
        rkv=model["kv_lora_rank"], dn=model["qk_nope_head_dim"],
        dr=model["qk_rope_head_dim"], dv=model["v_head_dim"],
        held=model["num_experts"], router=dep["published_num_experts"],
        shared=model["num_shared_experts"],
        k=model["num_experts_per_tok"],
        seq=a["seq_len"], batch=a["batch"])


def layer_counts(model):
    """Layers by mixer (layer ``l`` is MLA where ``(l + 1) %
    layer_group_size == 0``) and by FFN."""
    depth, group = model["num_hidden_layers"], model["layer_group_size"]
    mla = sum((i + 1) % group == 0 for i in range(depth))
    dense = model["first_k_dense_replace"]
    return {KDA: depth - mla, MLA: mla, "dense": dense,
            "moe": depth - dense}


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def _kda_matmul_params(s):
    """q, k, v, the decay's gate, the output gate and ``W_o``, and
    ``beta``."""
    return s["d"] * (6 * s["heads"] * s["hd"] + s["heads"])


def _mla_matmul_params(s):
    return (s["d"] * s["heads"] * (s["dn"] + s["dr"])
            + s["d"] * (s["rkv"] + s["dr"])
            + s["rkv"] * s["heads"] * (s["dn"] + s["dv"])
            + s["d"] * s["heads"]  # the gate a head
            + s["heads"] * s["dv"] * s["d"])


def expert_params(model):
    """One expert's three matrices."""
    s = _sizes(model)
    return 3 * s["d"] * s["fe"]


def expert_layers(model):
    return layer_counts(model)["moe"]


def held_rows_expected(model):
    """Assignments a step routes to the experts held here, one expert
    layer, if routing is uniform."""
    s = _sizes(model)
    return tokens_per_step(model) * s["k"] * s["held"] / s["router"]


def active_matmul_params(model):
    """Matmul parameters a token meets, the routed experts held here
    by expectation."""
    s, n = _sizes(model), layer_counts(model)
    one = expert_params(model)
    moe = (s["d"] * s["router"] + s["shared"] * one
           + s["k"] * s["held"] / s["router"] * one)
    return (n[KDA] * _kda_matmul_params(s) + n[MLA] * _mla_matmul_params(s)
            + n["dense"] * 3 * s["d"] * s["f"] + n["moe"] * moe
            + s["d"] * s["vocab"])


def param_count(model):
    """All parameters held here: every matrix (the table and the head
    apiece, every held expert whole), a KDA layer's three convolutions,
    ``A_log`` a head, ``dt_bias`` a channel and the gated norm's scale,
    an MLA layer's latent norm and three QK norms, two norm scales a
    layer and the final one. The router's selection bias is no
    parameter: a buffer of the training state."""
    s, n = _sizes(model), layer_counts(model)
    wide = s["heads"] * s["hd"]
    kda = (_kda_matmul_params(s) + 3 * s["conv"] * wide + s["heads"] + wide
           + s["hd"])
    mla = (_mla_matmul_params(s) + s["rkv"] + (s["dn"] + s["dr"]) + s["dn"]
           + s["dr"])
    moe = (s["d"] * s["router"]
           + (s["shared"] + s["held"]) * expert_params(model))
    return (n[KDA] * kda + n[MLA] * mla + s["depth"] * 2 * s["d"]
            + n["dense"] * 3 * s["d"] * s["f"] + n["moe"] * moe
            + 2 * s["d"] * s["vocab"] + s["d"])


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (a replayed forward, a block computed whole and
# masked, the chunked form's own products and float32 states, a padded
# row tile are the kernel's own cost and lower its share of the
# roofline).
#
# Latent attention: a visible (query, key) pair costs a query head
# 2 x (128 + 64) FLOPs in the scores and 2 x 128 in PV forward, and the
# backward twice that.


def mla_flops_per_step(model):
    s = _sizes(model)
    pairs = s["seq"] * (s["seq"] + 1) // 2
    per_pair = 2 * (s["dn"] + s["dr"]) + 2 * s["dv"]
    return (layer_counts(model)[MLA] * 3 * s["heads"] * per_pair * pairs
            * s["batch"])


def mla_bytes_per_step(model, bytes_per_elem=2):
    """The least HBM traffic of the MLA layers' calls: the forward
    reads q, k (the rotary key head once) and v and writes o; the
    backward reads those and o, do and writes dq, dk, dv; each once."""
    s = _sizes(model)
    rows = s["batch"] * s["seq"] * bytes_per_elem
    q = s["heads"] * (s["dn"] + s["dr"]) * rows
    k = (s["heads"] * s["dn"] + s["dr"]) * rows
    v = o = s["heads"] * s["dv"] * rows
    return layer_counts(model)[MLA] * (
        (q + k + v + o) + (q + k + v + 2 * o) + (q + k + v))


# The delta rule, as the recurrence defines it: a token and head meets
# its [dk, dv] state three times forward (S^T k to read what is there,
# the rank-one write, S^T q to answer), 2 x dk x dv FLOPs each, and
# twice that backward. The decay's dk x dv multiplies a token are a
# sixth of that and elementwise: not counted.


def kda_flops_per_step(model):
    s = _sizes(model)
    return (layer_counts(model)[KDA] * 3 * 3 * 2 * s["hd"] * s["hd"]
            * s["heads"] * tokens_per_step(model))


def kda_bytes_per_step(model, bytes_per_elem=2):
    """q, k, v, o (128 each a head) in the compute dtype, the gate
    (128 a head) and beta in float32, read or written once forward;
    they and their gradients once backward."""
    s = _sizes(model)
    a_pass = 4 * s["hd"] * bytes_per_elem + (s["hd"] + 1) * 4
    return (layer_counts(model)[KDA] * 3 * a_pass * s["heads"]
            * tokens_per_step(model))


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
    s = _sizes(model)
    weights = 3 * expert_layers(model) * s["held"] * expert_params(model)
    per_row = 3 * (2 * (s["d"] + s["fe"]) + (s["fe"] + s["d"]))
    return (weights + per_row * rows) * bytes_per_elem


def kernel_flops_per_step(model):
    """All the Mosaic kernels of a step: the latent attention, the rule
    and the grouped matmuls at the expected rows."""
    return (mla_flops_per_step(model) + kda_flops_per_step(model)
            + gmm_flops(model, expert_layers(model)
                        * held_rows_expected(model)))


def kernel_bytes_per_step(model, bytes_per_elem=2):
    return (mla_bytes_per_step(model, bytes_per_elem)
            + kda_bytes_per_step(model, bytes_per_elem)
            + gmm_bytes(model, expert_layers(model)
                        * held_rows_expected(model), bytes_per_elem))


def model_flops_per_step(model):
    return (6 * active_matmul_params(model) * tokens_per_step(model)
            + mla_flops_per_step(model) + kda_flops_per_step(model))
