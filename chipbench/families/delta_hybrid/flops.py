"""What a training step of the decoder of gated-delta-rule and full
attention layers (Olmo-Hybrid's block: both kinds followed by a SwiGLU
FFN, the sublayer's output normalised then added, an untied head)
costs, from the configuration file's dictionary alone: the published
keys and ``assumed`` (``batch``, ``seq_len``). Nothing here imports JAX
or the program.

Model FLOPs a step (forward and backward, recompute not counted):
6 x the matmul parameters a token meets x tokens, the full layers'
attention by visible pairs, and the rule's own work. A token meets, in
a linear layer, the q, k, v, gate and output projections and the two
``[hidden, heads]`` ones of ``beta`` and the decay; in a full layer
the four projections; in both the FFN's three; and the head over the
vocabulary held (the table is a gather). The convolutions, norms and
gates are elementwise and count for nothing.
"""

import os

# ``run.py`` loads this file before it starts anything, and nothing
# else of a family: a checkout whose program lacks the model this
# family measures (the parent of the PR that added it, with the
# benchmark's files laid over it) fails here at once, and not after
# the agent has restarted three times a worker that cannot import it.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *3 * [".."]))
if not os.path.exists(os.path.join(_ROOT, "dlrover_tpu", "models",
                                   "delta_hybrid.py")):
    raise SystemExit("chipbench/families/delta_hybrid measures "
                     "dlrover_tpu/models/delta_hybrid.py, which this "
                     "checkout does not have")

LINEAR, FULL = "linear_attention", "full_attention"


def _sizes(model):
    a = model["assumed"]
    return dict(
        d=model["hidden_size"], f=model["intermediate_size"],
        depth=model["num_hidden_layers"], vocab=model["vocab_size"],
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], hd=a["head_dim"],
        lin_heads=model["linear_num_value_heads"],
        dk=model["linear_key_head_dim"], dv=model["linear_value_head_dim"],
        conv=model["linear_conv_kernel_dim"],
        seq=a["seq_len"], batch=a["batch"])


def layer_counts(model):
    """Layers by mixer: the first ``num_hidden_layers`` entries of the
    published ``layer_types``."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    return {"gdn": kinds.count(LINEAR), "attn_full": kinds.count(FULL)}


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def _ffn_params(s):
    return 3 * s["d"] * s["f"]


def _linear_matmul_params(s):
    """q and k, v and the gate, the output, ``beta`` and the decay."""
    wide_k, wide_v = s["lin_heads"] * s["dk"], s["lin_heads"] * s["dv"]
    return s["d"] * (2 * wide_k + 3 * wide_v + 2 * s["lin_heads"])


def _full_matmul_params(s):
    return 2 * s["d"] * (s["heads"] + s["kv_heads"]) * s["hd"]


def active_matmul_params(model):
    """Matmul parameters a token meets."""
    s, n = _sizes(model), layer_counts(model)
    return (n["gdn"] * (_linear_matmul_params(s) + _ffn_params(s))
            + n["attn_full"] * (_full_matmul_params(s) + _ffn_params(s))
            + s["d"] * s["vocab"])


def param_count(model):
    """All parameters held here: every matrix (the table and the head
    apiece), the linear layers' three convolutions, ``A_log``,
    ``dt_bias`` and gated norm, the full layers' two QK norms, two norm
    scales a layer and the final one."""
    s, n = _sizes(model), layer_counts(model)
    wide_k, wide_v = s["lin_heads"] * s["dk"], s["lin_heads"] * s["dv"]
    linear = (_linear_matmul_params(s) + s["conv"] * (2 * wide_k + wide_v)
              + 2 * s["lin_heads"] + s["dv"])
    full = _full_matmul_params(s) + (s["heads"] + s["kv_heads"]) * s["hd"]
    both = _ffn_params(s) + 2 * s["d"]
    return (n["gdn"] * (linear + both) + n["attn_full"] * (full + both)
            + 2 * s["d"] * s["vocab"] + s["d"])


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (a replayed forward, a block computed whole and
# masked, the chunked form's own products and float32 states are the
# kernel's own cost and lower its share of the roofline).
#
# Full attention: a visible (query, key) pair costs a query head
# 2 x 128 FLOPs in the scores and 2 x 128 in PV forward, and the
# backward twice that (dV, dP, dQ, dK).

def _pairs_causal(seq):
    return seq * (seq + 1) // 2


def causal_flops_per_step(model):
    """The full layers: the causal half, forward and backward."""
    s = _sizes(model)
    return (layer_counts(model)["attn_full"] * 3 * s["heads"] * 4 * s["hd"]
            * _pairs_causal(s["seq"]) * s["batch"])


def causal_bytes_per_step(model, bytes_per_elem=2):
    """The least HBM traffic of the full layers' three calls: the
    forward reads q, k, v and writes o; the backward reads q, k, v, o,
    do and writes dq, dk, dv; each once."""
    s = _sizes(model)
    rows = s["batch"] * s["seq"] * bytes_per_elem
    q = o = s["heads"] * s["hd"] * rows
    k = v = s["kv_heads"] * s["hd"] * rows
    forward = q + k + v + o
    backward = (q + k + v + 2 * o) + (q + k + v)
    return layer_counts(model)["attn_full"] * (forward + backward)


# The gated delta rule, as the recurrence defines it: a token and head
# meets its [dk, dv] state three times forward (S k to read what is
# there, the rank-one write, S q to answer), 2 x dk x dv FLOPs each,
# and twice that backward.

def gdn_flops_per_step(model):
    s = _sizes(model)
    return (layer_counts(model)["gdn"] * 3 * 3 * 2 * s["dk"] * s["dv"]
            * s["lin_heads"] * tokens_per_step(model))


def gdn_bytes_per_step(model, bytes_per_elem=2):
    """q, k (dk each), v, o (dv each) in the compute dtype and g, beta
    in float32, read or written once forward; they and their gradients
    once backward."""
    s = _sizes(model)
    a_pass = (2 * s["dk"] + 2 * s["dv"]) * bytes_per_elem + 2 * 4
    return (layer_counts(model)["gdn"] * 3 * a_pass * s["lin_heads"]
            * tokens_per_step(model))


def kernel_flops_per_step(model):
    """All the Mosaic kernels of a step: the full layers' attention and
    the linear layers' rule."""
    return causal_flops_per_step(model) + gdn_flops_per_step(model)


def kernel_bytes_per_step(model, bytes_per_elem=2):
    return (causal_bytes_per_step(model, bytes_per_elem)
            + gdn_bytes_per_step(model, bytes_per_elem))


def model_flops_per_step(model):
    return (6 * active_matmul_params(model) * tokens_per_step(model)
            + causal_flops_per_step(model) + gdn_flops_per_step(model))
