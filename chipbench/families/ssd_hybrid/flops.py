"""What a training step of the decoder of Mamba-2 and position-free
attention layers (Granite-4.0-H's block: both kinds followed by the
shared MLP, pre-norm residuals under a multiplier, a head tied to the
token table) costs, from the configuration file's dictionary alone: the
published keys and ``assumed`` (``batch``, ``seq_len``, ``head_dim``).
Nothing here imports JAX or the program.

Model FLOPs a step (forward and backward, recompute not counted):
6 x the matmul parameters a token meets x tokens, the attention layers'
attention by visible pairs, and the recurrence's own work. A token
meets, in a Mamba layer, ``W_in`` (all its columns: z, x, B, C and dt)
and ``W_out``; in an attention layer the four projections; in both the
MLP's two matrices; and the head over the vocabulary (the tied table
read as a head; as a table it is a gather). The convolution, norms,
gates and multipliers are elementwise and count for nothing.
"""

import os

# ``run.py`` loads this file before it starts anything, and nothing
# else of a family: a checkout whose program lacks the model this
# family measures (the parent of the PR that added it, with the
# benchmark's files laid over it) fails here at once, and not after
# the agent has restarted three times a worker that cannot import it.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *3 * [".."]))
if not os.path.exists(os.path.join(_ROOT, "dlrover_tpu", "models",
                                   "ssd_hybrid.py")):
    raise SystemExit("chipbench/families/ssd_hybrid measures "
                     "dlrover_tpu/models/ssd_hybrid.py, which this "
                     "checkout does not have")

MAMBA, ATTENTION = "mamba", "attention"


def _sizes(model):
    a = model["assumed"]
    heads, p = model["mamba_n_heads"], model["mamba_d_head"]
    return dict(
        d=model["hidden_size"], f=model["shared_intermediate_size"],
        vocab=model["vocab_size"],
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], hd=a["head_dim"],
        m_heads=heads, p=p, n=model["mamba_d_state"],
        groups=model["mamba_n_groups"], taps=model["mamba_d_conv"],
        inner=heads * p,
        shared=2 * model["mamba_n_groups"] * model["mamba_d_state"],
        seq=a["seq_len"], batch=a["batch"])


def layer_counts(model):
    """Layers by mixer: the first ``num_hidden_layers`` entries of the
    published ``layer_types``."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    return {"ssd": kinds.count(MAMBA), "attn_full": kinds.count(ATTENTION)}


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def _mlp_params(s):
    return 3 * s["d"] * s["f"]


def _mamba_matmul_params(s):
    """``W_in`` ([z | xBC | dt]) and ``W_out``."""
    return (s["d"] * (2 * s["inner"] + s["shared"] + s["m_heads"])
            + s["inner"] * s["d"])


def _attention_matmul_params(s):
    return 2 * s["d"] * (s["heads"] + s["kv_heads"]) * s["hd"]


def active_matmul_params(model):
    """Matmul parameters a token meets (the tied table once, as the
    head)."""
    s, n = _sizes(model), layer_counts(model)
    return (n["ssd"] * (_mamba_matmul_params(s) + _mlp_params(s))
            + n["attn_full"] * (_attention_matmul_params(s) + _mlp_params(s))
            + s["d"] * s["vocab"])


def param_count(model):
    """All parameters held here: every matrix (the tied table once), a
    Mamba layer's convolution with its bias, ``A_log``, ``D`` and
    ``dt_bias`` and gated norm, two norm scales a layer and the final
    one."""
    s, n = _sizes(model), layer_counts(model)
    channels = s["inner"] + s["shared"]
    mamba = (_mamba_matmul_params(s) + (s["taps"] + 1) * channels
             + 3 * s["m_heads"] + s["inner"])
    both = _mlp_params(s) + 2 * s["d"]
    return (n["ssd"] * (mamba + both)
            + n["attn_full"] * (_attention_matmul_params(s) + both)
            + s["d"] * s["vocab"] + s["d"])


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (a replayed forward, a block computed whole and
# masked, the chunked form's own products and the float32 states each
# chunk starts from are the kernel's own cost and lower its share of
# the roofline).
#
# Attention: a visible (query, key) pair costs a query head 2 x 64
# FLOPs in the scores and 2 x 64 in PV forward, and the backward twice
# that (dV, dP, dQ, dK).

def _pairs_causal(seq):
    return seq * (seq + 1) // 2


def causal_flops_per_step(model):
    """The attention layers: the causal half, forward and backward."""
    s = _sizes(model)
    return (layer_counts(model)["attn_full"] * 3 * s["heads"] * 4 * s["hd"]
            * _pairs_causal(s["seq"]) * s["batch"])


def causal_bytes_per_step(model, bytes_per_elem=2):
    """The least HBM traffic of the attention layers' three calls: the
    forward reads q, k, v and writes o; the backward reads q, k, v, o,
    do and writes dq, dk, dv; each once."""
    s = _sizes(model)
    rows = s["batch"] * s["seq"] * bytes_per_elem
    q = o = s["heads"] * s["hd"] * rows
    k = v = s["kv_heads"] * s["hd"] * rows
    forward = q + k + v + o
    backward = (q + k + v + 2 * o) + (q + k + v)
    return layer_counts(model)["attn_full"] * (forward + backward)


# The state-space recurrence, as it is defined: a token and head meets
# its [P, N] state twice forward (the update ``dt x B^T`` with the
# decay, the read ``S C``), 2 x P x N FLOPs each, and twice that
# backward.

def ssd_flops_per_step(model):
    s = _sizes(model)
    return (layer_counts(model)["ssd"] * 3 * 2 * 2 * s["p"] * s["n"]
            * s["m_heads"] * tokens_per_step(model))


def ssd_bytes_per_step(model, bytes_per_elem=2):
    """x and y (``H P`` columns each) and a group's B and C in the
    compute dtype and dt in float32, read or written once forward; they
    and their gradients once backward."""
    s = _sizes(model)
    a_pass = ((2 * s["inner"] + s["shared"]) * bytes_per_elem
              + 4 * s["m_heads"])
    return layer_counts(model)["ssd"] * 3 * a_pass * tokens_per_step(model)


def kernel_flops_per_step(model):
    """All the Mosaic kernels of a step: the attention layers'
    attention and the Mamba layers' recurrence."""
    return causal_flops_per_step(model) + ssd_flops_per_step(model)


def kernel_bytes_per_step(model, bytes_per_elem=2):
    return (causal_bytes_per_step(model, bytes_per_elem)
            + ssd_bytes_per_step(model, bytes_per_elem))


def model_flops_per_step(model):
    return (6 * active_matmul_params(model) * tokens_per_step(model)
            + causal_flops_per_step(model) + ssd_flops_per_step(model))
