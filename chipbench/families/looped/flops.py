"""What a training step of the looped decoder (Ouro's block: one stack
of rotary full-attention layers with a SwiGLU FFN and four norms a
layer, run ``total_ut_steps`` times on the same weights, the one final
norm, the exit gate and the untied head after every pass) costs, from
the configuration file's dictionary alone: the published keys and
``assumed`` (``batch``, ``seq_len``). Nothing here imports JAX or the
program.

Model FLOPs a step (forward and backward, recompute not counted) are
no longer 6 x the parameters: a layer's matmul parameters and its
attention by visible pairs count once a PASS, and so does the head
over the whole vocabulary (the table is a gather, met once). The
norms, the rotation and the gate's ``[hidden, 1]`` product count for
nothing.
"""

import os

# ``run.py`` loads this file before it starts anything, and nothing
# else of a family: a checkout whose program lacks the model this
# family measures (the parent of the PR that added it, with the
# benchmark's files laid over it) fails here at once, and not after
# the agent has restarted three times a worker that cannot import it.
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), *3 * [".."]))
if not os.path.exists(os.path.join(_ROOT, "dlrover_tpu", "models",
                                   "looped.py")):
    raise SystemExit("chipbench/families/looped measures "
                     "dlrover_tpu/models/looped.py, which this "
                     "checkout does not have")


def _sizes(model):
    a = model["assumed"]
    return dict(
        d=model["hidden_size"], f=model["intermediate_size"],
        depth=model["num_hidden_layers"], passes=model["total_ut_steps"],
        vocab=model["vocab_size"], heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], hd=model["head_dim"],
        seq=a["seq_len"], batch=a["batch"])


def layer_passes(model):
    """Layers a token goes through in a step: every layer, every pass."""
    return model["num_hidden_layers"] * model["total_ut_steps"]


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def layer_matmul_params(model):
    """One layer's four attention projections and the FFN's three."""
    s = _sizes(model)
    return (2 * s["d"] * (s["heads"] + s["kv_heads"]) * s["hd"]
            + 3 * s["d"] * s["f"])


def active_matmul_params(model):
    """Matmul parameters a token meets in a step, one met ``T`` times
    counted ``T`` times: the layers and the head, once a pass."""
    s = _sizes(model)
    return (layer_passes(model) * layer_matmul_params(model)
            + s["passes"] * s["d"] * s["vocab"])


def param_count(model):
    """All parameters held here, each once: every matrix (the table and
    the head apiece), four norm scales a layer, the final one, and the
    gate's kernel and bias."""
    s = _sizes(model)
    return (s["depth"] * (layer_matmul_params(model) + 4 * s["d"])
            + 2 * s["d"] * s["vocab"] + s["d"] + s["d"] + 1)


# -- the kernels -------------------------------------------------------------
# The work the model asks of its kernels in one training step, whatever
# calls deliver it (a replayed forward, a block computed whole and
# masked are the kernel's own cost and lower its share of the
# roofline). A visible (query, key) pair costs a query head 2 x 128
# FLOPs in the scores and 2 x 128 in PV forward, and the backward twice
# that (dV, dP, dQ, dK); a layer is met once a pass.

def _pairs_causal(seq):
    return seq * (seq + 1) // 2


def causal_flops_per_step(model):
    """The causal half, forward and backward, of every layer pass."""
    s = _sizes(model)
    return (layer_passes(model) * 3 * s["heads"] * 4 * s["hd"]
            * _pairs_causal(s["seq"]) * s["batch"])


def causal_bytes_per_step(model, bytes_per_elem=2):
    """The least HBM traffic of a layer pass's three calls: the forward
    reads q, k, v and writes o; the backward reads q, k, v, o, do and
    writes dq, dk, dv; each once."""
    s = _sizes(model)
    rows = s["batch"] * s["seq"] * bytes_per_elem
    q = o = s["heads"] * s["hd"] * rows
    k = v = s["kv_heads"] * s["hd"] * rows
    forward = q + k + v + o
    backward = (q + k + v + 2 * o) + (q + k + v)
    return layer_passes(model) * (forward + backward)


def kernel_flops_per_step(model):
    """All the Mosaic kernels of a step: the layers' attention."""
    return causal_flops_per_step(model)


def kernel_bytes_per_step(model, bytes_per_elem=2):
    return causal_bytes_per_step(model, bytes_per_elem)


def model_flops_per_step(model):
    return (6 * active_matmul_params(model) * tokens_per_step(model)
            + causal_flops_per_step(model))
