"""What a step of a dense grouped-query decoder (pre-norm blocks of
rotary attention and a gated feed-forward, an untied head) costs, from
the configuration file's dictionary alone: the published keys of the
model's ``config.json`` and ``assumed`` (``batch``, ``seq_len``).
Nothing here imports JAX or the program. The readers of the per-layer
metrics call ``param_count``, ``model_flops_per_step``,
``kernel_flops_per_step`` and ``kernel_bytes_per_step``; every family
gives those four.

Model FLOPs a token (forward and backward, recompute not counted):
6 x matmul parameters + 6 x layers x sequence x hidden.

* matmul parameters are every layer kernel (q, k, v, o, gate, up,
  down) and the lm head. The embedding table is a gather, not a matrix
  multiplication, and the norms are elementwise: neither is counted.
* attention: QK^T and PV are each 2 x seq x hidden FLOPs a token
  forward over the full square; the causal mask needs half of it
  (2 x seq x hidden for both), and backward costs twice forward:
  3 x 2 x seq x hidden = 6 x seq x hidden a token a layer.
"""


def head_dim(model):
    return model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"])


def layer_matmul_params(model):
    d, f = model["hidden_size"], model["intermediate_size"]
    q = model["num_attention_heads"] * head_dim(model)
    kv = model["num_key_value_heads"] * head_dim(model)
    return d * q + 2 * d * kv + q * d + 3 * d * f


def matmul_params(model):
    """Parameters that are multiplied with every token."""
    return (model["num_hidden_layers"] * layer_matmul_params(model)
            + model["hidden_size"] * model["vocab_size"])


def param_count(model):
    """All parameters: the matmul kernels, the embedding table (the
    head is untied), two norm scales a layer and the final norm."""
    d = model["hidden_size"]
    if model.get("tie_word_embeddings"):
        raise ValueError("a tied head is not counted here")
    return (matmul_params(model) + model["vocab_size"] * d
            + 2 * model["num_hidden_layers"] * d + d)


def attention_flops_per_token(model, seq_len):
    """Causal attention, forward and backward, all layers."""
    width = model["num_attention_heads"] * head_dim(model)
    return 6 * model["num_hidden_layers"] * seq_len * width


def model_flops_per_token(model, seq_len):
    return 6 * matmul_params(model) + attention_flops_per_token(
        model, seq_len)


def tokens_per_step(model):
    return model["assumed"]["batch"] * model["assumed"]["seq_len"]


def model_flops_per_step(model):
    return (model_flops_per_token(model, model["assumed"]["seq_len"])
            * tokens_per_step(model))


# -- the attention kernels ---------------------------------------------------
# The work the model asks of its attention kernels in one training step,
# whatever calls deliver it: six half-square matmuls a head and layer
# (QK^T and PV forward; dV, dP, dQ and dK backward), the same six that
# the model FLOPs count. What a kernel executes beyond them is its own
# cost and lowers its share of the roofline: the flash kernels of PR 24
# recompute S in both backward calls and full remat replays the forward
# call, 11 half squares in all, and blocks on the diagonal are computed
# whole and masked. A change that drops the replay or keeps S raises the
# share because the same work then takes less kernel time.

def kernel_flops_per_step(model):
    return (attention_flops_per_token(model, model["assumed"]["seq_len"])
            * tokens_per_step(model))


def kernel_bytes_per_step(model, bytes_per_elem=2):
    """The least HBM traffic for that work: a forward call reads q, k,
    v and writes o; the backward reads q, k, v, o, do once and writes
    dq, dk, dv once."""
    a = model["assumed"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    q = a["batch"] * h * a["seq_len"] * head_dim(model) * bytes_per_elem
    k = a["batch"] * kv * a["seq_len"] * head_dim(model) * bytes_per_elem
    forward = q + 2 * k + q
    backward = q + 2 * k + 2 * q + q + 2 * k
    return model["num_hidden_layers"] * (forward + backward)
