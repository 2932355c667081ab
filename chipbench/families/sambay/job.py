"""The SambaY decoder as the program trains it
(``dlrover_tpu/models/sambay.py`` under the ``sambay`` sharding rules),
built from a configuration file's dictionary, and its plain reference
(``reference.py`` beside this file) run on the program's parameters.

``worker.py`` imports this module through the configuration's
``family`` and calls ``build``, which gives the ``Job`` of
``families/dense_gqa/job.py``.
"""

import jax
import jax.numpy as jnp

from chipbench.families.dense_gqa.job import Job  # the one contract
from chipbench.families.sambay import reference
from dlrover_tpu.models import sambay
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

# The system's loss against the float32 reference on one seeded row of
# ``seq_len`` tokens at the initial weights. The configuration states
# bf16 parameters and bf16 compute with float32 scan state, norms,
# softmax and cross entropy, so the two differ by bf16's rounding (8
# significant bits) of every activation, which over the 8192 positions
# of a row mostly averages out of the mean loss. On the chip the
# difference read 0.1e-4 to 7.8e-4 at a loss of 12.7 over ten seeds
# (PR 29; the three largest 4.8e-4, 5.9e-4, 7.8e-4). 2.5e-3 is three
# times the largest: the limit of a training cell's loss where the
# precision hardly moves it. It does hardly move it: the same reference
# with the operands of every matrix product rounded to 8-bit floating
# point (e4m3: 4 significant bits) moved by 2.7e-3 on one seed and by
# 2.3e-4 on another, because at random weights a rounding perturbs the
# logits at random and the mean over 8192 tokens keeps its second
# order alone. So this comparison tells 8 bits from 16 on some seeds
# and not on all; what it does catch, each at the toy size on the CPU
# (tests/chipbench/test_chipbench_sambay.py) and by far more than the
# limit: a dropped layer, a window edge off by one, a dropped lambda
# term (and, there, 8-bit operands). A float32 configuration (the CPU
# rehearsal) is held to 1e-4.
REFERENCE_TOL = {"bfloat16": 2.5e-3, "float32": 1e-4}

# the reference's name for each leaf of a layer's mixer, by kind, and
# of its MLP; fused projections are stored [hidden, 2, wide]
MIX_NAMES = {
    "ssm": {"w_in": ("in_proj", "kernel"), "conv_w": ("conv", "kernel"),
            "conv_b": ("conv", "bias"), "w_x": ("x_proj", "kernel"),
            "w_dt": ("dt_proj", "kernel"), "b_dt": ("dt_proj", "bias"),
            "a_log": ("a_log",), "d": ("d_skip",),
            "w_out": ("out_proj", "kernel")},
    "gmu": {"w_g": ("gate_proj", "kernel"),
            "w_out": ("out_proj", "kernel")},
    "attn": {"wq": ("q_proj", "kernel"), "bq": ("q_proj", "bias"),
             "wk": ("k_proj", "kernel"), "bk": ("k_proj", "bias"),
             "wv": ("v_proj", "kernel"), "bv": ("v_proj", "bias"),
             "wo": ("o_proj", "kernel"), "bo": ("o_proj", "bias"),
             "lq1": ("lambda_q1",), "lk1": ("lambda_k1",),
             "lq2": ("lambda_q2",), "lk2": ("lambda_k2",),
             "subln": ("subln", "scale")},
}
MLP_NAMES = {"w1": ("up_proj", "kernel"), "w2": ("down_proj", "kernel")}
FUSED = ("w_in", "w1")


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _named(tree, names):
    out = {}
    for name, path in names.items():
        if path[0] not in tree:
            continue  # a cross layer has no keys or values of its own
        leaf = _leaf(tree, path)
        if name in FUSED:  # [hidden, 2, wide] -> [hidden, 2 wide]
            leaf = leaf.reshape(leaf.shape[0], -1)
        out[name] = leaf
    return out


def _half_layers(period):
    """A period's two layers in the reference's form."""
    mixer = "ssm" if "ssm" in period else "gmu"
    return [
        {"mix_norm": period[mixer]["norm"],
         "mix": _named(period[mixer], MIX_NAMES[mixer]),
         "mlp_norm": period["mix_mlp"]["norm"],
         "mlp": _named(period["mix_mlp"], MLP_NAMES)},
        {"mix_norm": period["attn"]["norm"],
         "mix": _named(period["attn"], MIX_NAMES["attn"]),
         "mlp_norm": period["attn_mlp"]["norm"],
         "mlp": _named(period["attn_mlp"], MLP_NAMES)},
    ]


def reference_layers(params, config):
    """The program's parameters a layer at a time, in order. One period
    of a stack is taken out by a small program whose index is an
    argument, so one compile serves all periods."""
    pick = jax.jit(lambda stack, i: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
        stack))
    for p in range(config.self_periods):
        yield from _half_layers(pick(params["self_layers"], jnp.int32(p)))
    yield from _half_layers(params["boundary"])
    for p in range(config.cross_periods):
        yield from _half_layers(pick(params["cross_layers"], jnp.int32(p)))


def build(model):
    """The job of a configuration file's dictionary: the published
    keys give the widths, ``assumed`` what the source leaves open."""
    a = model["assumed"]
    if not model["tie_word_embeddings"] or model["mb_per_layer"] != 2:
        raise ValueError("models/sambay.py ties the head to the table "
                         "and alternates state-space and attention slots")
    config = sambay.SambaYConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=a["head_dim"],
        sliding_window=model["sliding_window"],
        d_inner=a["d_inner"], d_state=a["d_state"], d_conv=a["d_conv"],
        dt_rank=a["dt_rank"],
        layer_norm_eps=model["layer_norm_eps"],
        max_seq_len=a["seq_len"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
        use_kernels=a.get("kernels", "pallas") == "pallas",
    )
    strategy = Strategy(
        mesh=MeshPlan(**model["layout"]), rule_set="sambay",
        remat_policy="",  # the model remats per period itself
    )

    def reference_loss(params, ids, labels):
        return float(reference.loss(
            model, ids, labels, params["embed_tokens"]["embedding"],
            reference_layers(params, config), params["norm"]))

    return Job(
        init_fn=sambay.make_init_fn(config),
        loss_fn=sambay.make_loss_fn(config, head_chunk=a["head_chunk"]),
        strategy=strategy, vocab_size=config.vocab_size,
        seq_len=config.max_seq_len,
        param_count=sambay.param_count(config),
        layers=config.num_layers, reference_loss=reference_loss,
        reference_tol=REFERENCE_TOL[jnp.dtype(config.compute_dtype).name])
