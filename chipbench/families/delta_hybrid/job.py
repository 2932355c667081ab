"""The decoder of gated-delta-rule and full attention layers as the
program trains it (``dlrover_tpu/models/delta_hybrid.py`` under the
``delta_hybrid`` sharding rules), built from a configuration file's
dictionary, and its plain reference (``reference.py`` beside this file)
run on the program's parameters.

``worker.py`` imports this module through the configuration's
``family`` and calls ``build``, which gives the ``Job`` of
``families/dense_gqa/job.py``.
"""

import json

import jax
import jax.numpy as jnp

from chipbench.families.delta_hybrid import reference
from chipbench.families.dense_gqa.job import Job  # the one contract
# the median token's error is that family's, as it is
from chipbench.families.mla_moe.job import hidden_error
from dlrover_tpu.models import delta_hybrid
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

# Two limits decide the reference check, on one seeded row of
# ``seq_len`` tokens at the initial weights: the program (bf16, the
# rule in its chunked form through the ``gdn_*`` kernels) against the
# float32 reference (``reference.py``: the rule token by token), which
# differs from it by bf16's rounding of every activation.
#
# Every reading below is the harness's own comparison on the chip (PR
# 43, TPU v5 lite: ``tests/chipbench/delta_hybrid_controls.py``, which
# calls ``worker.ReferenceCheck``, the compiled ``eval_step`` against
# this job's ``reference_loss``) at the timed sizes (depth 8, one row of
# 8192, a quarter of the vocabulary): the sound reference on seeds
# 3000004311-18 and in the cell's own runs, each control on 3000004311
# and 3000004312.
#
# ``HIDDEN_TOL``, on the hidden states, is the limit that feels the
# precision and a wrong mechanism: the median over the row's tokens of
# ``|program - reference| / |reference|`` of the final normed hidden
# state (the program's ``apply_hidden`` on the same parameters and
# ids). Sound: 1.74% to 1.86% on the eight seeds (1.70% to 1.79% in
# three of the cell's own runs); twice the other families' 0.8%, because every sublayer's
# output is normalised before it is added, so no residual of a larger
# norm dilutes what a layer's rounding adds. The reference with e4m3
# operands, the nearest precision below the bf16 the configuration
# states: 33.0% and 32.4%. Each mechanism wrong in the reference alone:
# rotary (theta 5e5) applied on the full layers 3.16% and 3.34% (two
# layers of eight, and a causal softmax over normalised q and k feels
# positions little), the QK norms left off 36.9% and 37.5%, ``beta``
# not doubled 49.6% and 47.6%, the erase dropped 70.8% and 68.5%, the
# output gate dropped 83.0% and 82.8%, the convolutions left off 89.9%
# and 90.0%, the decay dropped 95.7% and 94.8%, pre-norm in place of
# the reordered norm 98.7% and 98.5%; q and k not l2-normalised
# overflows the recurrence (``beta`` up to 2 against keys of length 7)
# and reads no number, which is past every limit. 2.4e-2 lies 1.29
# times above the largest sound reading and 1.32 times below the
# smallest of the others (the sound readings lie within 0.12% of each
# other, so the room above is ten times their spread), and the harness
# said not ``ok`` of e4m3 and of all nine on both seeds. ``worker.py``
# reads one number, so a row that fails this limit gives it NaN for
# the reference's loss, which fails its comparison; the reading is
# printed beside it (event ``reference_hidden``, with the reference's
# loss).
#
# ``REFERENCE_TOL``, on the loss (what ``worker.py`` compares), is the
# coarse limit. The mean loss of a row at random weights hardly feels
# the precision, and a mechanism little: the final norm gives the
# logits the same spread whatever came before. Sound: 4.8e-6 to 4.9e-4
# at a loss of 10.63 on the nine rows; e4m3 operands 4.0e-3 and
# 1.2e-2; a wrong mechanism 6.9e-4 (rotary on the full layers) to
# 1.9e-2: the loss does not separate the weakest of them, which fails
# by the hidden states. 2e-3 is ``dense_gqa``'s limit, 4.1 times the
# largest sound reading, and what e4m3 and a gross error fail.
#
# A float32 configuration (the CPU rehearsal) is held to 1e-4 on both:
# there the two sides differ by the order of float32 sums.
REFERENCE_TOL = {"bfloat16": 2e-3, "float32": 1e-4}
HIDDEN_TOL = {"bfloat16": 2.4e-2, "float32": 1e-4}


def _reference_layer(layer, kind):
    """One layer of the program's parameters in the reference's form."""
    m = layer["mixer"]
    if kind == delta_hybrid.LINEAR:
        mixer = {"wq": m["q_proj"]["kernel"], "wk": m["k_proj"]["kernel"],
                 "wv": m["v_proj"]["kernel"], "wg": m["g_proj"]["kernel"],
                 "wa": m["a_proj"]["kernel"], "wb": m["b_proj"]["kernel"],
                 "wo": m["o_proj"]["kernel"],
                 "conv_q": m["q_conv"]["kernel"],
                 "conv_k": m["k_conv"]["kernel"],
                 "conv_v": m["v_conv"]["kernel"],
                 "a_log": m["a_log"], "dt_bias": m["dt_bias"],
                 "o_norm": m["o_norm"]["scale"]}
    else:
        mixer = {"wq": m["q_proj"]["kernel"], "wk": m["k_proj"]["kernel"],
                 "wv": m["v_proj"]["kernel"], "wo": m["o_proj"]["kernel"],
                 "q_norm": m["q_norm"]["scale"],
                 "k_norm": m["k_norm"]["scale"]}
    return {"mixer": mixer, "attn_norm": layer["attn_norm"]["scale"],
            "mlp": {"w_gate": layer["mlp"]["gate_proj"]["kernel"],
                    "w_up": layer["mlp"]["up_proj"]["kernel"],
                    "w_down": layer["mlp"]["down_proj"]["kernel"]},
            "ffn_norm": layer["ffn_norm"]["scale"]}


@jax.jit
def _pick(stack, i):
    """Layer ``i`` of a stack: the index is an argument, so one compile
    serves every layer of a kind."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def reference_layers(params, config):
    """The program's parameters a layer at a time, in order: layer
    ``l`` is at position ``l % period`` of period ``l // period``."""
    plan = delta_hybrid.layer_plan(config)
    for i in range(config.num_layers):
        j = i % len(plan)
        yield _reference_layer(
            _pick(params["layers"][str(j)], i // len(plan)), plan[j])


def model_config(model, **overrides):
    """``DeltaHybridConfig`` of a configuration file's dictionary: the
    published keys give the widths and the layer list, ``assumed`` what
    the source leaves open."""
    a = model["assumed"]
    if (model["tie_word_embeddings"] or model["attention_bias"]
            or model["hidden_act"] != "silu"
            or model["rope_parameters"]["rope_theta"] is not None
            or model["linear_num_key_heads"]
            != model["linear_num_value_heads"]):
        raise ValueError(
            "models/delta_hybrid.py computes an untied head, no bias, "
            "SiLU, full layers without positions, and as many key heads "
            "as value heads in a linear layer")
    config = dict(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=a["head_dim"],
        linear_num_heads=model["linear_num_value_heads"],
        linear_key_head_dim=model["linear_key_head_dim"],
        linear_value_head_dim=model["linear_value_head_dim"],
        linear_conv_kernel_dim=model["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=model["linear_allow_neg_eigval"],
        layer_types=tuple(model["layer_types"]),
        rms_norm_eps=model["rms_norm_eps"],
        embed_std=a["embed_std"],
        max_seq_len=a["seq_len"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
    )
    config.update(overrides)
    return delta_hybrid.DeltaHybridConfig(**config)


def reference_loss_of(model, config, params, ids, labels, hidden=None):
    return float(reference.loss(
        model, ids, labels, params["embed_tokens"]["embedding"],
        reference_layers(params, config), params["norm"]["scale"],
        params["lm_head"]["kernel"], hidden))


def build(model, **overrides):
    config = model_config(model, **overrides)
    strategy = Strategy(
        mesh=MeshPlan(**model["layout"]), rule_set="delta_hybrid",
        remat_policy="",  # the model remats per layer itself
    )
    precision = jnp.dtype(config.compute_dtype).name
    program_hidden = jax.jit(lambda params, ids: delta_hybrid.apply_hidden(
        params, ids[None], config)[0][0])

    def reference_loss(params, ids, labels):
        final = []
        loss = reference_loss_of(model, config, params, ids, labels, final)
        error = hidden_error(program_hidden(params, jnp.asarray(ids)),
                             final[0])
        print(json.dumps({"event": "reference_hidden",
                          "reference_loss": loss,
                          "median_token_error": error,
                          "tolerance": HIDDEN_TOL[precision]}), flush=True)
        return loss if error <= HIDDEN_TOL[precision] else float("nan")

    return Job(
        init_fn=delta_hybrid.make_init_fn(config),
        loss_fn=delta_hybrid.make_loss_fn(
            config, head_chunk=model["assumed"]["head_chunk"]),
        strategy=strategy, vocab_size=config.vocab_size,
        seq_len=config.max_seq_len,
        param_count=delta_hybrid.param_count(config),
        layers=config.num_layers, reference_loss=reference_loss,
        reference_tol=REFERENCE_TOL[precision])
