"""The decoder of Mamba-2 and position-free attention layers as the
program trains it (``dlrover_tpu/models/ssd_hybrid.py`` under the
``ssd_hybrid`` sharding rules), built from a configuration file's
dictionary, and its plain reference (``reference.py`` beside this file)
run on the program's parameters.

``worker.py`` imports this module through the configuration's
``family`` and calls ``build``, which gives the ``Job`` of
``families/dense_gqa/job.py``.
"""

import json

import jax
import jax.numpy as jnp

from chipbench.families.dense_gqa.job import Job  # the one contract
# the median token's error is that family's, as it is
from chipbench.families.mla_moe.job import hidden_error
from chipbench.families.ssd_hybrid import reference
from dlrover_tpu.models import ssd_hybrid
from dlrover_tpu.models.common import cast_floats
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

# Three limits decide the reference check, on one seeded row of
# ``seq_len`` tokens at the initial weights: the program (bf16, the
# recurrence in its chunked form through the ``ssd_*`` kernels) against
# the float32 reference (``reference.py``: the recurrence token by
# token), which differs from it by bf16's rounding of every activation.
#
# Every reading below is the harness's own comparison on the chip (PR
# 57, TPU v5 lite: ``tests/chipbench/ssd_hybrid_controls.py``, which
# calls ``worker.ReferenceCheck``, the compiled ``eval_step`` against
# this job's ``reference_loss``, and the cell's own runs) at the timed
# sizes (depth 20, one row of 8192, the whole vocabulary): the sound
# reference on seeds 3000005702-04, 3000005721-23, 3000005751-54 and in
# the cell's fourteen runs, each control on 3000005702, 3000005721, 3000005751
# and 3000005752.
#
# ``HIDDEN_TOL``, on the hidden states, is the limit that feels the
# precision and a wrong mechanism of a Mamba layer or of the block: the
# median over the row's tokens of ``|program - reference| /
# |reference|`` of the final normed hidden state (the program's
# ``apply_hidden`` on the same parameters and ids). Sound: 1.405% to
# 1.495% on twenty-four rows; nearer olmo's 1.8% than the other families'
# 0.8%: a sublayer adds 0.22 of its output to a stream that starts at
# a standard deviation of 0.24, so the stream's own norm dilutes a
# layer's rounding little. The reference with e4m3 operands, the
# nearest precision below the bf16 the configuration states: 19.8% to
# 22.2%. The carried state rounded to bf16 once a token
# (``lax.reduce_precision``; a pair of casts is dropped by the chip's
# compiler and read the sound 1.484%): 2.43%, 3.00% and 3.60%. Each
# mechanism wrong in the reference alone: the norm before the gate 49.2%
# to 51.0%, the convolution's bias left out 55.9% to 58.4%, ``D_skip``
# left out 72.6% to 75.6%, the residual multiplier at 1 71.3% to 74.3%,
# the embedding multiplier at 1 78.1% to 81.4%, ``dt_bias`` left out
# 87.1% to 88.7%. 2.0e-2 lies 1.34 times above the largest sound
# reading (the sound readings lie within 0.09 points of each other) and 1.2
# times below the smallest of the others, and the harness said not
# ``ok`` of e4m3, the bf16 state and all six.
#
# ``ATTENTION_TOL`` is the limit that feels an attention layer's own
# two mechanisms, which the final hidden states do not: at the initial
# weights an attention layer's scores are small (q . k / 64 has a
# standard deviation of 0.13), so each query averages its keys almost
# evenly whatever the scale or the rotation, two layers in twenty are
# attention, and rotary applied reads 1.431% to 1.494% there and a
# scale of 1/8 for 1/64 1.751% to 1.846%, both under ``HIDDEN_TOL``.
# So the program's first attention layer's mixer
# (``ssd_hybrid.attention_mixer``: the projections and the plain flash
# kernels under the explicit scale, at the timed shapes) is run alone
# on what the reference's mixer read there, the stream of the timed
# row after five Mamba layers, and the median token's error of its
# output against the reference's is taken. Sound: 0.228% to 0.235% on
# eleven rows (one layer's rounding, with no stream to carry it). Rotary
# applied in the reference: 0.943% and 1.031%; the scale of 1/8: 6.45%
# and 7.07%; e4m3 operands: no number (a probability of 1/8192
# underflows to 0, and so does the reference's output). A wrong Mamba
# layer or multiplier leaves it at the sound reading, as it should: the
# mixer's input is the reference's own. 5.0e-3 lies 2.1 times above
# the largest sound reading and 1.9 times below the smallest of
# rotary's.
#
# ``worker.py`` reads one number, so a row that fails either of these
# two limits gives it NaN for the reference's loss, which fails its
# comparison; the readings are printed beside it (event
# ``reference_hidden``, with the reference's loss). All three numbers
# come from forward programs: the backward kernels (``ssd_bwd``,
# ``flash_dkv``, ``flash_dq``) are held at the timed shapes by
# ``benchmarks/ssd_bench.py`` on the chip and at toy sizes by the CPU
# tests, not by ``correct``.
#
# ``REFERENCE_TOL``, on the loss (what ``worker.py`` compares), is the
# coarse limit. The mean loss of a row at random weights hardly feels
# the precision, and a mechanism little: the final norm gives the
# logits the same spread whatever came before. Sound: 9.5e-7 to 4.4e-5
# at a loss of 11.52 on the twenty-four rows; e4m3 operands 8.7e-5 to
# 1.9e-4; a wrong mechanism 9.5e-7 (``D_skip``) to 1.9e-3
# (``dt_bias``): the loss separates none of them, which fail by the
# hidden states. 2e-3 is ``dense_gqa``'s limit, 45 times the largest
# sound reading, and what a gross error fails.
#
# A float32 configuration (the CPU rehearsal) is held to 1e-4 on the
# loss and 1e-5 on the other two: there the two sides differ by the
# order of float32 sums.
REFERENCE_TOL = {"bfloat16": 2e-3, "float32": 1e-4}
HIDDEN_TOL = {"bfloat16": 2.0e-2, "float32": 1e-5}
ATTENTION_TOL = {"bfloat16": 5.0e-3, "float32": 1e-5}


def _reference_layer(layer, kind):
    """One layer of the program's parameters in the reference's form."""
    m = layer["mixer"]
    if kind == ssd_hybrid.MAMBA:
        mixer = {"w_in": m["in_proj"]["kernel"],
                 "conv_w": m["conv"]["kernel"], "conv_b": m["conv"]["bias"],
                 "a_log": m["a_log"], "dt_bias": m["dt_bias"],
                 "d_skip": m["d_skip"], "norm": m["norm"]["scale"],
                 "w_out": m["out_proj"]["kernel"]}
    else:
        mixer = {"wq": m["q_proj"]["kernel"], "wk": m["k_proj"]["kernel"],
                 "wv": m["v_proj"]["kernel"], "wo": m["o_proj"]["kernel"]}
    return {"mixer": mixer, "in_norm": layer["input_norm"]["scale"],
            "mlp": {"w_in": layer["mlp"]["gate_up_proj"]["kernel"],
                    "w_out": layer["mlp"]["down_proj"]["kernel"]},
            "post_norm": layer["post_norm"]["scale"]}


@jax.jit
def _pick(stack, i):
    """Layer ``i`` of a stack: the index is an argument, so one compile
    serves every layer of a kind."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def reference_layers(params, config):
    """The program's parameters a layer at a time, in order: layer
    ``l`` is at position ``l % period`` of period ``l // period``."""
    plan = ssd_hybrid.layer_plan(config)
    for i in range(config.num_layers):
        j = i % len(plan)
        yield _reference_layer(
            _pick(params["layers"][str(j)], i // len(plan)), plan[j])


def model_config(model, **overrides):
    """``SsdHybridConfig`` of a configuration file's dictionary: the
    published keys give the widths, the layer list and the four
    multipliers, ``assumed`` what the source leaves open."""
    a = model["assumed"]
    if (not model["tie_word_embeddings"] or model["attention_bias"]
            or model["hidden_act"] != "silu" or model["mamba_proj_bias"]
            or not model["mamba_conv_bias"]
            or model["num_local_experts"] or model["num_experts_per_tok"]
            or model["position_embedding_type"] != "nope"
            or model["normalization_function"] != "rmsnorm"
            or model["mamba_n_heads"] * model["mamba_d_head"]
            != model["mamba_expand"] * model["hidden_size"]
            or model["shared_intermediate_size"]
            != model["intermediate_size"]):
        raise ValueError(
            "models/ssd_hybrid.py computes a tied head, RMSNorm, no bias "
            "but the convolution's, SiLU, no position, the shared MLP "
            "alone, and mamba_n_heads x mamba_d_head = mamba_expand x "
            "hidden_size")
    config = dict(
        embedding_multiplier=float(model["embedding_multiplier"]),
        residual_multiplier=float(model["residual_multiplier"]),
        attention_multiplier=float(model["attention_multiplier"]),
        logits_scaling=float(model["logits_scaling"]),
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        shared_intermediate_size=model["shared_intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=a["head_dim"],
        mamba_n_heads=model["mamba_n_heads"],
        mamba_d_head=model["mamba_d_head"],
        mamba_d_state=model["mamba_d_state"],
        mamba_n_groups=model["mamba_n_groups"],
        mamba_d_conv=model["mamba_d_conv"],
        mamba_chunk_size=model["mamba_chunk_size"],
        layer_types=tuple(model["layer_types"]),
        rms_norm_eps=model["rms_norm_eps"],
        embed_std=a["embed_std"],
        max_seq_len=a["seq_len"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
    )
    config.update(overrides)
    return ssd_hybrid.SsdHybridConfig(**config)


def reference_loss_of(model, config, params, ids, labels, hidden=None,
                      attended=None):
    return float(reference.loss(
        model, ids, labels, params["embed_tokens"]["embedding"],
        reference_layers(params, config), params["norm"]["scale"], hidden,
        attended))


def build(model, **overrides):
    config = model_config(model, **overrides)
    strategy = Strategy(
        mesh=MeshPlan(**model["layout"]), rule_set="ssd_hybrid",
        remat_policy="",  # the model remats per layer itself
    )
    precision = jnp.dtype(config.compute_dtype).name
    program_hidden = jax.jit(lambda params, ids: ssd_hybrid.apply_hidden(
        params, ids[None], config)[0][0])
    # the program's first attention layer's mixer alone, on what the
    # reference's read
    first = str(ssd_hybrid.layer_plan(config).index(ssd_hybrid.ATTENTION))
    program_attention = jax.jit(lambda params, u: ssd_hybrid.attention_mixer(
        u[None].astype(config.compute_dtype), cast_floats(
            _pick(params["layers"][first], 0)["mixer"],
            config.compute_dtype), config)[0])

    def reference_loss(params, ids, labels):
        final, attended = [], []
        loss = reference_loss_of(model, config, params, ids, labels, final,
                                 attended)
        error = hidden_error(program_hidden(params, jnp.asarray(ids)),
                             final[0])
        attention_error = hidden_error(
            program_attention(params, attended[0]), attended[1])
        print(json.dumps({"event": "reference_hidden",
                          "reference_loss": loss,
                          "median_token_error": error,
                          "tolerance": HIDDEN_TOL[precision],
                          "attention_token_error": attention_error,
                          "attention_tolerance": ATTENTION_TOL[precision]}),
              flush=True)
        sound = (error <= HIDDEN_TOL[precision]
                 and attention_error <= ATTENTION_TOL[precision])
        return loss if sound else float("nan")

    return Job(
        init_fn=ssd_hybrid.make_init_fn(config),
        loss_fn=ssd_hybrid.make_loss_fn(
            config, head_chunk=model["assumed"]["head_chunk"]),
        strategy=strategy, vocab_size=config.vocab_size,
        seq_len=config.max_seq_len,
        param_count=ssd_hybrid.param_count(config),
        layers=config.num_layers, reference_loss=reference_loss,
        reference_tol=REFERENCE_TOL[precision])
