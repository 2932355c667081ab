"""The grouped differential latent-attention decoder with a sliding
window, PolyNorm experts and a router whose selection bias the step
moves, as the program trains it (``dlrover_tpu/models/mla_moe.py`` with
its differential switches, streams and a prediction module, under the
``mla_moe`` sharding rules), built from a configuration file's
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
# picking a tree's leaves by the reference's names and the median
# token's error are that family's, as they are
from chipbench.families.mla_moe.job import _named, hidden_error
from chipbench.families.mla_moe_gdla import flops, reference
from dlrover_tpu.models import mla_moe
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry.names import StepCounter

# Two limits decide the reference check, on one seeded row of
# ``seq_len`` tokens at the initial weights (the selection bias at the
# zeros it starts from): the program against the float32 reference
# (``reference.py``), which differs from it by bf16's rounding of every
# activation, by the router (its input is a bf16 activation, so a
# token's eighth and ninth scores can swap) and by the
# hyper-connection's projection (bf16 operands).
#
# ``REFERENCE_TOL``, on the loss (what ``worker.py`` compares: the
# program's own ``eval_step``, main loss plus 0.3 x the module's, 13.94
# at random weights). The mean loss of a row hardly feels the
# precision: on the chip (PR 55) the sound program read 7e-6 to
# 4.1e-4 on ten seeds and the reference with e4m3 operands 2.3e-4, so
# no limit between the two exists; it is the coarse limit,
# ``families/mla_moe_hc``'s 1e-2 (the same streams and module; 24
# times the largest sound reading), which a mechanism that changes the
# loss's own form fails.
#
# ``HIDDEN_TOL``, on the hidden states, is the limit that feels the
# precision and a wrong mechanism: the median over the row's tokens of
# ``|program - reference| / |reference|`` of the final normed hidden
# state, taken for the main model and for the prediction module, the
# LARGER of the two. The median, because a token whose expert set
# swapped differs by an expert's whole output and says nothing of the
# precision. On the chip (PR 55, TPU v5 lite, the timed sizes: depth 5,
# one row of 8192, 80 heads on 16, 16 held experts, the slice;
# ``tests/chipbench/mla_moe_gdla_controls.py``, sound on seeds
# 3000005501 and 3000005511-13 and in the cell's own six runs,
# 3000005521-26; each control on 3000005511) the sound program read
# 0.61% to 0.65% on ten seeds; the reference with e4m3 operands, the
# nearest precision below the configuration's bf16, 7.16%; and with one
# mechanism wrong: one Sinkhorn iteration 1.97%, the routed scale left
# out 4.27%, the layers of a period shifted by one 12.6%, rotary at a
# tenth of theta 12.4%, the window doubled 12.7%, the window left out
# 17.3%, PolyNorm without its output scale 49.9%. 1.6e-2 is 2.46 times
# the largest sound reading and 4.5 times under e4m3's; of the
# mechanisms, one Sinkhorn iteration lies 1.2 times above it, the
# others 2.7 times and more. The float32 pieces in bf16 (router scores,
# lambda, PolyNorm, the mappings, the softmax's scores, the logits)
# read 0.65%: beside bf16 activations their rounding is not felt, and
# the float32 rehearsal's limit below is the one that feels it.
# ``worker.py`` reads one number, so a row that fails this limit gives
# it NaN for the reference's loss, which fails its comparison; the
# readings are printed beside it (event ``reference_hidden``).
#
# A float32 configuration (the CPU rehearsal) is held to 1e-4 on both:
# there the two sides differ by the order of float32 sums, and a
# float32 piece computed in bf16 (2e-3 and more on the toy,
# ``tests/chipbench/test_chipbench_mla_moe_gdla.py``) does not stay
# inside it.
REFERENCE_TOL = {"bfloat16": 1e-2, "float32": 1e-4}
HIDDEN_TOL = {"bfloat16": 1.6e-2, "float32": 1e-4}

# the reference's name for each leaf of a layer
ATTN_NAMES = {"w_qa": ("q_a_proj", "kernel"), "q_norm": ("q_a_norm", "scale"),
              "w_qb": ("q_b_proj", "kernel"),
              "w_kva": ("kv_a_proj", "kernel"),
              "kv_norm": ("kv_a_norm", "scale"),
              "w_kvb": ("kv_b_proj", "kernel"),
              "w_lam": ("lam_proj", "kernel"), "w_g": ("g_proj", "kernel"),
              "w_o": ("o_proj", "kernel")}
GLU_NAMES = {"w_gate": ("gate_proj", "kernel"), "w_up": ("up_proj", "kernel"),
             "w_down": ("down_proj", "kernel"), "act": ("act",)}
EXPERT_NAMES = {"w_gate": ("gate", "kernel"), "w_up": ("up", "kernel"),
                "w_down": ("down", "kernel"), "act": ("act",)}
HC_NAMES = {"norm": ("norm", "scale"), "phi": ("phi", "kernel"),
            "alpha": ("alpha",), "bias": ("bias",)}


def _reference_layer(layer, bias):
    """One layer of the program's parameters in the reference's form;
    ``bias`` is an expert layer's selection bias, which the program
    keeps among its buffers."""
    out = {"input_norm": layer["input_norm"]["scale"],
           "attn": _named(layer["attn"], ATTN_NAMES),
           "post_norm": layer["post_norm"]["scale"],
           "hc_attn": _named(layer["hc_attn"], HC_NAMES),
           "hc_ffn": _named(layer["hc_ffn"], HC_NAMES)}
    if "mlp" in layer:
        out["mlp"] = _named(layer["mlp"], GLU_NAMES)
    else:
        moe = layer["moe"]
        out["moe"] = {"w_router": moe["router"]["kernel"],
                      "b_router": bias,
                      "shared": _named(moe["shared"], GLU_NAMES),
                      "experts": _named(moe["experts"], EXPERT_NAMES)}
    return out


@jax.jit
def _pick(stack, i):
    """Layer ``i`` of a stack: the index is an argument, so one compile
    serves all layers of a stack."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def _biases(config, buffers):
    """(the expert layers' selection biases [L, E], the module's [1,
    E]): the buffers', or the zeros they start at."""
    buffers = buffers or mla_moe.init_buffers(config)
    return (buffers["moe_layers"]["moe"]["router"]["bias"],
            buffers["mtp"]["layer"]["moe"]["router"]["bias"])


def reference_layers(params, config, buffers=None):
    """The program's parameters a layer at a time, in order."""
    bias, _ = _biases(config, buffers)
    for i in range(config.first_k_dense):
        yield _reference_layer(_pick(params["dense_layers"], i), None)
    for i in range(config.moe_layers):
        yield _reference_layer(_pick(params["moe_layers"], i), bias[i])


def reference_mtp(params, config, buffers=None):
    """The program's one prediction module in the reference's form."""
    mtp = _pick(params["mtp"], 0)
    return {"h_norm": mtp["h_norm"]["scale"],
            "e_norm": mtp["e_norm"]["scale"],
            "w_eh": mtp["eh_proj"]["kernel"],
            "layer": _reference_layer(mtp["layer"],
                                      _biases(config, buffers)[1][0]),
            "norm": mtp["norm"]["scale"]}


def model_config(model, **overrides):
    """``MlaMoeConfig`` of a configuration file's dictionary: the
    published keys give the widths and the mechanisms, ``deployment``
    the router's width, the experts held and which published layers
    these are, ``assumed`` what the source leaves open."""
    a, r, dep = model["assumed"], model["rope_scaling"], model["deployment"]
    if (model["attention_cls"] != "gdla" or not model["diff_v2"]
            or not model["elementwise_attn_output_gate"]
            or model["headwise_attn_output_gate"]
            or model["hidden_act"] != "poly_norm"
            or model["polynorm_output_scale_per_layer"]
            or model["score_func"] != "sigmoid"
            or model["score_before_experts"]
            or model["tie_word_embeddings"]
            or model["interleave_moe_layer_step"] != 1
            or not model["mhc_enabled"] or r["apply_yarn_scaling"]
            or not model["use_sliding_window"]
            or model["sliding_window_pattern"] != "interleave"
            or model["swa_rope_theta"] != model["rope_theta"]
            or model["num_nextn_predict_layers"] != 1):
        raise ValueError(
            "this family is grouped differential (V2) latent attention "
            "with an elementwise output gate, an interleaved sliding "
            "window at one rotary base and no YaRN scaling, PolyNorm "
            "FFNs, sigmoid top-k routing weighed after the experts, an "
            "untied head, an expert layer after every dense one, "
            "hyper-connected streams and one prediction module")
    if len(dep["experts_held"]) != model["num_experts"]:
        raise ValueError("num_experts counts the experts held here: "
                         "deployment.experts_held lists them")
    rope = model["qk_rope_head_dim"]
    config = dict(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_layers=model["num_hidden_layers"],
        first_k_dense=model["n_dense_first_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        num_noise_heads=model["num_noise_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["head_dim"] - rope,
        qk_rope_head_dim=rope,
        v_head_dim=model["v_head_dim"],
        sliding_window=model["sliding_window"],
        full_attention_layers=tuple(flops.full_layers(model)),
        attn_output_gate=True,
        ffn_activation="poly_norm",
        polynorm_scale=model["polynorm_output_scale"],
        polynorm_bias_clamp=model["polynorm_bias_clamp"],
        polynorm_eps=a["polynorm_eps"],
        n_routed_experts=dep["published_num_experts"],
        experts_held=tuple(dep["experts_held"]),
        n_shared_experts=model["num_shared_experts"],
        num_experts_per_tok=model["experts_top_k"],
        routed_scaling_factor=model["route_scale"],
        norm_topk_prob=model["route_norm"],
        balance_loss_weight=0.0,  # the source has no balance loss
        router_bias_rate=model["load_balance_coeff"],
        hc_mult=model["mhc_expansion_rate"],
        hc_sinkhorn_iters=model["mhc_sinkhorn_iters"],
        hc_clamp=(a["hc_clamp_min"], a["hc_clamp_max"]),
        hc_eps=a["hc_eps"],
        mtp_layers=model["num_nextn_predict_layers"],
        mtp_loss_weight=a["mtp_loss_weight"],
        # apply_yarn_scaling false: the plain rotary and the plain scale
        rope_theta=model["rope_theta"], rope_factor=1.0,
        rms_norm_eps=model["rms_norm_eps"],
        max_seq_len=a["seq_len"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
        expert_row_factor=a["expert_row_factor"],
    )
    config.update({k: a[k] for k in (
        "window_block", "flash_block_q", "flash_block_k", "expert_block_t")
        if k in a})
    config.update(overrides)
    return mla_moe.MlaMoeConfig(**config)


def reference_loss_of(model, config, params, ids, labels, selections=None,
                      hidden=None, buffers=None, lambdas=None):
    return float(reference.loss(
        model, ids, labels, params["embed_tokens"]["embedding"],
        reference_layers(params, config, buffers), params["norm"]["scale"],
        params["lm_head"]["kernel"], reference_mtp(params, config, buffers),
        selections, hidden, lambdas))


def no_row_dropped(loss_fn):
    """The cell promises that no assignment to a held expert is left
    out: a step whose counter says one was has a NaN loss, which
    ``run.py`` reports as not ``correct``. The selection bias the
    program keeps among its buffers passes through."""

    def held_to_it(params, batch, rng, buffers=None):
        loss, aux = loss_fn(params, batch, rng, buffers)
        return jnp.where(aux[StepCounter.MOE_ROWS_DROPPED] > 0, jnp.nan,
                         loss), aux

    held_to_it.step_buffers = loss_fn.step_buffers
    return held_to_it


def build(model, **overrides):
    config = model_config(model, **overrides)
    strategy = Strategy(
        mesh=MeshPlan(**model["layout"]), rule_set="mla_moe",
        remat_policy="",  # the model remats per layer itself
    )
    precision = jnp.dtype(config.compute_dtype).name
    program_hidden = jax.jit(
        lambda params, ids, labels: mla_moe.apply_all_hidden(
            params, ids[None], labels[None], config)[:, 0])

    def reference_loss(params, ids, labels):
        plain = []
        loss = reference_loss_of(model, config, params, ids, labels,
                                 hidden=plain)
        program = program_hidden(params, jnp.asarray(ids),
                                 jnp.asarray(labels))
        main, module = (hidden_error(a, b) for a, b in zip(program, plain))
        error = max(main, module)
        print(json.dumps({"event": "reference_hidden",
                          "reference_loss": loss,
                          "median_token_error": error,
                          "main": main, "module": module,
                          "tolerance": HIDDEN_TOL[precision]}), flush=True)
        return loss if error <= HIDDEN_TOL[precision] else float("nan")

    return Job(
        init_fn=mla_moe.make_init_fn(config),
        loss_fn=no_row_dropped(mla_moe.make_loss_fn(
            config, head_chunk=model["assumed"]["head_chunk"])),
        strategy=strategy, vocab_size=config.vocab_size,
        seq_len=config.max_seq_len,
        param_count=mla_moe.param_count(config),
        layers=config.num_layers, reference_loss=reference_loss,
        reference_tol=REFERENCE_TOL[precision])
