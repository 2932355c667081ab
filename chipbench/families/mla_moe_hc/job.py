"""The hyper-connected latent-attention decoder with held experts, a
selection bias in its router and a multi-token-prediction module, as
the program trains it (``dlrover_tpu/models/mla_moe.py`` with
``hc_mult``, ``router_bias`` and ``mtp_layers`` set, under the
``mla_moe`` sharding rules), built from a configuration file's
dictionary, and its plain reference (``reference.py`` beside this
file) run on the program's parameters.

``worker.py`` imports this module through the configuration's
``family`` and calls ``build``, which gives the ``Job`` of
``families/dense_gqa/job.py``.
"""

import json

import jax
import jax.numpy as jnp

from chipbench.families.dense_gqa.job import Job  # the one contract
# the latent block's and the experts' leaf names, the median token's
# error and the promise of no dropped row are that family's, as they are
from chipbench.families.mla_moe.job import (
    ATTN_NAMES,
    EXPERT_NAMES,
    SWIGLU_NAMES,
    _named,
    hidden_error,
    no_row_dropped,
)
from chipbench.families.mla_moe_hc import reference
from dlrover_tpu.models import mla_moe
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

# Two limits decide the reference check, on one seeded row of
# ``seq_len`` tokens at the initial weights: the program against the
# float32 reference (``reference.py``), which differs from it by bf16's
# rounding of every activation, by the router (its input is a bf16
# activation, so a token's fourth and fifth scores can swap) and by the
# hyper-connection's projection (bf16 operands).
#
# ``REFERENCE_TOL``, on the loss (what ``worker.py`` compares: the
# program's own ``eval_step``, main loss plus 0.3 x the module's, 14.15
# at random weights). The mean loss of a row hardly feels the
# precision, and of the mechanisms it feels only what changes the
# loss's own form. On the chip (PR 36) the sound program read 2.2e-4
# to 1.87e-3 on nineteen seeds (eleven by a probe, eight in the cell's
# own runs); the reference with e4m3 operands 8.8e-3 and 2.6e-3, with
# a mechanism of a layer left out 2.9e-4 to 2.3e-2, and with the
# module's term left out 3.26 and 3.27. 1e-2 is 5.3 times the largest
# sound reading and 330 times under the module's term: the coarse
# limit, which that one fault and a gross error fail.
#
# ``HIDDEN_TOL``, on the hidden states, is the limit that feels the
# precision and a wrong mechanism: the median over the row's tokens of
# ``|program - reference| / |reference|`` of the final normed hidden
# state, taken for the main model and for the prediction module, the
# LARGER of the two (the module's alone feels its own inputs). The
# median, because a token whose expert set swapped differs by an
# expert's whole output and says nothing of the precision. On the chip
# (PR 36; seeds 2147484001, 2147484101-108, 3000000109, 3000000110) the
# sound program read 1.84% to 2.41% on eleven seeds (main 1.56-1.79%,
# module 1.84-2.41%); on two of them (2147484101, 3000000110) the
# reference with e4m3 operands, the nearest precision below the bf16
# the configuration states, 23.5% and 19.9% (e5m2 35%); and with one
# mechanism left out: the Sinkhorn iterations cut to 1 8.5% and 5.9%,
# no selection bias 13.8% and 11.7%, ``H_res`` the identity 21.4% and
# 19.2%, no routed scale 27.3% and 25.3%, a wrong held set 60% and 59%,
# ``H_post`` without its factor 2 63% and 62%, the module without its
# embedding input 93% and 91%. 3.8e-2 lies 1.58 times above the largest
# sound reading and 1.55 times below the smallest of the others (one
# Sinkhorn iteration on one seed; the next, 11.7%, is 3.1 times above
# it). ``worker.py`` reads one number, so a row that fails this limit
# gives it NaN for the reference's loss, which fails its comparison;
# the readings are printed beside it (event ``reference_hidden``).
#
# A float32 configuration (the CPU rehearsal) is held to 1e-4 on both:
# there the two sides differ by the order of float32 sums.
REFERENCE_TOL = {"bfloat16": 1e-2, "float32": 1e-4}
HIDDEN_TOL = {"bfloat16": 3.8e-2, "float32": 1e-4}

# a hyper-connection's leaves, in the reference's names
HC_NAMES = {"norm": ("norm", "scale"), "phi": ("phi", "kernel"),
            "alpha": ("alpha",), "bias": ("bias",)}


def _reference_layer(layer):
    """One layer of the program's parameters in the reference's form."""
    out = {"input_norm": layer["input_norm"]["scale"],
           "attn": _named(layer["attn"], ATTN_NAMES),
           "post_norm": layer["post_norm"]["scale"],
           "hc_attn": _named(layer["hc_attn"], HC_NAMES),
           "hc_ffn": _named(layer["hc_ffn"], HC_NAMES)}
    if "mlp" in layer:
        out["mlp"] = _named(layer["mlp"], SWIGLU_NAMES)
    else:
        moe = layer["moe"]
        out["moe"] = {"w_router": moe["router"]["kernel"],
                      "b_router": moe["router"]["bias"],
                      "shared": _named(moe["shared"], SWIGLU_NAMES),
                      "experts": _named(moe["experts"], EXPERT_NAMES)}
    return out


@jax.jit
def _pick(stack, i):
    """Layer ``i`` of a stack: the index is an argument, so one compile
    serves all layers of a stack."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def reference_layers(params, config):
    """The program's parameters a layer at a time, in order."""
    for i in range(config.first_k_dense):
        yield _reference_layer(_pick(params["dense_layers"], i))
    for i in range(config.moe_layers):
        yield _reference_layer(_pick(params["moe_layers"], i))


def reference_mtp(params):
    """The program's one prediction module in the reference's form."""
    mtp = _pick(params["mtp"], 0)
    return {"h_norm": mtp["h_norm"]["scale"],
            "e_norm": mtp["e_norm"]["scale"],
            "w_eh": mtp["eh_proj"]["kernel"],
            "layer": _reference_layer(mtp["layer"]),
            "norm": mtp["norm"]["scale"]}


def model_config(model, **overrides):
    """``MlaMoeConfig`` of a configuration file's dictionary: the
    published keys give the widths, ``deployment`` the router's width
    and the experts held, ``assumed`` what the source leaves open."""
    a, r, dep = model["assumed"], model["rope_scaling"], model["deployment"]
    if (model["topk_method"] != "noaux_tc" or model["n_group"] != 1
            or model["topk_group"] != 1
            or model["scoring_func"] != "sigmoid"
            or model["tie_word_embeddings"] or model["moe_layer_freq"] != 1
            or r["type"] != "yarn" or model["attention_bias"]
            or model["num_key_value_heads"] != model["num_attention_heads"]
            or model["hc_mult"] < 2):
        raise ValueError("this family is sigmoid top-k routing with a "
                         "selection bias and one group, YaRN rotary, an "
                         "untied head, no biases, an expert layer after "
                         "every dense one and hyper-connected streams")
    if len(dep["experts_held"]) != model["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here: "
                         "deployment.experts_held lists them")
    config = dict(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_layers=model["num_hidden_layers"],
        first_k_dense=model["first_k_dense_replace"],
        num_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        n_routed_experts=dep["published_n_routed_experts"],
        experts_held=tuple(dep["experts_held"]),
        n_shared_experts=model["n_shared_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"],
        balance_loss_weight=0.0,  # the source has no ``seq_aux``
        router_bias=True,
        hc_mult=model["hc_mult"],
        hc_sinkhorn_iters=model["hc_sinkhorn_iters"],
        hc_clamp=(model["mhc_h_res_clamp_min"],
                  model["mhc_h_res_clamp_max"]),
        hc_eps=model["hc_eps"],
        mtp_layers=model["num_nextn_predict_layers"],
        mtp_loss_weight=a["mtp_loss_weight"],
        rope_theta=model["rope_theta"], rope_factor=r["factor"],
        rope_original_max=r["original_max_position_embeddings"],
        rope_beta_fast=r["beta_fast"], rope_beta_slow=r["beta_slow"],
        rope_mscale=r["mscale"], rope_mscale_all_dim=r["mscale_all_dim"],
        rms_norm_eps=model["rms_norm_eps"],
        max_seq_len=a["seq_len"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
        expert_row_factor=a["expert_row_factor"],
    )
    config.update(overrides)
    return mla_moe.MlaMoeConfig(**config)


def reference_loss_of(model, config, params, ids, labels, selections=None,
                      hidden=None):
    return float(reference.loss(
        model, ids, labels, params["embed_tokens"]["embedding"],
        reference_layers(params, config), params["norm"]["scale"],
        params["lm_head"]["kernel"], reference_mtp(params), selections,
        hidden))


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
