"""The latent-attention decoder with shared and routed experts as the
program trains it (``dlrover_tpu/models/mla_moe.py`` under the
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
from chipbench.families.mla_moe import reference
from dlrover_tpu.models import mla_moe
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry.names import StepCounter

# Two limits decide the reference check, on one seeded row of
# ``seq_len`` tokens at the initial weights: the program against the
# float32 reference (``reference.py``), which differs from it by bf16's
# rounding of every activation and by the router (its input is a bf16
# activation, so a token's eighth and ninth scores can swap).
#
# ``REFERENCE_TOL``, on the loss (what ``worker.py`` compares: the
# program's own ``eval_step``). The mean loss of a row at random
# weights hardly feels the precision: over 8192 positions the rounding
# averages out. On the chip (PR 34) it read 0.1e-4 to 3.6e-4 at a loss
# of 10.4 over 25 seeds (the three largest 3.2e-4, 3.3e-4, 3.6e-4;
# their root mean square 2.0e-4), and with every matrix product's
# operands of the reference rounded to e4m3 0.9e-4, 6.0e-4, 9.0e-4,
# 1.6e-3, 2.5e-3 over five seeds; with a mechanism left out of the
# reference (the shared expert, the renormalisation, the routed scale,
# a wrong held set) 5e-4 to 1.7e-2 over two: at random weights the
# final norm gives the logits the same spread whatever came before, so
# the loss alone separates neither. 1.1e-3 is three times the largest
# sound reading; it is the coarse limit.
#
# ``HIDDEN_TOL``, on the hidden states, is the limit that feels the
# precision and a wrong mechanism: the median over the row's tokens of
# ``|program - reference| / |reference|`` of the final normed hidden
# state (the program's ``apply_hidden`` on the same parameters and
# ids). The median, because a token whose expert set swapped differs
# by an expert's whole output and says nothing of the precision (300 to
# 710 of a layer's 8192 tokens select another set than the reference's,
# 10 to 60 of them a held expert). On the chip (PR 34, seeds
# 2147484501-508, 3000000509, 3000000510) it read 0.850% to 0.863% on
# ten seeds; the reference with e4m3 operands, the nearest precision
# below the bf16 the configuration states, 8.8%, 8.9%, 9.0% on three
# (e5m2 14.4%); the routed scale left out 8.9% and 9.3%, a wrong held
# set 21.6% and 22.2%, no renormalisation 67% and 69%, the shared expert
# dropped 78% on two. 2.7e-2 lies 3.1 times above the largest sound
# reading and 3.3 times below the smallest of the others.
# ``worker.py`` reads one number, so a row that fails this limit gives
# it NaN for the reference's loss, which fails its comparison; the
# reading is printed beside it (event ``reference_hidden``).
#
# A float32 configuration (the CPU rehearsal) is held to 1e-4 on both:
# there the two sides differ by the order of float32 sums.
REFERENCE_TOL = {"bfloat16": 1.1e-3, "float32": 1e-4}
HIDDEN_TOL = {"bfloat16": 2.7e-2, "float32": 1e-4}

# the reference's name for each leaf of a layer
ATTN_NAMES = {"w_qa": ("q_a_proj", "kernel"), "q_norm": ("q_a_norm", "scale"),
              "w_qb": ("q_b_proj", "kernel"),
              "w_kva": ("kv_a_proj", "kernel"),
              "kv_norm": ("kv_a_norm", "scale"),
              "w_kvb": ("kv_b_proj", "kernel"), "w_o": ("o_proj", "kernel")}
SWIGLU_NAMES = {"w_gate": ("gate_proj", "kernel"),
                "w_up": ("up_proj", "kernel"),
                "w_down": ("down_proj", "kernel")}
EXPERT_NAMES = {"w_gate": ("gate", "kernel"), "w_up": ("up", "kernel"),
                "w_down": ("down", "kernel")}


def _named(tree, names):
    out = {}
    for name, path in names.items():
        leaf = tree
        for key in path:
            leaf = leaf[key]
        out[name] = leaf
    return out


def _reference_layer(layer):
    """One layer of the program's parameters in the reference's form."""
    out = {"input_norm": layer["input_norm"]["scale"],
           "attn": _named(layer["attn"], ATTN_NAMES),
           "post_norm": layer["post_norm"]["scale"]}
    if "mlp" in layer:
        out["mlp"] = _named(layer["mlp"], SWIGLU_NAMES)
    else:
        moe = layer["moe"]
        out["moe"] = {"w_router": moe["router"]["kernel"],
                      "shared": _named(moe["shared"], SWIGLU_NAMES),
                      "experts": _named(moe["experts"], EXPERT_NAMES)}
    return out


def reference_layers(params, config):
    """The program's parameters a layer at a time, in order. One layer
    of a stack is taken out by a small program whose index is an
    argument, so one compile serves all layers of a stack."""
    pick = jax.jit(lambda stack, i: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
        stack))
    for i in range(config.first_k_dense):
        yield _reference_layer(pick(params["dense_layers"], jnp.int32(i)))
    for i in range(config.moe_layers):
        yield _reference_layer(pick(params["moe_layers"], jnp.int32(i)))


def model_config(model, **overrides):
    """``MlaMoeConfig`` of a configuration file's dictionary: the
    published keys give the widths, ``deployment`` the router's width
    and the experts held, ``assumed`` what the source leaves open."""
    a, r, dep = model["assumed"], model["rope_scaling"], model["deployment"]
    if (model["topk_method"] != "none" or model["scoring_func"] != "sigmoid"
            or model["tie_word_embeddings"] or model["moe_layer_freq"] != 1
            or r["type"] != "yarn" or model["attention_bias"]
            or model["num_key_value_heads"] != model["num_attention_heads"]):
        raise ValueError("models/mla_moe.py computes plain sigmoid top-k "
                         "routing, YaRN rotary, an untied head, no biases "
                         "and an expert layer after every dense one")
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
        balance_loss_weight=(a["balance_loss_weight"]
                             if model["seq_aux"] else 0.0),
        rope_theta=model["rope_theta"], rope_factor=r["factor"],
        rope_original_max=r["original_max_position_embeddings"],
        rope_beta_fast=r["beta_fast"], rope_beta_slow=r["beta_slow"],
        rope_mscale=r["mscale"], rope_mscale_all_dim=r["mscale_all_dim"],
        rms_norm_eps=model["rms_norm_eps"],
        max_seq_len=a["seq_len"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
    )
    config.update(overrides)
    return mla_moe.MlaMoeConfig(**config)


def reference_loss_of(model, config, params, ids, labels, selections=None,
                      hidden=None):
    return float(reference.loss(
        model, ids, labels, params["embed_tokens"]["embedding"],
        reference_layers(params, config), params["norm"]["scale"],
        params["lm_head"]["kernel"], selections, hidden))


def hidden_error(program, plain):
    """The median over tokens of ``|program - plain| / |plain|``, both
    [seq, hidden]."""
    program, plain = (jnp.asarray(a, jnp.float32) for a in (program, plain))
    return float(jnp.median(jnp.linalg.norm(program - plain, axis=-1)
                            / jnp.linalg.norm(plain, axis=-1)))


def no_row_dropped(loss_fn):
    """The cell promises that no assignment to a held expert is left
    out: a step whose counter says one was has a NaN loss, which
    ``run.py`` reports as not ``correct``."""

    def held_to_it(params, batch, rng):
        loss, aux = loss_fn(params, batch, rng)
        return jnp.where(aux[StepCounter.MOE_ROWS_DROPPED] > 0, jnp.nan,
                         loss), aux

    return held_to_it


def build(model, **overrides):
    config = model_config(model, **overrides)
    strategy = Strategy(
        mesh=MeshPlan(**model["layout"]), rule_set="mla_moe",
        remat_policy="",  # the model remats per layer itself
    )
    precision = jnp.dtype(config.compute_dtype).name
    program_hidden = jax.jit(lambda params, ids: mla_moe.apply_hidden(
        params, ids[None], config)[0][0])

    def reference_loss(params, ids, labels):
        final = []
        loss = reference_loss_of(model, config, params, ids, labels,
                                 hidden=final)
        error = hidden_error(program_hidden(params, jnp.asarray(ids)),
                             final[0])
        print(json.dumps({"event": "reference_hidden",
                          "median_token_error": error,
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
