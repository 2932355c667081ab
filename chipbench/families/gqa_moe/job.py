"""The grouped-query decoder of window and full layers with held
experts as the program trains it (``dlrover_tpu/models/gqa_moe.py``
under the ``gqa_moe`` sharding rules), built from a configuration
file's dictionary, and its plain reference (``reference.py`` beside
this file) run on the program's parameters.

``worker.py`` imports this module through the configuration's
``family`` and calls ``build``, which gives the ``Job`` of
``families/dense_gqa/job.py``.
"""

import json

import jax
import jax.numpy as jnp

from chipbench.families.dense_gqa.job import Job  # the one contract
from chipbench.families.gqa_moe import reference
# the median token's error and the promise of no dropped row are that
# family's, as they are
from chipbench.families.mla_moe.job import hidden_error, no_row_dropped
from dlrover_tpu.models import gqa_moe
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

# Two limits decide the reference check, on one seeded row of
# ``seq_len`` tokens at the initial weights: the program against the
# float32 reference (``reference.py``), which differs from it by bf16's
# rounding of every activation and by the router (its input is a bf16
# activation, so a token's sixth and seventh logits can swap).
#
# Every reading below is the harness's own comparison on the chip (PR
# 41, TPU v5 lite: ``tests/chipbench/gqa_moe_controls.py``, which calls
# ``worker.ReferenceCheck``, the compiled ``eval_step`` against this
# job's ``reference_loss``) at the timed sizes (depth 12, one row of
# 16,384, 16 held experts, the slice) and the configuration's table
# (``assumed.embed_std`` 2): the sound reference on seeds 3000004301-08,
# each control on 3000004301 and 3000004302. At a table of std 1 the
# blocks weigh twice as much in the hidden state and the same controls
# read 7.3% to 42% beside a sound 0.65-0.76% (``PERF.md`` section 6);
# at 1.5 5.1% to 44% beside 0.81-0.83%.
#
# ``HIDDEN_TOL``, on the hidden states, is the limit that feels the
# precision and a wrong mechanism: the median over the row's tokens of
# ``|program - reference| / |reference|`` of the final normed hidden
# state (the program's ``apply_hidden`` on the same parameters and
# ids). The median, because a token whose expert set swapped differs by
# an expert's whole output and says nothing of the precision; and the
# median token of a 16,384 row lies past the window, so it feels the
# band. Sound: 0.83% to 0.84% on the eight seeds. The reference with
# e4m3 operands, the nearest precision below the bf16 the configuration
# states: 18.8% and 19.2%. Each mechanism wrong in the reference alone:
# rotary applied on a full layer 3.01% and 3.04%, the router fed
# ``RMSNorm_post(x')`` 4.67% and 4.65%, rotary left off a window layer
# 5.62% and 5.65%, the window ignored on window layers 7.5% and 7.7%,
# SiLU for ReLU 8.9% and 9.0%, softmax over all 64 without
# renormalising 17.3% and 17.4%, a wrong held set (experts 16-31) 35.8%
# and 35.8%. 1.6e-2 lies 1.9 times above the largest sound reading and
# 1.9 times below the smallest of the others, and the harness said not
# ``ok`` of e4m3 and of all seven on both seeds. ``worker.py`` reads
# one number, so a row that fails this limit gives it NaN for the
# reference's loss, which fails its comparison; the reading is printed
# beside it (event ``reference_hidden``, with the reference's loss).
#
# ``REFERENCE_TOL``, on the loss (what ``worker.py`` compares), is the
# coarse limit. The mean loss of a row at random weights hardly feels
# the precision, and a mechanism not at all: over 16,384 positions the
# rounding averages out, and the final norm gives the logits the same
# spread whatever came before. Sound: 2.4e-5 to 2.65e-4 at a loss of
# 11.04 on the eight seeds (at std 1 up to 4.8e-4 on sixteen); e4m3
# operands 3.2e-3 and 1.3e-4; a wrong mechanism 1.9e-6 to 4.4e-3: the
# loss separates neither, and e4m3 fails by the hidden states. The
# cell's own twelve runs and eight more seeds of the controls then read
# 6.7e-6 to 3.7e-4 sound (and 0.83-0.84% on the hidden states): 1.4e-3
# is 3.8 times the largest, among the accepted families' 1.1e-3 to
# 5e-3, and what a gross error fails.
#
# A float32 configuration (the CPU rehearsal) is held to 1e-4 on both:
# there the two sides differ by the order of float32 sums.
REFERENCE_TOL = {"bfloat16": 1.4e-3, "float32": 1e-4}
HIDDEN_TOL = {"bfloat16": 1.6e-2, "float32": 1e-4}


def _reference_layer(layer):
    """One layer of the program's parameters in the reference's form."""
    attn, moe = layer["attn"], layer["moe"]
    return {"input_norm": layer["input_norm"]["scale"],
            "attn": {"wq": attn["q_proj"]["kernel"],
                     "wk": attn["k_proj"]["kernel"],
                     "wv": attn["v_proj"]["kernel"],
                     "wo": attn["o_proj"]["kernel"]},
            "post_norm": layer["post_norm"]["scale"],
            "w_router": moe["router"]["kernel"],
            "experts": {"w_gate": moe["experts"]["gate"]["kernel"],
                        "w_up": moe["experts"]["up"]["kernel"],
                        "w_down": moe["experts"]["down"]["kernel"]}}


@jax.jit
def _pick(stack, i):
    """Layer ``i`` of a stack: the index is an argument, so one compile
    serves every layer."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def reference_layers(params, config):
    """The program's parameters a layer at a time, in order: layer
    ``l`` is at position ``l % period`` of period ``l // period``."""
    period = len(gqa_moe.layer_plan(config))
    for i in range(config.num_layers):
        yield _reference_layer(_pick(params["layers"][str(i % period)],
                                     i // period))


def model_config(model, **overrides):
    """``GqaMoeConfig`` of a configuration file's dictionary: the
    published keys give the widths and the two layouts, ``deployment``
    the router's width and the experts held, ``assumed`` what the
    source leaves open."""
    a, dep = model["assumed"], model["deployment"]
    if (not model["moe_primary_router_apply_softmax"]
            or model["tie_word_embeddings"]
            or model["rope_scaling"] is not None):
        raise ValueError("models/gqa_moe.py computes a softmax top-k "
                         "router, plain rotary and an untied head")
    if not (len(dep["experts_held"]) == model["moe_num_primary_experts"]
            == model["n_routed_experts"]):
        raise ValueError(
            "moe_num_primary_experts counts the experts held here: "
            "deployment.experts_held lists them, and n_routed_experts "
            "says the same to layer_metrics/expert_load_imbalance.py")
    config = dict(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        moe_intermediate_size=model["moe_ffn_hidden_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        sliding_window=model["sliding_window_size"],
        window_layout=tuple(model["sliding_window_layout"]),
        rope_layout=tuple(model["rope_layout"]),
        rope_theta=model["rope_theta"],
        n_routed_experts=dep["published_moe_num_primary_experts"],
        experts_held=tuple(dep["experts_held"]),
        num_experts_per_tok=model["moe_num_active_primary_experts"],
        norm_topk_prob=model["norm_topk_prob"],
        rms_norm_eps=model["rms_norm_eps"],
        max_seq_len=a["seq_len"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
        expert_row_factor=a["expert_row_factor"],
    )
    config.update({k: a[k] for k in ("window_block", "expert_block_t")
                   if k in a})
    config.update(overrides)
    return gqa_moe.GqaMoeConfig(**config)


def table_at(init_fn, std):
    """The model's ``init_fn`` with the token table at ``std`` where the
    model makes it at 1 (``assumed.embed_std``, a reading of this
    benchmark and not a field of the model: what a randomly initialised
    router does with these weights is the benchmark's matter)."""

    def scaled(rng):
        params = init_fn(rng)
        table = params["embed_tokens"]["embedding"]
        params["embed_tokens"] = {"embedding": std * table}
        return params

    scaled.layer_kinds = init_fn.layer_kinds  # ElasticTrainer reads it
    return scaled


def reference_loss_of(model, config, params, ids, labels, selections=None,
                      hidden=None):
    return float(reference.loss(
        model, ids, labels, params["embed_tokens"]["embedding"],
        reference_layers(params, config), params["norm"]["scale"],
        params["lm_head"]["kernel"], selections, hidden))


def build(model, **overrides):
    config = model_config(model, **overrides)
    strategy = Strategy(
        mesh=MeshPlan(**model["layout"]), rule_set="gqa_moe",
        remat_policy="",  # the model remats per layer itself
    )
    precision = jnp.dtype(config.compute_dtype).name
    program_hidden = jax.jit(lambda params, ids: gqa_moe.apply_hidden(
        params, ids[None], config)[0][0])

    def reference_loss(params, ids, labels):
        final = []
        loss = reference_loss_of(model, config, params, ids, labels,
                                 hidden=final)
        error = hidden_error(program_hidden(params, jnp.asarray(ids)),
                             final[0])
        print(json.dumps({"event": "reference_hidden",
                          "reference_loss": loss,
                          "median_token_error": error,
                          "tolerance": HIDDEN_TOL[precision]}), flush=True)
        return loss if error <= HIDDEN_TOL[precision] else float("nan")

    return Job(
        init_fn=table_at(gqa_moe.make_init_fn(config),
                         model["assumed"]["embed_std"]),
        loss_fn=no_row_dropped(gqa_moe.make_loss_fn(
            config, head_chunk=model["assumed"]["head_chunk"])),
        strategy=strategy, vocab_size=config.vocab_size,
        seq_len=config.max_seq_len,
        param_count=gqa_moe.param_count(config),
        layers=config.num_layers, reference_loss=reference_loss,
        reference_tol=REFERENCE_TOL[precision])
