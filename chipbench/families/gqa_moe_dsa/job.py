"""The grouped-query decoder with learned sparse attention and held
experts as the program trains it (``dlrover_tpu/models/gqa_moe.py``
with its sparse switches, under the ``gqa_moe`` sharding rules), built
from a configuration file's dictionary, and its plain reference
(``reference.py`` beside this file) run on the program's parameters.

``worker.py`` imports this module through the configuration's
``family`` and calls ``build``, which gives the ``Job`` of
``families/dense_gqa/job.py``.
"""

import functools
import json

import jax
import jax.numpy as jnp

from chipbench.families.dense_gqa.job import Job  # the one contract
from chipbench.families.gqa_moe.job import table_at
from chipbench.families.gqa_moe_dsa import reference
# the median token's error and the promise of no dropped row are that
# family's, as they are
from chipbench.families.mla_moe.job import hidden_error, no_row_dropped
from dlrover_tpu.models import gqa_moe
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry.names import StepCounter

# What decides the reference check, on one seeded row of ``seq_len``
# tokens at the initial weights: the program (its ``apply_layers``, the
# layers of the timed ``train_step`` one at a time) against the float32
# reference, which differs from it by bf16's rounding of every
# activation, by the expert choice (a token's eighth and ninth logits
# can swap) and by the selection (a key whose index score lies at the
# query's threshold). So the reference runs TWICE: once GIVEN the
# program's selections and expert choices a layer (a boundary flip is
# then no error, and the limits below are on the arithmetic alone), and
# once on its OWN, where the share of the program's selected pairs that
# the reference selects too is held to a floor.
#
# The limits, each with the readings that place it (the harness's own
# comparison on the chip, PR 48, TPU v5 lite, the timed sizes: depth 8,
# one row of 16,384, 32 held experts, the slice; ``tests/chipbench/
# gqa_moe_dsa_controls.py`` and the cell's own runs, sound on 15 seeds,
# 3000004801-06, 10 and 21-28, each control on 3000004802 and 27;
# ``PERF.md`` section 6 has the table):
#
# ``HIDDEN_TOL``: the median over the row's tokens of ``|program -
# reference| / |reference|`` of the final normed hidden state, the
# reference given the program's choices. It feels the precision and a
# wrong mechanism in the attention or the experts. Sound 0.64% to
# 0.67%; e4m3 operands, the nearest precision below the configuration's
# bf16, 40.6% and 42.4%; rotary at a tenth of theta 8.7% and 8.5%.
# 1.3e-2 is 1.9 times the largest sound reading and 6.5 times under the
# smallest of the others.
# ``INDEX_KL_TOL``: ``|sum_layers L_I(program) - L_I(reference given)|
# / L_I(reference given)``: the indexer's loss feels the indexer's
# arithmetic and the attention's probabilities over the same selected
# set. Sound 4.4e-5 to 2.1e-4 (of 0.77 nats over the 8 layers); e4m3
# 7.2e-2 and 8.7e-2; rotary 0.49; the indexer without its ReLU 0.67.
# 3e-3 is 14 times the largest sound reading and 24 times under
# e4m3's.
# ``AGREE_FLOOR``: the pairs both the program and the reference on its
# own select over the pairs either selects, the least over the layers.
# Rounding moves only pairs at a query's threshold, and the two hidden
# states drift apart layer by layer: sound 0.955 to 0.961. A selection
# of the wrong size 0.513 (topk halved) and 0.234 (none at all), the
# indexer without its ReLU 0.57-0.59, e4m3 0.55-0.59, rotary 0.29-0.30.
# 0.80 has the sound readings 0.155 above it and the others 0.21 and
# more below.
# ``REFERENCE_TOL``, on the whole loss ``L_LM + L_I`` (what
# ``worker.py`` compares): the coarse limit; at random weights the mean
# cross entropy hardly feels the precision. Sound 1.7e-5 to 3.2e-4 at a
# loss of 11.8; e4m3 5.5e-2 and 7.0e-2; the indexer's loss left out
# 0.77. 2e-3 is 6 times the largest sound reading, among the accepted
# families' 1.1e-3 to 1e-2.
#
# What none of them feels on the chip: the reference's softmax in bf16
# (hidden state 0.67% beside a sound 0.67%, ``L_I`` 2.1e-4 beside
# 1.7e-4): the program itself hands its probabilities to the PV product
# in bf16, and 2048 roundings a row of random sign average out. In
# float32 (the CPU's toy) every limit below feels it.
#
# ``worker.py`` reads one number, so a row that fails one of the first
# three limits gives it NaN for the reference's loss, which fails its
# comparison; the readings are printed beside it (event
# ``reference_hidden``).
REFERENCE_TOL = {"bfloat16": 2e-3, "float32": 2e-4}
HIDDEN_TOL = {"bfloat16": 1.3e-2, "float32": 2e-4}
INDEX_KL_TOL = {"bfloat16": 3e-3, "float32": 2e-4}
AGREE_FLOOR = {"bfloat16": 0.80, "float32": 0.995}


def _reference_layer(layer):
    """One layer of the program's parameters in the reference's form."""
    attn, moe = layer["attn"], layer["moe"]
    return {"input_norm": layer["input_norm"]["scale"],
            "attn": {"wq": attn["q_proj"]["kernel"],
                     "wk": attn["k_proj"]["kernel"],
                     "wv": attn["v_proj"]["kernel"],
                     "wo": attn["o_proj"]["kernel"],
                     "q_norm": attn["q_norm"]["scale"],
                     "k_norm": attn["k_norm"]["scale"],
                     "index_wq": attn["index"]["q_proj"]["kernel"],
                     "index_wk": attn["index"]["k_proj"]["kernel"],
                     "index_ww": attn["index"]["w_proj"]["kernel"]},
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
    """The program's parameters a layer at a time, in order (the period
    is 1: every layer is of the one kind)."""
    period = len(gqa_moe.layer_plan(config))
    for i in range(config.num_layers):
        yield _reference_layer(_pick(params["layers"][str(i % period)],
                                     i // period))


def model_config(model, **overrides):
    """``GqaMoeConfig`` of a configuration file's dictionary: the
    published keys give the widths, the indexer and the rotary
    sections, ``deployment`` the router's width and the experts held,
    ``assumed`` what the source leaves open."""
    a, dep, sa = model["assumed"], model["deployment"], model["sa_config"]
    depth = model["num_hidden_layers"]
    if (model["tie_word_embeddings"] or model["mlp_only_layers"]
            or model["decoder_sparse_step"] != 1
            or model["use_sliding_window"] or model["attention_bias"]
            or model["hidden_act"] != "silu"
            or sa["indexer_num_kv_heads"] != 1
            or model["rope_scaling"]["rope_type"] != "default"):
        raise ValueError(
            "models/gqa_moe.py's sparse layers compute an untied head, "
            "an expert layer every layer, no window, no bias, SiLU-gated "
            "experts, ONE indexer key head and unscaled rotary")
    if not (len(dep["experts_held"]) == model["num_experts"]
            == model["num_local_experts"] == model["n_routed_experts"]):
        raise ValueError(
            "num_experts and num_local_experts count the experts held "
            "here: deployment.experts_held lists them, and "
            "n_routed_experts says the same to "
            "layer_metrics/expert_load_imbalance.py")
    config = dict(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_layers=depth,
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        window_layout=(gqa_moe.SPARSE,) * depth,
        rope_layout=(1,) * depth,
        rope_theta=model["rope_theta"],
        rope_sections=tuple(model["rope_scaling"]["mrope_section"]),
        qk_norm=a["qk_norm"],
        router_input="post_norm",
        expert_activation=model["hidden_act"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"],
        sparse_topk=sa["topk"],
        index_block_k=sa["kv_chunk_size"],  # unless assumed tunes it
        index_block_q=sa["q_chunk_size"],
        sparse_block_q=sa["q_chunk_size"],
        index_loss_weight=a["index_loss_weight"],
        n_routed_experts=dep["published_num_experts"],
        experts_held=tuple(dep["experts_held"]),
        num_experts_per_tok=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"],
        rms_norm_eps=model["rms_norm_eps"],
        max_seq_len=a["seq_len"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
        expert_row_factor=a["expert_row_factor"],
    )
    config.update({k: a[k] for k in (
        "index_block_q", "index_block_k", "sparse_block_q",
        "expert_block_t") if k in a})
    config.update(overrides)
    return gqa_moe.GqaMoeConfig(**config)


def compare(model, config, params, ids, labels, pos=None):
    """The readings of one row: the program against the reference given
    the program's choices, and against the reference on its own, the
    three computations a layer at a time in step (a layer's selection
    is 268 MB at the timed row and is dropped before the next).
    ``pos`` [3, seq] are the row's positions (None: text)."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    seq = len(ids)
    where = jnp.broadcast_to(jnp.arange(seq), (3, seq)) if pos is None else (
        jnp.asarray(pos))
    # the timed program's layers, one at a time, and last its final
    # normed hidden states
    program = gqa_moe.apply_layers(params, jnp.asarray(ids)[None], config,
                                   pos)
    # the reference's functions alone run at the highest precision: the
    # program's kernels take their operands as they are
    highest = functools.partial(jax.default_matmul_precision, "highest")
    with highest():
        own = jax.jit(lambda x, w: reference.layer(x, w, model, where))
        under = jax.jit(lambda x, w, keep, top_i: reference.layer(
            x, w, model, where, (keep, top_i)))
        final = jax.jit(lambda x, s: reference.rms_norm(
            x, s, model["rms_norm_eps"]))
    given = alone = jnp.asarray(
        params["embed_tokens"]["embedding"][ids], jnp.float32)
    kl_program = kl_given = kl_alone = 0.0
    agree = []
    for w in reference_layers(params, config):
        chose = next(program)
        keep, top_i = chose["selected"][0] != 0, chose["experts"]
        kl_program += float(chose[StepCounter.DSA_INDEX_KL])
        del chose
        with highest():
            given, kl, _, _ = under(given, f32(w), keep, top_i)
            kl_given += float(kl)
            alone, kl, mine, _ = own(alone, f32(w))
            kl_alone += float(kl)
        agree.append(float(jnp.sum(mine & keep) / jnp.sum(mine | keep)))
        del keep, mine
    hidden = next(program)[0]
    with highest():
        scale, head = f32(params["norm"]["scale"]), f32(
            params["lm_head"]["kernel"])
        given = final(given, scale)
        lm_given, lm_alone = (float(jax.jit(reference.head_loss)(
            h, head, jnp.asarray(labels))) for h in (
                given, final(alone, scale)))
    weight = model["assumed"]["index_loss_weight"]
    return {
        "reference_loss": lm_given + weight * kl_given,
        "reference_index_kl": kl_given,
        "program_index_kl": kl_program,
        "index_kl_error": abs(kl_program - kl_given) / kl_given,
        "median_token_error": hidden_error(hidden, given),
        "selection_agreement": min(agree),
        "own_reference_loss": lm_alone + weight * kl_alone,
    }


def build(model, **overrides):
    config = model_config(model, **overrides)
    strategy = Strategy(
        mesh=MeshPlan(**model["layout"]), rule_set="gqa_moe",
        remat_policy="",  # the model remats per layer itself
    )
    precision = jnp.dtype(config.compute_dtype).name

    def reference_loss(params, ids, labels):
        read = compare(model, config, params, ids, labels)
        ok = (read["median_token_error"] <= HIDDEN_TOL[precision]
              and read["index_kl_error"] <= INDEX_KL_TOL[precision]
              and read["selection_agreement"] >= AGREE_FLOOR[precision])
        print(json.dumps({
            "event": "reference_hidden", **read,
            "tolerance": HIDDEN_TOL[precision],
            "index_kl_tolerance": INDEX_KL_TOL[precision],
            "agreement_floor": AGREE_FLOOR[precision]}), flush=True)
        return read["reference_loss"] if ok else float("nan")

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
