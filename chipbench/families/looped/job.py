"""The looped decoder as the program trains it
(``dlrover_tpu/models/looped.py`` under the ``looped`` sharding rules),
built from a configuration file's dictionary, and its plain reference
(``reference.py`` beside this file) run on the program's parameters.

``worker.py`` imports this module through the configuration's
``family`` and calls ``build``, which gives the ``Job`` of
``families/dense_gqa/job.py``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.families.dense_gqa.job import Job  # the one contract
from chipbench.families.looped import reference
# the median token's error is that family's, as it is
from chipbench.families.mla_moe.job import hidden_error
from dlrover_tpu.models import looped
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

# Six limits decide the reference check, on one seeded row of
# ``seq_len`` tokens at the initial weights: the program (bf16, the
# flash kernels, the two nested scans, the head a chunk at a time)
# against the float32 reference (``reference.py``: a Python loop over
# the passes and the layers), which differs from it by bf16's rounding
# of every activation of ``T x L`` layer passes.
#
# Every reading below is the harness's own comparison on the chip (PR
# 65, TPU v5 lite: ``tests/chipbench/looped_controls.py``, which calls
# ``worker.ReferenceCheck``, the compiled ``eval_step`` against this
# job's ``reference_loss``, and the cell's own runs) at the timed sizes
# (12 layers, 4 passes, one row of 8192, the whole vocabulary): the
# sound reference on seeds 3000006511-14, 3000006521-28, 3000006557-58,
# 3000006581-90, 3000006611-16 and in twenty-two of the cell's own runs
# (fifty-two rows), each control on 3000006511, 3000006512,
# 3000006557, 3000006581, 3000006582, 3000006611 and 3000006612 (seven
# seeds). The program's numbers come from ``looped.loss_parts`` with
# the cell's ``head_chunk``, the function the timed loss function is
# made of, jitted for one row.
#
# ``HIDDEN_TOL``, on the last pass's normed states, is the limit that
# feels the precision and a wrong mechanism of a layer or of the loop:
# the median over the row's tokens of ``|program - reference| /
# |reference|``. Sound: 1.37% to 3.00% on the fifty-two rows; above
# olmohybrid's 1.8% at 8 layers: the sandwich normalises every
# sublayer's output before it is added, so no residual of a larger norm
# dilutes a layer's rounding, and the loop hands a pass's rounded state
# to the next, 48 layer passes in all. The reference with e4m3 operands,
# the nearest precision below the bf16 the configuration states: 74.3%
# to 82.4%. Each mechanism wrong in the reference alone: rotary base
# 1e4 44.8% to 61.4%, three passes for four 80.1% to 89.0%, the
# sandwich's output norms left out 85% to 112%, the final norm left
# out between passes 123% to 139%. 8e-2 lies 2.7 times above the
# largest sound reading and 5.6 times below the smallest of the others,
# and the harness said not ``ok`` of e4m3 and of all four on every seed.
#
# ``PASS_LOSS_TOL``, on the four ``L_t`` (each pass's mean cross entropy
# before its weight; the largest ``|L_t - L_t'|``), is what sees a pass
# too few (a reference of another number of passes reads ``inf``) and a
# state that went wrong before the last pass. Sound: 6.0e-5 to 9.1e-4 at
# ``L_t`` of 11.3. e4m3 operands 1.06e-2 to 1.50e-2; the mechanisms
# above 4.8e-3 (the final norm left out between passes, on one seed of
# seven; 7.0e-3 the next) to 2.7e-2. 3e-3 lies 3.3 times above the
# largest sound reading and 1.6 times below the smallest of the others.
#
# ``EXIT_TOL``, on the mean exit distribution (the largest ``|p_t -
# p_t'|`` of the means over the row's tokens), is what sees the gate and
# the distribution's own arithmetic. Sound: 7.1e-5 to 2.5e-3 (a gate's
# logit of standard deviation 1 on a state 2% off moves a token's
# ``p_t`` by a hundredth, and the mean over 8192 tokens by a tenth of
# that). ``p_T`` gated by ``lambda_T`` too 1.69e-2 to 1.58e-1; e4m3
# operands 3.38e-2 to 9.14e-2; the mechanisms above 1.28e-2 to 1.80e-1.
# 1e-2 lies 4 times above the largest sound reading and 3.4 times below
# e4m3's smallest; rotary base 1e4's smallest reading, 1.28e-2, fails by
# the states and the ``L_t``, and ``p_T`` gated too by the loss as well.
#
# ``worker.py`` reads one number, so a row that fails one of the five
# limits beside the loss's gives it NaN for the reference's loss, which fails its
# comparison; the readings are printed beside it (event
# ``reference_hidden``, with the reference's loss).
#
# ``REFERENCE_TOL``, on the loss (what ``worker.py`` compares: the
# compiled ``eval_step``'s ``mean[sum_t p_t L_t - beta H(p)]``), is what
# sees the objective's own arithmetic, which leaves the states, the
# ``L_t`` and the distribution alone. Sound: 2.9e-6 to 3.8e-4 at a loss
# of 11.24 on the fifty-two rows (their root mean square 1.2e-4: the
# sum of 32,768 tokens' roundings, half-normal by every sign of it, so
# 6e-4 is near five of its standard deviations). The reference with
# e4m3 operands on the seven seeds: 9.6e-5, 7.3e-4, 1.3e-3, 3.7e-3,
# 6.0e-3, 1.0e-2 and 1.4e-2: the mean loss of a row at random weights
# hardly feels the precision, one reading of seven lies INSIDE the
# sound range and no limit lies between the two. 6e-4 lies 1.6 times
# above the largest sound reading and below six of e4m3's seven; e4m3
# fails on all seven seeds by the states, the ``L_t`` and the
# distribution (and by both gradients on the four seeds read since
# they are compared); ``dense_gqa``'s 2e-3 would lie above three of the
# seven. The entropy's sign turned reads 8.9e-2 to
# 1.3e-1 (``2 beta H``); the head's weights all 1 33.9 (the four ``L_t``
# summed); ``p_T`` gated too 1.9e-1 to 1.8; a wrong layer mechanism
# 3.8e-6 to 3.1e-2, which fails by the states.
#
# ``GATE_GRAD_TOL`` and ``HEAD_GRAD_TOL``, on the gradient of the loss
# for the exit gate's kernel and for the head's (``|program - reference|
# / |reference|`` of each leaf as one vector), are what see the code the
# TIMED step runs and ``eval_step`` does not: under ``jax.grad`` the head
# is ``weighted_lm_head_loss``'s forward and backward rules (each
# chunk's ``dlogits`` under the token's weight, the ONE ``dW`` summed
# over 32 chunks in bf16, the weights' cotangent through which the gate
# learns from the ``L_t``). The program's side is ``jax.grad`` of
# ``loss_parts`` for those two leaves, the reference's ``jax.grad`` of
# its ``objective`` at ITS OWN states (one more head pass; nothing of
# the stack is differentiated on either side). Sound (the twenty-nine
# rows since seed 3000006581): the gate 0.40% to 2.26%, the head 1.34%
# to 2.06%. e4m3 operands (four seeds): the gate 59% to 79%, the head
# 71% to 78%. A head whose weights are all 1: 19% to 48% and 76% to
# 81%; the entropy's sign turned 150% to 207% on the gate, the head's
# untouched (1.5% to 1.8%); ``p_T`` gated too 98% to 100% on the gate;
# the layers' mechanisms 23% to 187% and 9% to 110%. 1e-1 on the gate
# lies 4.4 times above the largest sound reading and 5.9 times below
# e4m3's smallest (the gate's gradient is a sum with cancellation, so
# its sound readings spread five times where the head's spread 1.5);
# 6e-2 on the head 2.9 times above and 12 times below. A backward rule that lost the weights' cotangent or the
# kernel's reads 30% and more (``tests/chipbench``, the toy size).
#
# The first five numbers come from forward programs and the last two
# from the head's own backward: the gradient of every other leaf (a
# shared leaf's is the transposed loop's sum over the passes) is held
# against the reference's ``jax.grad`` at a toy size on the CPU
# (``tests/test_looped.py``), not by ``correct``: the float32 reference
# differentiated through 48 layer passes of 8192 tokens does not fit
# beside the training state.
#
# A float32 configuration (the CPU rehearsal) is held to 1e-4 on the
# loss, the ``L_t``, the states and the two gradients and 1e-5 on the
# distribution: there the two sides differ by the order of float32 sums.
REFERENCE_TOL = {"bfloat16": 6e-4, "float32": 1e-4}
PASS_LOSS_TOL = {"bfloat16": 3e-3, "float32": 1e-4}
EXIT_TOL = {"bfloat16": 1e-2, "float32": 1e-5}
HIDDEN_TOL = {"bfloat16": 8e-2, "float32": 1e-4}
GATE_GRAD_TOL = {"bfloat16": 1e-1, "float32": 1e-4}
HEAD_GRAD_TOL = {"bfloat16": 6e-2, "float32": 1e-4}

# the reference's name for each stacked [L, ...] leaf of the program
NAMES = {
    "input_norm": ("input_norm", "scale"),
    "attn": {"wq": ("attn", "q_proj", "kernel"),
             "wk": ("attn", "k_proj", "kernel"),
             "wv": ("attn", "v_proj", "kernel"),
             "wo": ("attn", "o_proj", "kernel")},
    "attn_out_norm": ("attn_out_norm", "scale"),
    "post_norm": ("post_norm", "scale"),
    "mlp": {"w_gate": ("mlp", "gate_proj", "kernel"),
            "w_up": ("mlp", "up_proj", "kernel"),
            "w_down": ("mlp", "down_proj", "kernel")},
    "mlp_out_norm": ("mlp_out_norm", "scale"),
}


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


@jax.jit
def _pick(stack, i):
    """Layer ``i`` of the stack in the reference's form: the index is an
    argument, so one compile serves every layer."""
    return jax.tree.map(
        lambda path: jax.lax.dynamic_index_in_dim(
            _leaf(stack, path), i, keepdims=False),
        NAMES, is_leaf=lambda node: isinstance(node, tuple))


def reference_layers(params, config):
    """``layers()`` as the reference takes it: the program's stack a
    layer at a time, in order, anew for every pass."""

    def layers():
        for i in range(config.num_layers):
            yield _pick(params["layers"], jnp.int32(i))

    return layers


def model_config(model, **overrides):
    """``LoopedConfig`` of a configuration file's dictionary: the
    published keys give the widths, the depth and the passes,
    ``assumed`` what the source leaves open."""
    a = model["assumed"]
    if (model["tie_word_embeddings"] or model["hidden_act"] != "silu"
            or model["use_sliding_window"] or model["sliding_window"]
            or model["rope_scaling"] is not None
            or set(model["layer_types"]) != {"full_attention"}):
        raise ValueError(
            "models/looped.py computes an untied head, SiLU, plain "
            "rotary and full attention on every layer, no window")
    config = dict(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        num_passes=model["total_ut_steps"],
        rope_theta=float(model["rope_theta"]),
        rms_norm_eps=model["rms_norm_eps"],
        exit_entropy_beta=a["exit_entropy_beta"],
        embed_std=a["embed_std"],
        max_seq_len=a["seq_len"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
    )
    config.update(overrides)
    return looped.LoopedConfig(**config)


def reference_parts(model, config, params, ids, labels):
    """``reference.run`` on the program's parameters."""
    return reference.run(
        model, ids, labels, params["embed_tokens"]["embedding"],
        reference_layers(params, config), params["norm"]["scale"],
        (params["exit_gate"]["kernel"], params["exit_gate"]["bias"]),
        params["lm_head"]["kernel"])


def _apart(program, plain):
    """The largest ``|program - plain|`` of two vectors a pass; a
    reference of another number of passes is as far as can be."""
    program, plain = (np.asarray(a, np.float32) for a in (program, plain))
    if program.shape != plain.shape:
        return float("inf")
    return float(np.abs(program - plain).max())


def _off(program, plain):
    """``|program - plain| / |plain|`` of two arrays as vectors."""
    program, plain = (jnp.asarray(a, jnp.float32) for a in (program, plain))
    return float(jnp.linalg.norm(program - plain) / jnp.linalg.norm(plain))


def gradient_readings(program, plain):
    """The gate's and the head's gradient of the program (its
    ``exit_gate`` and ``lm_head`` leaves) against the reference's
    ``objective_gradient`` (a pair), each by its norm."""
    return {
        "gate_grad_error": _off(program["exit_gate"]["kernel"], plain[0]),
        "head_grad_error": _off(program["lm_head"]["kernel"], plain[1]),
    }


def readings(program, plain):
    """What the check compares beside the loss and the two gradients,
    the program's ``loss_parts`` of one row against the reference's
    ``run``: the largest ``|L_t - L_t'|`` over the passes, the largest
    ``|p_t - p_t'|`` of the mean exit distribution, and the median
    token's error of the last pass's normed states."""
    return {
        "pass_loss_diff": _apart(program["pass_losses"],
                                 plain["pass_losses"]),
        "exit_diff": _apart(program["exit_distribution"],
                            plain["exit_distribution"]),
        "median_token_error": hidden_error(program["states"][-1, 0],
                                           plain["states"]),
    }


def build(model, **overrides):
    config = model_config(model, **overrides)
    strategy = Strategy(
        mesh=MeshPlan(**model["layout"]), rule_set="looped",
        remat_policy="",  # the model remats per layer itself
    )
    precision = jnp.dtype(config.compute_dtype).name
    head_chunk = model["assumed"]["head_chunk"]

    def parts_of(heads, params, ids, labels):
        parts = looped.loss_parts(
            {**params, **heads}, {"input_ids": ids[None],
                                  "labels": labels[None]}, config, head_chunk)
        return parts["loss"], parts

    # the timed program's own objective on one row, its parts kept, and
    # its gradient for the gate and the head: under ``jax.grad`` the head
    # is the timed step's (``weighted_lm_head_loss``'s forward and
    # backward rules), and the stack's states are not differentiated
    program_parts = jax.jit(jax.value_and_grad(parts_of, has_aux=True))
    limits = {"pass_loss_diff": PASS_LOSS_TOL[precision],
              "exit_diff": EXIT_TOL[precision],
              "median_token_error": HIDDEN_TOL[precision],
              "gate_grad_error": GATE_GRAD_TOL[precision],
              "head_grad_error": HEAD_GRAD_TOL[precision]}

    def reference_loss(params, ids, labels):
        plain = reference_parts(model, config, params, ids, labels)
        heads = {name: params[name] for name in ("exit_gate", "lm_head")}
        (_, parts), grads = program_parts(
            heads, params, jnp.asarray(ids), jnp.asarray(labels))
        found = readings(parts, plain)
        found.update(gradient_readings(grads, reference.objective_gradient(
            model, plain["pass_states"], labels,
            (heads["exit_gate"]["kernel"], heads["exit_gate"]["bias"]),
            heads["lm_head"]["kernel"])))
        loss = float(plain["loss"])
        print(json.dumps({"event": "reference_hidden",
                          "reference_loss": loss, **found,
                          "tolerances": limits}), flush=True)
        sound = all(found[name] <= limit for name, limit in limits.items())
        return loss if sound else float("nan")

    return Job(
        init_fn=looped.make_init_fn(config),
        loss_fn=looped.make_loss_fn(config, head_chunk=head_chunk),
        strategy=strategy, vocab_size=config.vocab_size,
        seq_len=config.max_seq_len,
        param_count=looped.param_count(config),
        layers=config.num_layers, reference_loss=reference_loss,
        reference_tol=REFERENCE_TOL[precision])
