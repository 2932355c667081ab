"""The dense grouped-query decoder as the program trains it
(``dlrover_tpu/models/llama.py`` under the ``llama`` sharding rules),
built from a configuration file's dictionary, and its plain reference
(``reference.py`` beside this file) run on the program's parameters.

``worker.py`` imports the ``job`` module of the configuration's
``family`` and calls ``build``; a family gives a ``Job`` with these
fields and nothing else. A model with another block is a new directory
under ``families/`` (``job.py``, ``reference.py``, ``flops.py``), not
an edit here.
"""

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from chipbench.families.dense_gqa import flops, reference
from dlrover_tpu.models import llama
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

# The system's loss against the float32 reference on one seeded row at
# the initial weights. The configuration states bf16 parameters and
# bf16 compute with float32 norms, softmax and cross entropy, so the
# two differ by bf16's rounding (8 significant bits, 2^-8 = 0.4% of
# each activation), which over the 4096 positions of a row mostly
# averages out of the mean loss: on the chip the difference read 0.6e-4
# to 3.8e-4 at a loss of 10.9 over the first four seeds (PR 24). 2e-3
# is five times the largest of those. A wrong mask, a wrong rotary base
# or a dropped layer moves the loss by 0.05 and more, and 8-bit
# floating point where bf16 is stated (3 significant bits, 16 times
# bf16's rounding) does not stay inside it. A float32 configuration
# (the CPU rehearsal) is held to 1e-4.
REFERENCE_TOL = {"bfloat16": 2e-3, "float32": 1e-4}

# the reference's name for each stacked [L, ...] leaf of the program
NAMES = {"input_norm": ("input_norm", "scale"),
         "wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"),
         "wv": ("v_proj", "kernel"), "wo": ("o_proj", "kernel"),
         "post_norm": ("post_norm", "scale"),
         "w_gate": ("gate_proj", "kernel"),
         "w_up": ("up_proj", "kernel"),
         "w_down": ("down_proj", "kernel")}


@dataclass
class Job:
    init_fn: Callable  # rng -> parameters
    loss_fn: Callable  # (parameters, batch, rng) -> (loss, aux)
    strategy: Any  # the program's Strategy: mesh plan and rule set
    vocab_size: int
    seq_len: int
    param_count: int
    layers: int
    reference_loss: Callable  # (parameters, ids [seq], labels [seq])
    reference_tol: float


def build(model):
    """The job of a configuration file's dictionary: the published
    keys give the widths, ``assumed`` what the source leaves open."""
    a = model["assumed"]
    heads = model["num_attention_heads"]
    if model["hidden_size"] != heads * flops.head_dim(model):
        raise ValueError("models/llama.py derives head_dim as "
                         "hidden_size / heads; this file disagrees")
    if model.get("sliding_window") or model.get("tie_word_embeddings"):
        raise ValueError("a sliding window or a tied head is not what "
                         "models/llama.py computes")
    config = llama.LlamaConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=model["num_key_value_heads"],
        max_seq_len=a["seq_len"],
        rope_theta=model["rope_theta"],
        rms_eps=model["rms_norm_eps"],
        param_dtype=jnp.dtype(a.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(a.get("compute_dtype", "bfloat16")),
        remat_policy=a["remat_policy"],
        use_flash=a.get("attention", "flash") == "flash",
    )
    strategy = Strategy(
        mesh=MeshPlan(**model["layout"]), rule_set="llama",
        remat_policy="",  # the model remats per layer itself
    )

    def reference_loss(params, ids, labels):
        # one layer of the stacked parameters at a time; the index is
        # an argument, so one small program serves all layers
        pick = jax.jit(lambda stack, i: {
            key: jax.lax.dynamic_index_in_dim(stack[a][b], i,
                                              keepdims=False)
            for key, (a, b) in NAMES.items()})
        layers = (pick(params["layers"], jnp.int32(i))
                  for i in range(config.num_layers))
        return float(reference.loss(
            model, ids, labels, params["embed_tokens"]["embedding"],
            layers, params["norm"]["scale"], params["lm_head"]["kernel"]))

    return Job(
        init_fn=llama.make_init_fn(config),
        loss_fn=llama.make_loss_fn(config, head_chunk=a["head_chunk"]),
        strategy=strategy, vocab_size=config.vocab_size,
        seq_len=config.max_seq_len, param_count=llama.param_count(config),
        layers=config.num_layers, reference_loss=reference_loss,
        reference_tol=REFERENCE_TOL[jnp.dtype(config.compute_dtype).name])
