"""Decoder whose ONE stack of layers runs several times a step on the
same weights, with a learned exit after every pass (Ouro-2.6B, the
looped language model of "Scaling Latent Reasoning via Looped Language
Models", publishes it at 48 layers of 2048 run ``total_ut_steps`` = 4
times). Training only.

With ``T`` passes and ``L`` layers, for one row of tokens (RMSNorm with
a learned scale and float32 statistics, no bias but the gate's, an
untied head)::

    h = E[ids]
    for t in 1..T:                       the SAME parameters at every t
        for l in 1..L:
            h = h + N2_l(Attn_l(N1_l(h)))      the sandwich: a sublayer
            h = h + N4_l(MLP_l(N3_l(h)))       reads a normed input and
                                               its OUTPUT is normed too
        h = N_f(h)                       the one final norm, every pass
        h^(t) = h                        what the head and the gate read
                                         at t AND what pass t + 1 takes in

    Attn(u): q, k, v = u W_q, u W_k, u W_v;  rotary (rotate-half) on q, k
             softmax(q k^T / sqrt(head_dim) + causal) v;  then W_o
    MLP(u) = (silu(u W_g) * (u W_u)) W_d

    lambda_t = sigmoid(h^(t) w_g + b_g)          one gate for all passes
    p_1 = lambda_1
    p_t = lambda_t prod_{j<t} (1 - lambda_j)     1 < t < T
    p_T = prod_{j<T} (1 - lambda_j)              the rest of the mass;
                                                 lambda_T is not used
    L_t = CE(h^(t) W_head, label)                one head for all passes
    loss = mean over unmasked tokens of [sum_t p_t L_t - beta H(p)]
    H(p) = - sum_t p_t log p_t

Both ``p_t`` and ``L_t`` carry gradient: the gate learns from the
``L_t``, the stack from the ``p_t``-weighted cross entropies of all
``T`` passes. ``T`` = 1 has ``p_1`` = 1 and ``H`` = 0: a one-pass
sandwich decoder under the plain cross entropy.

The loop over passes is one ``lax.scan`` whose body is the ``lax.scan``
over the stacked layers, with the stacked parameters a CONSTANT of the
outer loop: a layer's body is traced, lowered and compiled once, a
shared leaf's gradient is the sum of its ``T`` uses (the transposed
loop sums it in the gradient's dtype), and each layer is its own
checkpoint inside both loops, so a step keeps ``T x L`` layer inputs.
The ``T`` normed states leave the loop stacked for the gate and the
head (``losses.weighted_lm_head_loss``: the head's kernel against all
``T`` states under the exit distribution's weights). On a TPU the
attention is ``ops.flash_attention``; ``use_kernels=False`` takes XLA's
dense attention (a CPU rehearsal).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models import common
from dlrover_tpu.models.common import cast_floats, dense_init, rms_norm
# plain rotate-half rotary on every column is that module's as it is;
# ``_rotary_tables`` reads this config's ``head_dim`` and ``rope_theta``
from dlrover_tpu.models.gqa_moe import _rotary_tables, _rotate
from dlrover_tpu.models.losses import IGNORE_INDEX, weighted_lm_head_loss
from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.flash_attention import flash_attention_auto
from dlrover_tpu.ops.remat import apply_remat
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter


@dataclass(frozen=True)
class LoopedConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    # passes of the stack a step (``total_ut_steps``)
    num_passes: int = 4
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    # the weight of the exit distribution's entropy in the loss
    exit_entropy_beta: float = 0.05
    embed_std: float = 0.02
    max_seq_len: int = 8192
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "full"
    # the flash kernels (Mosaic on a TPU, the interpreter elsewhere);
    # False takes XLA's dense attention
    use_kernels: bool = True
    # None = interpret off the TPU; False forces Mosaic (a deviceless
    # compile traced on a CPU host)
    kernel_interpret: Any = None


def looped_tiny(**overrides) -> LoopedConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
                num_passes=3, rope_theta=1e4, max_seq_len=64,
                use_kernels=False)
    base.update(overrides)
    return LoopedConfig(**base)


def layer_kinds(config: LoopedConfig) -> Dict[str, int]:
    """Layers by mixer, for whoever reads a trace without the config:
    the layers held, not the passes over them."""
    return {DeviceScope.ATTN_FULL: config.num_layers}


# -- init -------------------------------------------------------------------


def init(rng: jax.Array, config: LoopedConfig) -> Dict:
    c = config
    if c.num_heads % c.num_kv_heads:
        raise ValueError(f"{c.num_kv_heads} KV heads do not divide "
                         f"{c.num_heads} query heads")
    if c.num_passes < 1 or c.num_layers < 1:
        raise ValueError(f"{c.num_passes} passes of {c.num_layers} layers")
    d, f, hd, dt = (c.hidden_size, c.intermediate_size, c.head_dim,
                    c.param_dtype)
    lead = (c.num_layers,)
    k = jax.random.split(rng, 10)

    def proj(key, *shape):
        return {"kernel": dense_init(key, lead + shape, dt)}

    return {
        "embed_tokens": {"embedding": c.embed_std * jax.random.normal(
            k[0], (c.vocab_size, d), dt)},
        "layers": {
            "input_norm": common.norm_init(lead, d, dt),
            "attn": {"q_proj": proj(k[1], d, c.num_heads * hd),
                     "k_proj": proj(k[2], d, c.num_kv_heads * hd),
                     "v_proj": proj(k[3], d, c.num_kv_heads * hd),
                     "o_proj": proj(k[4], c.num_heads * hd, d)},
            "attn_out_norm": common.norm_init(lead, d, dt),
            "post_norm": common.norm_init(lead, d, dt),
            "mlp": {"gate_proj": proj(k[5], d, f),
                    "up_proj": proj(k[6], d, f),
                    "down_proj": proj(k[7], f, d)},
            "mlp_out_norm": common.norm_init(lead, d, dt),
        },
        "norm": common.norm_init((), d, dt),
        "exit_gate": {"kernel": dense_init(k[8], (d, 1), dt),
                      "bias": jnp.zeros((1,), dt)},
        "lm_head": {"kernel": dense_init(k[9], (d, c.vocab_size), dt)},
    }


# -- forward ----------------------------------------------------------------


def _attention(u, p, c: LoopedConfig, rotary):
    """Causal softmax attention of the normed ``u`` [B, S, D], rotary on
    every column of q and k."""
    b, s, _ = u.shape
    h, kv, hd = c.num_heads, c.num_kv_heads, c.head_dim

    def heads(t, count):
        return t.reshape(b, s, count, hd).transpose(0, 2, 1, 3)

    q = _rotate(heads(u @ p["q_proj"]["kernel"], h), *rotary)
    k = _rotate(heads(u @ p["k_proj"]["kernel"], kv), *rotary)
    v = heads(u @ p["v_proj"]["kernel"], kv)
    if c.use_kernels:
        out = flash_attention_auto(q, k, v, causal=True,
                                   interpret=c.kernel_interpret)
    else:
        out = mha_reference(q, k, v, causal=True)
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * hd) @ p["o_proj"][
        "kernel"]


def _mlp(u, p):
    return (jax.nn.silu(u @ p["gate_proj"]["kernel"])
            * (u @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def _layer(c: LoopedConfig, rotary):
    """``layer(x, p) -> (x, None)``: the scan body over the stacked
    layers; a sublayer's two norms are inside its scope."""

    def layer(x, p):
        p = cast_floats(p, c.compute_dtype)
        eps = c.rms_norm_eps
        with jax.named_scope(DeviceScope.ATTN_FULL):
            u = rms_norm(x, p["input_norm"]["scale"], eps)
            x = x + rms_norm(_attention(u, p["attn"], c, rotary),
                             p["attn_out_norm"]["scale"], eps)
        with jax.named_scope(DeviceScope.FFN):
            u = rms_norm(x, p["post_norm"]["scale"], eps)
            x = x + rms_norm(_mlp(u, p["mlp"]),
                             p["mlp_out_norm"]["scale"], eps)
        return x, None

    return layer


def apply_hidden(params: Dict, input_ids: jax.Array,
                 config: LoopedConfig) -> jax.Array:
    """The normed state after each pass, [T, B, S, D] in the compute
    dtype: ``[t]`` is what the head and the gate read at pass ``t + 1``
    and what the next pass took in."""
    c = config
    x = params["embed_tokens"]["embedding"][input_ids].astype(
        c.compute_dtype)
    layer = apply_remat(_layer(c, _rotary_tables(input_ids.shape[1], c)),
                        c.remat_policy)
    final = params["norm"]["scale"].astype(c.compute_dtype)

    def one_pass(h, _):
        # the stack is closed over: a constant of the loop over passes
        h, _ = lax.scan(layer, h, params["layers"])
        h = rms_norm(h, final, c.rms_norm_eps)
        return h, h

    return lax.scan(one_pass, x, None, length=c.num_passes)[1]


def apply(params: Dict, input_ids: jax.Array,
          config: LoopedConfig) -> jax.Array:
    """Logits [B, S, V] in float32 of the last pass (no early exit)."""
    x = apply_hidden(params, input_ids, config)[-1]
    return (x @ params["lm_head"]["kernel"].astype(
        config.compute_dtype)).astype(jnp.float32)


@jax.named_scope(DeviceScope.EXIT_GATE)
def exit_distribution(states: jax.Array, gate: Dict):
    """``states`` [T, B, S, D] -> (the exit distribution ``p`` [T, B, S]
    and its entropy [B, S], float32). In logarithms: ``log p_t = log
    lambda_t + sum_{j<t} log(1 - lambda_j)``, and the last pass takes
    ``sum_{j<T} log(1 - lambda_j)``, the rest of the mass."""
    f32 = jnp.float32
    # the gate's logit after every pass but the last: lambda_T is not used
    z = jnp.einsum("tbsd,d->tbs", states[:-1],
                   gate["kernel"][:, 0].astype(states.dtype),
                   preferred_element_type=f32) + gate["bias"].astype(f32)
    rest = jnp.zeros((1,) + states.shape[1:3], f32)
    # sum_{j<t} log(1 - lambda_j): nothing before the first pass
    stayed = jnp.concatenate(
        [rest, jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)], axis=0)
    log_p = stayed + jnp.concatenate([jax.nn.log_sigmoid(z), rest], axis=0)
    p = jnp.exp(log_p)
    return p, -(p * log_p).sum(axis=0)


def loss_parts(params: Dict, batch: Dict, config: LoopedConfig,
               head_chunk: int = 0) -> Dict:
    """Everything of the objective on batches {"input_ids", "labels"}:
    ``loss``; ``pass_losses`` [T], each pass's mean cross entropy
    before its weight; ``exit_distribution`` [T] and ``exit_entropy``,
    means over the unmasked tokens; ``states`` [T, B, S, D]. The head is
    fused with the cross entropy ``head_chunk`` tokens of a row of a
    pass at a time (``losses.weighted_lm_head_loss``); 0 takes a whole
    row as one chunk."""
    c = config
    labels = batch["labels"]
    states = apply_hidden(params, batch["input_ids"], c)
    mask = (labels != IGNORE_INDEX).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    p, entropy = exit_distribution(states, params["exit_gate"])
    weighted, sums = weighted_lm_head_loss(
        states, params["lm_head"]["kernel"], labels, p,
        head_chunk or labels.shape[1])
    with jax.named_scope(DeviceScope.EXIT_GATE):
        exit_entropy = (entropy * mask).sum() / denom
        mean_p = (p * mask).sum(axis=(1, 2)) / denom
    return {"loss": weighted - c.exit_entropy_beta * exit_entropy,
            "pass_losses": sums / denom, "exit_distribution": mean_p,
            "exit_entropy": exit_entropy, "states": states}


# -- training glue ----------------------------------------------------------


def make_init_fn(config: LoopedConfig):
    return common.make_init_fn(init, config, layer_kinds(config),
                               passes=config.num_passes)


def make_loss_fn(config: LoopedConfig, head_chunk: int = 0):
    """The stage-I objective (``loss_parts``); the aux carries the
    loop's counters: the exit distribution's entropy, the expected exit
    pass (1 to ``T``) and the first and last pass's own cross
    entropy."""

    def loss_fn(params, batch, rng):
        del rng  # no dropout
        parts = loss_parts(params, batch, config, head_chunk)
        passes = jnp.arange(1, config.num_passes + 1, dtype=jnp.float32)
        return parts["loss"], {
            StepCounter.LOOP_EXIT_ENTROPY: parts["exit_entropy"],
            StepCounter.LOOP_EXIT_MEAN_PASS: (
                passes * parts["exit_distribution"]).sum(),
            StepCounter.LOOP_LOSS_FIRST: parts["pass_losses"][0],
            StepCounter.LOOP_LOSS_LAST: parts["pass_losses"][-1],
        }

    return loss_fn


def param_count(config: LoopedConfig) -> int:
    return common.param_count(make_init_fn(config))
