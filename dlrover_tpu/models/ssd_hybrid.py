"""Decoder whose token mixers are of two kinds with different parameter
trees, chosen by a per-layer list: Mamba-2 state-space mixers (the
chunked state-space dual) and grouped-query softmax attention without
positions (Granite-4.0-H-Micro publishes it at 40 layers of 2048: nine
Mamba-2 layers to one attention layer). Training only.

The family's four multipliers stand where a plain decoder has ones.
RMSNorm with a learned scale and float32 statistics, pre-norm, no bias
but the convolution's, the head tied to the token table::

    h_0  = embedding_multiplier * E[ids]
    h'   = h  + residual_multiplier * mixer_l(RMSNorm_in(h))
    h''  = h' + residual_multiplier * MLP(RMSNorm_post(h'))
    logits = RMSNorm_f(h_L) E^T / logits_scaling

    MLP(u) = (silu(a) * b) W_out,  [a | b] = u W_in     one matrix

``mixer_l`` where ``layer_types[l]`` is ``"attention"`` (``num_heads``
query heads on ``num_kv_heads`` KV heads)::

    q, k, v = u W_q, u W_k, u W_v                   NO position
    a = softmax(q k^T * attention_multiplier + causal) v
    mixer = a W_o

the scale is the published multiplier itself (1/64 at a head of 64),
not ``1 / sqrt(head_dim)``. Where it is ``"mamba"`` (``H`` heads of
``P``, ``G`` groups, a state of ``N``; ``d_inner = H P``)::

    [z | xBC | dt_raw] = u W_in        columns d_inner | d_inner + 2 G N | H
    xBC = silu(conv(xBC) + b_conv)     causal, depthwise, a filter a channel
    [x | B | C] = xBC                  columns d_inner | G N | G N
    dt = softplus(dt_raw + dt_bias)    [H], no clamp
    A  = -exp(A_log)                   [H]
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
                                       ``ops.ssd``
    o = RMSNorm_g(y * silu(z))         the gate FIRST, then the norm over
                                       all d_inner columns, one learned scale
    mixer = o W_out

float32 for ``dt`` (its columns leave ``W_in``'s product in float32),
``A`` and everything of the recurrence that the op keeps so, the norms'
statistics, the softmax and the cross entropy. The loss function's aux
carries the step's mean ``dt`` over Mamba layers, tokens and heads
(``StepCounter.SSD_DT_MEAN``): ``softplus(0)`` = 0.693 where the bias
is left out at small weights, a few hundredths where the published
parametrisation ran at this initialisation.

``layer_types`` is the published list, as long as the published depth;
the first ``num_layers`` entries are used. Its smallest period ``p`` is
found (Granite-4.0-H-Micro: 10), ``num_layers`` is a whole number of
periods, and the layers are stacked and scanned by the period, each
under ``remat_policy`` on its own (``models/common.py``, "the
period-stacked decoder": the two kinds keep their own trees). On a TPU
an attention layer's attention is ``ops.flash_attention`` and a Mamba
layer's recurrence the ``ssd_fwd`` / ``ssd_bwd`` kernels (both under
``shard_map`` where a mesh is ambient);
``use_kernels=False`` takes XLA's dense attention and the chunked form
as a ``lax.scan`` over chunks (a CPU rehearsal).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.models import common
from dlrover_tpu.models.common import cast_floats, dense_init, rms_norm
from dlrover_tpu.models.losses import lm_head_loss
from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.flash_attention import flash_attention_auto
from dlrover_tpu.ops.remat import apply_remat
from dlrover_tpu.ops.ssd import ssd_auto
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter

MAMBA, ATTENTION = "mamba", "attention"
# Granite-4.0-H-Micro's published list: attention at 5, 15, 25, 35
_PUBLISHED_TYPES = ((MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4
# Mamba-2's initialisation: a step log-uniform in ``DT_RANGE``, a decay
# rate uniform in ``A_RANGE`` a head
DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1.0, 16.0)


@dataclass(frozen=True)
class SsdHybridConfig:
    # the family's multipliers, as the source publishes them: no
    # default, so that no configuration keeps a silent 1
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_layers: int = 40
    # the attention layers
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    # the Mamba-2 layers
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # by layer, as long as the published depth
    layer_types: Tuple[str, ...] = _PUBLISHED_TYPES
    rms_norm_eps: float = 1e-5
    embed_std: float = 0.02
    max_seq_len: int = 8192
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "full"
    # the Pallas kernels (Mosaic on a TPU, the interpreter elsewhere);
    # False takes XLA's dense attention and the chunked form as a scan
    use_kernels: bool = True
    # None = interpret off the TPU; False forces Mosaic (a deviceless
    # compile traced on a CPU host)
    kernel_interpret: Any = None
    flash_block_q: int = 512
    flash_block_k: int = 1024


# Granite-4.0-H-Micro's four, for who builds a config by hand
PUBLISHED_MULTIPLIERS = dict(
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0)


def ssd_hybrid_tiny(**overrides) -> SsdHybridConfig:
    base = dict(PUBLISHED_MULTIPLIERS, attention_multiplier=1.0 / 16,
                vocab_size=256, hidden_size=64, shared_intermediate_size=128,
                num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=32,
                mamba_chunk_size=16, layer_types=(MAMBA, ATTENTION) * 4,
                max_seq_len=64, use_kernels=False)
    base.update(overrides)
    return SsdHybridConfig(**base)


def layer_plan(config: SsdHybridConfig) -> List[str]:
    """One period of the model's layers, each its kind
    (``common.period_of`` the published list). Refuses a kind it does
    not know."""
    kinds = list(config.layer_types)
    if set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(
            f"layer_types {sorted(set(kinds))} gives each layer its kind, "
            f"{MAMBA!r} or {ATTENTION!r}")
    return kinds[:common.period_of(kinds, config.num_layers, "layer_types")]


def layer_kinds(config: SsdHybridConfig) -> Dict[str, int]:
    """Layers by mixer, for whoever reads a trace without the config."""
    plan = layer_plan(config)
    mamba = plan.count(MAMBA) * (config.num_layers // len(plan))
    return {DeviceScope.SSD: mamba,
            DeviceScope.ATTN_FULL: config.num_layers - mamba}


def _mamba_widths(c: SsdHybridConfig) -> Tuple[int, int]:
    """(``d_inner``, the convolution's channels ``d_inner + 2 G N``)."""
    inner = c.mamba_n_heads * c.mamba_d_head
    return inner, inner + 2 * c.mamba_n_groups * c.mamba_d_state


# -- init -------------------------------------------------------------------


def _mamba_mixer_init(key, lead, c: SsdHybridConfig):
    d, h, dt = c.hidden_size, c.mamba_n_heads, c.param_dtype
    inner, channels = _mamba_widths(c)
    k = jax.random.split(key, 6)
    taps = c.mamba_d_conv
    # Mamba-2's: a decay rate uniform in ``A_RANGE`` a head, a step
    # log-uniform in ``DT_RANGE`` through the inverse of the softplus,
    # a skip of 1
    low, high = map(math.log, DT_RANGE)
    step = jnp.exp(jax.random.uniform(k[4], lead + (h,), jnp.float32)
                   * (high - low) + low)
    rate = jax.random.uniform(k[5], lead + (h,), jnp.float32,
                              minval=A_RANGE[0], maxval=A_RANGE[1])
    return {
        # [z | xBC | dt_raw], in this order
        "in_proj": {"kernel": dense_init(
            k[0], lead + (d, inner + channels + h), dt)},
        "conv": {"kernel": dense_init(k[1], lead + (taps, channels), dt,
                                      scale=1.0 / math.sqrt(taps)),
                 "bias": jax.random.uniform(
                     k[2], lead + (channels,), dt,
                     minval=-1.0 / math.sqrt(taps),
                     maxval=1.0 / math.sqrt(taps))},
        "a_log": jnp.log(rate).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "d_skip": jnp.ones(lead + (h,), dt),
        "norm": common.norm_init(lead, inner, dt),
        "out_proj": {"kernel": dense_init(k[3], lead + (inner, d), dt)},
    }


def _attention_mixer_init(key, lead, c: SsdHybridConfig):
    d, hd, dt = c.hidden_size, c.head_dim, c.param_dtype
    k = jax.random.split(key, 4)

    def proj(key, *shape):
        return {"kernel": dense_init(key, lead + shape, dt)}

    return {
        "q_proj": proj(k[0], d, c.num_heads * hd),
        "k_proj": proj(k[1], d, c.num_kv_heads * hd),
        "v_proj": proj(k[2], d, c.num_kv_heads * hd),
        "o_proj": proj(k[3], c.num_heads * hd, d),
    }


def _layers_init(key, lead, c: SsdHybridConfig, kind: str):
    """The layers at one position of the period, stacked over the
    periods (``lead``)."""
    d, f, dt = c.hidden_size, c.shared_intermediate_size, c.param_dtype
    k = jax.random.split(key, 3)
    mixer = _mamba_mixer_init if kind == MAMBA else _attention_mixer_init
    return {
        "mixer": mixer(k[0], lead, c),
        "input_norm": common.norm_init(lead, d, dt),
        "mlp": {"gate_up_proj": {"kernel": dense_init(
                    k[1], lead + (d, 2 * f), dt)},
                "down_proj": {"kernel": dense_init(k[2], lead + (f, d), dt)}},
        "post_norm": common.norm_init(lead, d, dt),
    }


def init(rng: jax.Array, config: SsdHybridConfig) -> Dict:
    c = config
    plan = layer_plan(c)  # refuses a depth the plan cannot have
    if c.num_heads % c.num_kv_heads or c.mamba_n_heads % c.mamba_n_groups:
        raise ValueError(
            f"{c.num_kv_heads} KV heads do not divide {c.num_heads} query "
            f"heads, or {c.mamba_n_groups} groups {c.mamba_n_heads} heads")
    k = jax.random.split(rng, 2)
    lead = (c.num_layers // len(plan),)
    return {
        # the token table, and the head (``tie_word_embeddings``)
        "embed_tokens": {"embedding": c.embed_std * jax.random.normal(
            k[0], (c.vocab_size, c.hidden_size), c.param_dtype)},
        "layers": common.stacked_init(
            k[1], plan, lambda key, kind: _layers_init(key, lead, c, kind)),
        "norm": common.norm_init((), c.hidden_size, c.param_dtype),
    }


# -- forward ----------------------------------------------------------------


def _mamba_mixer(u, p, c: SsdHybridConfig):
    """The Mamba-2 mixer of the normed ``u`` [B, S, D]: (output, the
    mean of ``dt`` over tokens and heads)."""
    f32 = jnp.float32
    b, s, _ = u.shape
    h, hd, g, n = (c.mamba_n_heads, c.mamba_d_head, c.mamba_n_groups,
                   c.mamba_d_state)
    inner, channels = _mamba_widths(c)
    w_in = p["in_proj"]["kernel"]
    zx = u @ w_in  # z and xBC are read of it; its dt columns are not
    # what feeds the recurrence's scalars leaves its matmul in float32
    # (64 columns computed a second time: no copy of the other 8448)
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", u, w_in[:, inner + channels:],
                   preferred_element_type=f32) + p["dt_bias"].astype(f32))
    xbc = jax.nn.silu(common.causal_conv(
        zx[..., inner:inner + channels], p["conv"]["kernel"],
        p["conv"]["bias"]))
    y = ssd_auto(
        xbc[..., :inner].reshape(b, s, h, hd), dt,
        -jnp.exp(p["a_log"].astype(f32)),
        xbc[..., inner:inner + g * n].reshape(b, s, g, n),
        xbc[..., inner + g * n:].reshape(b, s, g, n), p["d_skip"],
        chunk=c.mamba_chunk_size, use_kernels=c.use_kernels,
        interpret=c.kernel_interpret)
    # the gate first, then the norm over all the columns
    o = rms_norm(y.reshape(b, s, inner) * jax.nn.silu(zx[..., :inner]),
                 p["norm"]["scale"], c.rms_norm_eps)
    return o @ p["out_proj"]["kernel"], jnp.mean(dt)


def attention_mixer(u, p, c: SsdHybridConfig):
    """Causal grouped-query softmax attention of the normed ``u``
    [B, S, D], without positions, scaled by the published multiplier
    (``p`` an attention layer's ``mixer`` in the compute dtype; the
    benchmark's reference check reads it alone too)."""
    b, s, _ = u.shape
    h, kv, hd = c.num_heads, c.num_kv_heads, c.head_dim

    def heads(t, count):
        return t.reshape(b, s, count, hd).transpose(0, 2, 1, 3)

    q = heads(u @ p["q_proj"]["kernel"], h)
    k = heads(u @ p["k_proj"]["kernel"], kv)
    v = heads(u @ p["v_proj"]["kernel"], kv)
    if c.use_kernels:
        out = flash_attention_auto(
            q, k, v, causal=True, scale=c.attention_multiplier,
            block_q=c.flash_block_q, block_k=c.flash_block_k,
            interpret=c.kernel_interpret)
    else:
        out = mha_reference(q, k, v, causal=True,
                            scale=c.attention_multiplier)
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * hd) @ p["o_proj"][
        "kernel"]


def _mlp(u, p, c: SsdHybridConfig):
    f = c.shared_intermediate_size
    ab = u @ p["gate_up_proj"]["kernel"]
    return (jax.nn.silu(ab[..., :f]) * ab[..., f:]) @ p["down_proj"]["kernel"]


def _layer(c: SsdHybridConfig, kind: str):
    """``layer(x, p) -> (x, the mixer's mean dt)`` of one kind; an
    attention layer has no ``dt``."""

    def layer(x, p):
        p = cast_floats(p, c.compute_dtype)
        scale = jnp.asarray(c.residual_multiplier, x.dtype)
        eps = c.rms_norm_eps
        if kind == MAMBA:
            with jax.named_scope(DeviceScope.SSD):
                y, dt_mean = _mamba_mixer(
                    rms_norm(x, p["input_norm"]["scale"], eps), p["mixer"], c)
                x = x + scale * y
        else:
            with jax.named_scope(DeviceScope.ATTN_FULL):
                x = x + scale * attention_mixer(
                    rms_norm(x, p["input_norm"]["scale"], eps), p["mixer"], c)
            dt_mean = jnp.float32(0.0)
        with jax.named_scope(DeviceScope.FFN):
            x = x + scale * _mlp(
                rms_norm(x, p["post_norm"]["scale"], eps), p["mlp"], c)
        return x, dt_mean

    return layer


def apply_hidden(params: Dict, input_ids: jax.Array,
                 config: SsdHybridConfig):
    """(final normed hidden states [B, S, D] in the compute dtype, the
    mean over the Mamba layers of their mean ``dt``)."""
    c = config
    plan = layer_plan(c)
    x = params["embed_tokens"]["embedding"][input_ids].astype(
        c.compute_dtype) * jnp.asarray(c.embedding_multiplier,
                                       c.compute_dtype)
    x, means = common.scan_periods(
        [apply_remat(_layer(c, kind), c.remat_policy) for kind in plan],
        x, params["layers"])
    x = rms_norm(x, params["norm"]["scale"].astype(c.compute_dtype),
                 c.rms_norm_eps)
    mamba = layer_kinds(c)[DeviceScope.SSD]
    return x, means.sum() / max(mamba, 1)


def _scaled(hidden, c: SsdHybridConfig):
    """The hidden states as the tied head reads them: the logits'
    division moved onto the narrower operand."""
    return hidden * jnp.asarray(1.0 / c.logits_scaling, hidden.dtype)


def apply(params: Dict, input_ids: jax.Array,
          config: SsdHybridConfig) -> jax.Array:
    """Logits [B, S, V] in float32 (the head is the token table)."""
    x, _ = apply_hidden(params, input_ids, config)
    table = params["embed_tokens"]["embedding"].astype(config.compute_dtype)
    return (_scaled(x, config) @ table.T).astype(jnp.float32)


# -- training glue ----------------------------------------------------------


def make_init_fn(config: SsdHybridConfig):
    return common.make_init_fn(init, config, layer_kinds(config))


def make_loss_fn(config: SsdHybridConfig, head_chunk: int = 0):
    """Causal-LM loss over batches {"input_ids", "labels"}; the aux is
    the step's mean ``dt`` over Mamba layers, tokens and heads. With
    ``head_chunk`` the tied head is fused with the cross entropy over
    sequence chunks (``losses.lm_head_loss`` on the table's
    transpose)."""

    def loss_fn(params, batch, rng):
        del rng  # no dropout
        hidden, dt_mean = apply_hidden(params, batch["input_ids"], config)
        loss = lm_head_loss(
            _scaled(hidden, config), params["embed_tokens"]["embedding"].T,
            batch["labels"], head_chunk)
        return loss, {StepCounter.SSD_DT_MEAN: dt_mean}

    return loss_fn


def param_count(config: SsdHybridConfig) -> int:
    return common.param_count(make_init_fn(config))
