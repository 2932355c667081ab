"""Decoder whose token mixers are of two kinds with different parameter
trees, chosen by a per-layer list: gated-delta-rule linear attention
(Gated DeltaNet) and full softmax attention without positions
(Olmo-Hybrid-7B publishes it at 32 layers of 3840: three linear layers
to one full). Training only.

Both kinds of layer share the family's reordered norm: the sublayer
reads ``x`` itself and its OUTPUT is normalised, then added. RMSNorm,
no bias anywhere, an untied head::

    x'  = x  + RMSNorm_attn(mixer_l(x))
    x'' = x' + RMSNorm_ffn(W_down(silu(W_gate x') * (W_up x')))

``mixer_l`` where ``layer_types[l]`` is ``"full_attention"``::

    q, k, v = x W_q, x W_k, x W_v
    q, k = RMSNorm_q(q), RMSNorm_k(k)     over ALL columns, before the
                                          heads are split
    a = softmax(q k^T / sqrt(head_dim) + causal) v      NO position
    mixer = a W_o

and where it is ``"linear_attention"`` (``H`` heads of ``dk`` keys and
``dv`` values; per head unless said)::

    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
        conv: causal, depthwise, a filter a channel, no bias
    q, k = q / |q|, k / |k|;   q = q / sqrt(dk)
    beta = 2 sigmoid(x W_b)               the 2 where ``allow_neg_eigval``
    g = -exp(A_log) softplus(x W_a + dt_bias)       the log of the decay
    S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t                         ``ops.gated_delta``
    o = RMSNorm_o(o) * silu(x W_g)        one learned [dv] scale
    mixer = o W_o

float32 for ``beta``, ``g`` and everything of the rule that the op
keeps so. With ``beta`` up to 2 the transition ``I - beta k k^T`` has an
eigenvalue down to -1 along ``k``; the loss function's aux counts the
share of updates with ``beta > 1`` (``StepCounter.GDN_NEG_EIG``).

``layer_types`` is the published list, as long as the published depth;
the first ``num_layers`` entries are used. Its smallest period ``p`` is
found (Olmo-Hybrid: 4), ``num_layers`` is a whole number of periods,
and the layers are stacked and scanned by the period, each under
``remat_policy`` on its own (``models/common.py``, "the period-stacked
decoder": the two kinds keep their own trees). On a TPU a full layer's
attention is ``ops.flash_attention`` and a linear layer's rule the
``gdn_rule_fwd``, ``gdn_rule_starts`` and ``gdn_rule_bwd`` kernels (both
under ``shard_map`` where a mesh is ambient); a linear layer's
checkpoint keeps the rule's output (``ops.gated_delta.KEPT_NAMES``), so
the forward kernel runs once a step; ``use_kernels=False`` takes XLA's
dense attention and the rule's two steps, the preparation in XLA and
the chain as a ``lax.scan`` over chunks (a CPU rehearsal).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models import common
from dlrover_tpu.models.common import cast_floats, dense_init, rms_norm
from dlrover_tpu.models.losses import lm_head_loss
from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.flash_attention import flash_attention_auto
from dlrover_tpu.ops.gated_delta import KEPT_NAMES as GDN_KEPT_NAMES
from dlrover_tpu.ops.gated_delta import gated_delta_rule_auto
from dlrover_tpu.ops.remat import apply_remat
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter

LINEAR, FULL = "linear_attention", "full_attention"
# Olmo-Hybrid-7B's published list: one period of four, eight times
_PUBLISHED_TYPES = (LINEAR, LINEAR, LINEAR, FULL) * 8


@dataclass(frozen=True)
class DeltaHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_layers: int = 32
    # the full layers
    num_heads: int = 30
    num_kv_heads: int = 30
    head_dim: int = 128
    # the linear layers
    linear_num_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # by layer, as long as the published depth
    layer_types: Tuple[str, ...] = _PUBLISHED_TYPES
    rms_norm_eps: float = 1e-6
    # the decay's step at initialisation, log-uniform (Mamba's)
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    embed_std: float = 0.02
    max_seq_len: int = 8192
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "full"
    # the Pallas kernels (Mosaic on a TPU, the interpreter elsewhere);
    # False takes XLA's dense attention and the rule's chain as a scan
    use_kernels: bool = True
    # None = interpret off the TPU; False forces Mosaic (a deviceless
    # compile traced on a CPU host)
    kernel_interpret: Any = None
    flash_block_q: int = 512
    flash_block_k: int = 1024


def delta_hybrid_tiny(**overrides) -> DeltaHybridConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_layers=4, num_heads=4, num_kv_heads=4, head_dim=16,
                linear_num_heads=4, linear_key_head_dim=8,
                linear_value_head_dim=16,
                layer_types=(LINEAR, FULL) * 4, max_seq_len=64,
                use_kernels=False)
    base.update(overrides)
    return DeltaHybridConfig(**base)


def layer_plan(config: DeltaHybridConfig) -> List[str]:
    """One period of the model's layers, each its kind
    (``common.period_of`` the published list). Refuses a kind it does
    not know."""
    kinds = list(config.layer_types)
    if set(kinds) - {LINEAR, FULL}:
        raise ValueError(
            f"layer_types {sorted(set(kinds))} gives each layer its kind, "
            f"{LINEAR!r} or {FULL!r}")
    return kinds[:common.period_of(kinds, config.num_layers, "layer_types")]


def layer_kinds(config: DeltaHybridConfig) -> Dict[str, int]:
    """Layers by mixer, for whoever reads a trace without the config."""
    plan = layer_plan(config)
    linear = plan.count(LINEAR) * (config.num_layers // len(plan))
    return {DeviceScope.GDN: linear,
            DeviceScope.ATTN_FULL: config.num_layers - linear}


# -- init -------------------------------------------------------------------


def _linear_mixer_init(key, lead, c: DeltaHybridConfig):
    d, h, dt = c.hidden_size, c.linear_num_heads, c.param_dtype
    wide_k, wide_v = h * c.linear_key_head_dim, h * c.linear_value_head_dim
    k = jax.random.split(key, 12)

    def proj(key, *shape):
        return {"kernel": dense_init(key, lead + shape, dt)}

    def conv(key, channels):
        width = c.linear_conv_kernel_dim
        return {"kernel": dense_init(key, lead + (width, channels), dt,
                                     scale=1.0 / math.sqrt(width))}

    # the public layer's, which is Mamba's: a decay rate uniform in
    # (0, 16) a head, a step log-uniform in [dt_min, dt_max] through the
    # inverse of the softplus
    step = jnp.exp(jax.random.uniform(k[10], lead + (h,), jnp.float32)
                   * (math.log(c.dt_max) - math.log(c.dt_min))
                   + math.log(c.dt_min))
    rate = jax.random.uniform(k[11], lead + (h,), jnp.float32,
                              minval=1e-4, maxval=16.0)
    return {
        "q_proj": proj(k[0], d, wide_k), "k_proj": proj(k[1], d, wide_k),
        "v_proj": proj(k[2], d, wide_v), "g_proj": proj(k[3], d, wide_v),
        "a_proj": proj(k[4], d, h), "b_proj": proj(k[5], d, h),
        "o_proj": proj(k[6], wide_v, d),
        "q_conv": conv(k[7], wide_k), "k_conv": conv(k[8], wide_k),
        "v_conv": conv(k[9], wide_v),
        "a_log": jnp.log(rate).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "o_norm": common.norm_init(lead, c.linear_value_head_dim, dt),
    }


def _full_mixer_init(key, lead, c: DeltaHybridConfig):
    d, hd, dt = c.hidden_size, c.head_dim, c.param_dtype
    k = jax.random.split(key, 4)

    def proj(key, *shape):
        return {"kernel": dense_init(key, lead + shape, dt)}

    return {
        "q_proj": proj(k[0], d, c.num_heads * hd),
        "k_proj": proj(k[1], d, c.num_kv_heads * hd),
        "v_proj": proj(k[2], d, c.num_kv_heads * hd),
        "o_proj": proj(k[3], c.num_heads * hd, d),
        "q_norm": common.norm_init(lead, c.num_heads * hd, dt),
        "k_norm": common.norm_init(lead, c.num_kv_heads * hd, dt),
    }


def _layers_init(key, lead, c: DeltaHybridConfig, kind: str):
    """The layers at one position of the period, stacked over the
    periods (``lead``)."""
    d, f, dt = c.hidden_size, c.intermediate_size, c.param_dtype
    k = jax.random.split(key, 4)
    mixer = _linear_mixer_init if kind == LINEAR else _full_mixer_init
    return {
        "mixer": mixer(k[0], lead, c),
        "attn_norm": common.norm_init(lead, d, dt),
        "mlp": {"gate_proj": {"kernel": dense_init(k[1], lead + (d, f), dt)},
                "up_proj": {"kernel": dense_init(k[2], lead + (d, f), dt)},
                "down_proj": {"kernel": dense_init(k[3], lead + (f, d), dt)}},
        "ffn_norm": common.norm_init(lead, d, dt),
    }


def init(rng: jax.Array, config: DeltaHybridConfig) -> Dict:
    c = config
    plan = layer_plan(c)  # refuses a depth the plan cannot have
    if c.num_heads % c.num_kv_heads:
        raise ValueError(f"{c.num_kv_heads} KV heads do not divide "
                         f"{c.num_heads} query heads")
    k = jax.random.split(rng, 3)
    lead = (c.num_layers // len(plan),)
    return {
        "embed_tokens": {"embedding": c.embed_std * jax.random.normal(
            k[0], (c.vocab_size, c.hidden_size), c.param_dtype)},
        "layers": common.stacked_init(
            k[1], plan, lambda key, kind: _layers_init(key, lead, c, kind)),
        "norm": common.norm_init((), c.hidden_size, c.param_dtype),
        "lm_head": {"kernel": dense_init(
            k[2], (c.hidden_size, c.vocab_size), c.param_dtype)},
    }


# -- forward ----------------------------------------------------------------


def _linear_mixer(x, p, c: DeltaHybridConfig):
    """The gated-delta-rule mixer of ``x`` [B, S, D] itself: (output,
    the share of its updates with ``beta > 1``)."""
    f32 = jnp.float32
    b, s, _ = x.shape
    h, dk, dv = (c.linear_num_heads, c.linear_key_head_dim,
                 c.linear_value_head_dim)

    # what is elementwise around the rule is a checkpoint of its own
    # inside the layer's: the layer's backward then holds a projection's
    # output once, not once a stage (convolved, activated, normalised)
    @partial(jax.checkpoint, static_argnums=(2, 3))
    def mixed(u, taps, width, length):
        u = jax.nn.silu(common.causal_conv(u, taps, 0.0)).reshape(
            b, s, h, width)
        if length is None:
            return u
        uf = u.astype(f32)  # a head's vector at that length
        return (length * uf * lax.rsqrt(
            jnp.sum(uf * uf, axis=-1, keepdims=True) + c.rms_norm_eps)
                ).astype(u.dtype)

    def stream(name, width, length):
        return mixed(x @ p[f"{name}_proj"]["kernel"],
                     p[f"{name}_conv"]["kernel"], width, length)

    q = stream("q", dk, 1.0 / math.sqrt(dk))
    k = stream("k", dk, 1.0)
    v = stream("v", dv, None)
    # what feeds the recurrence's scalars leaves the matmuls in float32
    beta = jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", x, p["b_proj"]["kernel"],
        preferred_element_type=f32))
    if c.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x, p["a_proj"]["kernel"],
                   preferred_element_type=f32)
        + p["dt_bias"].astype(f32))
    o = gated_delta_rule_auto(q, k, v, g, beta, use_kernels=c.use_kernels,
                              interpret=c.kernel_interpret)
    gate = jax.nn.silu(x @ p["g_proj"]["kernel"]).reshape(b, s, h, dv)
    o = (rms_norm(o, p["o_norm"]["scale"], c.rms_norm_eps)
         * gate).reshape(b, s, h * dv)
    return (o @ p["o_proj"]["kernel"],
            jnp.mean((beta > 1.0).astype(f32)))


def _full_mixer(x, p, c: DeltaHybridConfig):
    """Causal softmax attention of ``x`` [B, S, D] itself, without
    positions, q and k normalised over all their columns."""
    b, s, _ = x.shape
    h, kv, hd = c.num_heads, c.num_kv_heads, c.head_dim

    def heads(u, n):
        return u.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = heads(rms_norm(x @ p["q_proj"]["kernel"], p["q_norm"]["scale"],
                       c.rms_norm_eps), h)
    k = heads(rms_norm(x @ p["k_proj"]["kernel"], p["k_norm"]["scale"],
                       c.rms_norm_eps), kv)
    v = heads(x @ p["v_proj"]["kernel"], kv)
    if c.use_kernels:
        out = flash_attention_auto(
            q, k, v, causal=True, block_q=c.flash_block_q,
            block_k=c.flash_block_k, interpret=c.kernel_interpret)
    else:
        out = mha_reference(q, k, v, causal=True)
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * hd) @ p["o_proj"][
        "kernel"]


def _mlp(x, p):
    return (jax.nn.silu(x @ p["gate_proj"]["kernel"])
            * (x @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def _layer(c: DeltaHybridConfig, kind: str):
    """``layer(x, p) -> (x, the share of the mixer's updates with
    beta > 1)`` of one kind; a full layer has no such update."""

    def layer(x, p):
        p = cast_floats(p, c.compute_dtype)
        eps = c.rms_norm_eps
        if kind == LINEAR:
            with jax.named_scope(DeviceScope.GDN):
                y, neg_eig = _linear_mixer(x, p["mixer"], c)
                x = x + rms_norm(y, p["attn_norm"]["scale"], eps)
        else:
            with jax.named_scope(DeviceScope.ATTN_FULL):
                x = x + rms_norm(_full_mixer(x, p["mixer"], c),
                                 p["attn_norm"]["scale"], eps)
            neg_eig = jnp.float32(0.0)
        with jax.named_scope(DeviceScope.FFN):
            x = x + rms_norm(_mlp(x, p["mlp"]), p["ffn_norm"]["scale"], eps)
        return x, neg_eig

    return layer


def apply_hidden(params: Dict, input_ids: jax.Array,
                 config: DeltaHybridConfig):
    """(final hidden states [B, S, D] in the compute dtype, the mean
    over the linear layers of the share of updates with ``beta > 1``)."""
    c = config
    plan = layer_plan(c)
    x = params["embed_tokens"]["embedding"][input_ids].astype(
        c.compute_dtype)
    x, shares = common.scan_periods(
        [apply_remat(_layer(c, kind), c.remat_policy,
                     keep=GDN_KEPT_NAMES if kind == LINEAR else ())
         for kind in plan],
        x, params["layers"])
    x = rms_norm(x, params["norm"]["scale"].astype(c.compute_dtype),
                 c.rms_norm_eps)
    linear = layer_kinds(c)[DeviceScope.GDN]
    return x, shares.sum() / max(linear, 1)


def apply(params: Dict, input_ids: jax.Array,
          config: DeltaHybridConfig) -> jax.Array:
    """Logits [B, S, V] in float32."""
    x, _ = apply_hidden(params, input_ids, config)
    return (x @ params["lm_head"]["kernel"].astype(
        config.compute_dtype)).astype(jnp.float32)


# -- training glue ----------------------------------------------------------


def make_init_fn(config: DeltaHybridConfig):
    return common.make_init_fn(init, config, layer_kinds(config))


def make_loss_fn(config: DeltaHybridConfig, head_chunk: int = 0):
    """Causal-LM loss over batches {"input_ids", "labels"}; the aux is
    the share of the linear layers' updates whose transition has a
    negative eigenvalue. With ``head_chunk`` the head is fused with the
    cross entropy over sequence chunks (``losses.lm_head_loss``)."""

    def loss_fn(params, batch, rng):
        del rng  # no dropout
        hidden, neg_eig = apply_hidden(params, batch["input_ids"], config)
        loss = lm_head_loss(hidden, params["lm_head"]["kernel"],
                            batch["labels"], head_chunk)
        return loss, {StepCounter.GDN_NEG_EIG: neg_eig}

    return loss_fn


def param_count(config: DeltaHybridConfig) -> int:
    return common.param_count(make_init_fn(config))
