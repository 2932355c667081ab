"""Decoder whose token mixers are of two kinds, Kimi delta attention
(KDA: the delta rule under a per-channel decay) and multi-head latent
attention without a query latent (MLA), with a dense SwiGLU FFN in the
leading layers and group-limited sigmoid experts behind a shared one in
the rest (Ling-3.0-flash publishes it at 42 layers of 2560: five KDA
layers then one MLA layer a group of six, two leading dense layers, 512
routed experts). Training only.

Every layer is ``x = x + mixer(RMSNorm(x)); x = x + ffn(RMSNorm(x))``,
no bias, a final RMSNorm, an untied head. Layer ``l`` is MLA where
``(l + 1) % layer_group_size == 0`` and KDA otherwise.

**KDA** (Kimi Linear, arXiv:2510.26692), ``u`` the normed input, ``H``
heads of ``head_dim`` keys and values::

    q, k, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
        conv: causal, depthwise, a filter a channel, no bias
    q, k = q / |q|, k / |k|  a head;   q = q / sqrt(head_dim)
    beta = sigmoid(u W_b)                             [S, H]
    g = kda_lower_bound * sigmoid(exp(A_log_h) * (u W_f + dt_bias))
        [S, H, head_dim] float32 in (kda_lower_bound, 0): the log of
        the decay a key CHANNEL; W_f a full matrix
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                   ``ops.kda``
    mixer = (RMSNorm_head(o) * sigmoid(u W_g)) W_o
        one learned [head_dim] scale; W_g a full matrix

No rotary and no other position. float32 for ``beta``, ``g`` and
everything of the rule that the op keeps so; the bound is what keeps
the op's sub-chunk factors under float32's range (``ops/kda.py``).

**MLA** (DeepSeek-V2's, materialised, ``q_lora_rank`` null)::

    q = u W_q -> H heads of [q_nope | q_rope], RMSNorm_q a head over
        both parts, one learned scale
    [c_kv | k_r] = u W_kva;  c_kv = RMSNorm(c_kv)
    [k_nope | v] = c_kv W_kvb  a head;  k_nope = RMSNorm_k(k_nope) a
        head, k_r = RMSNorm_r(k_r): ONE rotary key head for all
    s = (q_nope . k_nope + rot(q_rope) . rot(k_r)) * (nope + rope)^-0.5
    mixer = concat_h(softmax_causal(s) v * sigmoid(u W_gate)_h) W_o

``W_gate`` ``[hidden, H]``: one gate value a head and token. Plain
rotary at ``rope_theta``, no scaling. On a TPU the attention is
``ops.flash_attention.flash_attention_mla`` and the layer's checkpoint
keeps its output and logsumexp (``KEPT_NAMES``), so the forward kernel
runs once a step; ``use_kernels=False`` takes XLA's dense attention,
the rule's chain as a ``lax.scan`` and the einsum experts (a CPU
rehearsal).

**The expert FFN** is ``models/mla_moe.py``'s (``_moe``, read through
this config's fields of the same names): sigmoid scores over ALL
``n_routed_experts`` in float32, the selection on ``s + bias`` among the
experts of the ``topk_group`` of ``n_group`` groups whose two largest
biased scores add up to most (``ops.moe.group_limited_routing``),
weights ``s_i / sum over the selected`` times
``routed_scaling_factor``, one shared SwiGLU beside the routed ones,
the sequence-wise balance loss times ``balance_loss_weight``.
``experts_held`` says which routed experts this chip holds (all when
empty); the router is whole and what the experts held elsewhere would
add is left out (``ops.moe.held_expert_ffn``). The selection bias is a
buffer of the training state (``init_buffers``; zeros at the start)
that the compiled step moves after the optimizer by each expert's load
(``update_buffers``, ``ops.moe.selection_bias_update``,
``router_bias_rate``).

The leading ``first_k_dense`` layers (all KDA: fewer than a group) are
one scanned stack under ``dense_layers/``. The expert layers are
scanned by the group (``models/common.py``, "the period-stacked
decoder"), and a group's layers are stacked by RUN of one kind of
mixer: ``layers/<r>/`` holds run ``r`` of every group, ``[groups, the
run's layers, ...]`` (a group of six from the first expert layer on is
a run of four KDA layers, the MLA layer, a KDA layer), and a run is a
scan of its own inside the group's body, so the program holds one
layer body a run and not one a layer. Each layer is its own checkpoint
under ``remat_policy``; a KDA layer's keeps
the rule's output (``ops.kda.KEPT_NAMES``: its head groups are
checkpoints of their own, so the layer's replay then leaves the rule's
forward out). The expert layers are
a whole number of groups: the published 2 + 40 is not, and a tail
outside the scan is not written.

The loss function's aux carries ``mla_moe``'s expert counters
(``MOE_ROWS_*``, ``MOE_GROUP_REACH``, ``MOE_GROUP_TOKENS``, and after
the step's update ``ROUTER_BIAS_ABS``), ``ATTN_KEPT_BYTES`` and
``KDA_LOG_DECAY_MEAN``: the mean of ``g`` over tokens, heads, channels
and KDA layers.
"""

from __future__ import annotations

import itertools
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
# the expert layer, its SwiGLU and the rotation are that module's as
# they are; ``_moe`` reads this config's fields of the same names
from dlrover_tpu.models.mla_moe import (
    ROUTER_LOAD,
    _bias_buffer,
    _moe,
    _rotate,
    _swiglu,
    _swiglu_init,
)
from dlrover_tpu.ops import flash_attention, moe, sparse_attention
from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.kda import KEPT_NAMES as KDA_KEPT_NAMES
from dlrover_tpu.ops.kda import kda_auto
from dlrover_tpu.ops.remat import apply_remat, remat_enabled
from dlrover_tpu.parallel.accelerate import StepBuffers
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter

KDA, MLA = "kda", "mla"


@dataclass(frozen=True)
class KdaMlaMoeConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144  # of a leading dense layer's FFN
    moe_intermediate_size: int = 768  # of one expert, shared or routed
    num_layers: int = 42
    first_k_dense: int = 2
    layer_group_size: int = 6  # the last layer of a group is MLA
    num_heads: int = 32  # of both kinds of mixer
    # KDA: keys and values a head, the convolution's taps, the gate's
    # bound, the step at initialisation (log-uniform, Mamba's)
    head_dim: int = 128
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    # MLA
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    # the expert FFN (``mla_moe._moe`` reads these by name)
    n_routed_experts: int = 512  # the router's width
    experts_held: Tuple[int, ...] = ()  # () = all of them
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    balance_loss_weight: float = 1e-4
    router_bias_rate: float = 1e-3
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 8192
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "full"
    # the Pallas kernels (Mosaic on a TPU, the interpreter elsewhere);
    # False takes XLA's dense attention, the rule's chain as a scan and
    # the einsum experts
    use_kernels: bool = True
    # None = interpret off the TPU; False forces Mosaic (a deviceless
    # compile traced on a CPU host)
    kernel_interpret: Any = None
    flash_block_q: int = 512
    flash_block_k: int = 1024
    expert_row_factor: float = 4.0
    expert_block_t: int = 128

    @property
    def held(self) -> Tuple[int, ...]:
        return tuple(self.experts_held) or tuple(
            range(self.n_routed_experts))

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


def kda_mla_moe_tiny(**overrides) -> KdaMlaMoeConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_layers=5, first_k_dense=1,
                layer_group_size=4, num_heads=4, head_dim=16,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, n_routed_experts=32, num_experts_per_tok=4,
                n_group=4, topk_group=2, max_seq_len=64, expert_block_t=8,
                use_kernels=False)
    base.update(overrides)
    return KdaMlaMoeConfig(**base)


def mixer_kinds(config: KdaMlaMoeConfig, layers: int) -> List[str]:
    """The mixer of each of the first ``layers`` layers."""
    return [MLA if (i + 1) % config.layer_group_size == 0 else KDA
            for i in range(layers)]


def layer_plan(config: KdaMlaMoeConfig) -> List[Tuple[str, int]]:
    """One group of the expert layers as runs of one mixer, ``(the
    mixer, how many layers in a row)`` (``common.period_of`` the rule's
    list from ``first_k_dense`` on). Refuses a leading MLA layer and
    expert layers that are no whole number of groups."""
    c = config
    if not 0 <= c.first_k_dense < min(c.num_layers, c.layer_group_size):
        raise ValueError(
            f"{c.first_k_dense} leading dense layers of {c.num_layers} in "
            f"groups of {c.layer_group_size}: all KDA, and at least one "
            "expert layer follows")
    if c.router_bias_rate <= 0:
        raise ValueError("the router's selection bias is a buffer that "
                         "the step moves: router_bias_rate is positive")
    # two groups more than the depth, so that the list shows its period
    kinds = mixer_kinds(c, c.num_layers + 2 * c.layer_group_size)[
        c.first_k_dense:]
    group = kinds[:common.period_of(kinds, c.moe_layers,
                                    "the mixers by layer_group_size")]
    return [(kind, len(list(run))) for kind, run in itertools.groupby(group)]


def layer_slot(config: KdaMlaMoeConfig, index: int):
    """Where expert layer ``index`` (counted from the first expert
    layer) lies in the tree: (its run's key under ``layers/``, its
    group, its place in the run)."""
    plan = layer_plan(config)
    group, place = divmod(index, sum(count for _, count in plan))
    for run, (_, count) in enumerate(plan):
        if place < count:
            return str(run), group, place
        place -= count


def layer_kinds(config: KdaMlaMoeConfig) -> Dict[str, int]:
    """Layers by mixer and by FFN, for whoever reads a trace without
    the config."""
    kinds = mixer_kinds(config, config.num_layers)
    return {DeviceScope.KDA: kinds.count(KDA),
            DeviceScope.MLA: kinds.count(MLA),
            "dense": config.first_k_dense, "moe": config.moe_layers}


# -- init -------------------------------------------------------------------


def _kda_init(key, lead, c: KdaMlaMoeConfig):
    d, h, dt = c.hidden_size, c.num_heads, c.param_dtype
    wide = h * c.head_dim
    k = jax.random.split(key, 12)

    def proj(key, *shape):
        return {"kernel": dense_init(key, lead + shape, dt)}

    def conv(key):
        return {"kernel": dense_init(key, lead + (c.conv_kernel, wide), dt,
                                     scale=1.0 / math.sqrt(c.conv_kernel))}

    # the public layer's: a rate uniform in (1, 16) a head, a step
    # log-uniform in [dt_min, dt_max] a channel through the inverse of
    # the softplus
    step = jnp.exp(jax.random.uniform(k[10], lead + (wide,), jnp.float32)
                   * (math.log(c.dt_max) - math.log(c.dt_min))
                   + math.log(c.dt_min))
    rate = jax.random.uniform(k[11], lead + (h,), jnp.float32,
                              minval=1.0, maxval=16.0)
    return {
        "q_proj": proj(k[0], d, wide), "k_proj": proj(k[1], d, wide),
        "v_proj": proj(k[2], d, wide), "f_proj": proj(k[3], d, wide),
        "g_proj": proj(k[4], d, wide), "b_proj": proj(k[5], d, h),
        "o_proj": proj(k[6], wide, d),
        "q_conv": conv(k[7]), "k_conv": conv(k[8]), "v_conv": conv(k[9]),
        "a_log": jnp.log(rate).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "o_norm": common.norm_init(lead, c.head_dim, dt),
    }


def _mla_init(key, lead, c: KdaMlaMoeConfig):
    d, h, dt = c.hidden_size, c.num_heads, c.param_dtype
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    k = jax.random.split(key, 5)

    def proj(key, fan_in, fan_out):
        return {"kernel": dense_init(key, lead + (fan_in, fan_out), dt)}

    return {
        "q_proj": proj(k[0], d, h * (dn + dr)),
        "kv_a_proj": proj(k[1], d, c.kv_lora_rank + dr),
        "kv_a_norm": common.norm_init(lead, c.kv_lora_rank, dt),
        "kv_b_proj": proj(k[2], c.kv_lora_rank, h * (dn + dv)),
        "g_proj": proj(k[3], d, h),
        "o_proj": proj(k[4], h * dv, d),
        "q_norm": common.norm_init(lead, dn + dr, dt),
        "k_norm": common.norm_init(lead, dn, dt),
        "k_rope_norm": common.norm_init(lead, dr, dt),
    }


def _layers_init(key, lead, c: KdaMlaMoeConfig, mixer: str, dense: bool):
    """The layers at one position, stacked over ``lead``."""
    d, dt = c.hidden_size, c.param_dtype
    k = jax.random.split(key, 5)
    out = {"input_norm": common.norm_init(lead, d, dt),
           "mixer": (_kda_init if mixer == KDA else _mla_init)(k[0], lead, c),
           "post_norm": common.norm_init(lead, d, dt)}
    if dense:
        out["mlp"] = _swiglu_init(k[1], lead, d, c.intermediate_size, dt)
        return out
    f = c.moe_intermediate_size
    experts = _swiglu_init(k[4], lead + (len(c.held),), d, f, dt)
    out["moe"] = {
        "router": {"kernel": dense_init(
            k[2], lead + (d, c.n_routed_experts), dt)},
        "shared": _swiglu_init(k[3], lead, d, f * c.n_shared_experts, dt),
        "experts": {"gate": experts["gate_proj"], "up": experts["up_proj"],
                    "down": experts["down_proj"]},
    }
    return out


def init(rng: jax.Array, config: KdaMlaMoeConfig) -> Dict:
    c = config
    plan = layer_plan(c)  # refuses a depth the plan cannot have
    if sorted(set(c.held)) != list(c.held) or not (
            0 <= c.held[0] and c.held[-1] < c.n_routed_experts):
        raise ValueError(f"experts_held {c.held}: distinct indices in "
                         f"order, below {c.n_routed_experts}")
    k = jax.random.split(rng, 4)
    groups = c.moe_layers // sum(count for _, count in plan)
    out = {
        # a table of std 1, as the latent expert decoders': a random
        # router then spreads its rows (``models/mla_moe.py``'s note)
        "embed_tokens": {"embedding": jax.random.normal(
            k[0], (c.vocab_size, c.hidden_size), c.param_dtype)},
        "layers": common.stacked_init(
            k[2], plan, lambda key, run: _layers_init(
                key, (groups, run[1]), c, run[0], False)),
        "norm": common.norm_init((), c.hidden_size, c.param_dtype),
        "lm_head": {"kernel": dense_init(
            k[3], (c.hidden_size, c.vocab_size), c.param_dtype)},
    }
    if c.first_k_dense:
        out["dense_layers"] = _layers_init(k[1], (c.first_k_dense,), c, KDA,
                                           True)
    return out


# -- forward ----------------------------------------------------------------


def rotary_tables(seq: int, c: KdaMlaMoeConfig):
    d = c.qk_rope_head_dim
    inv_freq = 1.0 / c.rope_theta ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)  # [S, d/2]


def kda_mixer(u, p, c: KdaMlaMoeConfig):
    """The KDA mixer of the normed ``u`` [B, S, D]: (output, the mean of
    the log decay ``g``)."""
    f32 = jnp.float32
    b, s, _ = u.shape
    h, hd = c.num_heads, c.head_dim

    # what is elementwise around the rule is a checkpoint of its own
    # inside the layer's: the layer's backward then holds a projection's
    # output once, not once a stage (convolved, activated, normalised)
    @partial(jax.checkpoint, static_argnums=(2,))
    def mixed(y, taps, length):
        y = jax.nn.silu(common.causal_conv(y, taps, 0.0)).reshape(
            b, s, h, hd)
        if length is None:
            return y
        yf = y.astype(f32)  # a head's vector at that length
        return (length * yf * lax.rsqrt(
            jnp.sum(yf * yf, axis=-1, keepdims=True) + c.rms_norm_eps)
                ).astype(y.dtype)

    def stream(name, length):
        return mixed(u @ p[f"{name}_proj"]["kernel"],
                     p[f"{name}_conv"]["kernel"], length)

    q = stream("q", 1.0 / math.sqrt(hd))
    k = stream("k", 1.0)
    v = stream("v", None)
    # what feeds the recurrence's gates leaves the matmuls in float32
    beta = jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", u, p["b_proj"]["kernel"],
        preferred_element_type=f32))
    raw = jnp.einsum("bsd,dw->bsw", u, p["f_proj"]["kernel"],
                     preferred_element_type=f32) + p["dt_bias"].astype(f32)
    g = c.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(p["a_log"].astype(f32))[:, None]
        * raw.reshape(b, s, h, hd))
    o = kda_auto(q, k, v, g, beta, use_kernels=c.use_kernels,
                 interpret=c.kernel_interpret)
    gate = jax.nn.sigmoid(u @ p["g_proj"]["kernel"]).reshape(b, s, h, hd)
    o = (rms_norm(o, p["o_norm"]["scale"], c.rms_norm_eps)
         * gate).reshape(b, s, h * hd)
    return o @ p["o_proj"]["kernel"], jnp.mean(g)


def mla_mixer(u, p, c: KdaMlaMoeConfig, rotary):
    """Latent attention of the normed ``u`` [B, S, D] without a query
    latent, a norm a head on queries and keys, a gate a head."""
    b, s, _ = u.shape
    h, dn, dr, dv = (c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                     c.v_head_dim)
    eps = c.rms_norm_eps
    # the queries are ONE matmul, normed over a head's [nope | rope]
    # columns and split afterwards
    q = rms_norm((u @ p["q_proj"]["kernel"]).reshape(b, s, h, dn + dr),
                 p["q_norm"]["scale"], eps).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :dn], _rotate(q[..., dn:], *rotary)
    ckv = u @ p["kv_a_proj"]["kernel"]
    c_kv = rms_norm(ckv[..., :c.kv_lora_rank], p["kv_a_norm"]["scale"], eps)
    # a head's [nope | value] columns are projected apart (the weights
    # are sliced, never the activations)
    w_kv = p["kv_b_proj"]["kernel"].reshape(c.kv_lora_rank, h, dn + dv)
    k_nope = rms_norm(jnp.einsum("bsr,rhd->bhsd", c_kv, w_kv[..., :dn]),
                      p["k_norm"]["scale"], eps)
    v = jnp.einsum("bsr,rhd->bhsd", c_kv, w_kv[..., dn:])
    k_rope = _rotate(rms_norm(ckv[:, None, :, c.kv_lora_rank:],
                              p["k_rope_norm"]["scale"], eps), *rotary)
    if c.use_kernels:
        out = flash_attention.flash_attention_mla_auto(
            q_nope, q_rope, k_nope, k_rope, v, c.softmax_scale,
            c.flash_block_q, c.flash_block_k, c.kernel_interpret)
    else:
        out = mha_reference(
            jnp.concatenate([q_nope, q_rope], axis=-1),
            jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope, (b, h, s, dr))], axis=-1),
            v, causal=True, scale=c.softmax_scale)
    with jax.named_scope(DeviceScope.ATTN_GATE):
        gate = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", u, p["g_proj"]["kernel"],
            preferred_element_type=jnp.float32)).astype(out.dtype)
        out = out.transpose(0, 2, 1, 3) * gate[..., None]
    return out.reshape(b, s, h * dv) @ p["o_proj"]["kernel"]


def _layer(c: KdaMlaMoeConfig, mixer: str, dense: bool, rotary):
    """``layer(x, xs) -> (x, out)`` of one mixer and FFN for the scans;
    ``xs`` is ``{"p": the parameters, "bias": the router's selection
    bias or nothing}``. ``out``: the mixer's mean log decay (0 of an
    MLA layer) and, of an expert layer, the balance loss, the held
    experts' counters and its load."""

    def layer(x, xs):
        p = cast_floats(xs["p"], c.compute_dtype)
        eps = c.rms_norm_eps
        u = rms_norm(x, p["input_norm"]["scale"], eps)
        if mixer == KDA:
            with jax.named_scope(DeviceScope.KDA):
                y, decay = kda_mixer(u, p["mixer"], c)
        else:
            with jax.named_scope(DeviceScope.MLA):
                y, decay = mla_mixer(u, p["mixer"], c, rotary), 0.0
        x = x + y
        u = rms_norm(x, p["post_norm"]["scale"], eps)
        out = {"decay": jnp.float32(decay)}
        if dense:
            with jax.named_scope(DeviceScope.FFN):
                return x + _swiglu(u, p["mlp"]), out
        y, balance, stats = _moe(u, p["moe"], c, None, xs.get("bias"))
        out.update(balance=balance, load=stats.pop("load"), stats=stats)
        return x + y, out

    return layer


def _run(layer, which: int, plan, width: int):
    """A run's layers as one scan of ``layer`` for the group's body:
    what they returned added up, but their loads, a row a layer under
    the run's own key (the other runs' are zeros, so that a group's
    runs add up leaf by leaf)."""

    def run(x, xs):
        x, out = lax.scan(layer, x, xs)
        load = out.pop("load")
        out = jax.tree.map(lambda a: a.sum(axis=0), out)
        return x, dict(out, load={
            str(r): load if r == which else jnp.zeros((count, width),
                                                      jnp.float32)
            for r, (_, count) in enumerate(plan)})

    return run


def _trunk(params: Dict, input_ids: jax.Array, c: KdaMlaMoeConfig,
           buffers=None):
    """The layers: (the residual before the final norm [B, S, D]; what
    the layers returned, the dense stack's and the groups' added up,
    ``load`` a run ``[groups, the run's layers, experts]``)."""
    plan = layer_plan(c)
    x = params["embed_tokens"]["embedding"][input_ids].astype(
        c.compute_dtype)
    rotary = rotary_tables(input_ids.shape[1], c)
    decay = jnp.float32(0.0)
    if c.first_k_dense:
        layer = apply_remat(_layer(c, KDA, True, rotary), c.remat_policy,
                            keep=KDA_KEPT_NAMES)
        x, out = lax.scan(layer, x, {"p": params["dense_layers"]})
        decay = out["decay"].sum()
    if buffers is None:
        buffers = init_buffers(c)
    runs = [_run(apply_remat(
        _layer(c, kind, False, rotary), c.remat_policy,
        keep=flash_attention.KEPT_NAMES if kind == MLA else KDA_KEPT_NAMES),
        r, plan, c.n_routed_experts) for r, (kind, _) in enumerate(plan)]
    x, out = common.scan_periods(runs, x, {
        r: {"p": p, "bias": buffers["layers"][r]["moe"]["router"]["bias"]}
        for r, p in params["layers"].items()})
    load = out.pop("load")
    out = jax.tree.map(lambda a: a.sum(axis=0), out)
    return x, dict(out, load=load, decay=out["decay"] + decay)


def apply_hidden(params: Dict, input_ids: jax.Array,
                 config: KdaMlaMoeConfig, buffers=None):
    """(final hidden states [B, S, D] in the compute dtype, what the
    layers returned: ``_trunk``'s)."""
    c = config
    x, out = _trunk(params, input_ids, c, buffers)
    return rms_norm(x, params["norm"]["scale"].astype(c.compute_dtype),
                    c.rms_norm_eps), out


def apply(params: Dict, input_ids: jax.Array,
          config: KdaMlaMoeConfig) -> jax.Array:
    """Logits [B, S, V] in float32."""
    x, _ = apply_hidden(params, input_ids, config)
    return (x @ params["lm_head"]["kernel"].astype(
        config.compute_dtype)).astype(jnp.float32)


# -- training glue ----------------------------------------------------------


def make_init_fn(config: KdaMlaMoeConfig):
    return common.make_init_fn(init, config, layer_kinds(config))


def init_buffers(config: KdaMlaMoeConfig) -> Dict:
    """The router's selection bias of every expert layer, float32 zeros,
    under the paths its parameter would have
    (``layers/<r>/moe/router/bias``, ``[groups, the run's layers,
    experts]``: the sharding rule for ``router/bias`` matches them under
    ``buffers/``)."""
    c = config
    plan = layer_plan(c)
    groups = c.moe_layers // sum(count for _, count in plan)
    return {"layers": {str(r): _bias_buffer(jnp.zeros(
        (groups, count, c.n_routed_experts), jnp.float32))
        for r, (_, count) in enumerate(plan)}}


def update_buffers(buffers: Dict, aux: Dict, config: KdaMlaMoeConfig):
    """``StepBuffers.update``: every expert layer's bias moved by its
    own load of this step (``ops.moe.selection_bias_update``), and the
    aux with the loads taken out and ``router_bias_abs`` put in."""
    aux = dict(aux)
    load = aux.pop(ROUTER_LOAD)  # a run [groups, its layers, experts]
    with jax.named_scope(DeviceScope.ROUTER_BIAS):
        new = {"layers": {r: _bias_buffer(moe.selection_bias_update(
            stack["moe"]["router"]["bias"], load[r],
            config.router_bias_rate))
            for r, stack in buffers["layers"].items()}}
        leaves = jax.tree.leaves(new)
        aux[StepCounter.ROUTER_BIAS_ABS] = (
            sum(jnp.sum(jnp.abs(b)) for b in leaves)
            / sum(b.size for b in leaves))
    return new, aux


def make_loss_fn(config: KdaMlaMoeConfig, head_chunk: int = 0):
    """Causal-LM loss over batches {"input_ids", "labels"} plus the
    balance loss; the aux counts the held experts' rows, the tokens the
    group limit lets reach them and the mean log decay of the KDA
    layers. With ``head_chunk`` the head is fused with the cross entropy
    over sequence chunks (``losses.lm_head_loss``)."""
    c = config
    kinds = layer_kinds(c)

    def loss_fn(params, batch, rng, buffers=None):
        del rng  # no dropout, no router noise
        hidden, out = apply_hidden(params, batch["input_ids"], c, buffers)
        loss = lm_head_loss(hidden, params["lm_head"]["kernel"],
                            batch["labels"], head_chunk)
        loss = loss + c.balance_loss_weight * out["balance"]
        stats = out["stats"]
        # the latent kernels' forward rules alone name what is kept, and
        # with no remat there is no checkpoint to keep it
        rows, seq = batch["input_ids"].shape
        kept = (c.use_kernels and remat_enabled(c.remat_policy)) * kinds[
            DeviceScope.MLA]
        return loss, {
            StepCounter.MOE_ROWS_HELD: stats["rows_held"],
            StepCounter.MOE_ROWS_MAX: stats["rows_max"],
            StepCounter.MOE_ROWS_DROPPED: stats["rows_dropped"],
            StepCounter.MOE_ROWS_BUFFERED: stats["rows_buffered"],
            StepCounter.MOE_GROUP_REACH: stats["group_reach"],
            StepCounter.MOE_GROUP_TOKENS: stats["group_tokens"],
            StepCounter.ATTN_KEPT_BYTES: jnp.float32(
                kept * sparse_attention.kept_bytes(
                    rows, c.num_heads, seq, c.v_head_dim, c.compute_dtype)),
            StepCounter.KDA_LOG_DECAY_MEAN: out["decay"] / max(
                kinds[DeviceScope.KDA], 1),
            ROUTER_LOAD: out["load"],
        }

    # ``accelerate`` keeps the bias in ``TrainState.buffers``, hands it
    # to ``loss_fn`` and moves it after the optimizer
    loss_fn.step_buffers = StepBuffers(
        init=lambda params: init_buffers(c),
        update=partial(update_buffers, config=c))
    return loss_fn


def param_count(config: KdaMlaMoeConfig) -> int:
    return common.param_count(make_init_fn(config))
