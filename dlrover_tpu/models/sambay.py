"""SambaY decoder-hybrid-decoder (Phi-4-mini-flash-reasoning): state-space
layers, window and full differential attention, and one layer's keys,
values and scan output shared by the layers above it.

Every layer is ``x = x + Mix(LN(x)); x = x + MLP(LN(x))`` with a
LayerNorm (scale and bias), a gated MLP (``W2 (a * silu(g))``, ``[g, a]
= W1 u``), a tied embedding table, and no positional encoding. ``Mix``
by 0-based layer index ``i`` of ``L`` (``layer_plan``):

* ``i < L/2``, the self-decoder: even ``i`` a Mamba-1 layer (``ssm``),
  odd ``i`` differential attention over a sliding window
  (``attention_window``);
* ``i = L/2``: a Mamba layer whose scan output, before its gate, is the
  memory ``m``; ``i = L/2 + 1``: full causal differential attention
  (``attention_full``) whose projected keys and values are the shared
  KV;
* ``i >= L/2 + 2``, the cross-decoder: even ``i`` a gated memory unit
  (``gmu``: ``W_out (m * silu(W_g x))``, no scan), odd ``i`` cross
  attention (``attention_cross``): a query and an output projection
  only, attending causally to the shared KV.

So the model is three groups, not one block repeated: the self-decoder
periods (Mamba + window attention) stacked under ``self_layers/`` and
scanned, the boundary pair under ``boundary/``, and the cross-decoder
periods (gated memory + cross attention) stacked under
``cross_layers/`` and scanned with ``m`` and the shared KV as loop
constants, whose gradients the scan's transpose sums over the periods.
Each period and the pair are rematerialised per ``remat_policy``.

Differential attention (all three kinds): 40 query and 20 key heads of
64 pair up, adjacent heads a pair; values are 10 heads of 128. Query
pair ``p`` reads key pair and value head ``p // 2``::

    a_c = softmax(q_c k_c^T / 8 + mask) v          c = 1, 2
    out = RMSNorm_128(a_1 - lam * a_2) * (1 - lam0)
    lam = exp(<lq1, lk1>) - exp(<lq2, lk2>) + lam0
    lam0 = 0.8 - 0.6 exp(-0.3 i)

On a TPU the scan is ``ops.selective_scan`` and the attention two flash
calls a layer with a value width of 128 (``q_c`` against ``k_c`` and the
whole ``v``: the kernels take a value width of their own), windowed
calls with a band-limited grid. ``use_kernels=False`` takes the XLA
references instead (a CPU rehearsal).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models import common
from dlrover_tpu.models.common import (
    cast_floats,
    dense_init,
    layer_norm,
    rms_norm,
)
from dlrover_tpu.models.losses import lm_head_loss
from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.flash_attention import (
    band_tile_counters,
    flash_attention_auto,
)
from dlrover_tpu.ops.remat import apply_remat
from dlrover_tpu.ops.selective_scan import (
    selective_scan_auto,
    selective_scan_reference,
)
from dlrover_tpu.telemetry.names import DeviceScope

KINDS = ("ssm", "attention_window", "attention_full", "gmu",
         "attention_cross")


@dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40  # query heads, paired
    num_kv_heads: int = 20  # key heads, paired; half as many value heads
    head_dim: int = 64  # of a query or key head; a value head is twice it
    sliding_window: int = 512
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    layer_norm_eps: float = 1e-5
    lambda_std: float = 0.1
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    max_seq_len: int = 8192
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "full"
    # the Pallas kernels (Mosaic on a TPU, the interpreter elsewhere);
    # False takes the XLA references
    use_kernels: bool = True
    # None = interpret off the TPU; False forces Mosaic (a deviceless
    # compile traced on a CPU host), as ``LlamaConfig.flash_interpret``
    kernel_interpret: Any = None
    flash_block_q: int = 512
    flash_block_k: int = 1024
    # the most a side of a window layer's tiles may be; the kernels pick
    # the tiles from the row and the window (``flash_attention.
    # window_tiles``)
    window_block: int = 1024
    # tokens and channels a grid step of the scan holds: measured on
    # the v5e (PR 29), 13.5 ms a layer forward and backward against
    # 25.7 at 128 x 640; larger blocks gain nothing more
    scan_chunk: int = 32
    scan_block_c: int = 2560

    @property
    def value_dim(self) -> int:
        return 2 * self.head_dim

    @property
    def self_periods(self) -> int:
        return self.num_layers // 4

    @property
    def cross_periods(self) -> int:
        return self.num_layers // 4 - 1


def sambay_tiny(**overrides) -> SambaYConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                num_layers=8, num_heads=4, num_kv_heads=2, head_dim=16,
                sliding_window=8, d_inner=128, d_state=16, dt_rank=4,
                max_seq_len=32, scan_chunk=8, use_kernels=False)
    base.update(overrides)
    return SambaYConfig(**base)


def layer_plan(num_layers: int) -> List[str]:
    """The kind of every layer, by index."""
    if num_layers < 8 or num_layers % 4:
        raise ValueError(
            f"{num_layers} layers: the plan needs a multiple of 4, at "
            "least 8 (self-decoder periods, the boundary pair, "
            "cross-decoder periods)")
    half = num_layers // 2
    plan = []
    for i in range(num_layers):
        if i % 2 == 0:
            plan.append("ssm" if i <= half else "gmu")
        elif i < half:
            plan.append("attention_window")
        else:
            plan.append("attention_full" if i == half + 1
                        else "attention_cross")
    return plan


def layer_kinds(config: SambaYConfig) -> Dict[str, int]:
    """Layers by kind, for whoever reads a trace without the config."""
    plan = layer_plan(config.num_layers)
    return {kind: plan.count(kind) for kind in KINDS}


def lambda_init(index):
    """``lam0`` of the attention layer at 0-based ``index``."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


# -- init -------------------------------------------------------------------


def _mlp_init(key, lead, c: SambaYConfig):
    d, f, dt = c.hidden_size, c.intermediate_size, c.param_dtype
    k1, k2 = jax.random.split(key)
    return {
        "norm": common.norm_init(lead, d, dt, bias=True),
        # [g, a] = W1 u: the two halves on an axis of their own, so a
        # split of the wide axis never cuts across them
        "up_proj": {"kernel": dense_init(
            k1, lead + (d, 2, f), dt, scale=1.0 / math.sqrt(d))},
        "down_proj": {"kernel": dense_init(k2, lead + (f, d), dt)},
    }


def _ssm_init(key, lead, c: SambaYConfig):
    d, di, n, r = c.hidden_size, c.d_inner, c.d_state, c.dt_rank
    dt = c.param_dtype
    k = jax.random.split(key, 6)
    # as published for Mamba-1: a step in [dt_min, dt_max], log-uniform,
    # through the inverse of the softplus; A = -(1..N); D = 1
    step = jnp.exp(jax.random.uniform(k[4], lead + (di,), jnp.float32)
                   * (math.log(c.dt_max) - math.log(c.dt_min))
                   + math.log(c.dt_min))
    return {
        "norm": common.norm_init(lead, d, dt, bias=True),
        "in_proj": {"kernel": dense_init(
            k[0], lead + (d, 2, di), dt, scale=1.0 / math.sqrt(d))},
        "conv": {"kernel": dense_init(
            k[1], lead + (c.d_conv, di), dt,
            scale=1.0 / math.sqrt(c.d_conv)),
            "bias": jnp.zeros(lead + (di,), dt)},
        "x_proj": {"kernel": dense_init(k[2], lead + (di, r + 2 * n), dt)},
        "dt_proj": {"kernel": dense_init(k[3], lead + (r, di), dt),
                    "bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt)},
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
            lead + (di, n)).astype(dt),
        "d_skip": jnp.ones(lead + (di,), dt),
        "out_proj": {"kernel": dense_init(k[5], lead + (di, d), dt)},
    }


def _attn_init(key, lead, c: SambaYConfig, cross: bool):
    d, hd, dt = c.hidden_size, c.head_dim, c.param_dtype
    q_out = c.num_heads * hd
    kv_out = c.num_kv_heads * hd
    k = jax.random.split(key, 8)

    def proj(key, fan_in, fan_out):
        return {"kernel": dense_init(key, lead + (fan_in, fan_out), dt),
                "bias": jnp.zeros(lead + (fan_out,), dt)}

    def vec(key):
        return (jax.random.normal(key, lead + (hd,), jnp.float32)
                * c.lambda_std).astype(dt)

    out = {
        "norm": common.norm_init(lead, d, dt, bias=True),
        "q_proj": proj(k[0], d, q_out),
        "o_proj": proj(k[3], c.num_heads // 2 * c.value_dim, d),
        "lambda_q1": vec(k[4]), "lambda_k1": vec(k[5]),
        "lambda_q2": vec(k[6]), "lambda_k2": vec(k[7]),
        "subln": common.norm_init(lead, c.value_dim, dt),
    }
    if not cross:
        out["k_proj"] = proj(k[1], d, kv_out)
        out["v_proj"] = proj(k[2], d, kv_out)
    return out


def _gmu_init(key, lead, c: SambaYConfig):
    d, di, dt = c.hidden_size, c.d_inner, c.param_dtype
    k1, k2 = jax.random.split(key)
    return {"norm": common.norm_init(lead, d, dt, bias=True),
            "gate_proj": {"kernel": dense_init(k1, lead + (d, di), dt)},
            "out_proj": {"kernel": dense_init(k2, lead + (di, d), dt)}}


def init(rng: jax.Array, config: SambaYConfig) -> Dict:
    c = config
    layer_plan(c.num_layers)  # refuses a depth the plan cannot have
    if c.num_heads != 2 * c.num_kv_heads or c.num_kv_heads % 2:
        raise ValueError("differential attention pairs the heads: twice "
                         "as many query as key heads, both even")
    keys = iter(jax.random.split(rng, 16))

    def period(lead, cross):
        first = (_gmu_init if cross else _ssm_init)(next(keys), lead, c)
        return {
            ("gmu" if cross else "ssm"): first,
            "mix_mlp": _mlp_init(next(keys), lead, c),
            "attn": _attn_init(next(keys), lead, c, cross),
            "attn_mlp": _mlp_init(next(keys), lead, c),
        }

    return {
        "embed_tokens": {"embedding": jax.random.normal(
            next(keys), (c.vocab_size, c.hidden_size), c.param_dtype)
            * 0.02},
        "self_layers": period((c.self_periods,), cross=False),
        "boundary": period((), cross=False),
        "cross_layers": period((c.cross_periods,), cross=True),
        "norm": common.norm_init((), c.hidden_size, c.param_dtype, bias=True),
    }


# -- forward ----------------------------------------------------------------


def _ln(x, p, c):
    return layer_norm(x, p["scale"], p["bias"], c.layer_norm_eps)


@jax.named_scope(DeviceScope.FFN)
def _mlp(x, p, c: SambaYConfig):
    ga = jnp.einsum("bsd,dkf->bskf", _ln(x, p["norm"], c),
                    p["up_proj"]["kernel"])
    return (ga[:, :, 1] * jax.nn.silu(ga[:, :, 0])) @ p["down_proj"][
        "kernel"]


@jax.named_scope(DeviceScope.SSM)
def _ssm(x, p, c: SambaYConfig):
    """The Mamba-1 mixer on the normed ``x``; returns (output, the scan
    output before its gate: the memory, where the layer is the
    boundary's)."""
    f32 = jnp.float32
    n, r = c.d_state, c.dt_rank
    uz = jnp.einsum("bsd,dkc->bskc", x, p["in_proj"]["kernel"])
    u = jax.nn.silu(common.causal_conv(
        uz[:, :, 0], p["conv"]["kernel"], p["conv"]["bias"]))
    # what feeds the recurrence leaves the matmuls in float32
    rbc = jnp.einsum("bsc,ck->bsk", u, p["x_proj"]["kernel"],
                     preferred_element_type=f32)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", rbc[..., :r].astype(x.dtype),
                   p["dt_proj"]["kernel"], preferred_element_type=f32)
        + p["dt_proj"]["bias"].astype(f32))
    args = (u, dt, -jnp.exp(p["a_log"].astype(f32)), rbc[..., r:r + n],
            rbc[..., r + n:], p["d_skip"])
    if c.use_kernels:
        y = selective_scan_auto(*args, chunk=c.scan_chunk,
                                block_c=c.scan_block_c,
                                interpret=c.kernel_interpret)
    else:
        y = selective_scan_reference(*args)
    y = y.astype(x.dtype)
    return (y * jax.nn.silu(uz[:, :, 1])) @ p["out_proj"]["kernel"], y


@jax.named_scope(DeviceScope.GMU)
def _gmu(x, memory, p):
    gate = jax.nn.silu(x @ p["gate_proj"]["kernel"])
    return (memory * gate) @ p["out_proj"]["kernel"]


def _project_kv(x, p, c: SambaYConfig):
    """(k1, k2) [B, pairs, S, head_dim] each and v [B, pairs, S, 2 *
    head_dim] of the normed ``x``."""
    b, s, _ = x.shape
    pairs = c.num_kv_heads // 2
    k = (x @ p["k_proj"]["kernel"] + p["k_proj"]["bias"]).reshape(
        b, s, pairs, 2, c.head_dim).transpose(3, 0, 2, 1, 4)
    v = (x @ p["v_proj"]["kernel"] + p["v_proj"]["bias"]).reshape(
        b, s, pairs, c.value_dim).transpose(0, 2, 1, 3)
    return k[0], k[1], v


def _softmax_attention(q, k, v, c: SambaYConfig, window):
    if c.use_kernels:
        return flash_attention_auto(
            q, k, v, causal=True,
            block_q=c.window_block if window else c.flash_block_q,
            block_k=c.flash_block_k, interpret=c.kernel_interpret,
            window=window)
    bias = None
    if window:
        t = jnp.arange(q.shape[2])
        bias = jnp.where(t[:, None] - t[None, :] < window, 0.0,
                         jnp.finfo(jnp.float32).min)
    return mha_reference(q, k, v, causal=True, bias=bias)


def _diff_attention(x, p, c: SambaYConfig, lam0, kv, window=None):
    """Differential attention of the normed ``x`` against ``kv`` (its
    own layer's, or the shared one)."""
    b, s, _ = x.shape
    f32 = jnp.float32
    pairs = c.num_heads // 2
    q = (x @ p["q_proj"]["kernel"] + p["q_proj"]["bias"]).reshape(
        b, s, pairs, 2, c.head_dim).transpose(3, 0, 2, 1, 4)
    k1, k2, v = kv
    a1 = _softmax_attention(q[0], k1, v, c, window)  # [B, pairs, S, 2 hd]
    a2 = _softmax_attention(q[1], k2, v, c, window)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"].astype(f32)
                           * p["lambda_k1"].astype(f32)))
           - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32)
                             * p["lambda_k2"].astype(f32))) + lam0)
    diff = a1.astype(f32) - lam * a2.astype(f32)
    out = (rms_norm(diff, p["subln"]["scale"], c.layer_norm_eps)
           * (1.0 - lam0)).astype(x.dtype)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, pairs * c.value_dim)
    return out @ p["o_proj"]["kernel"] + p["o_proj"]["bias"]


def _cast(p, c: SambaYConfig):
    """A period's parameters in the compute dtype, but for what the
    recurrence itself reads (``A``, ``D``, the step's bias), which stay
    as they are stored."""
    out = cast_floats(p, c.compute_dtype)
    if "ssm" in p:
        ssm = p["ssm"]
        out["ssm"] = dict(
            out["ssm"], a_log=ssm["a_log"], d_skip=ssm["d_skip"],
            dt_proj=dict(out["ssm"]["dt_proj"],
                         bias=ssm["dt_proj"]["bias"]))
    return out


def _self_period(c: SambaYConfig, window):
    """Mamba, then attention over its own keys and values: a
    self-decoder period (``window``) or the boundary pair (full)."""
    scope = (DeviceScope.ATTENTION_WINDOW if window
             else DeviceScope.ATTENTION_FULL)

    def period(x, p, lam0):
        p = _cast(p, c)
        mixed, memory = _ssm(_ln(x, p["ssm"]["norm"], c), p["ssm"], c)
        x = x + mixed
        x = x + _mlp(x, p["mix_mlp"], c)
        with jax.named_scope(scope):
            normed = _ln(x, p["attn"]["norm"], c)
            kv = _project_kv(normed, p["attn"], c)
            x = x + _diff_attention(normed, p["attn"], c, lam0, kv, window)
        return x + _mlp(x, p["attn_mlp"], c), memory, kv

    return period


def _cross_period(c: SambaYConfig, memory, kv):
    def period(x, p_lam0):
        p, lam0 = p_lam0
        p = _cast(p, c)
        x = x + _gmu(_ln(x, p["gmu"]["norm"], c), memory, p["gmu"])
        x = x + _mlp(x, p["mix_mlp"], c)
        with jax.named_scope(DeviceScope.ATTENTION_CROSS):
            x = x + _diff_attention(_ln(x, p["attn"]["norm"], c),
                                    p["attn"], c, lam0, kv)
        return x + _mlp(x, p["attn_mlp"], c), None

    return period


def apply_hidden(params: Dict, input_ids: jax.Array,
                 config: SambaYConfig) -> jax.Array:
    """Final hidden states [B, S, D] in the compute dtype: everything
    but the head."""
    c = config
    half = c.num_layers // 2
    x = params["embed_tokens"]["embedding"][input_ids].astype(
        c.compute_dtype)
    lam0 = lambda idx: jnp.asarray(  # noqa: E731
        [lambda_init(i) for i in idx], jnp.float32)

    window = _self_period(c, c.sliding_window)
    x, _ = lax.scan(
        apply_remat(lambda x, p_l: (window(x, *p_l)[0], None),
                    c.remat_policy),
        x, (params["self_layers"],
            lam0(2 * p + 1 for p in range(c.self_periods))))
    x, memory, kv = apply_remat(_self_period(c, None), c.remat_policy)(
        x, params["boundary"], jnp.float32(lambda_init(half + 1)))
    x, _ = lax.scan(
        apply_remat(_cross_period(c, memory, kv), c.remat_policy),
        x, (params["cross_layers"],
            lam0(half + 3 + 2 * p for p in range(c.cross_periods))))
    return _ln(x, cast_floats(params["norm"], c.compute_dtype), c)


def apply(params: Dict, input_ids: jax.Array,
          config: SambaYConfig) -> jax.Array:
    """Logits [B, S, V] in float32 (the head is the embedding table)."""
    x = apply_hidden(params, input_ids, config)
    table = params["embed_tokens"]["embedding"].astype(config.compute_dtype)
    return (x @ table.T).astype(jnp.float32)


# -- training glue ----------------------------------------------------------


def make_init_fn(config: SambaYConfig):
    return common.make_init_fn(init, config, layer_kinds(config))


def make_loss_fn(config: SambaYConfig, head_chunk: int = 0):
    """Causal-LM loss over batches {"input_ids", "labels"}. With
    ``head_chunk`` the tied head is fused with the cross entropy over
    sequence chunks (``losses.lm_head_loss`` on the table's transpose).
    The aux counts the band's tiles that the window layers' forward
    kernels visit (``flash_attention.band_tile_counters``)."""

    def loss_fn(params, batch, rng):
        del rng  # no dropout
        loss = lm_head_loss(
            apply_hidden(params, batch["input_ids"], config),
            params["embed_tokens"]["embedding"].T, batch["labels"],
            head_chunk)
        if not config.use_kernels:  # XLA's dense attention visits no tile
            return loss, {}
        # a window layer makes two calls of half the heads each
        rows, seq = batch["input_ids"].shape
        return loss, band_tile_counters(
            rows * config.num_heads * config.self_periods, seq,
            config.sliding_window, config.window_block)

    return loss_fn


def param_count(config: SambaYConfig) -> int:
    return common.param_count(make_init_fn(config))
