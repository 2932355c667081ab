"""Llama-family decoder (the flagship model of this framework).

Role parity: the reference accelerates HF Llama through module surgery
(``atorch/modules/transformer/layers.py:1268`` LlamaAttentionFA swap-in,
Megatron TP rewrites, FSDP wrapping). Here the model is written TPU-first:

  * functional init/apply (no module tree) so every parameter path has a
    sharding rule (``parallel.sharding_rules.llama_rules``);
  * **scan over layers**: layer params are stacked [L, ...] and the block
    runs under ``lax.scan`` — one layer's XLA program compiled once,
    which keeps 7B-scale compile times sane and makes remat-per-layer
    trivial;
  * attention via the in-tree Pallas flash kernel (TPU) or the XLA
    reference (CPU tests), with an optional ring-attention path over the
    "seq" mesh axis for long context;
  * optional switch-MoE FFN (expert parallelism over the expert submesh).

Numerics follow Llama-2: RMSNorm (f32), RoPE, GQA, SwiGLU, untied head.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.models.common import (
    cast_floats,
    dense_init as _dense,
    param_count as common_param_count,
    rms_norm as _rms_norm,
    segment_positions,
)
from dlrover_tpu.models.losses import chunked_lm_head_loss, masked_lm_loss
from dlrover_tpu.ops import moe as moe_ops
from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.flash_attention import flash_attention_auto
from dlrover_tpu.ops.remat import apply_remat, remat_enabled
from dlrover_tpu.ops.ring_attention import (
    ring_attention,
    ring_attention_local,
)
from dlrover_tpu.telemetry.names import DeviceScope


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "dots_saveable"
    # the Pallas flash kernel: Mosaic on a TPU, the interpreter
    # elsewhere; never the XLA reference (that is use_flash=False)
    use_flash: bool = True
    # pallas flash kernel tiling (VMEM working-set vs grid overhead
    # trade; benchmarks/flash_bench.py times the kernel alone)
    flash_block_q: int = 512
    flash_block_k: int = 1024
    # backward-kernel tiles (0 = same as forward): the dKV/dQ passes
    # hold more live VMEM than the forward, so their optimum is often
    # smaller — a long-context tuning lever
    flash_block_q_bwd: int = 0
    flash_block_k_bwd: int = 0
    # None = auto (interpret off TPU); False forces the Mosaic kernel —
    # required when TRACING on a CPU host but COMPILING for a deviceless
    # TPU topology (parallel.aot), where the backend-sniffing default
    # would silently lower the interpreter emulation
    flash_interpret: Any = None
    # sequence parallelism: set seq_axis="seq" and pass the Mesh to run
    # ring attention (shard_map) inside the jitted GSPMD program; with
    # mesh=None the model must itself be running under shard_map.
    seq_axis: Optional[str] = None
    mesh: Any = None
    # MoE (0 = dense)
    num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # "gather" (fast, capacity) | "einsum" (reference oracle) |
    # "grouped" (dropless Pallas kernel — per-shard experts) |
    # "grouped_ep" (dropless + expert-parallel: shard_map + all_to_all
    # over the ``moe_ep_axes`` expert submesh; pair with the "moe_ep"
    # rule set so expert weights shard where the all-to-all lands them)
    moe_dispatch: str = "gather"
    # "grouped_ep" only: the expert submesh axes. Defaults to the
    # canonical (data x fsdp) expert submesh; the mesh itself resolves
    # ambiently per accelerate (elastic-safe), or from ``mesh`` above.
    moe_ep_axes: Tuple[str, ...] = ("data", "fsdp")
    # "grouped_ep" only: chunked double-buffered dispatch — split the
    # row exchange into this many ppermute-ring chunks so the grouped
    # GEMM overlaps the in-flight exchange (ops.moe). 0 = resolve the
    # Context knob (``dispatch_chunks``) at trace time, which is how
    # the runtime optimizer's chosen chunking reaches a retuned program.
    moe_dispatch_chunks: int = 0
    # "grouped_ep" only: the wire precision of the row exchanges —
    # "bf16" | "fp8" (block-scaled e4m3 + f32 scales, ~half the wire
    # bytes) | "fp8_qdq" (the bitwise reference oracle). "" = resolve
    # the Context knob (``moe_precision``) at trace time, the same
    # retune-without-rebuild contract as the chunk knob (ops.moe).
    moe_precision: str = ""
    # FSDP layer prefetch: gather layer l+1's params while layer l
    # computes (double-buffered carry through the scan-over-layers).
    # None = the Context knob (``fsdp_prefetch``). Same math, but the
    # scan(L-1)+epilogue restructure changes fusion/reduction order, so
    # results match the plain scan to float roundoff, not bitwise.
    fsdp_prefetch: Any = None
    # dense FSDP wire precision: what the per-layer param gathers of
    # the scan-over-layers ship — "bf16" (the param dtype, today's
    # wire), "fp8" (the stacked per-layer weight matrices quantize to
    # block-scaled e4m3 + f32 scales BEFORE the scan, the layer slice
    # moves quantized, and dequant happens at consumption inside the
    # block — ~1/4 of the f32 wire) or "fp8_qdq" (the bitwise
    # reference oracle: the identical quantize->dequantize applied to
    # the stack, with the wire itself left at full precision). A pure-
    # forward transform: gradients pass straight through to the
    # original params (the gather is dequant-exact, so no error
    # feedback is needed — unlike the gradient direction, see
    # ``parallel.accelerate``). "" = resolve the Context knob
    # (``fsdp_precision``) at TRACE time, the retune-without-rebuild
    # contract shared with moe_precision/dispatch_chunks.
    fsdp_precision: str = ""

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def llama2_7b(**overrides) -> LlamaConfig:
    return replace(LlamaConfig(), **overrides)


def llama2_13b(**overrides) -> LlamaConfig:
    return replace(
        LlamaConfig(hidden_size=5120, intermediate_size=13824,
                    num_layers=40, num_heads=40, num_kv_heads=40),
        **overrides,
    )


def llama3_8b(**overrides) -> LlamaConfig:
    """Llama-3-8B shape: GQA 32/8, 128k vocab, theta 5e5."""
    return replace(
        LlamaConfig(vocab_size=128256, hidden_size=4096,
                    intermediate_size=14336, num_layers=32,
                    num_heads=32, num_kv_heads=8, max_seq_len=8192,
                    rope_theta=500000.0),
        **overrides,
    )


def llama3_70b(**overrides) -> LlamaConfig:
    return replace(
        LlamaConfig(vocab_size=128256, hidden_size=8192,
                    intermediate_size=28672, num_layers=80,
                    num_heads=64, num_kv_heads=8, max_seq_len=8192,
                    rope_theta=500000.0),
        **overrides,
    )


def llama_tiny(**overrides) -> LlamaConfig:
    """Test-scale config (runs on the 8-device CPU mesh)."""
    return replace(
        LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
            compute_dtype=jnp.float32, use_flash=False,
        ),
        **overrides,
    )


# -- init -------------------------------------------------------------------


def init(rng: jax.Array, config: LlamaConfig) -> Dict:
    c = config
    dt = c.param_dtype
    keys = iter(jax.random.split(rng, 16))
    l, d, f = c.num_layers, c.hidden_size, c.intermediate_size
    h, kv, hd = c.num_heads, c.num_kv_heads, c.head_dim

    layers = {
        "input_norm": {"scale": jnp.ones((l, d), dt)},
        "q_proj": {"kernel": _dense(next(keys), (l, d, h * hd), dt)},
        "k_proj": {"kernel": _dense(next(keys), (l, d, kv * hd), dt)},
        "v_proj": {"kernel": _dense(next(keys), (l, d, kv * hd), dt)},
        "o_proj": {"kernel": _dense(next(keys), (l, h * hd, d), dt)},
        "post_norm": {"scale": jnp.ones((l, d), dt)},
    }
    if c.num_experts > 0:
        e = c.num_experts
        layers["router"] = {
            "kernel": _dense(next(keys), (l, d, e), dt)
        }
        layers["experts"] = {
            "up": {"kernel": _dense(next(keys), (l, e, d, f), dt)},
            "down": {"kernel": _dense(
                next(keys), (l, e, f, d), dt, scale=1.0 / math.sqrt(f))},
        }
    else:
        layers["gate_proj"] = {"kernel": _dense(next(keys), (l, d, f), dt)}
        layers["up_proj"] = {"kernel": _dense(next(keys), (l, d, f), dt)}
        layers["down_proj"] = {
            "kernel": _dense(next(keys), (l, f, d), dt,
                             scale=1.0 / math.sqrt(f))
        }

    return {
        "embed_tokens": {
            "embedding": jax.random.normal(
                next(keys), (c.vocab_size, d), dt) * 0.02,
        },
        "layers": layers,
        "norm": {"scale": jnp.ones((d,), dt)},
        "lm_head": {"kernel": _dense(next(keys), (d, c.vocab_size), dt)},
    }


# -- forward ----------------------------------------------------------------


def _rope(x, positions, theta):
    """x: [B, S, H, Dh]; rotate pairs (even, odd halves)."""
    b, s, h, hd = x.shape
    half = hd // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def _ring_impl(c: LlamaConfig):
    """See ``ops.ring_attention.impl_from_flags`` — the shared mapping
    from (use_flash, flash_interpret) to the ring impl selector."""
    from dlrover_tpu.ops.ring_attention import impl_from_flags

    return impl_from_flags(c.use_flash, c.flash_interpret)


@jax.named_scope(DeviceScope.ATTENTION)
def _attention_block(x, layer, config: LlamaConfig, positions,
                     segment_ids=None, return_kv: bool = False):
    c = config
    b, s, d = x.shape
    h, kv, hd = c.num_heads, c.num_kv_heads, c.head_dim
    q = (x @ layer["q_proj"]["kernel"]).reshape(b, s, h, hd)
    k = (x @ layer["k_proj"]["kernel"]).reshape(b, s, kv, hd)
    v = (x @ layer["v_proj"]["kernel"]).reshape(b, s, kv, hd)
    q = _rope(q, positions, c.rope_theta)
    k = _rope(k, positions, c.rope_theta)
    # serving prefill captures the post-RoPE K/V — exactly what the
    # decode steps will read back from the KV pages
    kv_out = (k, v) if return_kv else None
    # GQA kv heads are NOT repeated: the flash/ring kernels index the
    # shared KV head per query group, so HBM holds (and the ring
    # rotates) only the kv heads — h/kv less traffic than the repeat
    # the reference pays before its CUDA kernel (layers.py:1268).
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B,H,S,Dh]
    ring_mesh = None
    if c.seq_axis:
        # an explicit config mesh wins; otherwise the AMBIENT mesh
        # (rebuilt by every accelerate) keeps ring configs elastic-safe
        from dlrover_tpu.ops.ring_attention import ambient_ring_mesh

        ring_mesh = (c.mesh if c.mesh is not None
                     else ambient_ring_mesh(c.seq_axis))
    if segment_ids is not None:
        # packed sequences: per-document masking fused into the kernel;
        # under sequence parallelism the segment ids ride the ring with
        # the KV shards (documents may span ring shards)
        if c.seq_axis and ring_mesh is not None:
            out = ring_attention(
                q, k, v, ring_mesh, axis_name=c.seq_axis,
                causal=True,
                batch_axes=("data", "fsdp"), head_axis="tensor",
                block_q=c.flash_block_q, block_k=c.flash_block_k,
                segment_ids=segment_ids, impl=_ring_impl(c),
                block_q_bwd=c.flash_block_q_bwd,
                block_k_bwd=c.flash_block_k_bwd,
            )
        elif c.seq_axis:
            out = ring_attention_local(
                q, k, v, axis_name=c.seq_axis, causal=True,
                block_q=c.flash_block_q, block_k=c.flash_block_k,
                segment_ids=segment_ids, impl=_ring_impl(c),
                block_q_bwd=c.flash_block_q_bwd,
                block_k_bwd=c.flash_block_k_bwd,
            )
        else:
            from dlrover_tpu.ops.flash_attention import (
                segmented_attention,
            )

            out = segmented_attention(
                q, k, v, segment_ids, c.use_flash,
                block_q=c.flash_block_q, block_k=c.flash_block_k,
                interpret=c.flash_interpret,
                block_q_bwd=c.flash_block_q_bwd,
                block_k_bwd=c.flash_block_k_bwd,
            )
    elif c.seq_axis and ring_mesh is not None:
        out = ring_attention(
            q, k, v, ring_mesh, axis_name=c.seq_axis, causal=True,
            batch_axes=("data", "fsdp"), head_axis="tensor",
            block_q=c.flash_block_q, block_k=c.flash_block_k,
            impl=_ring_impl(c),
            block_q_bwd=c.flash_block_q_bwd,
            block_k_bwd=c.flash_block_k_bwd,
        )
    elif c.seq_axis:
        out = ring_attention_local(q, k, v, axis_name=c.seq_axis,
                                   causal=True,
                                   block_q=c.flash_block_q,
                                   block_k=c.flash_block_k,
                                   impl=_ring_impl(c),
                                   block_q_bwd=c.flash_block_q_bwd,
                                   block_k_bwd=c.flash_block_k_bwd)
    elif c.use_flash:
        # auto-routes through shard_map under a non-trivial mesh (GSPMD
        # cannot partition the Mosaic call itself)
        out = flash_attention_auto(q, k, v, True,
                                   block_q=c.flash_block_q,
                                   block_k=c.flash_block_k,
                                   interpret=c.flash_interpret,
                                   block_q_bwd=c.flash_block_q_bwd,
                                   block_k_bwd=c.flash_block_k_bwd)
    else:
        out = mha_reference(q, k, v, causal=True)
    out = checkpoint_name(out, "attn_out")
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    out = out @ layer["o_proj"]["kernel"]
    if return_kv:
        return out, kv_out
    return out


@jax.named_scope(DeviceScope.FFN)
def _ffn_block(x, layer, config: LlamaConfig, rng):
    """Returns (out, aux_loss, dropped_frac, expert_load) — the last two
    are the MoE load-balance observability signals (zeros for dense)."""
    if config.num_experts > 0:
        moe_params = {
            "router": layer["router"],
            "experts": {
                "up": layer["experts"]["up"],
                "down": layer["experts"]["down"],
            },
        }
        cfg = moe_ops.MoEConfig(
            num_experts=config.num_experts,
            capacity_factor=config.moe_capacity_factor,
            top_k=config.moe_top_k,
            dispatch=config.moe_dispatch,
            # the grouped kernel follows the flash knob: False forces
            # Mosaic (deviceless-AOT tracing), None auto-detects
            kernel_interpret=config.flash_interpret,
            # grouped_ep: an explicit config mesh wins; otherwise the
            # AMBIENT mesh (rebuilt by every accelerate) keeps the
            # expert-parallel shard_map elastic-safe, mirroring the
            # ring-attention mesh convention above
            ep_axes=tuple(config.moe_ep_axes),
            mesh=config.mesh,
            dispatch_chunks=config.moe_dispatch_chunks,
            precision=config.moe_precision,
        )
        out, aux, metrics = moe_ops.moe_ffn(
            moe_params, x, cfg, activation=jax.nn.silu, rng=rng
        )
        return out, aux, metrics["dropped_frac"], metrics["expert_load"]
    gate = jax.nn.silu(x @ layer["gate_proj"]["kernel"])
    up = x @ layer["up_proj"]["kernel"]
    zero = jnp.zeros((), jnp.float32)
    return ((gate * up) @ layer["down_proj"]["kernel"], zero, zero,
            jnp.zeros((1,), jnp.float32))




def _prefetch_enabled(c: LlamaConfig) -> bool:
    """The FSDP layer-prefetch toggle: the config wins when set, else
    the Context knob (``fsdp_prefetch``) — resolved at TRACE time so a
    re-accelerate picks up a changed knob."""
    if c.fsdp_prefetch is not None:
        return bool(c.fsdp_prefetch)
    from dlrover_tpu.common.config import get_context

    return bool(getattr(get_context(), "fsdp_prefetch", False))


# -- dense FSDP wire (low-precision param gathers) --------------------------


def resolve_fsdp_precision(config: LlamaConfig) -> str:
    """The effective dense-wire precision at TRACE time: an explicit
    ``config.fsdp_precision`` wins; "" resolves the global Context knob
    (``fsdp_precision``) — how the runtime optimizer's chosen precision
    reaches a re-traced program without rebuilding the model config
    (the ``moe_precision`` pattern, ops.moe.resolve_moe_precision). A
    quantized choice degrades to "bf16" (logged, never raised) when the
    backend fails the fp8 capability probe."""
    p = (getattr(config, "fsdp_precision", "") or "").strip()
    if not p:
        from dlrover_tpu.common.config import get_context

        p = str(getattr(get_context(), "fsdp_precision", "bf16")
                or "bf16").strip() or "bf16"
    from dlrover_tpu.ops.quantize import PRECISIONS

    if p not in PRECISIONS:
        raise ValueError(
            f"unknown FSDP wire precision {p!r}; choose one of "
            f"{PRECISIONS}"
        )
    if p != "bf16":
        from dlrover_tpu.ops.shard_compat import fp8_wire_supported

        if not fp8_wire_supported():
            import logging

            logging.getLogger("dlrover_tpu.models.llama").warning(
                "fsdp precision %r requested but the backend fails the "
                "fp8 probe; falling back to the bf16 wire", p,
            )
            return "bf16"
    return p


def _wire_leaf(a) -> bool:
    """Which stacked layer params ride the quantized wire: the rank-3
    per-layer weight matrices ([L, in, out] — the bytes that dominate
    the per-layer gather). Vector params (norm scales, [L, D]) are a
    rounding error of the traffic and stay exact; rank-4 expert
    tensors are consumed shard-local inside the grouped_ep shard_map
    (never gathered), so quantizing them would add drift for zero wire
    win."""
    return (getattr(a, "ndim", 0) == 3
            and jnp.issubdtype(a.dtype, jnp.floating))


def _quantize_layer_stack(layers: Dict, mode: str) -> Dict[str, Dict]:
    """path -> wire form of every wired leaf of the STACKED layer tree.

    Quantization runs on the stacked, still-sharded params (elementwise
    per 32-channel block along the last dim, so it computes shardwise
    and commutes with the per-layer slice the scan takes): the scan's
    xs then carry e4m3 values + f32 scales and the per-layer gather
    moves the quantized bytes. "fp8_qdq" dequantizes here instead —
    identical numbers (slice commutes with the elementwise decode), but
    the wire ships full precision: the dequant-exact oracle the bitwise
    tests pin fp8 against."""
    from dlrover_tpu.ops.quantize import (
        dequantize_block_scaled,
        quantize_block_scaled,
    )

    wire: Dict[str, Dict] = {}
    for path, leaf in _flatten_layers(layers):
        if not _wire_leaf(leaf):
            continue
        v, s = quantize_block_scaled(leaf)
        if mode == "fp8":
            wire[path] = {"v": v, "s": s}
        else:  # fp8_qdq: decode locally, wire at full precision
            wire[path] = {"dq": dequantize_block_scaled(v, s, leaf.dtype)}
    return wire


def _flatten_layers(layers: Dict):
    """(path, leaf) pairs of a nested-dict layer tree, "/"-joined —
    the addressing `_consume_wire` uses to splice dequantized leaves
    back into the per-layer param tree."""
    out = []

    def walk(node, prefix):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], prefix + (k,))
        else:
            out.append(("/".join(prefix), node))

    walk(layers, ())
    return out


def _set_path(tree: Dict, path: str, value):
    keys = path.split("/")
    node = tree
    for k in keys[:-1]:
        node = node[k]
    node[keys[-1]] = value


def _get_path(tree: Dict, path: str):
    node = tree
    for k in path.split("/"):
        node = node[k]
    return node


@jax.custom_vjp
def _consume_fp8(v, s, w):
    """Dequantize one wired leaf at consumption. ``w`` (the original
    full-precision slice) contributes NO forward value — it exists so
    the backward has a full-precision cotangent path back to the
    stacked params: the transform is pure-forward (straight-through),
    and without this route the scan's xs cotangent would be e4m3,
    which cannot carry a gradient. The forward never reads ``w``, so
    its f32 slice is dead code the compiler drops — the layer gather
    moves only the quantized bytes."""
    from dlrover_tpu.ops.quantize import dequantize_block_scaled

    return dequantize_block_scaled(v, s, w.dtype)


def _consume_fp8_fwd(v, s, w):
    return _consume_fp8(v, s, w), (v, s)


def _consume_fp8_bwd(res, g):
    v, s = res
    return jnp.zeros(v.shape, v.dtype), jnp.zeros(s.shape, s.dtype), g


_consume_fp8.defvjp(_consume_fp8_fwd, _consume_fp8_bwd)


@jax.custom_vjp
def _consume_qdq(dq, w):
    """The fsdp_qdq oracle's consumption: the pre-decoded value, with
    the identical straight-through backward as ``_consume_fp8`` — so
    fp8 and fp8_qdq are bitwise equal fwd AND bwd."""
    return dq


def _consume_qdq_fwd(dq, w):
    return dq, (dq,)


def _consume_qdq_bwd(res, g):
    (dq,) = res
    return jnp.zeros(dq.shape, dq.dtype), g


_consume_qdq.defvjp(_consume_qdq_fwd, _consume_qdq_bwd)


def _consume_wire(wire_slice: Dict[str, Dict], orig_slice: Dict) -> Dict:
    """Per-layer param tree with every wired leaf replaced by its
    dequantized wire form (non-wired leaves come from ``orig_slice``
    untouched)."""
    out = jax.tree.map(lambda x: x, orig_slice)  # fresh containers
    for path, form in wire_slice.items():
        w = _get_path(orig_slice, path)
        if "dq" in form:
            _set_path(out, path, _consume_qdq(form["dq"], w))
        else:
            _set_path(out, path, _consume_fp8(form["v"], form["s"], w))
    return out


def _wire_block(block, wired: bool):
    """Adapter running ``block`` over (wire, orig) xs pairs when the
    quantized wire is active — INSIDE the remat wrapper, so a remat'd
    backward re-derives the dequantized params from the quantized xs
    (the re-gather leg of the backward also moves fp8)."""
    if not wired:
        return block

    def wired_block(carry, xs):
        wire_slice, orig_slice = xs
        return block(carry, _consume_wire(wire_slice, orig_slice))

    return wired_block


def _prefetch_gather(tree):
    """Issue the gather of ONE layer's params now: a sharding
    constraint to replicated over the ambient mesh — exactly the
    all-gather FSDP pays per layer anyway, but as an op with NO data
    dependency on the current layer's compute, so XLA's latency-hiding
    scheduler can run it underneath (the HSDP-paper prefetch,
    PAPERS.md 2602.00277). Values are untouched (a sharding constraint
    never changes numerics); without an ambient mesh this is the
    identity."""
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.ops.shard_compat import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None:
        return tree
    try:
        rep = NamedSharding(mesh, PartitionSpec())
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, rep), tree
        )
    except (ValueError, TypeError):  # mesh flavor unsupported here
        return tree


def _decoder_block(c: LlamaConfig, segment_ids=None, positions=None):
    """Scan body over stacked layer params; shared by the plain and the
    pipelined forward so the two cannot drift. ``positions`` is computed
    ONCE by the caller (it is layer-invariant; inside the scan body it
    would run per layer, and again per layer under remat)."""

    def block(carry, layer_params):
        x, block_rng = carry
        # params may be stored f32; compute in the configured dtype
        layer_params = cast_floats(layer_params, c.compute_dtype)
        pos = positions
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        block_rng, ffn_rng = jax.random.split(block_rng)
        attn_in = _rms_norm(x, layer_params["input_norm"]["scale"], c.rms_eps)
        x = x + _attention_block(attn_in, layer_params, c, pos,
                                 segment_ids)
        ffn_in = _rms_norm(x, layer_params["post_norm"]["scale"], c.rms_eps)
        ffn_out, aux, dropped, load = _ffn_block(
            ffn_in, layer_params, c, ffn_rng
        )
        return (x + ffn_out, block_rng), (aux, dropped, load)

    return block


def apply_hidden(
    params: Dict, input_ids: jax.Array, config: LlamaConfig,
    rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    with_moe_metrics: bool = False,
):
    """Returns (final hidden states [B, S, D] in compute dtype,
    moe_aux_loss scalar) — everything except the lm head. With
    ``with_moe_metrics`` a third element is returned: the layer-averaged
    load-balance dict {"moe_dropped_frac", "moe_expert_load" [E]}.

    ``segment_ids`` [B, S]: packed-sequence mode — per-document
    attention masking and segment-relative RoPE positions."""
    c = config
    x = params["embed_tokens"]["embedding"][input_ids].astype(c.compute_dtype)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    positions = (segment_positions(segment_ids)
                 if segment_ids is not None else None)
    wire_mode = resolve_fsdp_precision(c)
    layers = params["layers"]
    wire_stack = (_quantize_layer_stack(layers, wire_mode)
                  if wire_mode != "bf16" else {})
    wired = bool(wire_stack)
    block = apply_remat(
        _wire_block(_decoder_block(c, segment_ids, positions), wired),
        c.remat_policy,
    )
    # wired xs: (quantized wire forms, original tree) — the original
    # rides along as the straight-through gradient route; its wired
    # leaves are never read forward, so only quantized bytes move
    scan_xs = (wire_stack, layers) if wired else layers
    if _prefetch_enabled(c) and c.num_layers >= 2:
        # FSDP layer prefetch: the scan carries layer l's ALREADY
        # GATHERED params and issues layer l+1's gather before the
        # block compute — a double-buffered carry, so the per-layer
        # param all-gather runs under the previous layer's compute
        # instead of on the critical path. The last layer runs as an
        # epilogue (its params were gathered during layer L-2). The
        # gather stays OUTSIDE the remat'd block: the backward re-plays
        # compute, not the exchange schedule. Same blocks, same order,
        # same rng chain — but the restructure changes XLA's fusion /
        # reduction order, so outputs match the plain scan to float
        # roundoff, NOT bitwise (pinned with allclose). On the
        # quantized wire only the WIRE forms ride the prefetched
        # (constraint-issued) gather and the double-buffered carry —
        # dequant still happens at consumption inside the block, and
        # the gradient-route originals stay out of the carry.
        if wired:
            wire_first = jax.tree.map(lambda a: a[0], wire_stack)
            wire_rest = jax.tree.map(lambda a: a[1:], wire_stack)
            orig_head = jax.tree.map(lambda a: a[:-1], layers)
            orig_last = jax.tree.map(lambda a: a[-1], layers)

            def pf_block(carry, xs_i):
                inner, cur_wire = carry
                wire_next, orig_cur = xs_i
                gathered = _prefetch_gather(wire_next)  # prefetch l+1
                inner, ys = block(inner, (cur_wire, orig_cur))
                return (inner, gathered), ys

            (inner, last_wire), (aux_losses, dropped, load) = lax.scan(
                pf_block,
                ((x, rng), _prefetch_gather(wire_first)),
                (wire_rest, orig_head),
            )
            inner, (aux_l, drop_l, load_l) = block(
                inner, (last_wire, orig_last))
        else:
            first = jax.tree.map(lambda a: a[0], layers)
            rest = jax.tree.map(lambda a: a[1:], layers)

            def pf_block(carry, next_sharded):
                inner, cur = carry
                gathered = _prefetch_gather(next_sharded)  # prefetch l+1
                inner, ys = block(inner, cur)  # compute layer l
                return (inner, gathered), ys

            (inner, last), (aux_losses, dropped, load) = lax.scan(
                pf_block, ((x, rng), _prefetch_gather(first)), rest
            )
            inner, (aux_l, drop_l, load_l) = block(inner, last)
        x, _ = inner
        aux_losses = jnp.concatenate([aux_losses, aux_l[None]])
        dropped = jnp.concatenate([dropped, drop_l[None]])
        load = jnp.concatenate([load, load_l[None]], axis=0)
    else:
        (x, _), (aux_losses, dropped, load) = lax.scan(
            block, (x, rng), scan_xs
        )
    x = _rms_norm(x, params["norm"]["scale"], c.rms_eps)
    if with_moe_metrics:
        metrics = {
            "moe_dropped_frac": jnp.mean(dropped),
            "moe_expert_load": jnp.mean(load, axis=0),
        }
        return x, jnp.sum(aux_losses), metrics
    return x, jnp.sum(aux_losses)


def apply(params: Dict, input_ids: jax.Array, config: LlamaConfig,
          rng: Optional[jax.Array] = None,
          segment_ids: Optional[jax.Array] = None,
          with_moe_metrics: bool = False,
          ):
    """Returns (logits [B, S, V] in f32, moe_aux_loss scalar) — plus
    the load-balance metrics dict when ``with_moe_metrics``."""
    c = config
    out = apply_hidden(params, input_ids, config, rng, segment_ids,
                       with_moe_metrics=with_moe_metrics)
    x = out[0]
    logits = (x @ params["lm_head"]["kernel"].astype(c.compute_dtype))
    return (logits.astype(jnp.float32),) + out[1:]


def apply_pipelined(
    params: Dict,
    input_ids: jax.Array,
    config: LlamaConfig,
    num_stages: int,
    num_microbatches: int,
    rng: Optional[jax.Array] = None,
    num_virtual: int = 1,
    stage_depths: Optional[Sequence[int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Forward pass with the decoder blocks run as a GPipe pipeline over
    the "pipe" mesh axis (``parallel.pipeline``); embed/final-norm/head
    stay outside the pipeline in the surrounding GSPMD program.

    Equivalent to ``apply`` up to bf16 rounding for dense configs. For
    MoE configs the math intentionally differs: expert capacity is
    computed per *microbatch* (B/M tokens) rather than per batch, and
    each stage restarts the rng chain, so routing overflow/jitter
    decisions are not bit-identical to ``apply``. Use with the
    "llama_pp" rule set so the stacked layer dim lands on "pipe".

    ``stage_depths``: per-stage-chunk layer counts (V*P entries in visit
    order, summing to num_layers) for UNEVEN stage splits — a lighter
    first/last stage, or L % (V*P) != 0. Padded layer slots are skipped
    via a validity mask; see ``pipeline.stack_stages_uneven`` for the
    cost model (wall-clock equals the heaviest stage either way).
    """
    from dlrover_tpu.parallel.pipeline import (
        dispatch_pipeline,
        merge_microbatches,
        pipe_batch_constraint,
        split_microbatches,
    )

    c = config
    x = params["embed_tokens"]["embedding"][input_ids].astype(c.compute_dtype)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def stage_fn(chunk_and_mask, state):
        layers_chunk, mask = chunk_and_mask
        x, aux = state
        block = apply_remat(_decoder_block(c), c.remat_policy)
        if mask is None:  # even split: plain scan over the chunk
            (x, _), (auxs, _, _) = lax.scan(block, (x, rng), layers_chunk)
            return (x, aux + jnp.sum(auxs))

        def slot(carry, inp):
            layer, valid = inp
            new_carry, (aux_l, _, _) = block(carry, layer)
            x_new, rng_new = new_carry
            x_old, _ = carry
            # padded slot: carry the state through untouched (zero
            # params keep the garbage compute finite, so the masked
            # branch cannot poison the selected one's gradient); the
            # rng chain advances regardless so depth layout never
            # changes a real layer's dropout/jitter stream position
            x_sel = jnp.where(valid > 0, x_new, x_old)
            return (x_sel, rng_new), aux_l * valid
        (x, _), auxs = lax.scan(slot, (x, rng), (layers_chunk, mask))
        return (x, aux + jnp.sum(auxs))

    x_mb = split_microbatches(x, num_microbatches)
    aux_mb = jnp.zeros((num_microbatches,), jnp.float32)
    out_mb, aux_out = dispatch_pipeline(
        stage_fn, params["layers"], (x_mb, aux_mb),
        num_stages, num_virtual, stage_depths,
        remat_stage=remat_enabled(c.remat_policy),
    )
    x = merge_microbatches(out_mb)
    aux = jnp.sum(aux_out)

    # the outer final-norm/head must not replicate over the pipe axis
    # (see pipe_batch_constraint: comm-free slice, head FLOPs / pipe)
    x = pipe_batch_constraint(x)

    x = _rms_norm(x, params["norm"]["scale"], c.rms_eps)
    logits = (x @ params["lm_head"]["kernel"].astype(c.compute_dtype))
    return logits.astype(jnp.float32), aux


# -- serving: single-token decode over the paged KV cache --------------------
#
# The decode-step apply of the serving tier (``dlrover_tpu.serving``):
# the same stacked-layer params, the same scan-over-layers, but the
# sequence dimension is replaced by a KV-page READ — attention for slot
# ``s`` is a plain slice of its own contiguous pages (gather-free; see
# ``serving.kv_cache`` for the slot-major pool layout). Numerics follow
# the training forward (f32 attention logits, ``finfo.min`` masking,
# f32 softmax — the ``mha_reference`` conventions), so prefill+decode
# matches the one-shot forward to float roundoff; ``prefill_sequence``
# goes further and routes the whole prompt through ``_attention_block``
# itself — ring attention included for long-context ``seq_axis``
# configs — so its hidden states (and the first generated token) are
# BITWISE the training forward's.


def _kv_write_token(k_l, scale_l, new_kv, pos, active, spec):
    """Write one token's K (or V) into its slot page at ``pos``,
    masked by ``active`` (an admitted-and-decoding slot). The write
    touches exactly one page row per slot — a scatter at
    ``(slot, pos)`` — and inactive slots keep their old row, so a slot
    mid-prefill (or parked) is never corrupted by the batch-wide
    decode step."""
    from dlrover_tpu.serving.kv_cache import encode_kv

    s = k_l.shape[0]
    idx = jnp.arange(s)
    t = k_l.shape[1]
    vals, scales = encode_kv(new_kv, spec)
    # masked scatter by index redirection: an inactive slot's row index
    # is pushed out of bounds, and mode="drop" discards the update —
    # no gather of the old row just to feed a where() (the gather-free
    # decode invariant, G110: per-slot random reads belong to the host)
    row = jnp.where(active, jnp.clip(pos, 0, t - 1), t)
    k_l = k_l.at[idx, row].set(vals, mode="drop")
    if scales is not None and scale_l is not None:
        scale_l = scale_l.at[idx, row].set(scales, mode="drop")
    return k_l, scale_l


def _paged_attention(q, k_l, ks_l, v_l, vs_l, pos, spec, config):
    """Decode attention: ``q [S, H, HD]`` against each slot's own pages
    ``[S, T, KV, HD]`` with the causal mask ``t <= pos[s]``. GQA via a
    grouped einsum (KV heads are never repeated — the pages hold, and
    the read moves, only the KV heads). Mirrors ``mha_reference``:
    f32 logits, ``finfo.min`` mask, f32 softmax."""
    from dlrover_tpu.serving.kv_cache import decode_kv

    s, h, hd = q.shape
    kvh = k_l.shape[2]
    t = k_l.shape[1]
    group = h // kvh
    k = decode_kv(k_l, ks_l, spec)      # [S, T, KV, HD] f32
    v = decode_kv(v_l, vs_l, spec)
    qg = q.reshape(s, kvh, group, hd)
    logits = jnp.einsum(
        "skgd,stkd->skgt", qg, k, preferred_element_type=jnp.float32
    ) * (1.0 / (hd ** 0.5))
    mask = jnp.arange(t)[None, :] <= pos[:, None]  # [S, T]
    logits = jnp.where(mask[:, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("skgt,stkd->skgd", probs.astype(v.dtype), v)
    return out.reshape(s, h * hd).astype(config.compute_dtype)


def _kv_write_tokens(k_l, scale_l, new_kv, pos, valid, spec):
    """Masked MULTI-token append: write up to ``K+1`` tokens' K (or V)
    per slot at rows ``pos[s] .. pos[s]+K``, guarded by ``valid
    [S, K+1]`` — the speculative-verify generalization of
    ``_kv_write_token``. Same discipline: masked scatter by index
    redirection (an invalid row index is pushed out of bounds and
    ``mode="drop"`` discards it), so there is no gather of old rows to
    feed a ``where()`` and the G110 gather-free invariant holds. Rows
    past the pool end are also dropped (the host caps draft lengths so
    this only guards against a buggy caller, not silent clamping)."""
    from dlrover_tpu.serving.kv_cache import encode_kv

    s, k1 = new_kv.shape[0], new_kv.shape[1]
    t = k_l.shape[1]
    idx = jnp.arange(s)[:, None]
    vals, scales = encode_kv(new_kv, spec)
    rows_raw = pos[:, None] + jnp.arange(k1)[None, :]   # [S, K+1]
    ok = valid & (rows_raw < t)
    rows = jnp.where(ok, jnp.clip(rows_raw, 0, t - 1), t)
    k_l = k_l.at[idx, rows].set(vals, mode="drop")
    if scales is not None and scale_l is not None:
        scale_l = scale_l.at[idx, rows].set(scales, mode="drop")
    return k_l, scale_l


def _verify_attention(q, k_l, ks_l, v_l, vs_l, pos, spec, config):
    """Speculative-verify attention: ``q [S, K+1, H, HD]`` — every
    slot's current token plus its drafts — against each slot's own
    pages, causal mask ``t <= pos[s] + i``. The batched-over-slots
    generalization of ``_chunk_attention`` (same grouped einsum, f32
    logits, ``finfo.min`` mask, f32 softmax), which is what makes the
    verified positions compute-per-position identical to the decode
    path — the per-row parity the bitwise acceptance contract rests
    on."""
    from dlrover_tpu.serving.kv_cache import decode_kv

    s, k1, h, hd = q.shape
    kvh = k_l.shape[2]
    t = k_l.shape[1]
    group = h // kvh
    k = decode_kv(k_l, ks_l, spec)      # [S, T, KV, HD] f32
    v = decode_kv(v_l, vs_l, spec)
    qg = q.reshape(s, k1, kvh, group, hd)
    logits = jnp.einsum(
        "sikgd,stkd->sikgt", qg, k, preferred_element_type=jnp.float32
    ) * (1.0 / (hd ** 0.5))
    mask = (jnp.arange(t)[None, None, :]
            <= (pos[:, None] + jnp.arange(k1)[None, :])[:, :, None])
    logits = jnp.where(mask[:, :, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("sikgt,stkd->sikgd", probs.astype(v.dtype), v)
    return out.reshape(s, k1, h * hd).astype(config.compute_dtype)


def _chunk_attention(q, k_slot, ks_slot, v_slot, vs_slot, start, spec,
                     config):
    """Prefill-chunk attention: chunk queries ``[C, H, HD]`` against
    ONE slot's pages (which already contain the chunk's own K/V at
    ``start..start+C``), causal mask ``t <= start + i``."""
    from dlrover_tpu.serving.kv_cache import decode_kv

    cq, h, hd = q.shape
    kvh = k_slot.shape[1]
    t = k_slot.shape[0]
    group = h // kvh
    k = decode_kv(k_slot, ks_slot, spec)    # [T, KV, HD] f32
    v = decode_kv(v_slot, vs_slot, spec)
    qg = q.reshape(cq, kvh, group, hd)
    logits = jnp.einsum(
        "ckgd,tkd->ckgt", qg, k, preferred_element_type=jnp.float32
    ) * (1.0 / (hd ** 0.5))
    mask = (jnp.arange(t)[None, :]
            <= start + jnp.arange(cq)[:, None])  # [C, T]
    logits = jnp.where(mask[:, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("ckgt,tkd->ckgd", probs.astype(v.dtype), v)
    return out.reshape(cq, h * hd).astype(config.compute_dtype)


def _cache_xs(cache):
    """(k, k_scale-or-None, v, v_scale-or-None) in scan-xs order; the
    scale leaves exist only for int8 pools."""
    return (cache["k"], cache.get("k_scale"), cache["v"],
            cache.get("v_scale"))


def _rebuild_cache(cache, k, ks, v, vs, length):
    out = dict(cache, k=k, v=v, length=length)
    if ks is not None:
        out["k_scale"] = ks
    if vs is not None:
        out["v_scale"] = vs
    return out


def decode_step(params, cache, tokens, active, config: LlamaConfig,
                spec):
    """One continuous-batching decode step for EVERY slot at once.

    ``tokens [S] int32``: each slot's current token (the one whose
    successor is being predicted). ``active [S] bool``: slots that are
    admitted and decoding — inactive (free / mid-prefill) slots compute
    harmlessly but neither write pages nor advance ``length``. Returns
    ``(next_tokens [S], logits [S, V] f32, cache)`` with greedy
    next-token selection done ON DEVICE, so the engine's dispatch
    window never needs a host sync to feed step k+1.

    Dense FFN only: MoE expert dispatch for single-token batches is a
    different kernel regime (ROADMAP item 3 names it) — a config with
    experts must serve through ``prefill_sequence`` + a dense head or
    wait for the MoE decode path.
    """
    c = config
    if c.num_experts > 0:
        raise NotImplementedError(
            "decode_step serves dense llama configs; MoE decode "
            "dispatch is not built yet (ROADMAP item 3)")
    s = tokens.shape[0]
    pos = cache["length"]  # the position this step writes
    x = params["embed_tokens"]["embedding"][tokens].astype(c.compute_dtype)

    def block(x_in, xs):
        layer, k_l, ks_l, v_l, vs_l = xs
        layer = cast_floats(layer, c.compute_dtype)
        h, kvh, hd = c.num_heads, c.num_kv_heads, c.head_dim
        attn_in = _rms_norm(x_in, layer["input_norm"]["scale"], c.rms_eps)
        q = (attn_in @ layer["q_proj"]["kernel"]).reshape(s, h, hd)
        k_new = (attn_in @ layer["k_proj"]["kernel"]).reshape(s, kvh, hd)
        v_new = (attn_in @ layer["v_proj"]["kernel"]).reshape(s, kvh, hd)
        # RoPE at each slot's own position (slots are a batch of
        # length-1 sequences)
        q = _rope(q[:, None], pos[:, None], c.rope_theta)[:, 0]
        k_new = _rope(k_new[:, None], pos[:, None], c.rope_theta)[:, 0]
        k_l, ks_l = _kv_write_token(k_l, ks_l, k_new, pos, active, spec)
        v_l, vs_l = _kv_write_token(v_l, vs_l, v_new, pos, active, spec)
        attn = _paged_attention(q, k_l, ks_l, v_l, vs_l, pos, spec, c)
        x_mid = x_in + attn @ layer["o_proj"]["kernel"]
        ffn_in = _rms_norm(x_mid, layer["post_norm"]["scale"], c.rms_eps)
        gate = jax.nn.silu(ffn_in @ layer["gate_proj"]["kernel"])
        up = ffn_in @ layer["up_proj"]["kernel"]
        ffn = (gate * up) @ layer["down_proj"]["kernel"]
        return x_mid + ffn, (k_l, ks_l, v_l, vs_l)

    k, ks, v, vs = _cache_xs(cache)
    xs = (params["layers"], k, ks, v, vs)
    x, (k, ks, v, vs) = lax.scan(block, x, xs)
    x = _rms_norm(x, params["norm"]["scale"], c.rms_eps)
    logits = (x @ params["lm_head"]["kernel"].astype(c.compute_dtype))
    logits = logits.astype(jnp.float32)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    length = jnp.where(active, pos + 1, pos).astype(jnp.int32)
    return next_tokens, logits, _rebuild_cache(cache, k, ks, v, vs,
                                               length)


def verify_step(params, cache, tokens, active, n_draft,
                config: LlamaConfig, spec):
    """One speculative-decode VERIFY step for every slot at once: run
    the model over each slot's current token plus up to K drafted
    continuations in one batched call, greedily accept the longest
    matching draft prefix on device, and commit exactly the accepted
    tokens' KV.

    ``tokens [S, K+1] int32``: column 0 is the slot's current token
    (what ``decode_step`` would be fed), columns 1..K are host-drafted
    candidates for the following positions. ``n_draft [S] int32``: how
    many of the K draft columns are real for each slot (mixed-K slots
    in ONE compiled program — K is static, validity is data).
    ``active [S] bool``: as in ``decode_step``.

    Acceptance contract (greedy, bitwise): with ``g[i] = argmax`` of
    the logits at position ``pos+i``, the accepted length ``a`` is the
    longest prefix with ``tokens[i+1] == g[i]`` for ``i < a``. The
    slot emits ``a+1`` tokens — ``g[0..a]``: the accepted drafts plus
    the bonus token the last verified position predicts — and its next
    current token is ``g[a]``. Since ``g[0]`` is computed over exactly
    the context ``decode_step`` would see, and each accepted draft
    equals the token greedy decode would have produced, the emitted
    stream is token-for-token what plain greedy decode emits at EVERY
    acceptance pattern (induction over accepted prefixes; per-position
    compute parity is ``_verify_attention``'s contract).

    Rollback is a cursor rewind, not a wipe: rejected positions
    ``pos+a+1 .. pos+n_draft`` hold garbage K/V rows, but every
    attention mask is position-bounded by the committed length and
    future writes land in order, overwriting them before they could
    ever be read.

    Returns ``(greedy [S, K+1], accepted [S], next_tokens [S],
    cache)`` — the host reads ``greedy[:, :accepted+1]`` once per
    verify step, amortized over up to K+1 emitted tokens.
    """
    c = config
    if c.num_experts > 0:
        raise NotImplementedError(
            "verify_step serves dense llama configs; MoE decode "
            "dispatch is not built yet (ROADMAP item 3)")
    s, k1 = tokens.shape
    pos = cache["length"]               # first position this step writes
    offs = jnp.arange(k1)
    valid = active[:, None] & (offs[None, :] <= n_draft[:, None])
    positions = pos[:, None] + offs[None, :]        # [S, K+1]
    x = params["embed_tokens"]["embedding"][tokens].astype(c.compute_dtype)

    def block(x_in, xs):
        layer, k_l, ks_l, v_l, vs_l = xs
        layer = cast_floats(layer, c.compute_dtype)
        h, kvh, hd = c.num_heads, c.num_kv_heads, c.head_dim
        attn_in = _rms_norm(x_in, layer["input_norm"]["scale"], c.rms_eps)
        q = (attn_in @ layer["q_proj"]["kernel"]).reshape(s, k1, h, hd)
        k_new = (attn_in @ layer["k_proj"]["kernel"]).reshape(
            s, k1, kvh, hd)
        v_new = (attn_in @ layer["v_proj"]["kernel"]).reshape(
            s, k1, kvh, hd)
        q = _rope(q, positions, c.rope_theta)
        k_new = _rope(k_new, positions, c.rope_theta)
        k_l, ks_l = _kv_write_tokens(k_l, ks_l, k_new, pos, valid, spec)
        v_l, vs_l = _kv_write_tokens(v_l, vs_l, v_new, pos, valid, spec)
        attn = _verify_attention(q, k_l, ks_l, v_l, vs_l, pos, spec, c)
        x_mid = x_in + attn @ layer["o_proj"]["kernel"]
        ffn_in = _rms_norm(x_mid, layer["post_norm"]["scale"], c.rms_eps)
        gate = jax.nn.silu(ffn_in @ layer["gate_proj"]["kernel"])
        up = ffn_in @ layer["up_proj"]["kernel"]
        ffn = (gate * up) @ layer["down_proj"]["kernel"]
        return x_mid + ffn, (k_l, ks_l, v_l, vs_l)

    k, ks, v, vs = _cache_xs(cache)
    xs = (params["layers"], k, ks, v, vs)
    x, (k, ks, v, vs) = lax.scan(block, x, xs)
    x = _rms_norm(x, params["norm"]["scale"], c.rms_eps)
    logits = (x @ params["lm_head"]["kernel"].astype(c.compute_dtype))
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, K+1]
    if k1 > 1:
        match = ((tokens[:, 1:] == greedy[:, :-1])
                 & (jnp.arange(1, k1)[None, :] <= n_draft[:, None]))
        accepted = jnp.cumprod(
            match.astype(jnp.int32), axis=1).sum(axis=1)
    else:
        accepted = jnp.zeros((s,), jnp.int32)
    accepted = jnp.where(active, accepted, 0).astype(jnp.int32)
    # rank-2 take_along_axis: a benign table gather, not a pool gather
    next_tokens = jnp.take_along_axis(
        greedy, accepted[:, None], axis=1)[:, 0].astype(jnp.int32)
    length = jnp.where(active, pos + accepted + 1, pos).astype(jnp.int32)
    return greedy, accepted, next_tokens, _rebuild_cache(
        cache, k, ks, v, vs, length)


def prefill_chunk(params, cache, tokens, slot, start, n_valid,
                  config: LlamaConfig, spec):
    """Prefill ONE chunk of one slot's prompt: write the chunk's K/V
    pages and return ``(cache, last_logits [V])`` — the logits of token
    ``n_valid - 1``, which seed the first decode step when this is the
    prompt's final chunk.

    ``tokens [C] int32`` (fixed chunk shape — the ``prefill_chunk``
    knob), ``slot`` / ``start`` / ``n_valid`` traced scalars, so
    admission at any slot with any prompt length is the SAME compiled
    program: chunked prefill interleaves with the decode stream and a
    long prompt can never stall the batch behind a monolithic prefill.
    Chunks past the first attend to the slot's earlier pages through
    the cache, exactly like decode. Trailing padding (``n_valid < C``)
    is written but never read: decode's next write lands at
    ``start + n_valid``, and every mask is position-bounded."""
    c = config
    if c.num_experts > 0:
        raise NotImplementedError(
            "prefill_chunk serves dense llama configs; use "
            "prefill_sequence for MoE prompts")
    cq = tokens.shape[0]
    positions = start + jnp.arange(cq)
    x = params["embed_tokens"]["embedding"][tokens].astype(c.compute_dtype)

    def block(x_in, xs):
        from dlrover_tpu.serving.kv_cache import encode_kv

        layer, k_l, ks_l, v_l, vs_l = xs
        layer = cast_floats(layer, c.compute_dtype)
        h, kvh, hd = c.num_heads, c.num_kv_heads, c.head_dim
        attn_in = _rms_norm(x_in, layer["input_norm"]["scale"], c.rms_eps)
        q = (attn_in @ layer["q_proj"]["kernel"]).reshape(cq, h, hd)
        k_new = (attn_in @ layer["k_proj"]["kernel"]).reshape(cq, kvh, hd)
        v_new = (attn_in @ layer["v_proj"]["kernel"]).reshape(cq, kvh, hd)
        q = _rope(q[None], positions[None], c.rope_theta)[0]
        k_new = _rope(k_new[None], positions[None], c.rope_theta)[0]
        kv_vals, kv_scales = encode_kv(k_new, spec)
        vv_vals, vv_scales = encode_kv(v_new, spec)
        k_l = lax.dynamic_update_slice(
            k_l, kv_vals[None], (slot, start, 0, 0))
        v_l = lax.dynamic_update_slice(
            v_l, vv_vals[None], (slot, start, 0, 0))
        if ks_l is not None:
            ks_l = lax.dynamic_update_slice(
                ks_l, kv_scales[None], (slot, start, 0, 0))
            vs_l = lax.dynamic_update_slice(
                vs_l, vv_scales[None], (slot, start, 0, 0))
        k_slot = lax.dynamic_index_in_dim(k_l, slot, 0, keepdims=False)
        v_slot = lax.dynamic_index_in_dim(v_l, slot, 0, keepdims=False)
        ks_slot = (lax.dynamic_index_in_dim(ks_l, slot, 0, False)
                   if ks_l is not None else None)
        vs_slot = (lax.dynamic_index_in_dim(vs_l, slot, 0, False)
                   if vs_l is not None else None)
        attn = _chunk_attention(q, k_slot, ks_slot, v_slot, vs_slot,
                                start, spec, c)
        x_mid = x_in + attn @ layer["o_proj"]["kernel"]
        ffn_in = _rms_norm(x_mid, layer["post_norm"]["scale"], c.rms_eps)
        gate = jax.nn.silu(ffn_in @ layer["gate_proj"]["kernel"])
        up = ffn_in @ layer["up_proj"]["kernel"]
        ffn = (gate * up) @ layer["down_proj"]["kernel"]
        return x_mid + ffn, (k_l, ks_l, v_l, vs_l)

    k, ks, v, vs = _cache_xs(cache)
    xs = (params["layers"], k, ks, v, vs)
    x, (k, ks, v, vs) = lax.scan(block, x, xs)
    x = _rms_norm(x, params["norm"]["scale"], c.rms_eps)
    last = lax.dynamic_index_in_dim(
        x, jnp.clip(n_valid - 1, 0, cq - 1), 0, keepdims=False)
    logits = (last @ params["lm_head"]["kernel"].astype(c.compute_dtype))
    length = cache["length"]
    length = length.at[slot].set((start + n_valid).astype(jnp.int32))
    return _rebuild_cache(cache, k, ks, v, vs, length), \
        logits.astype(jnp.float32)


def prefill_sequence(params, cache, tokens, slot, config: LlamaConfig,
                     spec):
    """One-shot prefill of a whole prompt into slot ``slot`` (start
    must be 0: a freshly admitted slot), returning ``(cache,
    last_logits [V])``.

    Unlike ``prefill_chunk`` this routes the prompt through the
    TRAINING forward itself — ``_attention_block`` with ``return_kv``,
    so flash kernels, packed-segment masking and the ``seq_axis`` RING
    attention path (``ops.ring_attention``) all apply for long-context
    configs, and the hidden states (hence the first generated token)
    are bitwise the training ``apply``'s. The long-prompt path of the
    promotion scenario; continuous batching admits through
    ``prefill_chunk`` so the batch never stalls."""
    c = config
    p = tokens.shape[0]
    x = params["embed_tokens"]["embedding"][tokens][None].astype(
        c.compute_dtype)
    positions = jnp.broadcast_to(jnp.arange(p), (1, p))

    def block(carry, xs):
        from dlrover_tpu.serving.kv_cache import encode_kv

        x_in, block_rng = carry
        layer, k_l, ks_l, v_l, vs_l = xs
        layer = cast_floats(layer, c.compute_dtype)
        block_rng, ffn_rng = jax.random.split(block_rng)
        attn_in = _rms_norm(x_in, layer["input_norm"]["scale"], c.rms_eps)
        attn, (k_new, v_new) = _attention_block(
            attn_in, layer, c, positions, return_kv=True)
        x_mid = x_in + attn
        kv_vals, kv_scales = encode_kv(k_new[0], spec)
        vv_vals, vv_scales = encode_kv(v_new[0], spec)
        k_l = lax.dynamic_update_slice(
            k_l, kv_vals[None], (slot, 0, 0, 0))
        v_l = lax.dynamic_update_slice(
            v_l, vv_vals[None], (slot, 0, 0, 0))
        if ks_l is not None:
            ks_l = lax.dynamic_update_slice(
                ks_l, kv_scales[None], (slot, 0, 0, 0))
            vs_l = lax.dynamic_update_slice(
                vs_l, vv_scales[None], (slot, 0, 0, 0))
        ffn_in = _rms_norm(x_mid, layer["post_norm"]["scale"], c.rms_eps)
        ffn_out, _aux, _dropped, _load = _ffn_block(
            ffn_in, layer, c, ffn_rng)
        return (x_mid + ffn_out, block_rng), (k_l, ks_l, v_l, vs_l)

    k, ks, v, vs = _cache_xs(cache)
    xs = (params["layers"], k, ks, v, vs)
    (x, _), (k, ks, v, vs) = lax.scan(
        block, (x, jax.random.PRNGKey(0)), xs)
    x = _rms_norm(x, params["norm"]["scale"], c.rms_eps)
    logits = (x[0, -1] @ params["lm_head"]["kernel"].astype(
        c.compute_dtype))
    length = cache["length"].at[slot].set(jnp.int32(p))
    return _rebuild_cache(cache, k, ks, v, vs, length), \
        logits.astype(jnp.float32)


# -- training glue ----------------------------------------------------------


def make_init_fn(config: LlamaConfig):
    return partial(init, config=config)


def make_loss_fn(config: LlamaConfig, z_loss_weight: float = 0.0,
                 head_chunk: int = 0):
    """Causal-LM loss over batches {"input_ids", "labels"} (labels==-100
    are masked, HF convention).

    ``head_chunk`` > 0 fuses the lm head with the cross entropy over
    sequence chunks (``losses.chunked_lm_head_loss``) so the [B, S, V]
    f32 logits never materialize — the memory lever for long sequences
    and large vocabularies.
    """

    def loss_fn(params, batch, rng):
        segment_ids = batch.get("segment_ids")
        moe = config.num_experts > 0
        extra = {}
        if head_chunk > 0:
            out = apply_hidden(
                params, batch["input_ids"], config, rng,
                segment_ids=segment_ids, with_moe_metrics=moe,
            )
            hidden, moe_aux = out[0], out[1]
            loss = chunked_lm_head_loss(
                hidden, params["lm_head"]["kernel"], batch["labels"],
                chunk_size=head_chunk, z_loss_weight=z_loss_weight,
            )
        else:
            out = apply(params, batch["input_ids"], config,
                        rng, segment_ids=segment_ids, with_moe_metrics=moe)
            moe_aux = out[1]
            loss = masked_lm_loss(out[0], batch["labels"], z_loss_weight)
        if moe:
            loss = loss + config.moe_aux_weight * moe_aux / max(
                1, config.num_layers
            )
            # load-balance observability: ride the step-metrics dict
            # (switch_gating.py:24-195 parity — overflow accounting)
            extra = dict(out[2])
        return loss, extra

    return loss_fn


def param_count(config: LlamaConfig) -> int:
    return common_param_count(partial(init, config=config))

