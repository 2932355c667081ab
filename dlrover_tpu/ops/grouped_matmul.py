"""Grouped matmul Pallas kernel — the dropless-MoE expert compute.

``y[i] = x[i] @ w[expert_of_row_i]`` where rows are SORTED by expert and
every expert's group is padded to a multiple of the row-tile, so each
row-tile belongs to exactly one expert. The per-tile expert index rides
scalar prefetch (``PrefetchScalarGridSpec``), and the kernel picks that
expert's weight block via the BlockSpec index map — no [T, E, C]
one-hot tensors, no capacity, no dropped tokens.

Role parity: the reference delegates its MoE hot path to a fused CUDA
backend (``atorch/atorch/modules/moe/moe_layer.py:511`` fastmoe); the
public megablocks line of work frames the same computation as
block-sparse "grouped GEMM". The TPU formulation here: tile-aligned
group padding costs at most ``E * (block_t - 1)`` pad rows — versus the
capacity approach's ``(factor - 1) * T`` padded slots PLUS dropped
overflow tokens — and the MXU sees plain dense [block_t, D] x
[D, block_f] tiles.

Backward is a custom VJP:
  dx = dy @ w[e]^T       — the same kernel over transposed weights;
  dw[e] = sum over e's tiles of x_tile^T @ dy_tile — an accumulation
  kernel whose grid runs row-tiles FASTEST so consecutive steps that
  share an expert keep the output block resident and accumulate
  (tiles of one expert are contiguous by construction, so no output
  block is ever revisited after being left).

Everything accumulates in f32 regardless of input dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_block(dim: int, want: int) -> int:
    """Largest tile <= ``want`` that divides ``dim``, preferring
    lane-aligned multiples of 128 (Mosaic's happy path); falls back to
    any divisor, then to ``dim`` itself."""
    want = min(want, dim)
    for cand in range(want - want % 128, 0, -128):
        if dim % cand == 0:
            return cand
    for cand in range(want, 0, -1):
        if dim % cand == 0:
            return cand
    return dim


# Mosaic gives one kernel 16 MiB of scoped VMEM on the v5e and the
# pallas pipeline double-buffers every block. ``block_f`` is therefore
# an UPPER bound: each kernel shrinks its tile until this estimate of
# its working set fits (at 4096 -> 11008 the caller's 512 does not —
# the chip's compiler refuses it, interpret mode never notices).
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def _fit_block(dim: int, want: int, working_set) -> int:
    """``_pick_block``, shrunk while ``working_set(block)`` bytes exceed
    the VMEM budget. When nothing fits it returns the smallest legal
    tile and the compiler's refusal stands — nothing catches it."""
    block = _pick_block(dim, want)
    while working_set(block) > _VMEM_BUDGET_BYTES:
        smaller = _pick_block(dim, block - 1) if block > 1 else block
        if smaller >= block or (block % 128 == 0 and smaller % 128):
            break  # no smaller lane-aligned divisor
        block = smaller
    return block


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _fwd_kernel(tile_expert_ref, x_ref, w_ref, y_ref):
    del tile_expert_ref  # consumed by the index maps
    y_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(y_ref.dtype)


def _dw_kernel(tile_expert_ref, x_ref, dy_ref, dw_ref):
    i = pl.program_id(2)  # row-tile index (fastest grid dim)
    e_here = tile_expert_ref[i]
    e_prev = tile_expert_ref[jnp.maximum(i - 1, 0)]
    first = jnp.logical_or(i == 0, e_here != e_prev)
    contrib = jax.lax.dot_general(
        x_ref[...], dy_ref[...],
        (((0,), (0,)), ((), ())),  # [block_t, D]^T @ [block_t, F]
        preferred_element_type=jnp.float32,
    )

    @pl.when(first)
    def _init():
        dw_ref[0] = contrib.astype(dw_ref.dtype)

    @pl.when(jnp.logical_not(first))
    def _acc():
        dw_ref[0] = (dw_ref[0] + contrib).astype(dw_ref.dtype)


def _fwd_kernel_quant(tile_expert_ref, x_ref, s_ref, w_ref, y_ref):
    """The quantized-LHS forward kernel: dequantize the fp8 row tile
    IN KERNEL (one f32 multiply per element against the per-block
    scales riding their own tile) and run the same f32-accumulating
    dot. The multiply happens in f32 exactly like
    ``ops.quantize.dequantize_block_scaled``, so this kernel is bitwise
    equal to dequant-then-``_fwd_kernel`` — the oracle contract the
    tests pin."""
    del tile_expert_ref  # consumed by the index maps
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    bt, d = x.shape
    nb = s.shape[1]
    x = (x.reshape(bt, nb, d // nb) * s[:, :, None]).reshape(bt, d)
    y_ref[...] = jax.lax.dot_general(
        x, w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(y_ref.dtype)


def _grouped_matmul_fwd_quant(values, scales, w, tile_expert, block_t,
                              block_f, interpret, out_dtype):
    tp, d = values.shape
    e, dw_, f = w.shape
    assert d == dw_, (values.shape, w.shape)
    assert tp % block_t == 0, (tp, block_t)
    nb = scales.shape[1]
    num_t = tp // block_t
    wb, ob = w.dtype.itemsize, jnp.dtype(out_dtype).itemsize
    # the row tile enters at 1 B/elem and is dequantized to f32 in
    # kernel (values and product: two f32 [block_t, D] temporaries)
    rows = block_t * (2 * (d + nb * 4) + 2 * d * 4)
    bf = _fit_block(f, block_f, lambda b: (
        rows + 2 * (d * b * wb + block_t * b * ob) + block_t * b * 4))
    num_f = f // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_t, num_f),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j, te: (i, 0)),
            pl.BlockSpec((block_t, nb), lambda i, j, te: (i, 0)),
            pl.BlockSpec((1, d, bf), lambda i, j, te: (te[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, bf), lambda i, j, te: (i, j)),
    )
    return pl.pallas_call(
        _fwd_kernel_quant,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, f), out_dtype),
        interpret=interpret,
    )(tile_expert, values, scales, w)


def _grouped_matmul_fwd(x, w, tile_expert, block_t, block_f, interpret):
    tp, d = x.shape
    e, dw_, f = w.shape
    assert d == dw_, (x.shape, w.shape)
    assert tp % block_t == 0, (tp, block_t)
    num_t = tp // block_t
    xb, wb = x.dtype.itemsize, w.dtype.itemsize
    # the contraction dim D stays resident: [block_t, D] rows and a
    # [D, bf] weight tile, double-buffered, plus the f32 product
    bf = _fit_block(f, block_f, lambda b: (
        2 * (block_t * d * xb + d * b * wb + block_t * b * xb)
        + block_t * b * 4))
    num_f = f // bf

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_t, num_f),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j, te: (i, 0)),
            pl.BlockSpec((1, d, bf), lambda i, j, te: (te[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, bf), lambda i, j, te: (i, j)),
    )
    return pl.pallas_call(
        _fwd_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, f), x.dtype),
        interpret=interpret,
    )(tile_expert, x, w)


def _grouped_matmul_dw(x, dy, tile_expert, num_experts, block_t, block_f,
                       interpret):
    tp, d = x.shape
    _, f = dy.shape
    num_t = tp // block_t
    xb = x.dtype.itemsize

    # D and F are both OUTPUT dims of dw, so both tile: an f32
    # [bd, bf] block stays resident (double-buffered, plus the product
    # being added) next to the [block_t, bd] and [block_t, bf] rows
    def working_set(bd, bf):
        return (2 * (block_t * (bd + bf) * xb + bd * bf * 4)
                + bd * bf * 4)

    bf = _fit_block(f, block_f, lambda b: working_set(d, b))
    bd = _fit_block(d, d, lambda b: working_set(b, bf))
    num_f = f // bf
    num_d = d // bd

    # row-tiles FASTEST (innermost): consecutive steps sharing an expert
    # accumulate into the resident output block; a left block is never
    # revisited because each expert's tiles are contiguous
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_d, num_f, num_t),
        in_specs=[
            pl.BlockSpec((block_t, bd), lambda k, j, i, te: (i, k)),
            pl.BlockSpec((block_t, bf), lambda k, j, i, te: (i, j)),
        ],
        out_specs=pl.BlockSpec(
            (1, bd, bf), lambda k, j, i, te: (te[i], k, j)),
    )
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_experts, d, f), jnp.float32),
        interpret=interpret,
    )(tile_expert, x, dy)


def _check_tile_expert(tile_expert, num_experts: int):
    """Cheap debug-mode contract check, CONCRETE values only (a traced
    ``tile_expert`` — the jitted production path — skips it for free).

    The two contract violations it catches produce silent garbage on
    real TPU but NOT in interpret mode: the interpreter zero-fills
    pallas output buffers, so (a) an expert absent from ``tile_expert``
    reads back a zero dw block instead of the uninitialized garbage
    Mosaic would leave, and (b) a non-monotone ``tile_expert`` revisits
    a dw block the accumulation kernel already left, whose first-tile
    predicate then re-INITIALIZES it, silently dropping the earlier
    tiles' contributions.
    """
    if isinstance(tile_expert, jax.core.Tracer):
        return
    import numpy as np

    te = np.asarray(tile_expert)
    if te.size and np.any(np.diff(te) < 0):
        raise ValueError(
            "grouped_matmul: tile_expert must be NON-DECREASING (each "
            "expert's tiles contiguous) — the dw kernel accumulates "
            "into the resident output block and never revisits one; "
            f"got {te.tolist()}"
        )
    missing = sorted(set(range(num_experts)) - set(int(v) for v in te))
    if missing:
        raise ValueError(
            "grouped_matmul: every expert 0..E-1 must own at least one "
            f"row-tile, but experts {missing} are absent from "
            "tile_expert — their dw output blocks would be "
            "UNINITIALIZED garbage on real TPU (interpret mode "
            "zero-fills, masking the bug). Give each empty expert one "
            "sentinel tile of zero rows (see ops.moe._moe_compute_grouped)"
        )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def grouped_matmul(x, w, tile_expert, block_t=128, block_f=512,
                   interpret=None):
    """``y[i] = x[i] @ w[tile_expert[i // block_t]]``.

    Args:
      x: [Tp, D] rows sorted by expert, each expert's group padded to a
        multiple of ``block_t`` (pad rows may be garbage; their outputs
        are garbage and must be masked by the caller's un-sort).
      w: [E, D, F] per-expert weights.
      tile_expert: [Tp // block_t] int32, the expert owning each
        row-tile — every row in a tile MUST share the expert (the
        tile-aligned padding guarantees it). Two further contract
        requirements exist for the BACKWARD pass and are invisible in
        interpret mode (which zero-fills output buffers):
        * every expert 0..E-1 must appear at least once — an expert
          owning no tile leaves its dw output block UNINITIALIZED
          (garbage) on real TPU, because the accumulation grid never
          visits it. Callers give empty experts one sentinel tile of
          zero rows (``ops.moe._moe_compute_grouped``).
        * values must be NON-DECREASING (each expert's tiles
          contiguous) — the dw kernel initializes an expert's block on
          its first tile and accumulates while resident; a revisited
          block would be re-initialized, dropping earlier tiles.
        Concrete (non-traced) ``tile_expert`` values are validated at
        call time (``_check_tile_expert``); traced values are the
        caller's responsibility.
      interpret: None = auto (interpreter off TPU, Mosaic on TPU);
        False forces Mosaic (the deviceless-AOT contract).
    Returns [Tp, F] in x's dtype (f32 accumulation inside).
    """
    _check_tile_expert(tile_expert, w.shape[0])
    interp = _auto_interpret(interpret)
    return _grouped_matmul_fwd(x, w, tile_expert, block_t, block_f,
                               interp)


def _gm_fwd(x, w, tile_expert, block_t, block_f, interpret):
    y = grouped_matmul(x, w, tile_expert, block_t, block_f, interpret)
    return y, (x, w, tile_expert)


def _gm_bwd(block_t, block_f, interpret, res, dy):
    x, w, tile_expert = res
    interp = _auto_interpret(interpret)
    # dx: the same grouped product against w^T ([E, F, D])
    w_t = jnp.swapaxes(w, 1, 2)
    dx = _grouped_matmul_fwd(
        dy.astype(x.dtype), w_t, tile_expert, block_t, block_f, interp
    )
    dw = _grouped_matmul_dw(
        x, dy.astype(x.dtype), tile_expert, w.shape[0], block_t,
        block_f, interp
    ).astype(w.dtype)
    return dx.astype(x.dtype), dw, None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def grouped_matmul_quantized(values, scales, w, tile_expert,
                             block_t=128, block_f=512, interpret=None,
                             out_dtype=jnp.float32):
    """``grouped_matmul`` over a BLOCK-SCALED fp8 LHS, dequantized IN
    KERNEL: ``y[i] = (values[i] * scales[i])  @ w[tile_expert[i //
    block_t]]`` where ``values`` is [Tp, D] e4m3 and ``scales`` is
    [Tp, D/block] f32 (``ops.quantize.quantize_block_scaled`` layout;
    pad rows carry zero values, so any scale decodes them to zero).

    The contract the tests pin: bitwise equal to
    ``grouped_matmul(dequantize_block_scaled(values, scales), w, ...)``
    — the dequant multiply runs in f32 inside the kernel exactly as the
    standalone decode does, so fusing it costs nothing numerically
    while the rows enter the kernel at wire precision (the point: the
    [Tp, D] buffer the exchange produced is never re-materialized at
    4x/2x the bytes just to feed the GEMM).

    Differentiable in ``w`` ONLY: ``dw[e] = dequant(values, scales)^T @
    dy`` through the same accumulation kernel as the unquantized path.
    ``values``/``scales`` get zero cotangents — they arrived over the
    wire already quantized; the activation gradient flows through the
    caller's wire boundary (``ops.moe``'s quantized exchange defines
    the straight-through chain), not through the encode.
    """
    interp = _auto_interpret(interpret)
    return _grouped_matmul_fwd_quant(values, scales, w, tile_expert,
                                     block_t, block_f, interp,
                                     out_dtype)


def _gmq_fwd(values, scales, w, tile_expert, block_t, block_f,
             interpret, out_dtype):
    y = grouped_matmul_quantized(values, scales, w, tile_expert,
                                 block_t, block_f, interpret, out_dtype)
    return y, (values, scales, w, tile_expert)


def _gmq_bwd(block_t, block_f, interpret, out_dtype, res, dy):
    from dlrover_tpu.ops.quantize import dequantize_block_scaled

    values, scales, w, tile_expert = res
    interp = _auto_interpret(interpret)
    x_deq = dequantize_block_scaled(values, scales, jnp.float32)
    dw = _grouped_matmul_dw(
        x_deq, dy.astype(x_deq.dtype), tile_expert, w.shape[0],
        block_t, block_f, interp,
    ).astype(w.dtype)
    return jnp.zeros_like(values), jnp.zeros_like(scales), dw, None


grouped_matmul_quantized.defvjp(_gmq_fwd, _gmq_bwd)
