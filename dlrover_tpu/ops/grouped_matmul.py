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

The row buffer has a static size, and a caller may say how many of its
row-tiles hold rows (``num_tiles``): a tile past them is skipped,
forward and backward: no product, no copy of its rows or of a weight
block, zeros written. Every grid runs the row-tiles INNERMOST, so the
consecutive tiles of one expert keep its weight block resident: the
weights are read once a call, not once a row-tile.

Backward is a custom VJP:
  dx = dy @ w[e]^T       — contracting over the LAST axis of both (as
  ``q k^T`` does), so no transposed copy of the weights is made;
  dw[e] = sum over e's tiles of x_tile^T @ dy_tile — accumulated in
  float32 scratch over the expert's consecutive tiles and written
  once, in the weights' dtype, at its last tile (tiles of one expert
  are contiguous by construction, so no output block is ever revisited
  after being left).

Everything accumulates in f32 regardless of input dtype. The kernels
are named ``gmm``, ``gmm_dx`` and ``gmm_dw`` in a trace.
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


# Mosaic gives one kernel 16 MiB of scoped VMEM on the v5e unless asked
# for more (the chip has 128 MiB), and the pallas pipeline
# double-buffers every block. The kernels ask for 64 MiB and size their
# tiles against most of it: the wider an expert's resident weight
# block, the fewer times the rows are read again. ``block_f`` is
# therefore an UPPER bound: each kernel shrinks its tile until this
# estimate of its working set fits (the chip's compiler refuses one
# that does not; interpret mode never notices).
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_VMEM_BUDGET_BYTES = 40 * 1024 * 1024


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _fit_block(dim: int, want: int, working_set) -> int:
    """``_pick_block``, shrunk while ``working_set(block)`` bytes exceed
    the VMEM budget. When nothing fits it returns the smallest legal
    tile and the compiler's refusal stands — nothing catches it."""
    block = _pick_block(dim, want)
    budget = _VMEM_BUDGET_BYTES
    while working_set(block) > budget:
        smaller = _pick_block(dim, block - 1) if block > 1 else block
        if smaller >= block or (block % 128 == 0 and smaller % 128):
            break  # no smaller lane-aligned divisor
        block = smaller
    return block


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _last_real(i, num_tiles):
    """Row-tile ``i``, or the last real one where ``i`` is past them: an
    index map that holds it copies nothing for a skipped tile."""
    return jnp.minimum(i, num_tiles[0] - 1)


def _fwd_kernel(tile_expert_ref, num_tiles_ref, x_ref, w_ref, y_ref):
    """``y = x @ w[e]`` of a real row-tile; a tile past the first
    ``num_tiles`` is written as zeros and costs no product (its blocks
    are not copied either: the index maps hold the last real tile's)."""
    del tile_expert_ref  # consumed by the index maps
    real = pl.program_id(1) < num_tiles_ref[0]  # row-tiles innermost

    @pl.when(real)
    def _compute():
        y_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(y_ref.dtype)

    @pl.when(jnp.logical_not(real))
    def _skip():
        y_ref[...] = jnp.zeros_like(y_ref)


def _dx_kernel(tile_expert_ref, num_tiles_ref, dy_ref, w_ref, dx_ref):
    """``dx = dy @ w[e]^T`` of a real row-tile, contracting over the
    LAST axis of both (as ``q k^T`` does), so no transposed copy of the
    weights is ever made; zeros past the real tiles."""
    del tile_expert_ref  # consumed by the index maps
    real = pl.program_id(1) < num_tiles_ref[0]  # row-tiles innermost

    @pl.when(real)
    def _compute():
        dx_ref[...] = jax.lax.dot_general(
            dy_ref[...], w_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dx_ref.dtype)

    @pl.when(jnp.logical_not(real))
    def _skip():
        dx_ref[...] = jnp.zeros_like(dx_ref)


def _dw_kernel(tile_expert_ref, num_tiles_ref, x_ref, dy_ref, dw_ref,
               acc_ref):
    """``dw[e] = sum of x_tile^T @ dy_tile`` over an expert's real
    row-tiles (consecutive, the grid's innermost axis), accumulated in
    float32 scratch and written once, in the weights' own dtype, at the
    expert's last real tile: no float32 [E, D, F] array and no pass to
    round it."""
    i = pl.program_id(2)
    n = num_tiles_ref[0]

    @pl.when(i < n)
    def _compute():
        e_here = tile_expert_ref[i]
        first = jnp.logical_or(
            i == 0, e_here != tile_expert_ref[jnp.maximum(i - 1, 0)])
        last = jnp.logical_or(
            i == n - 1,
            e_here != tile_expert_ref[jnp.minimum(i + 1, n - 1)])
        contrib = jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(first)
        def _init():
            acc_ref[...] = contrib

        @pl.when(jnp.logical_not(first))
        def _acc():
            acc_ref[...] = acc_ref[...] + contrib

        @pl.when(last)
        def _write():
            dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)


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
        interpret=interpret, compiler_params=_compiler_params(),
    )(tile_expert, values, scales, w)


def _grouped_matmul_fwd(x, w, tile_expert, num_tiles, block_t, block_f,
                        interpret):
    tp, d = x.shape
    e, dw_, f = w.shape
    assert d == dw_, (x.shape, w.shape)
    assert tp % block_t == 0, (tp, block_t)
    xb, wb = x.dtype.itemsize, w.dtype.itemsize
    # the contraction dim D stays resident: [block_t, D] rows and a
    # [D, bf] weight tile, double-buffered, plus the f32 product
    bf = _fit_block(f, block_f, lambda b: (
        2 * (block_t * d * xb + d * b * wb + block_t * b * xb)
        + block_t * b * 4))
    # grid (f block, row-tile), the row-tiles INNERMOST: consecutive
    # tiles of one expert keep its [D, bf] weight block resident, so the
    # weights are read once a call and not once a row-tile; the rows are
    # read once an f block. A tile past the real ones keeps the last
    # real tile's block indices: nothing is copied for it.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(f // bf, tp // block_t),
        in_specs=[
            pl.BlockSpec((block_t, d),
                         lambda j, i, te, n: (_last_real(i, n), 0)),
            pl.BlockSpec((1, d, bf),
                         lambda j, i, te, n: (te[_last_real(i, n)], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, bf), lambda j, i, te, n: (i, j)),
    )
    return pl.pallas_call(
        _fwd_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, f), x.dtype),
        interpret=interpret, name="gmm",
        compiler_params=_compiler_params(),
    )(tile_expert, num_tiles, x, w)


def _grouped_matmul_dx(dy, w, tile_expert, num_tiles, block_t, block_d,
                       interpret):
    """``dx[i] = dy[i] @ w[tile_expert[i // block_t]]^T`` for ``w`` [E,
    D, F] as the forward holds it: the contraction F is whole in every
    block, the output D is tiled."""
    tp, f = dy.shape
    _, d, fw = w.shape
    assert f == fw and tp % block_t == 0, (dy.shape, w.shape, block_t)
    yb, wb = dy.dtype.itemsize, w.dtype.itemsize
    # as the forward: (d block, row-tile) with the row-tiles innermost,
    # an expert's [bd, F] weight block resident over its tiles
    bd = _fit_block(d, block_d, lambda b: (
        2 * (block_t * f * yb + b * f * wb + block_t * b * yb)
        + block_t * b * 4))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d // bd, tp // block_t),
        in_specs=[
            pl.BlockSpec((block_t, f),
                         lambda j, i, te, n: (_last_real(i, n), 0)),
            pl.BlockSpec((1, bd, f),
                         lambda j, i, te, n: (te[_last_real(i, n)], j, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, bd), lambda j, i, te, n: (i, j)),
    )
    return pl.pallas_call(
        _dx_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, d), dy.dtype),
        interpret=interpret, name="gmm_dx",
        compiler_params=_compiler_params(),
    )(tile_expert, num_tiles, dy, w)


def _grouped_matmul_dw(x, dy, tile_expert, num_tiles, num_experts,
                       block_t, block_f, interpret, out_dtype):
    tp, d = x.shape
    _, f = dy.shape
    xb, ob = x.dtype.itemsize, jnp.dtype(out_dtype).itemsize

    # D and F are both OUTPUT dims of dw, so both tile: a [bd, bf]
    # output block (double-buffered) and its float32 scratch (plus the
    # product being added) next to the [block_t, bd] and [block_t, bf]
    # rows, which are read again once an (F block, D block)
    def working_set(bd, bf):
        return (2 * (block_t * (bd + bf) * xb + bd * bf * ob)
                + 2 * bd * bf * 4)

    bf = _fit_block(f, block_f, lambda b: working_set(d, b))
    bd = _fit_block(d, d, lambda b: working_set(b, bf))
    # row-tiles FASTEST (innermost): consecutive steps sharing an expert
    # accumulate into the scratch block; a left block is never
    # revisited because each expert's tiles are contiguous
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d // bd, f // bf, tp // block_t),
        in_specs=[
            pl.BlockSpec((block_t, bd),
                         lambda k, j, i, te, n: (_last_real(i, n), k)),
            pl.BlockSpec((block_t, bf),
                         lambda k, j, i, te, n: (_last_real(i, n), j)),
        ],
        out_specs=pl.BlockSpec(
            (1, bd, bf),
            lambda k, j, i, te, n: (te[_last_real(i, n)], k, j)),
        scratch_shapes=[pltpu.VMEM((bd, bf), jnp.float32)],
    )
    return pl.pallas_call(
        _dw_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_experts, d, f), out_dtype),
        interpret=interpret, name="gmm_dw",
        compiler_params=_compiler_params(),
    )(tile_expert, num_tiles, x, dy)


def _all_tiles(rows: int, block_t: int):
    return jnp.full((1,), rows // block_t, jnp.int32)


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


def grouped_matmul(x, w, tile_expert, block_t=128, block_f=2048,
                   interpret=None, num_tiles=None):
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
      block_f: upper bound on the width of the resident weight block.
      interpret: None = auto (interpreter off TPU, Mosaic on TPU);
        False forces Mosaic (the deviceless-AOT contract).
      num_tiles: int32 ``[1]``, at least 1: only the first that many
        row-tiles hold rows; a later one is skipped, forward and
        backward, and reads back as zeros. The contract above then
        holds over the real tiles, and ``tile_expert`` holds any value
        of the last real tile's expert or above over the rest. None:
        every tile is real.
    Returns [Tp, F] in x's dtype (f32 accumulation inside).
    """
    _check_tile_expert(tile_expert, w.shape[0])
    if num_tiles is None:
        num_tiles = _all_tiles(x.shape[0], block_t)
    return _grouped_matmul(x, w, tile_expert, num_tiles, block_t, block_f,
                           _auto_interpret(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _grouped_matmul(x, w, tile_expert, num_tiles, block_t, block_f,
                    interpret):
    return _grouped_matmul_fwd(x, w, tile_expert, num_tiles, block_t,
                               block_f, interpret)


def _gm_fwd(x, w, tile_expert, num_tiles, block_t, block_f, interpret):
    y = _grouped_matmul_fwd(x, w, tile_expert, num_tiles, block_t,
                            block_f, interpret)
    return y, (x, w, tile_expert, num_tiles)


def _gm_bwd(block_t, block_f, interpret, res, dy):
    x, w, tile_expert, num_tiles = res
    dy = dy.astype(x.dtype)
    dx = _grouped_matmul_dx(dy, w, tile_expert, num_tiles, block_t,
                            block_f, interpret)
    dw = _grouped_matmul_dw(x, dy, tile_expert, num_tiles, w.shape[0],
                            block_t, block_f, interpret, w.dtype)
    return dx, dw, None, None


_grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


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
        x_deq, dy.astype(x_deq.dtype), tile_expert,
        _all_tiles(x_deq.shape[0], block_t), w.shape[0], block_t,
        block_f, interp, w.dtype)
    return jnp.zeros_like(values), jnp.zeros_like(scales), dw, None


grouped_matmul_quantized.defvjp(_gmq_fwd, _gmq_bwd)
