"""Pallas TPU flash attention.

Role parity: the FlashAttention adapters the reference injects into HF
models (``atorch/atorch/modules/transformer/layers.py:729-1502`` — thin
wrappers over the external CUDA ``flash_attn`` package). Here the kernel
itself is in-tree, written for the TPU memory hierarchy: Q/K/V blocks are
streamed HBM->VMEM by the pallas pipeline, the [Bq, Bk] logits tile lives
only in registers/VMEM, and softmax is computed online (running max +
normalizer in VMEM scratch carried across the K grid dimension), so HBM
traffic is O(S*D) instead of O(S^2).

Forward and backward are Pallas kernels (FlashAttention-2 style: a dKV
pass with k blocks outer / q blocks inner, and a dQ pass with q outer / k
inner), recomputing probability tiles from the saved logsumexp — no
O(S^2) residuals are ever materialized. All MXU dots run on the storage
dtype (bf16) with f32 accumulation. Long-context scaling across chips is
handled one level up by ``ops.ring_attention``.

GQA is native: K/V may carry fewer heads than Q (``num_kv_heads``
divides ``num_heads``); the kernels index the shared KV block per query
group (``h // group`` in the BlockSpec index maps) instead of
materializing repeated heads, so HBM traffic for K/V is ``kv/h`` of the
MHA equivalent (the reference pays the full repeat before its CUDA
kernel, ``modules/transformer/layers.py:1268``). ``flash_attention_lse``
additionally returns the per-row logsumexp and is differentiable in it,
which is what lets ``ring_attention`` rescale and merge per-ring-step
outputs without ever forming an [S, S] tile.

Entry points (each has an ``_auto`` form, or is one, that routes itself
through ``shard_map`` under a mesh: GSPMD cannot partition a Mosaic
call):

* ``flash_attention`` / ``flash_attention_lse`` / ``flash_attention_auto``:
  causal or not; kernels ``flash_fwd``, ``flash_dkv``, ``flash_dq``;
* ``flash_attention_window`` (``flash_attention_auto(window=...)``): a
  causal band, query ``t`` sees key ``j`` where ``t - window < j <= t``;
  kernels ``flash_win_fwd`` and ``flash_win_bwd`` (``flash_win_dkv`` and
  ``_dq`` on rows too long for it), on the band's tiles only (``band_walk``);
* ``flash_attention_segmented`` (packed documents),
  ``flash_attention_segmented_pair_lse`` (ring steps),
  ``flash_attention_prefix`` / ``_lse`` (prefix-LM).

The first two ops' forward rules and the three latent ops' (below:
``flash_attention_mla``, ``flash_attention_mla_grouped``,
``flash_attention_mla_by_kind``) name their kernel's output and
logsumexp (``KEPT_NAMES``), as results and as residuals: a layer whose
checkpoint keeps the names (``ops.remat.apply_remat``'s ``keep``) does
not run ``flash_fwd``, ``flash_win_fwd``, ``flash_mla_fwd`` or
``flash_mla_win_fwd`` again in its replay; a caller that keeps nothing
runs what it ran before. The segmented and the prefix rules name
nothing.

Shapes and blocks: q ``[B, H, S, D]``, k ``[B, H_kv, S, D]``, v ``[B,
H_kv, S, Dv]``; ``Dv`` may differ from ``D`` (differential attention
reads values of 128 with queries and keys of 64) and is the width of
the output and of the dV and output accumulators. Block sizes are
fitted to divisors of the sequence (``_fit_block``); on the chip
``block_q`` is a multiple of 128 or the whole sequence (the per-row
residuals ride the lanes), as is ``block_k`` with segment ids; a window
walks the band in tiles chosen from the row and the window
(``window_tiles``: sides of 1024 or 512 by default, the forward's and
the backward's each their own), each tile run by the body of its kind
(``band_walk``). ``D`` and ``Dv`` are whole in
every block, so any width lowers that fills a tile's lanes or is the
array's own (64 and 128 are compiled for the v5e in
``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.telemetry.names import StepCounter

NEG_INF = float(jnp.finfo(jnp.float32).min)
LANES = 128
# the plain, the window and the latent ops' output and logsumexp, as
# their forward rules name them (``_kept``): what a layer's checkpoint
# keeps so that its replay leaves the op's forward kernel out
KEPT_NAMES = ("flash_attn_out", "flash_attn_lse")


def _fit_block(requested: int, dim: int) -> int:
    """Largest divisor of ``dim`` that is <= ``requested`` — block sizes
    must tile the sequence exactly, but callers shouldn't have to match
    the defaults to their sequence length. Sequences whose only fitting
    blocks would break the TPU sublane rule (multiple of 8, unless the
    block covers the whole dim) are rejected with a clear error rather
    than silently degrading to tiny blocks."""
    b = min(requested, dim)
    while dim % b:
        b -= 1
    if b != dim and b % 8:
        raise ValueError(
            f"no legal block tiling for sequence length {dim} under block "
            f"size {requested}: best divisor {b} is not a multiple of 8; "
            "pad the sequence to a multiple of 8"
        )
    return b


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref,  # [1, 1, Bq|Bk, D] VMEM blocks
    *rest,  # (+seg_q_ref, seg_k_ref when segmented; +prefix_ref when
    # prefix) o_ref, lse_ref, scratch
    scale: float, causal: bool, block_q: int, block_k: int,
    segmented: bool = False, prefix: bool = False, window: int = 0,
    kinds=(),
):
    if segmented:
        (seg_q_ref, seg_k_ref, o_ref, lse_ref,
         m_scratch, l_scratch, acc_scratch) = rest
    elif prefix:
        seg_q_ref = seg_k_ref = None
        (prefix_ref, o_ref, lse_ref,
         m_scratch, l_scratch, acc_scratch) = rest
    else:
        seg_q_ref = seg_k_ref = None
        o_ref, lse_ref, m_scratch, l_scratch, acc_scratch = rest
    i = pl.program_id(2)  # q block index
    jj = pl.program_id(3)  # k grid index (innermost, sequential on TPU)
    nk = pl.num_programs(3)
    # windowed: the grid holds the band's k tiles only, the last of
    # them the tile with the q block's last query (``band_walk``); j is
    # the tile's index in the row, and an entry before the band's first
    # tile is skipped
    j = _band_last_k(i, block_q, block_k) - (nk - 1) + jj if window else jj

    @pl.when(jj == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # with causal masking, blocks fully above the diagonal contribute
    # nothing; in prefix-LM mode a block is also needed when it holds
    # prefix columns (bidirectionally visible)
    causal_needed = jnp.logical_or(
        jnp.logical_not(causal), j * block_k <= i * block_q + block_q - 1
    )
    if prefix:
        p_len = prefix_ref[0, 0, 0]
        block_needed = jnp.logical_or(causal_needed, j * block_k < p_len)
    elif window:
        block_needed = j >= _band_first_k(i, block_q, block_k, window)
    else:
        block_needed = causal_needed

    def _compute(diagonal=True, far=True):
        # inputs stay in their storage dtype (bf16) so the MXU runs at
        # full rate; only the accumulators are f32
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [Bq, Bk] f32

        if window:
            s = _band_mask(s, i, j, block_q, block_k, window, diagonal, far)
        elif causal or prefix:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            ) + i * block_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            ) + j * block_k
            allowed = rows >= cols
            if prefix:
                # prefix-LM: the prompt is bidirectionally visible
                allowed = jnp.logical_or(allowed, cols < p_len)
            s = jnp.where(allowed, s, NEG_INF)
        if segmented:
            # packed sequences: tokens attend only within their segment
            sq = seg_q_ref[0, 0, 0, :]  # [Bq] int32
            sk = seg_k_ref[0, 0, 0, :]  # [Bk]
            s = jnp.where(sq[:, None] == sk[None, :], s, NEG_INF)

        m_prev = m_scratch[:, :1]  # [Bq, 1]
        l_prev = l_scratch[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        if segmented or prefix or (window and far):
            # a visited block can be FULLY masked for some rows (their
            # segment's keys live elsewhere; a prefix-needed block
            # past both the diagonal and the prefix for early rows; the
            # band's far tiles for the rows whose window starts later):
            # m_new stays NEG_INF there and exp(NEG_INF - NEG_INF)
            # would poison the accumulator with NaN. Clamp the
            # subtrahend — those rows have l_prev == 0, so any finite
            # alpha is harmless.
            m_sub = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
        else:
            m_sub = m_new
        p = jnp.exp(s - m_sub)  # [Bq, Bk]
        alpha = jnp.exp(m_prev - m_sub)  # correction for old accumulator
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    _when_tile(block_needed, i, j, block_q, block_k, window, kinds,
               _compute)

    @pl.when(jj == nk - 1)
    def _finalize():
        m = m_scratch[:, :1]
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        # logsumexp residual for the blockwise backward pass
        lse = m + jnp.log(l_safe)
        lse_ref[0, 0, 0, :] = lse[:, 0]


def _check_mosaic_lane_block(interpret: bool, block: int, dim: int,
                             what: str) -> None:
    """The lse/delta/segment-id operands ride the LANE dimension in
    (1, 1, 1, block)-shaped VMEM blocks, and Mosaic requires a block's
    last dim to be a multiple of 128 or cover the whole array dim.
    Production tiles (512/1024) always satisfy this; a small block on
    the real-TPU path must fail HERE with an actionable message, not in
    the lowering (interpret mode never enforces tiling — the round-4
    deviceless lowering drive is what surfaced it)."""
    if not interpret and block != dim and block % LANES:
        raise ValueError(
            f"TPU Mosaic lowering needs {what}={block} to be a "
            f"multiple of {LANES} or to cover the whole sequence "
            f"({dim}): the per-row residuals are lane-blocked by "
            f"{what}. Use {what}>=128 (or interpret=True off-TPU)."
        )


# -- the band walk ------------------------------------------------------------
#
# A window's band over tiles of ``block_q`` x ``block_k``: which k tiles
# a q block walks, which q blocks a k tile, and what a walked tile is.
# The same expressions serve Python ints (``band_walk``, from which the
# grids are built and the tiles counted) and traced scalars (the grid
# indices in a kernel or an index map); every dividend is non-negative.


def _floordiv(a, b: int):
    return a // b if isinstance(a, int) else jax.lax.div(a, jnp.int32(b))


def _clamp(a, low=None, high=None):
    if isinstance(a, int):
        a = a if low is None else max(a, low)
        return a if high is None else min(a, high)
    a = a if low is None else jnp.maximum(a, low)
    return a if high is None else jnp.minimum(a, high)


def _band_first_k(i, block_q, block_k, window):
    """The k tile that holds the first key q block ``i`` sees."""
    return _floordiv(_clamp(i * block_q - window + 1, low=0), block_k)


def _band_last_k(i, block_q, block_k):
    """The k tile that holds q block ``i``'s last query."""
    return _floordiv((i + 1) * block_q - 1, block_k)


def _band_first_q(j, block_q, block_k):
    """The q block that holds k tile ``j``'s first key."""
    return _floordiv(j * block_k, block_q)


def _band_last_q(j, block_q, block_k, window, q_blocks):
    """The last q block with a query that sees k tile ``j``'s last key."""
    return _clamp(_floordiv((j + 1) * block_k + window - 2, block_q),
                  high=q_blocks - 1)


def _band_tile_kind(i, j, block_q, block_k, window):
    """``(diagonal, far)`` of tile (i, j) of the band: whether a key of
    it lies after a query (the tile needs ``rows >= cols``) and whether
    one lies a window or more before a query (``rows - cols <
    window``). A tile that is neither is wholly visible."""
    diagonal = (j + 1) * block_k - 1 > i * block_q
    far = (i + 1) * block_q - 1 - j * block_k >= window
    return diagonal, far


class BandWalk(NamedTuple):
    k_steps: int  # k tiles a q block walks: the forward and dQ grids'
    q_steps: int  # q blocks a k tile walks: the dKV grid's
    tiles: int  # tiles of the band, over the row
    unmasked: int  # of them wholly visible
    # the ``(diagonal, far)`` kinds on the grid: a kernel holds a body
    # for each of these and no other
    kinds: Tuple[Tuple[bool, bool], ...]


@functools.lru_cache(maxsize=None)
def band_walk(seq: int, window: int, block_q: int, block_k: int) -> BandWalk:
    """The band ``t - window < j <= t`` of a row of ``seq`` over tiles
    of ``block_q`` x ``block_k`` (each divides ``seq``). The grids of
    the three window kernels and the model's ``attn_band_tiles``
    counters are read from here."""
    q_blocks, k_tiles = seq // block_q, seq // block_k
    count = {}
    k_steps = 0
    for i in range(q_blocks):
        first = _band_first_k(i, block_q, block_k, window)
        last = _band_last_k(i, block_q, block_k)
        k_steps = max(k_steps, last - first + 1)
        for j in range(first, last + 1):
            kind = _band_tile_kind(i, j, block_q, block_k, window)
            count[kind] = count.get(kind, 0) + 1
    q_steps = max(
        _band_last_q(j, block_q, block_k, window, q_blocks)
        - _band_first_q(j, block_q, block_k) + 1 for j in range(k_tiles))
    return BandWalk(k_steps, q_steps, sum(count.values()),
                    count.get((False, False), 0), tuple(sorted(count)))


def _band_mask(s, i, j, block_q, block_k, window, diagonal, far):
    """The scores of band tile (i, j) with what its kind hides at
    NEG_INF: one ``rows - cols`` difference and the comparison the kind
    needs; a wholly visible tile is returned as it came."""
    if not (diagonal or far):
        return s
    diff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            + (i * block_q - j * block_k))
    if diagonal and far:  # a window shorter than a tile
        allowed = jnp.logical_and(diff >= 0, diff < window)
    else:
        allowed = diff >= 0 if diagonal else diff < window
    return jnp.where(allowed, s, NEG_INF)


def _when_tile(needed, i, j, block_q, block_k, window, kinds, body):
    """Run ``body`` where tile (i, j) is ``needed``; under a window,
    ``body(diagonal, far)`` in the body of the band tile's kind."""
    if not window:
        pl.when(needed)(body)
        return
    diagonal, far = _band_tile_kind(i, j, block_q, block_k, window)
    for d, f in kinds:
        of_kind = jnp.logical_and(
            diagonal if d else jnp.logical_not(diagonal),
            far if f else jnp.logical_not(far))
        pl.when(jnp.logical_and(needed, of_kind))(
            functools.partial(body, d, f))


def _check_window(causal, other_mask):
    if not causal or other_mask:
        raise ValueError("a window is a causal band, without segment "
                         "ids or a prefix")


def _group_size(q, k) -> int:
    """Query heads per KV head (1 = MHA). Static, from the shapes."""
    heads, kv_heads = q.shape[1], k.shape[1]
    if heads % kv_heads:
        raise ValueError(
            f"num_heads {heads} not divisible by num_kv_heads {kv_heads}"
        )
    return heads // kv_heads


def _flash_forward(
    q, k, v, *, scale: float, causal: bool,
    block_q: int, block_k: int, interpret: bool,
    segment_ids=None,  # [B, S_q] int32 — packed-sequence masking
    segment_ids_kv=None,  # [B, S_k] — kv-side ids when they differ
    # (ring steps: local q vs a VISITING kv shard); defaults to the
    # q-side array
    prefix_len=None,  # [B] int32 — prefix-LM (bidirectional prompt)
    window: int = 0,  # > 0: key j visible where t - window < j <= t
):
    batch, heads, s_q, head_dim = q.shape
    s_k, v_dim = k.shape[2], v.shape[3]
    group = _group_size(q, k)
    if causal and s_q != s_k:
        raise ValueError(
            f"causal flash attention requires s_q == s_k (got {s_q} vs "
            f"{s_k}); use causal=False for cross attention"
        )
    block_q = _fit_block(block_q, s_q)
    block_k = _fit_block(block_k, s_k)
    _check_mosaic_lane_block(interpret, block_q, s_q, "block_q")
    if segment_ids is not None:
        _check_mosaic_lane_block(interpret, block_k, s_k, "block_k")
    segmented = segment_ids is not None
    prefixed = prefix_len is not None
    if segmented and prefixed:
        raise ValueError("segment_ids and prefix_len are mutually "
                         "exclusive masking modes")
    kinds = ()
    if window:
        _check_window(causal, segmented or prefixed)
        # the grid's k dimension covers the band alone; its last entry
        # is the tile of the q block's last query, and an entry before
        # the band's first tile is clamped to it (no new copy) and
        # skipped by the kernel
        walk = band_walk(s_k, window, block_q, block_k)
        kinds = walk.kinds
        grid = (batch, heads, s_q // block_q, walk.k_steps)
        kj = lambda i, j: jnp.maximum(  # noqa: E731
            _band_last_k(i, block_q, block_k) - (walk.k_steps - 1) + j,
            _band_first_k(i, block_q, block_k, window))
    else:
        grid = (batch, heads, s_q // block_q, s_k // block_k)
        kj = lambda i, j: j  # noqa: E731

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, segmented=segmented,
        prefix=prefixed, window=window, kinds=kinds,
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, head_dim),
                     lambda b, h, i, j: (b, h, i, 0)),
        # GQA: query head h reads KV head h // group
        pl.BlockSpec((1, 1, block_k, head_dim),
                     lambda b, h, i, j: (b, h // group, kj(i, j), 0)),
        pl.BlockSpec((1, 1, block_k, v_dim),
                     lambda b, h, i, j: (b, h // group, kj(i, j), 0)),
    ]
    operands = [q, k, v]
    if segmented:
        seg4q = segment_ids.astype(jnp.int32).reshape(batch, 1, 1, s_q)
        seg_kv = (segment_ids_kv if segment_ids_kv is not None
                  else segment_ids)
        seg4k = seg_kv.astype(jnp.int32).reshape(batch, 1, 1, s_k)
        # broadcast over heads: index map pins the head/row dims to 0
        in_specs.append(pl.BlockSpec((1, 1, 1, block_q),
                                     lambda b, h, i, j: (b, 0, 0, i)))
        in_specs.append(pl.BlockSpec((1, 1, 1, block_k),
                                     lambda b, h, i, j: (b, 0, 0, j)))
        operands += [seg4q, seg4k]
    if prefixed:
        # [B, 1, LANES] so the BLOCK's last two dims (1, LANES)
        # equal the array's — Mosaic requires the trailing two block
        # dims be (8,128)-divisible OR exactly the array dims, and a
        # (1, LANES) block over a [B, LANES] array violates that for
        # B > 1 (caught by deviceless lowering; interpret mode never
        # enforces tiling). The kernel reads lane 0.
        p2 = jnp.broadcast_to(
            prefix_len.astype(jnp.int32)[:, None, None],
            (batch, 1, LANES))
        in_specs.append(pl.BlockSpec((1, 1, LANES),
                                     lambda b, h, i, j: (b, 0, 0)))
        operands.append(p2)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, v_dim),
                         lambda b, h, i, j: (b, h, i, 0)),
            # [B, H, 1, Sq] so the last-two block dims (1, block_q) satisfy
            # the TPU (8, 128) tiling rule; squeezed after the call
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, s_q, v_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, s_q), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q, LANES)),  # running max m
            _vmem((block_q, LANES)),  # running normalizer l
            _vmem((block_q, v_dim)),  # output accumulator
        ],
        interpret=interpret,
        name="flash_win_fwd" if window else "flash_fwd",
    )(*operands)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9)
)
def flash_attention_lse(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, H_kv, S, D] (H_kv divides H)
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
):
    """Attention returning ``(out, lse)`` where ``lse[b,h,s]`` is the
    row logsumexp of the (scaled, masked) scores. Differentiable in both
    outputs — the lse cotangent folds into the backward's delta term
    (``ds = p * (dp - (delta - dlse))``), which is what makes the
    ring-attention merge exact under autodiff.

    ``block_q_bwd``/``block_k_bwd`` (0 = same as forward) tile the
    backward kernels independently: the dKV/dQ passes hold more live
    VMEM tiles than the forward, so their optimum is usually smaller —
    a long-context tuning lever (``LlamaConfig.flash_block_q_bwd``)."""
    (out, lse), _ = _flash_attention_lse_fwd(
        q, k, v, causal, scale, block_q, block_k, interpret,
        block_q_bwd, block_k_bwd,
    )
    return out, lse


def flash_attention(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
) -> jax.Array:
    """Memory-efficient attention; differentiable (blockwise recompute
    backward from the saved logsumexp, no quadratic residuals)."""
    return flash_attention_lse(
        q, k, v, causal, scale, block_q, block_k, interpret,
        block_q_bwd, block_k_bwd,
    )[0]


def ambient_shard_mesh():
    """The ambient mesh when tracing under a mesh context (``set_mesh``
    — see ``shard_compat.ambient_mesh_with_axes``) with >1 device on the
    flash-relevant (data/fsdp/tensor) axes; None when single-device,
    unsharded, or under a partial mesh missing one of those axes (the
    sharded wrapper's PartitionSpec names all three)."""
    from dlrover_tpu.ops.shard_compat import ambient_mesh_with_axes

    return ambient_mesh_with_axes(("data", "fsdp", "tensor"))


def flash_attention_auto(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
    window: Optional[int] = None,
) -> jax.Array:
    """``flash_attention`` that routes itself through the ``shard_map``
    wrapper whenever the ambient mesh is non-trivial — GSPMD cannot
    auto-partition a Mosaic custom call, so every model's flash call
    site must make this choice; centralizing it here keeps them all
    multi-chip-safe. With ``window`` it is ``flash_attention_window``
    in tiles of at most ``block_q`` a side (``window_tiles``);
    ``block_k`` and the backward's blocks are the full kernels'."""
    mesh = ambient_shard_mesh()
    if window is not None:
        def band(ql, kl, vl):
            return flash_attention_window(ql, kl, vl, window, scale,
                                          block_q, interpret)

        if mesh is None:
            return band(q, k, v)
        return _shard_mapped_attention(mesh, band, q, k, v)
    if mesh is not None:
        return flash_attention_sharded(
            q, k, v, mesh, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
        )
    return flash_attention(q, k, v, causal, scale, block_q, block_k,
                           interpret, block_q_bwd, block_k_bwd)


def _shard_mapped_attention(mesh, body, q, k, v, extras=(),
                            extra_ndims=(), batch_axes=("data", "fsdp"),
                            head_axis: Optional[str] = "tensor"):
    """Shared shard_map routing for every flash variant: GQA head-shard
    legalization and (batch, head) partition specs live HERE once. ``extras`` are additional
    operands sharded along batch only (segment ids, prefix lengths);
    ``extra_ndims`` gives each one's rank so its spec pads with None."""
    from jax.sharding import PartitionSpec as P

    if head_axis is not None:
        sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
        ways = sizes.get(head_axis, 1)
        rep = minimal_kv_repeat(k.shape[1], q.shape[1], ways)
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
    spec = P(batch_axes, head_axis, None, None)
    extra_specs = tuple(
        P(batch_axes, *([None] * (nd - 1))) for nd in extra_ndims
    )
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec) + extra_specs, out_specs=spec,
        check_vma=False,  # a pallas_call output carries no vma
    )(q, k, v, *extras)


def flash_attention_segmented_auto(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,  # [B, S]
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    batch_axes=("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
) -> jax.Array:
    """Multi-chip-safe ``flash_attention_segmented``: same shard_map
    routing discipline as ``flash_attention_auto`` — GSPMD cannot
    partition the Mosaic call, and segmented attention with an unsharded
    sequence is embarrassingly parallel over (batch, head) shards, with
    segment ids sharded along batch only."""
    mesh = ambient_shard_mesh()
    if mesh is None:
        return flash_attention_segmented(
            q, k, v, segment_ids, causal, scale, block_q, block_k,
            interpret, block_q_bwd, block_k_bwd,
        )

    def body(ql, kl, vl, segl):
        return flash_attention_segmented(
            ql, kl, vl, segl, causal, scale, block_q, block_k,
            interpret, block_q_bwd, block_k_bwd,
        )

    return _shard_mapped_attention(
        mesh, body, q, k, v, extras=(segment_ids,), extra_ndims=(2,),
        batch_axes=batch_axes, head_axis=head_axis,
    )


def minimal_kv_repeat(kv_heads: int, num_heads: int, ways: int) -> int:
    """Smallest repeat making ``kv_heads * rep`` divisible by ``ways``
    while still dividing ``num_heads`` (the GQA head-shard legalizer
    shared by the sharded flash wrapper and ring attention; the planner
    prices the same factor, ``planner.ring_kv_repeat``)."""
    if kv_heads <= 0 or ways <= 1 or kv_heads % ways == 0:
        return 1
    for rep in range(1, num_heads // kv_heads + 1):
        if (kv_heads * rep) % ways == 0 and num_heads % (
            kv_heads * rep
        ) == 0:
            return rep
    raise ValueError(
        f"cannot shard {kv_heads} kv heads (of {num_heads} query heads) "
        f"over {ways} ways"
    )


def flash_attention_sharded(
    q: jax.Array,  # global [B, H, S, D]
    k: jax.Array,  # global [B, H_kv, S, D]
    v: jax.Array,
    mesh,
    causal: bool = True,
    scale: Optional[float] = None,
    batch_axes=("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
) -> jax.Array:
    """The multi-chip flash path: GSPMD cannot auto-partition a Mosaic
    custom call, so the kernel runs under ``shard_map`` with batch on
    the data axes and heads on the tensor axis — attention with an
    unsharded sequence is embarrassingly parallel over (batch, head)
    shards, so the body needs zero collectives. The (seq-sharded)
    counterpart is ``ops.ring_attention``."""

    def body(ql, kl, vl):
        return flash_attention(ql, kl, vl, causal, scale,
                               block_q, block_k, interpret,
                               block_q_bwd, block_k_bwd)

    return _shard_mapped_attention(
        mesh, body, q, k, v, batch_axes=batch_axes, head_axis=head_axis,
    )


def _resolve(scale, head_dim, interpret):
    scale = scale if scale is not None else 1.0 / (head_dim ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return scale, interpret


def _kept(out, lse):
    """A forward kernel's two results under ``KEPT_NAMES``. Named INSIDE
    the op's forward rule, the results and the residuals are the same
    named values, and a checkpoint that saves the names has nothing of
    the kernel left to replay (a name on the op's result outside its
    ``custom_vjp`` saves a copy and the kernel is still run again for
    the residual). Outside a checkpoint, and under one that is given no
    ``keep``, a name is the identity and lowers to nothing."""
    return tuple(checkpoint_name(a, name)
                 for a, name in zip((out, lse), KEPT_NAMES))


def _flash_attention_lse_fwd(q, k, v, causal, scale, block_q, block_k,
                             interpret, block_q_bwd=0, block_k_bwd=0):
    scale_v, interp = _resolve(scale, q.shape[-1], interpret)
    out, lse = _flash_forward(
        q, k, v, scale=scale_v, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interp,
    )
    lse = lse.reshape(q.shape[0], q.shape[1], q.shape[2])
    out, lse = _kept(out, lse)
    return (out, lse), (q, k, v, out, lse)


def _recompute_p(q, k, lse, *, scale, causal, i, j, block_q, block_k,
                 seg_q=None, seg_k=None, prefix_len=None, window=0,
                 diagonal=True, far=True):
    """Recompute the [Bq, Bk] probability tile from (q, k, lse): exact
    probs p = exp(q k^T * scale - lse) with causal (segment / prefix)
    masking re-applied; under a window, what the band tile's kind
    (``diagonal``, ``far``) hides."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [Bq, Bk] f32
    if window:
        s = _band_mask(s, i, j, block_q, block_k, window, diagonal, far)
    elif causal or prefix_len is not None:
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        ) + i * block_q
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        ) + j * block_k
        allowed = rows >= cols
        if prefix_len is not None:
            allowed = jnp.logical_or(allowed, cols < prefix_len)
        s = jnp.where(allowed, s, NEG_INF)
    if seg_q is not None:
        s = jnp.where(seg_q[:, None] == seg_k[None, :], s, NEG_INF)
        # rows whose segment has no keys in this block: s == NEG_INF and
        # (for all-pad rows) lse == NEG_INF too — clamp so the masked
        # entries stay exactly 0 instead of exp(NEG_INF - NEG_INF) = NaN
        lse = jnp.where(lse <= NEG_INF * 0.5, 0.0, lse)
    return jnp.exp(s - lse[:, None])


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,  # VMEM blocks
    *rest,  # (+seg refs / prefix_ref per mode) dk_ref, dv_ref, scratch
    scale: float, causal: bool, block_q: int, block_k: int,
    segmented: bool = False, prefix: bool = False, window: int = 0,
    q_blocks: int = 0, kinds=(),
):
    prefix_ref = seg_q_ref = seg_k_ref = None
    if segmented:
        (seg_q_ref, seg_k_ref, dk_ref, dv_ref,
         dk_scratch, dv_scratch) = rest
    elif prefix:
        prefix_ref, dk_ref, dv_ref, dk_scratch, dv_scratch = rest
    else:
        dk_ref, dv_ref, dk_scratch, dv_scratch = rest
    # grid (batch, kv_head, j, g, i): the two innermost (sequential)
    # dims sweep the query heads of this KV head's group and the q
    # blocks, so dk/dv accumulate over both without write conflicts.
    j = pl.program_id(2)  # k block index
    g = pl.program_id(3)  # query-head index within the KV group
    ii = pl.program_id(4)  # q grid index (innermost, sequential)
    ng = pl.num_programs(3)
    nq = pl.num_programs(4)
    # windowed: the grid holds the q blocks whose band touches this k
    # tile, first the block of the tile's first key; past the last of
    # them i is clamped by the index maps and the entry skipped here
    i = _band_first_q(j, block_q, block_k) + ii if window else ii

    @pl.when(jnp.logical_and(g == 0, ii == 0))
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    # with causal masking, q blocks strictly above the k block's diagonal
    # see none of these keys; prefix columns are visible to every q block
    block_needed = jnp.logical_or(
        jnp.logical_not(causal), i * block_q + block_q - 1 >= j * block_k
    )
    if prefix:
        block_needed = jnp.logical_or(
            block_needed, j * block_k < prefix_ref[0, 0, 0]
        )
    if window:
        block_needed = i <= _band_last_q(j, block_q, block_k, window,
                                         q_blocks)

    def _compute(diagonal=True, far=True):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, 0, :]  # [Bq]
        delta = delta_ref[0, 0, 0, :]  # [Bq]
        p = _recompute_p(
            q, k, lse, scale=scale, causal=causal,
            i=i, j=j, block_q=block_q, block_k=block_k,
            seg_q=seg_q_ref[0, 0, 0, :] if segmented else None,
            seg_k=seg_k_ref[0, 0, 0, :] if segmented else None,
            prefix_len=prefix_ref[0, 0, 0] if prefix else None,
            window=window, diagonal=diagonal, far=far,
        )
        p_lo = p.astype(do.dtype)
        # dv += p^T do  : contract over the q rows
        dv_scratch[:] = dv_scratch[:] + jax.lax.dot_general(
            p_lo, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dp = do v^T  : [Bq, Bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        # dk += ds^T q
        dk_scratch[:] = dk_scratch[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when_tile(block_needed, i, j, block_q, block_k, window, kinds,
               _compute)

    @pl.when(jnp.logical_and(g == ng - 1, ii == nq - 1))
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    *rest,  # (+seg refs / prefix_ref per mode) dq_ref, dq_scratch
    scale: float, causal: bool, block_q: int, block_k: int,
    segmented: bool = False, prefix: bool = False, window: int = 0,
    kinds=(),
):
    prefix_ref = seg_q_ref = seg_k_ref = None
    if segmented:
        seg_q_ref, seg_k_ref, dq_ref, dq_scratch = rest
    elif prefix:
        prefix_ref, dq_ref, dq_scratch = rest
    else:
        dq_ref, dq_scratch = rest
    i = pl.program_id(2)  # q block index
    jj = pl.program_id(3)  # k grid index (innermost, sequential)
    nk = pl.num_programs(3)
    # windowed: as in the forward kernel
    j = _band_last_k(i, block_q, block_k) - (nk - 1) + jj if window else jj

    @pl.when(jj == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    block_needed = jnp.logical_or(
        jnp.logical_not(causal), j * block_k <= i * block_q + block_q - 1
    )
    if prefix:
        block_needed = jnp.logical_or(
            block_needed, j * block_k < prefix_ref[0, 0, 0]
        )
    if window:
        block_needed = j >= _band_first_k(i, block_q, block_k, window)

    def _compute(diagonal=True, far=True):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, 0, :]
        delta = delta_ref[0, 0, 0, :]
        p = _recompute_p(
            q, k, lse, scale=scale, causal=causal,
            i=i, j=j, block_q=block_q, block_k=block_k,
            seg_q=seg_q_ref[0, 0, 0, :] if segmented else None,
            seg_k=seg_k_ref[0, 0, 0, :] if segmented else None,
            prefix_len=prefix_ref[0, 0, 0] if prefix else None,
            window=window, diagonal=diagonal, far=far,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        # dq += ds k
        dq_scratch[:] = dq_scratch[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when_tile(block_needed, i, j, block_q, block_k, window, kinds,
               _compute)

    @pl.when(jj == nk - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scratch[:].astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, do, dlse, *, causal, scale,
                    block_q, block_k, interpret, segment_ids=None,
                    segment_ids_kv=None, prefix_len=None, window=0):
    """Pallas backward: a dKV kernel (k blocks outer, q inner) and a dQ
    kernel (q outer, k inner), both recomputing probability tiles from the
    saved logsumexp — peak extra memory is O(Bq * Bk), never O(S^2).

    The lse cotangent is exact and free: d(lse)/d(scores) is the prob
    tile itself, so it enters as ``ds = p * (dp - (delta - dlse))`` —
    the existing delta term with ``dlse`` subtracted."""
    scale_v, interp = _resolve(scale, q.shape[-1], interpret)

    batch, heads, s_q, d = q.shape
    s_k, dv_dim = k.shape[2], v.shape[3]
    group = _group_size(q, k)
    bq = _fit_block(block_q, s_q)
    bk = _fit_block(block_k, s_k)
    _check_mosaic_lane_block(interp, bq, s_q, "block_q")
    if segment_ids is not None:
        _check_mosaic_lane_block(interp, bk, s_k, "block_k")
    segmented = segment_ids is not None
    prefixed = prefix_len is not None
    nq, nk = s_q // bq, s_k // bk
    if window:
        _check_window(causal, segmented or prefixed)
        # both grids cover the band alone (see ``_flash_forward``): the
        # dKV pass walks the q blocks from the one of the k tile's first
        # key on, the dQ pass the k tiles up to that of the q block's
        # last query
        walk = band_walk(s_k, window, bq, bk)
        q_steps, k_steps, kinds = walk.q_steps, walk.k_steps, walk.kinds
        qi_of = lambda j, i: jnp.minimum(  # noqa: E731
            _band_first_q(j, bq, bk) + i,
            _band_last_q(j, bq, bk, window, nq))
        kj_of = lambda i, j: jnp.maximum(  # noqa: E731
            _band_last_k(i, bq, bk) - (k_steps - 1) + j,
            _band_first_k(i, bq, bk, window))
    else:
        q_steps, k_steps, kinds = nq, nk, ()
        qi_of = lambda j, i: i  # noqa: E731
        kj_of = lambda i, j: j  # noqa: E731

    f32 = jnp.float32
    delta = jnp.sum(
        do.astype(f32) * out.astype(f32), axis=-1
    ) - dlse.astype(f32)  # [B,H,Sq]
    # [B, H, 1, S] layout so the last-two block dims obey TPU tiling
    lse4 = lse.reshape(batch, heads, 1, s_q)
    delta4 = delta.reshape(batch, heads, 1, s_q)
    seg4q = (segment_ids.astype(jnp.int32).reshape(batch, 1, 1, s_q)
             if segmented else None)
    seg4k = None
    if segmented:
        seg_kv = (segment_ids_kv if segment_ids_kv is not None
                  else segment_ids)
        seg4k = seg_kv.astype(jnp.int32).reshape(batch, 1, 1, s_k)
    # [B, 1, LANES]: see the forward's prefix operand comment
    p2 = (jnp.broadcast_to(prefix_len.astype(jnp.int32)[:, None, None],
                           (batch, 1, LANES))
          if prefixed else None)

    # dKV grid (b, kv_head, j, g, i): g sweeps the query heads sharing
    # this KV head, i sweeps q blocks; both are sequential on TPU so the
    # f32 scratch accumulates across the whole group (the GQA head-sum).
    qh = lambda b, hk, j, g, i: (  # noqa: E731
        b, hk * group + g, qi_of(j, i), 0)
    kvh = lambda b, hk, j, g, i: (b, hk, j, 0)  # noqa: E731
    row = lambda b, hk, j, g, i: (  # noqa: E731
        b, hk * group + g, 0, qi_of(j, i))
    dkv_specs = [
        pl.BlockSpec((1, 1, bq, d), qh),
        pl.BlockSpec((1, 1, bk, d), kvh),
        pl.BlockSpec((1, 1, bk, dv_dim), kvh),
        pl.BlockSpec((1, 1, bq, dv_dim), qh),
        pl.BlockSpec((1, 1, 1, bq), row),
        pl.BlockSpec((1, 1, 1, bq), row),
    ]
    dkv_operands = [q, k, v, do, lse4, delta4]
    if segmented:
        dkv_specs.append(pl.BlockSpec(
            (1, 1, 1, bq), lambda b, hk, j, g, i: (b, 0, 0, i)))
        dkv_specs.append(pl.BlockSpec(
            (1, 1, 1, bk), lambda b, hk, j, g, i: (b, 0, 0, j)))
        dkv_operands += [seg4q, seg4k]
    if prefixed:
        dkv_specs.append(pl.BlockSpec(
            (1, 1, LANES), lambda b, hk, j, g, i: (b, 0, 0)))
        dkv_operands.append(p2)
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale_v, causal=causal,
            block_q=bq, block_k=bk, segmented=segmented,
            prefix=prefixed, window=window, q_blocks=nq,
            kinds=kinds,
        ),
        grid=(batch, k.shape[1], nk, group, q_steps),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), kvh),
            pl.BlockSpec((1, 1, bk, dv_dim), kvh),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[_vmem((bk, d)), _vmem((bk, dv_dim))],
        interpret=interp,
        name="flash_win_dkv" if window else "flash_dkv",
    )(*dkv_operands)

    # dQ grid (b, h, i, j): per-q-head, reads the group's shared KV head
    qi = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    kj = lambda b, h, i, j: (  # noqa: E731
        b, h // group, kj_of(i, j), 0)
    ri = lambda b, h, i, j: (b, h, 0, i)  # noqa: E731
    dq_specs = [
        pl.BlockSpec((1, 1, bq, d), qi),
        pl.BlockSpec((1, 1, bk, d), kj),
        pl.BlockSpec((1, 1, bk, dv_dim), kj),
        pl.BlockSpec((1, 1, bq, dv_dim), qi),
        pl.BlockSpec((1, 1, 1, bq), ri),
        pl.BlockSpec((1, 1, 1, bq), ri),
    ]
    dq_operands = [q, k, v, do, lse4, delta4]
    if segmented:
        dq_specs.append(pl.BlockSpec(
            (1, 1, 1, bq), lambda b, h, i, j: (b, 0, 0, i)))
        dq_specs.append(pl.BlockSpec(
            (1, 1, 1, bk), lambda b, h, i, j: (b, 0, 0, j)))
        dq_operands += [seg4q, seg4k]
    if prefixed:
        dq_specs.append(pl.BlockSpec(
            (1, 1, LANES), lambda b, h, i, j: (b, 0, 0)))
        dq_operands.append(p2)
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale_v, causal=causal,
            block_q=bq, block_k=bk, segmented=segmented,
            prefix=prefixed, window=window, kinds=kinds,
        ),
        grid=(batch, heads, nq, k_steps),
        in_specs=dq_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), qi),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[_vmem((bq, d))],
        interpret=interp,
        name="flash_win_dq" if window else "flash_dq",
    )(*dq_operands)[0]

    return dq, dk, dv


def _flash_attention_lse_bwd(causal, scale, block_q, block_k, interpret,
                             block_q_bwd, block_k_bwd, residuals,
                             cotangents):
    q, k, v, out, lse = residuals
    do, dlse = cotangents
    return _flash_backward(
        q, k, v, out, lse, do, dlse, causal=causal, scale=scale,
        block_q=block_q_bwd or block_q, block_k=block_k_bwd or block_k,
        interpret=interpret,
    )


flash_attention_lse.defvjp(
    _flash_attention_lse_fwd, _flash_attention_lse_bwd
)


# -- sliding-window flash attention -----------------------------------------


def window_tiles(seq: int, window: int, block: int = 1024):
    """``((block_q, block_k) of the forward, of the backward)`` for a
    band of ``window`` over a row of ``seq``, no side over ``block``.
    A side is ``block`` (fitted to the row) or its half, by what the
    v5e read a layer alone (bf16, heads of 128 at 16,384 tokens and of
    64 with values of 128 at 8192, windows 512 to 4096; ``PERF.md``
    section 7): the forward, which the VPU binds and a grid step's
    fixed work weighs on, takes the whole square wherever the window
    fills one, and below that keeps the k side whole and halves the q
    side; the backward, near what the MXU allows on the tiles it runs
    (the one kernel and the pair alike), takes the whole square only
    where the band is two of them wide (the larger tiles' saving then
    outweighs the keys they compute beyond the band), else the half
    square."""
    whole = _fit_block(block, seq)
    half = whole // 2 if whole % 16 == 0 else whole
    forward = (whole if window >= whole else half, whole)
    backward = (whole, whole) if window >= 2 * whole else (half, half)
    return forward, backward


def band_tile_counters(calls: int, seq: int, window: int,
                       block: int = 1024) -> Dict[str, int]:
    """What ``calls`` one-head forward calls of ``flash_attention_window``
    over rows of ``seq`` visit, as a loss's aux counts it
    (``StepCounter``): the band's tiles and those of them that run
    unmasked."""
    walk = band_walk(seq, window, *window_tiles(seq, window, block)[0])
    return {StepCounter.ATTN_BAND_TILES: calls * walk.tiles,
            StepCounter.ATTN_BAND_TILES_UNMASKED: calls * walk.unmasked}


def flash_attention_window(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, H_kv, S, D]
    v: jax.Array,  # [B, H_kv, S, Dv]
    window: int,
    scale: Optional[float] = None,
    block: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal attention over a sliding window: query ``t`` sees key
    ``j`` where ``t - window < j <= t``. The kernels (``flash_win_fwd``
    and one backward kernel, ``flash_win_bwd``: scores, probabilities
    and ``ds`` once a tile, dQ, dK and dV out of them) walk the band in
    the tiles ``window_tiles`` picks from the row and the window (no
    side over ``block``; the forward and the backward each their own),
    and their grids hold the band's tiles only (``band_walk``), so the
    work is linear in the row: at 8192 tokens and a window of 512 the
    backward's squares of 512 are 2 of 16 k tiles a q block, not 16
    computed and 14 masked away. A tile knows its kind from its grid
    indices: one wholly inside the band runs with no mask, one on the
    diagonal or at the band's far edge builds the one comparison it
    needs. Rows whose float32 gradient rows do not fit the backward
    kernel's VMEM (``_win_row_state_bytes``: beyond 16,384 at widths of
    128 in bf16) run ``flash_win_dkv`` and ``flash_win_dq`` instead,
    each recomputing the tile; the gradients are the same bit for bit."""
    fwd, bwd = window_tiles(q.shape[2], window, block)
    return _flash_window(q, k, v, window, scale, fwd, bwd, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_window(q, k, v, window, scale, tiles_fwd, tiles_bwd, interpret):
    """``flash_attention_window`` at given ``(block_q, block_k)`` for
    the forward and for the backward kernels."""
    return _flash_window_fwd(q, k, v, window, scale, tiles_fwd, tiles_bwd,
                             interpret)[0]


def _flash_window_fwd(q, k, v, window, scale, tiles_fwd, tiles_bwd,
                      interpret):
    if window < 1:
        raise ValueError(f"a window holds at least one key, not {window}")
    scale_v, interp = _resolve(scale, q.shape[-1], interpret)
    out, lse = _flash_forward(
        q, k, v, scale=scale_v, causal=True, block_q=tiles_fwd[0],
        block_k=tiles_fwd[1], interpret=interp, window=window,
    )
    lse = lse.reshape(q.shape[0], q.shape[1], q.shape[2])
    out, lse = _kept(out, lse)
    return out, (q, k, v, out, lse)


# The windowed backward is one kernel where its whole-row state fits in
# VMEM: for one query head the query's gradient in float32, for one KV
# head the key's and the value's (summed over the group's query heads),
# and their whole-row output blocks, which the pallas pipeline
# double-buffers (``_win_row_state_bytes``: 48 MiB at rows of 16,384 and
# widths of 128 in bf16, 24 MiB at 8192 and 64 / 128). Longer rows take
# ``_flash_backward``'s two kernels, which hold a block each. As for the
# latent kernel, the one kernel asks Mosaic for the state's budget and
# as much again for its tiles, of the v5e's 128 MiB.
_WIN_ROW_STATE_BUDGET_BYTES = 48 * 1024 * 1024
_WIN_VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def _lanes(width: int) -> int:
    """The lanes a minor axis of ``width`` occupies in VMEM."""
    return -(-width // LANES) * LANES


def _win_row_state_bytes(seq, d, dv, itemsize):
    """VMEM bytes of ``flash_win_bwd``'s whole-row accumulators and
    output blocks (dQ, dK of ``d``, dV of ``dv``)."""
    return (4 + 2 * itemsize) * seq * (2 * _lanes(d) + _lanes(dv))


def _flash_win_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dk_ref, dv_ref, dq_scratch, dk_scratch,
                          dv_scratch, *, scale, block_q, block_k, window,
                          q_blocks, kinds):
    # grid (batch, kv_head, g, j, ii), all sequential: the band's tiles
    # as the dKV kernel walks them (k tile j, then the q blocks whose
    # band touches it), once a query head g of the KV head's group. One
    # (p, ds) a tile feeds all three gradients. They are whole rows in
    # VMEM: the query's carried over the k tiles of a head (a q block
    # zeroed at the first k tile of its band, written at the last), the
    # key's and the value's over the q blocks and the heads of the
    # group. The sums run in the two kernels' order.
    g = pl.program_id(2)
    j = pl.program_id(3)
    ii = pl.program_id(4)
    ng = pl.num_programs(2)
    nq = pl.num_programs(4)
    # past the band's last q block the index maps clamp i and the entry
    # is skipped here
    i = _band_first_q(j, block_q, block_k) + ii
    needed = i <= _band_last_q(j, block_q, block_k, window, q_blocks)
    q_rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
    k_rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)

    @pl.when(jnp.logical_and(g == 0, ii == 0))
    def _init_kv():
        dk_scratch[k_rows, :] = jnp.zeros(
            (block_k, dk_scratch.shape[1]), dk_scratch.dtype)
        dv_scratch[k_rows, :] = jnp.zeros(
            (block_k, dv_scratch.shape[1]), dv_scratch.dtype)

    @pl.when(jnp.logical_and(
        needed, j == _band_first_k(i, block_q, block_k, window)))
    def _init_q():
        dq_scratch[q_rows, :] = jnp.zeros(
            (block_q, dq_scratch.shape[1]), dq_scratch.dtype)

    def _compute(diagonal, far):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        p = _recompute_p(
            q, k, lse_ref[0, 0, 0, :], scale=scale, causal=True, i=i, j=j,
            block_q=block_q, block_k=block_k, window=window,
            diagonal=diagonal, far=far)
        over_q = (((0,), (0,)), ((), ()))
        dv_scratch[k_rows, :] = dv_scratch[k_rows, :] + jax.lax.dot_general(
            p.astype(do.dtype), do, over_q,
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0, 0, :, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0, 0, :][:, None]) * scale).astype(
            q.dtype)
        dk_scratch[k_rows, :] = dk_scratch[k_rows, :] + jax.lax.dot_general(
            ds, q, over_q, preferred_element_type=jnp.float32)
        dq_scratch[q_rows, :] = dq_scratch[q_rows, :] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _when_tile(needed, i, j, block_q, block_k, window, kinds, _compute)

    @pl.when(jnp.logical_and(needed, j == _band_last_k(i, block_q, block_k)))
    def _finalize_q():
        dq_ref[0, 0, q_rows, :] = dq_scratch[q_rows, :].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(g == ng - 1, ii == nq - 1))
    def _finalize_kv():
        dk_ref[0, 0, k_rows, :] = dk_scratch[k_rows, :].astype(dk_ref.dtype)
        dv_ref[0, 0, k_rows, :] = dv_scratch[k_rows, :].astype(dv_ref.dtype)


def _flash_window_backward_one_call(q, k, v, out, lse, do, window, scale,
                                    block_q, block_k, interpret):
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq, d = q.shape
    kv_heads, dv_dim = k.shape[1], v.shape[3]
    group = _group_size(q, k)
    bq, bk = _fit_block(block_q, seq), _fit_block(block_k, seq)
    _check_mosaic_lane_block(interpret, bq, seq, "block_q")
    walk = band_walk(seq, window, bq, bk)
    f32 = jnp.float32
    delta4 = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1).reshape(
        batch, heads, 1, seq)
    lse4 = lse.reshape(batch, heads, 1, seq)
    # grid (b, kv_head, g, j, ii): as ``_flash_backward``'s dKV grid with
    # the group's heads outside the k tiles, so that a head's dQ row is
    # whole before the next head's begins
    qi_of = lambda j, ii: jnp.minimum(  # noqa: E731
        _band_first_q(j, bq, bk) + ii,
        _band_last_q(j, bq, bk, window, seq // bq))
    qh = lambda b, hk, g, j, ii: (  # noqa: E731
        b, hk * group + g, qi_of(j, ii), 0)
    kvh = lambda b, hk, g, j, ii: (b, hk, j, 0)  # noqa: E731
    row = lambda b, hk, g, j, ii: (  # noqa: E731
        b, hk * group + g, 0, qi_of(j, ii))
    # whole rows: resident for a query head (dQ) and for a KV head (dK,
    # dV), written back when that index moves on
    head_row = lambda b, hk, g, j, ii: (b, hk * group + g, 0, 0)  # noqa: E731
    kv_row = lambda b, hk, g, j, ii: (b, hk, 0, 0)  # noqa: E731
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_win_bwd_kernel, scale=scale, block_q=bq, block_k=bk,
            window=window, q_blocks=seq // bq, kinds=walk.kinds),
        grid=(batch, kv_heads, group, seq // bk, walk.q_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), qh),
            pl.BlockSpec((1, 1, bk, d), kvh),
            pl.BlockSpec((1, 1, bk, dv_dim), kvh),
            pl.BlockSpec((1, 1, bq, dv_dim), qh),
            pl.BlockSpec((1, 1, 1, bq), row),
            pl.BlockSpec((1, 1, 1, bq), row),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, seq, d), head_row),
            pl.BlockSpec((1, 1, seq, d), kv_row),
            pl.BlockSpec((1, 1, seq, dv_dim), kv_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[_vmem((seq, d)), _vmem((seq, d)),
                        _vmem((seq, dv_dim))],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_WIN_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="flash_win_bwd",
    )(q, k, v, do, lse4, delta4)
    return dq, dk, dv


def _flash_window_bwd(window, scale, tiles_fwd, tiles_bwd, interpret,
                      residuals, do):
    q, k, v, out, lse = residuals
    state = _win_row_state_bytes(q.shape[2], q.shape[3], v.shape[3],
                                 q.dtype.itemsize)
    if state <= _WIN_ROW_STATE_BUDGET_BYTES:
        scale_v, interp = _resolve(scale, q.shape[-1], interpret)
        return _flash_window_backward_one_call(
            q, k, v, out, lse, do, window, scale_v, *tiles_bwd, interp)
    return _flash_backward(
        q, k, v, out, lse, do, jnp.zeros_like(lse), causal=True,
        scale=scale, block_q=tiles_bwd[0], block_k=tiles_bwd[1],
        interpret=interpret, window=window,
    )


_flash_window.defvjp(_flash_window_fwd, _flash_window_bwd)


# -- packed-sequence (segmented) flash attention ----------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def flash_attention_segmented(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, H_kv, S, D]
    v: jax.Array,
    segment_ids: jax.Array,  # [B, S] int — tokens attend within segment
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
) -> jax.Array:
    """Flash attention over PACKED sequences: multiple documents share one
    row, separated by ``segment_ids``; tokens attend only within their
    segment (AND causally). The efficient alternative to padding — no
    wasted FLOPs on pad tokens, exact per-document attention.

    Role parity: the reference packs via attention-mask adapters on its
    CUDA kernels (``atorch/modules/transformer/layers.py:1095``
    ``flash_attn_with_mask_bias``); here the mask is fused into the
    Pallas tiles, never materializing S x S."""
    del block_q_bwd, block_k_bwd  # backward-only (vjp reads them)
    out, _lse = _flash_seg_fwd_impl(
        q, k, v, segment_ids, causal, scale, block_q, block_k, interpret
    )
    return out


def _flash_seg_fwd_impl(q, k, v, segment_ids, causal, scale, block_q,
                        block_k, interpret):
    scale_v, interp = _resolve(scale, q.shape[-1], interpret)
    out, lse = _flash_forward(
        q, k, v, scale=scale_v, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interp,
        segment_ids=segment_ids,
    )
    return out, lse.reshape(q.shape[0], q.shape[1], q.shape[2])


def _flash_seg_fwd(q, k, v, segment_ids, causal, scale, block_q, block_k,
                   interpret, block_q_bwd=0, block_k_bwd=0):
    out, lse = _flash_seg_fwd_impl(
        q, k, v, segment_ids, causal, scale, block_q, block_k, interpret
    )
    return out, (q, k, v, segment_ids, out, lse)


def _flash_seg_bwd(causal, scale, block_q, block_k, interpret,
                   block_q_bwd, block_k_bwd, residuals, do):
    import numpy as np

    q, k, v, segment_ids, out, lse = residuals
    dlse = jnp.zeros_like(lse)
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, do, dlse, causal=causal, scale=scale,
        block_q=block_q_bwd or block_q, block_k=block_k_bwd or block_k,
        interpret=interpret, segment_ids=segment_ids,
    )
    # integer primal: cotangent is float0 (no gradient flows to ids)
    dseg = np.zeros(segment_ids.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dseg


flash_attention_segmented.defvjp(_flash_seg_fwd, _flash_seg_bwd)


# NB: no single-array segmented-lse variant exists — ring attention's
# pair variant below with seg_q == seg_k subsumes it, and keeping two
# vjps in sync with _flash_backward bought nothing.


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def flash_attention_segmented_pair_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seg_q: jax.Array,  # [B, S_q]
    seg_k: jax.Array,  # [B, S_k] — independent kv-side ids
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
):
    """Segmented flash where the q-side and kv-side segment ids are
    INDEPENDENT arrays — the ring-attention step shape (local queries
    against a visiting KV shard). Returns (out, lse)."""
    del block_q_bwd, block_k_bwd  # backward-only (vjp reads them)
    return _flash_seg_pair_impl(
        q, k, v, seg_q, seg_k, causal, scale, block_q, block_k, interpret
    )


def _flash_seg_pair_impl(q, k, v, seg_q, seg_k, causal, scale, block_q,
                         block_k, interpret):
    scale_v, interp = _resolve(scale, q.shape[-1], interpret)
    out, lse = _flash_forward(
        q, k, v, scale=scale_v, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interp,
        segment_ids=seg_q, segment_ids_kv=seg_k,
    )
    return out, lse.reshape(q.shape[0], q.shape[1], q.shape[2])


def _flash_seg_pair_fwd(q, k, v, seg_q, seg_k, causal, scale, block_q,
                        block_k, interpret, block_q_bwd=0,
                        block_k_bwd=0):
    out, lse = _flash_seg_pair_impl(
        q, k, v, seg_q, seg_k, causal, scale, block_q, block_k, interpret
    )
    return (out, lse), (q, k, v, seg_q, seg_k, out, lse)


def _flash_seg_pair_bwd(causal, scale, block_q, block_k, interpret,
                        block_q_bwd, block_k_bwd, residuals,
                        cotangents):
    import numpy as np

    q, k, v, seg_q, seg_k, out, lse = residuals
    do, dlse = cotangents
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, do, dlse, causal=causal, scale=scale,
        block_q=block_q_bwd or block_q, block_k=block_k_bwd or block_k,
        interpret=interpret, segment_ids=seg_q, segment_ids_kv=seg_k,
    )
    f0 = jax.dtypes.float0
    return (dq, dk, dv, np.zeros(seg_q.shape, f0),
            np.zeros(seg_k.shape, f0))


flash_attention_segmented_pair_lse.defvjp(_flash_seg_pair_fwd,
                                          _flash_seg_pair_bwd)


# -- prefix-LM flash attention ----------------------------------------------


def flash_attention_prefix(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,
    v: jax.Array,
    prefix_len: jax.Array,  # [B] int — bidirectional over [0, prefix)
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
) -> jax.Array:
    """Prefix-LM flash attention (GLM's mask): token ``i`` attends key
    ``j`` iff ``j <= i`` (causal) OR ``j < prefix_len`` (the prompt is
    bidirectionally visible). Fused into the Pallas tiles — the GLM
    family's alternative to materializing an S x S bias. Reference
    counterpart: ``fa2_with_glm_mask``
    (``atorch/modules/transformer/layers.py:1191``).

    Thin wrapper over ``flash_attention_prefix_lse`` (single-vjp
    discipline: a dropped lse output has a zero cotangent, giving the
    identical backward — see the segmented variants' note)."""
    return flash_attention_prefix_lse(
        q, k, v, prefix_len, scale, block_q, block_k, interpret,
        block_q_bwd, block_k_bwd,
    )[0]


def _flash_prefix_fwd_impl(q, k, v, prefix_len, scale, block_q, block_k,
                           interpret):
    scale_v, interp = _resolve(scale, q.shape[-1], interpret)
    out, lse = _flash_forward(
        q, k, v, scale=scale_v, causal=True,
        block_q=block_q, block_k=block_k, interpret=interp,
        prefix_len=prefix_len,
    )
    return out, lse.reshape(q.shape[0], q.shape[1], q.shape[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def flash_attention_prefix_lse(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,
    v: jax.Array,
    prefix_len: jax.Array,  # [B] int — bidirectional over [0, prefix)
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
):
    """``flash_attention_prefix`` returning ``(out, lse)``,
    differentiable in both — the prefix-LM counterpart of
    ``flash_attention_lse``, needed wherever per-shard outputs merge by
    logsumexp (the sequence-parallel prefix ring)."""
    del block_q_bwd, block_k_bwd  # backward-only (vjp reads them)
    return _flash_prefix_fwd_impl(
        q, k, v, prefix_len, scale, block_q, block_k, interpret
    )


def _flash_prefix_lse_fwd(q, k, v, prefix_len, scale, block_q, block_k,
                          interpret, block_q_bwd=0, block_k_bwd=0):
    out, lse = _flash_prefix_fwd_impl(
        q, k, v, prefix_len, scale, block_q, block_k, interpret
    )
    return (out, lse), (q, k, v, prefix_len, out, lse)


def _flash_prefix_lse_bwd(scale, block_q, block_k, interpret,
                          block_q_bwd, block_k_bwd, residuals,
                          cotangents):
    import numpy as np

    q, k, v, prefix_len, out, lse = residuals
    do, dlse = cotangents
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, do, dlse, causal=True, scale=scale,
        block_q=block_q_bwd or block_q, block_k=block_k_bwd or block_k,
        interpret=interpret, prefix_len=prefix_len,
    )
    dprefix = np.zeros(prefix_len.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dprefix


flash_attention_prefix_lse.defvjp(_flash_prefix_lse_fwd,
                                  _flash_prefix_lse_bwd)


def segmented_attention(q, k, v, segment_ids, use_flash: bool,
                        block_q: int = 512, block_k: int = 1024,
                        interpret: Optional[bool] = None,
                        block_q_bwd: int = 0,
                        block_k_bwd: int = 0) -> jax.Array:
    """The one segmented-attention dispatch every model family shares:
    fused Pallas kernel (shard_map-routed) when flash is on, additive
    bias over the XLA reference otherwise. Centralized so the mask
    semantics cannot drift between families."""
    if use_flash:
        return flash_attention_segmented_auto(
            q, k, v, segment_ids, causal=True,
            block_q=block_q, block_k=block_k, interpret=interpret,
            block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
        )
    same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
    bias = jnp.where(same, 0.0, jnp.finfo(jnp.float32).min)
    return mha_reference(q, k, v, causal=True, bias=bias)


def flash_attention_prefix_auto(
    q, k, v, prefix_len,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    batch_axes=("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
) -> jax.Array:
    """Multi-chip-safe ``flash_attention_prefix`` (same shard_map
    discipline as the other auto wrappers; prefix lengths shard along
    batch only)."""
    mesh = ambient_shard_mesh()
    if mesh is None:
        return flash_attention_prefix(
            q, k, v, prefix_len, scale, block_q, block_k, interpret
        )

    def body(ql, kl, vl, pl_):
        return flash_attention_prefix(
            ql, kl, vl, pl_, scale, block_q, block_k, interpret
        )

    return _shard_mapped_attention(
        mesh, body, q, k, v, extras=(prefix_len,), extra_ndims=(1,),
        batch_axes=batch_axes, head_axis=head_axis,
    )


# -- latent (MLA) flash attention -------------------------------------------
#
# Multi-head latent attention scores a query head against its own key
# head of ``Dn`` and against ONE rotary key head of ``Dr`` that all query
# heads share: ``s = (q_nope k_nope^T + q_rope k_rope^T) * scale``, with
# values of a width of their own. ``Dn + Dr`` (128 + 64 = 192) is no
# multiple of the 128 lanes, and concatenating would copy the shared
# rotary head once a query head, so the kernels below take the two score
# operands as they are: two MXU products into one [Bq, Bk] tile, and in
# the backward two products out of one ``ds``. The rotary key's gradient
# is the sum over every query head, so the head axis of the backward's
# grid is sequential. The backward is one kernel (``flash_mla_bwd``:
# scores, probabilities and ``ds`` once a tile, all five gradients out
# of them) where a row's accumulators fit in VMEM, and a dKV and a dQ
# kernel, each recomputing the tile, beyond that. Causal only; block
# index maps are clamped to the causal half, so a block above the
# diagonal is neither copied nor computed.


def _mla_raw_scores(qn, qr, kn, kr, scale):
    """The [Bq, Bk] float32 score tile before any mask: the two MXU
    products into one tile."""
    dims = (((1,), (1,)), ((), ()))
    return (jax.lax.dot_general(qn, kn, dims,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qr, kr, dims,
                                  preferred_element_type=jnp.float32)) * scale


def _mla_scores(qn, qr, kn, kr, scale, i, j, block_q, block_k):
    """The masked [Bq, Bk] float32 score tile of q block ``i`` against
    k block ``j``."""
    s = _mla_raw_scores(qn, qr, kn, kr, scale)
    rows = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + i * block_q
    cols = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1) + j * block_k
    return jnp.where(rows >= cols, s, NEG_INF)


def _mla_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                    m_scratch, l_scratch, acc_scratch, *, scale, block_q,
                    block_k):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    @pl.when(j * block_k <= i * block_q + block_q - 1)
    def _compute():
        v = v_ref[0, 0, :, :]
        s = _mla_scores(qn_ref[0, 0, :, :], qr_ref[0, 0, :, :],
                        kn_ref[0, 0, :, :], kr_ref[0, 0, :, :], scale,
                        i, j, block_q, block_k)
        m_prev = m_scratch[:, :1]
        l_prev = l_scratch[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scratch[:, :1]
        o_ref[0, 0, :, :] = (acc_scratch[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0, 0, :] = (m_scratch[:, :1] + jnp.log(l))[:, 0]


def _mla_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
            delta_ref, scale, i, j, block_q, block_k):
    """(p, ds) of one tile, recomputed from the saved logsumexp."""
    qn = qn_ref[0, 0, :, :]
    s = _mla_scores(qn, qr_ref[0, 0, :, :], kn_ref[0, 0, :, :],
                    kr_ref[0, 0, :, :], scale, i, j, block_q, block_k)
    p = jnp.exp(s - lse_ref[0, 0, 0, :][:, None])
    dp = jax.lax.dot_general(
        do_ref[0, 0, :, :], v_ref[0, 0, :, :], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = (p * (dp - delta_ref[0, 0, 0, :][:, None]) * scale).astype(
        qn.dtype)
    return p, ds


def _mla_dkv_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dkn_ref, dkr_ref, dv_ref, dkn_scratch,
                    dkr_scratch, dv_scratch, *, scale, block_q, block_k):
    # grid (batch, j, h, i): h and i are sequential, so the rotary
    # key's gradient accumulates over every head and q block of this k
    # block, its own key's and the value's over the q blocks of a head
    j = pl.program_id(1)
    h = pl.program_id(2)
    i = pl.program_id(3)
    nh = pl.num_programs(2)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dkn_scratch[:] = jnp.zeros_like(dkn_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    @pl.when(jnp.logical_and(h == 0, i == 0))
    def _init_shared():
        dkr_scratch[:] = jnp.zeros_like(dkr_scratch)

    @pl.when(i * block_q + block_q - 1 >= j * block_k)
    def _compute():
        p, ds = _mla_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                        lse_ref, delta_ref, scale, i, j, block_q, block_k)
        do = do_ref[0, 0, :, :]
        over_q = (((0,), (0,)), ((), ()))
        dv_scratch[:] = dv_scratch[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, over_q,
            preferred_element_type=jnp.float32)
        dkn_scratch[:] = dkn_scratch[:] + jax.lax.dot_general(
            ds, qn_ref[0, 0, :, :], over_q,
            preferred_element_type=jnp.float32)
        dkr_scratch[:] = dkr_scratch[:] + jax.lax.dot_general(
            ds, qr_ref[0, 0, :, :], over_q,
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finalize():
        dkn_ref[0, 0, :, :] = dkn_scratch[:].astype(dkn_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scratch[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(h == nh - 1, i == nq - 1))
    def _finalize_shared():
        dkr_ref[0, 0, :, :] = dkr_scratch[:].astype(dkr_ref.dtype)


def _mla_dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dqn_ref, dqr_ref, dqn_scratch, dqr_scratch,
                   *, scale, block_q, block_k):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dqn_scratch[:] = jnp.zeros_like(dqn_scratch)
        dqr_scratch[:] = jnp.zeros_like(dqr_scratch)

    @pl.when(j * block_k <= i * block_q + block_q - 1)
    def _compute():
        _, ds = _mla_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                        lse_ref, delta_ref, scale, i, j, block_q, block_k)
        over_k = (((1,), (0,)), ((), ()))
        dqn_scratch[:] = dqn_scratch[:] + jax.lax.dot_general(
            ds, kn_ref[0, 0, :, :], over_k,
            preferred_element_type=jnp.float32)
        dqr_scratch[:] = dqr_scratch[:] + jax.lax.dot_general(
            ds, kr_ref[0, 0, :, :], over_k,
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dqn_ref[0, 0, :, :] = dqn_scratch[:].astype(dqn_ref.dtype)
        dqr_ref[0, 0, :, :] = dqr_scratch[:].astype(dqr_ref.dtype)


def _mla_bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                    dqn_scratch, dqr_scratch, dkn_scratch, dkr_scratch,
                    dv_scratch, *, scale, block_q, block_k):
    # grid (batch, h, j, i), all sequential. One (p, ds) a tile feeds all
    # five gradients: its own key's and the value's accumulate over the
    # q blocks of this k block as in ``_mla_dkv_kernel``; the query's
    # and the shared rotary key's are whole rows in VMEM, the query's
    # carried over the k blocks of a head (zeroed at its first, written
    # at the last k block a q block sees), the rotary key's over every
    # head of a batch row. The sums run in the two kernels' order.
    h = pl.program_id(1)
    j = pl.program_id(2)
    i = pl.program_id(3)
    nh = pl.num_programs(1)
    nq = pl.num_programs(3)
    q_rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
    k_rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)

    @pl.when(i == 0)
    def _init():
        dkn_scratch[:] = jnp.zeros_like(dkn_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    @pl.when(jnp.logical_and(h == 0, i == 0))
    def _init_shared():
        dkr_scratch[k_rows, :] = jnp.zeros(
            (block_k, dkr_scratch.shape[1]), dkr_scratch.dtype)

    @pl.when(j == 0)
    def _init_q():
        dqn_scratch[q_rows, :] = jnp.zeros(
            (block_q, dqn_scratch.shape[1]), dqn_scratch.dtype)
        dqr_scratch[q_rows, :] = jnp.zeros(
            (block_q, dqr_scratch.shape[1]), dqr_scratch.dtype)

    @pl.when(i * block_q + block_q - 1 >= j * block_k)
    def _compute():
        p, ds = _mla_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                        lse_ref, delta_ref, scale, i, j, block_q, block_k)
        do = do_ref[0, 0, :, :]
        over_q = (((0,), (0,)), ((), ()))
        over_k = (((1,), (0,)), ((), ()))
        dv_scratch[:] = dv_scratch[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, over_q,
            preferred_element_type=jnp.float32)
        dkn_scratch[:] = dkn_scratch[:] + jax.lax.dot_general(
            ds, qn_ref[0, 0, :, :], over_q,
            preferred_element_type=jnp.float32)
        dkr_scratch[k_rows, :] = dkr_scratch[k_rows, :] + jax.lax.dot_general(
            ds, qr_ref[0, 0, :, :], over_q,
            preferred_element_type=jnp.float32)
        dqn_scratch[q_rows, :] = dqn_scratch[q_rows, :] + jax.lax.dot_general(
            ds, kn_ref[0, 0, :, :], over_k,
            preferred_element_type=jnp.float32)
        dqr_scratch[q_rows, :] = dqr_scratch[q_rows, :] + jax.lax.dot_general(
            ds, kr_ref[0, 0, :, :], over_k,
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finalize():
        dkn_ref[0, 0, :, :] = dkn_scratch[:].astype(dkn_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scratch[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(h == nh - 1, i == nq - 1))
    def _finalize_shared():
        dkr_ref[0, 0, k_rows, :] = dkr_scratch[k_rows, :].astype(
            dkr_ref.dtype)

    @pl.when(j == (i * block_q + block_q - 1) // block_k)
    def _finalize_q():
        dqn_ref[0, 0, q_rows, :] = dqn_scratch[q_rows, :].astype(
            dqn_ref.dtype)
        dqr_ref[0, 0, q_rows, :] = dqr_scratch[q_rows, :].astype(
            dqr_ref.dtype)


def _mla_blocks(q_nope, q_rope, k_nope, k_rope, v, block_q, block_k,
                interpret):
    batch, heads, seq, dn = q_nope.shape
    dr, dv = q_rope.shape[3], v.shape[3]
    want = {"q_rope": (batch, heads, seq, dr),
            "k_nope": (batch, heads, seq, dn),
            "k_rope": (batch, 1, seq, dr), "v": (batch, heads, seq, dv)}
    got = {"q_rope": q_rope.shape, "k_nope": k_nope.shape,
           "k_rope": k_rope.shape, "v": v.shape}
    if got != want:
        raise ValueError(
            f"latent attention of q_nope {q_nope.shape} wants {want}, "
            f"got {got}: one key and value head a query head, one "
            "rotary key head for all")
    bq, bk = _fit_block(block_q, seq), _fit_block(block_k, seq)
    _check_mosaic_lane_block(interpret, bq, seq, "block_q")
    return bq, bk


def _mla_forward(q_nope, q_rope, k_nope, k_rope, v, scale, block_q,
                 block_k, interpret):
    batch, heads, seq, dn = q_nope.shape
    dr, dv = q_rope.shape[3], v.shape[3]
    bq, bk = _mla_blocks(q_nope, q_rope, k_nope, k_rope, v, block_q,
                         block_k, interpret)
    # the last k block a q block sees; later grid entries keep its
    # index, so nothing is copied for them
    kj = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)  # noqa: E731
    qi = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    kh = lambda b, h, i, j: (b, h, kj(i, j), 0)  # noqa: E731
    k1 = lambda b, h, i, j: (b, 0, kj(i, j), 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_mla_fwd_kernel, scale=scale, block_q=bq,
                          block_k=bk),
        grid=(batch, heads, seq // bq, seq // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dn), qi),
            pl.BlockSpec((1, 1, bq, dr), qi),
            pl.BlockSpec((1, 1, bk, dn), kh),
            pl.BlockSpec((1, 1, bk, dr), k1),
            pl.BlockSpec((1, 1, bk, dv), kh),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), qi),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, seq, dv), q_nope.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, seq), jnp.float32),
        ],
        scratch_shapes=[_vmem((bq, LANES)), _vmem((bq, LANES)),
                        _vmem((bq, dv))],
        interpret=interpret,
        name="flash_mla_fwd",
    )(q_nope, q_rope, k_nope, k_rope, v)


# The backward is one kernel where its whole-row state fits in VMEM:
# for one head the query's gradient in float32, for one batch row the
# shared rotary key's, and their whole-row output blocks, which the
# pallas pipeline double-buffers (``_mla_row_state_bytes``: 24 MiB at
# rows of 8192 in bf16). Longer rows take the two kernels, which hold a
# block each. The v5e has 128 MiB of VMEM, of which Mosaic gives a
# kernel 16 MiB unless asked: the one kernel asks for the state's
# budget and as much again for its tiles.
_MLA_ROW_STATE_BUDGET_BYTES = 32 * 1024 * 1024
_MLA_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _mla_row_state_bytes(seq, dn, dr, itemsize):
    """VMEM bytes of ``flash_mla_bwd``'s whole-row accumulators and
    output blocks."""
    return (4 + 2 * itemsize) * seq * (_lanes(dn) + 2 * _lanes(dr))


def _mla_backward(q_nope, q_rope, k_nope, k_rope, v, out, lse4, do, scale,
                  block_q, block_k, interpret):
    batch, heads, seq, dn = q_nope.shape
    dr = q_rope.shape[3]
    bq, bk = _mla_blocks(q_nope, q_rope, k_nope, k_rope, v, block_q,
                         block_k, interpret)
    f32 = jnp.float32
    delta4 = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1).reshape(
        batch, heads, 1, seq)
    operands = (q_nope, q_rope, k_nope, k_rope, v, do, lse4, delta4)
    state = _mla_row_state_bytes(seq, dn, dr, q_nope.dtype.itemsize)
    calls = (_mla_backward_one_call if state <= _MLA_ROW_STATE_BUDGET_BYTES
             else _mla_backward_two_calls)
    return calls(operands, scale, bq, bk, interpret)


def _mla_backward_one_call(operands, scale, bq, bk, interpret):
    from jax.experimental.pallas import tpu as pltpu

    q_nope, q_rope, k_nope, k_rope, v = operands[:5]
    batch, heads, seq, dn = q_nope.shape
    dr, dv = q_rope.shape[3], v.shape[3]
    # grid (b, h, j, i); the first q block that sees k block j
    qi_of = lambda j, i: jnp.maximum(i, (j * bk) // bq)  # noqa: E731
    qh = lambda b, h, j, i: (b, h, qi_of(j, i), 0)  # noqa: E731
    kh = lambda b, h, j, i: (b, h, j, 0)  # noqa: E731
    k1 = lambda b, h, j, i: (b, 0, j, 0)  # noqa: E731
    row = lambda b, h, j, i: (b, h, 0, qi_of(j, i))  # noqa: E731
    # whole rows: resident for a head (dQ) and for a batch row (the
    # shared key's gradient), written back when that index moves on
    head_row = lambda b, h, j, i: (b, h, 0, 0)  # noqa: E731
    shared_row = lambda b, h, j, i: (b, 0, 0, 0)  # noqa: E731
    dqn, dqr, dkn, dkr, dvv = pl.pallas_call(
        functools.partial(_mla_bwd_kernel, scale=scale, block_q=bq,
                          block_k=bk),
        grid=(batch, heads, seq // bk, seq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dn), qh),
            pl.BlockSpec((1, 1, bq, dr), qh),
            pl.BlockSpec((1, 1, bk, dn), kh),
            pl.BlockSpec((1, 1, bk, dr), k1),
            pl.BlockSpec((1, 1, bk, dv), kh),
            pl.BlockSpec((1, 1, bq, dv), qh),
            pl.BlockSpec((1, 1, 1, bq), row),
            pl.BlockSpec((1, 1, 1, bq), row),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, seq, dn), head_row),
            pl.BlockSpec((1, 1, seq, dr), head_row),
            pl.BlockSpec((1, 1, bk, dn), kh),
            pl.BlockSpec((1, 1, seq, dr), shared_row),
            pl.BlockSpec((1, 1, bk, dv), kh),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
            jax.ShapeDtypeStruct(q_rope.shape, q_rope.dtype),
            jax.ShapeDtypeStruct(k_nope.shape, k_nope.dtype),
            jax.ShapeDtypeStruct(k_rope.shape, k_rope.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[_vmem((seq, dn)), _vmem((seq, dr)), _vmem((bk, dn)),
                        _vmem((seq, dr)), _vmem((bk, dv))],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_MLA_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="flash_mla_bwd",
    )(*operands)
    return dqn, dqr, dkn, dkr, dvv


def _mla_backward_two_calls(operands, scale, bq, bk, interpret):
    q_nope, q_rope, k_nope, k_rope, v = operands[:5]
    batch, heads, seq, dn = q_nope.shape
    dr, dv = q_rope.shape[3], v.shape[3]
    nq, nk = seq // bq, seq // bk
    kernel_args = dict(scale=scale, block_q=bq, block_k=bk)

    # dKV grid (b, j, h, i); the first q block that sees k block j
    qi_of = lambda j, i: jnp.maximum(i, (j * bk) // bq)  # noqa: E731
    qh = lambda b, j, h, i: (b, h, qi_of(j, i), 0)  # noqa: E731
    kh = lambda b, j, h, i: (b, h, j, 0)  # noqa: E731
    k1 = lambda b, j, h, i: (b, 0, j, 0)  # noqa: E731
    row = lambda b, j, h, i: (b, h, 0, qi_of(j, i))  # noqa: E731
    dkn, dkr, dvv = pl.pallas_call(
        functools.partial(_mla_dkv_kernel, **kernel_args),
        grid=(batch, nk, heads, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dn), qh),
            pl.BlockSpec((1, 1, bq, dr), qh),
            pl.BlockSpec((1, 1, bk, dn), kh),
            pl.BlockSpec((1, 1, bk, dr), k1),
            pl.BlockSpec((1, 1, bk, dv), kh),
            pl.BlockSpec((1, 1, bq, dv), qh),
            pl.BlockSpec((1, 1, 1, bq), row),
            pl.BlockSpec((1, 1, 1, bq), row),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, dn), kh),
            pl.BlockSpec((1, 1, bk, dr), k1),
            pl.BlockSpec((1, 1, bk, dv), kh),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k_nope.shape, k_nope.dtype),
            jax.ShapeDtypeStruct(k_rope.shape, k_rope.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[_vmem((bk, dn)), _vmem((bk, dr)), _vmem((bk, dv))],
        interpret=interpret,
        name="flash_mla_dkv",
    )(*operands)

    kj_of = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)  # noqa: E731
    qi = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    kh = lambda b, h, i, j: (b, h, kj_of(i, j), 0)  # noqa: E731
    k1 = lambda b, h, i, j: (b, 0, kj_of(i, j), 0)  # noqa: E731
    ri = lambda b, h, i, j: (b, h, 0, i)  # noqa: E731
    dqn, dqr = pl.pallas_call(
        functools.partial(_mla_dq_kernel, **kernel_args),
        grid=(batch, heads, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dn), qi),
            pl.BlockSpec((1, 1, bq, dr), qi),
            pl.BlockSpec((1, 1, bk, dn), kh),
            pl.BlockSpec((1, 1, bk, dr), k1),
            pl.BlockSpec((1, 1, bk, dv), kh),
            pl.BlockSpec((1, 1, bq, dv), qi),
            pl.BlockSpec((1, 1, 1, bq), ri),
            pl.BlockSpec((1, 1, 1, bq), ri),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dn), qi),
            pl.BlockSpec((1, 1, bq, dr), qi),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
            jax.ShapeDtypeStruct(q_rope.shape, q_rope.dtype),
        ],
        scratch_shapes=[_vmem((bq, dn)), _vmem((bq, dr))],
        interpret=interpret,
        name="flash_mla_dq",
    )(*operands)
    return dqn, dqr, dkn, dkr, dvv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_attention_mla(
    q_nope: jax.Array,  # [B, H, S, Dn]
    q_rope: jax.Array,  # [B, H, S, Dr], rotated
    k_nope: jax.Array,  # [B, H, S, Dn]
    k_rope: jax.Array,  # [B, 1, S, Dr], rotated: one head for all
    v: jax.Array,  # [B, H, S, Dv]
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal latent attention in its materialised (training) form:
    ``softmax((q_nope k_nope^T + q_rope k_rope^T) * scale) v`` with the
    rotary key head shared by all query heads. ``scale`` defaults to
    ``(Dn + Dr) ** -0.5``. Kernels ``flash_mla_fwd`` and
    ``flash_mla_bwd``; rows too long for the backward's whole-row
    accumulators in VMEM (``_mla_row_state_bytes``: beyond 8192 at
    128 + 64 in bf16) run ``flash_mla_dkv`` and ``flash_mla_dq``
    instead."""
    return _flash_mla_fwd(q_nope, q_rope, k_nope, k_rope, v, scale,
                          block_q, block_k, interpret)[0]


def _flash_mla_fwd(q_nope, q_rope, k_nope, k_rope, v, scale, block_q,
                   block_k, interpret):
    scale_v, interp = _resolve(
        scale, q_nope.shape[-1] + q_rope.shape[-1], interpret)
    out, lse4 = _kept(*_mla_forward(q_nope, q_rope, k_nope, k_rope, v,
                                    scale_v, block_q, block_k, interp))
    return out, (q_nope, q_rope, k_nope, k_rope, v, out, lse4)


def _flash_mla_bwd(scale, block_q, block_k, interpret, residuals, do):
    q_nope, q_rope, k_nope, k_rope, v, out, lse4 = residuals
    scale_v, interp = _resolve(
        scale, q_nope.shape[-1] + q_rope.shape[-1], interpret)
    return _mla_backward(q_nope, q_rope, k_nope, k_rope, v, out, lse4, do,
                         scale_v, block_q, block_k, interp)


flash_attention_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


# -- grouped and windowed latent attention ----------------------------------
#
# The same attention where ``G`` key/value heads serve ``H = rep * G``
# query heads (query heads ``rep * g .. rep * g + rep - 1`` read K/V
# head ``g``), and, with ``window``, over the causal band ``t - window <
# s <= t`` alone. A grid step holds a group: its ``rep`` query heads'
# blocks side by side, one K/V block and one block of the shared rotary
# key, which are so read once a group and not once a query head; the
# heads are walked in the step, each with its own running maximum, sum
# and accumulator. The backward is a dKV and a dQ kernel (a group's
# whole-row query gradients, ``rep`` times ``flash_mla_bwd``'s, do not
# fit VMEM): K's and V's gradients sum over the group's heads and q
# blocks, the rotary key's over every group besides. Under a window the
# grids hold the band's tiles only (``band_walk``) and a tile runs the
# body of its kind, as ``flash_win_*`` do. Kernels ``flash_mla_fwd``,
# ``flash_mla_dkv``, ``flash_mla_dq``; the band's ``flash_mla_win_*``.
# ``G == H`` with no window is ``flash_attention_mla`` above, untouched.


def _mla_tile_scores(qn, qr, kn, kr, scale, i, j, block_q, block_k, window,
                     diagonal, far):
    """The masked float32 score tile of one query head."""
    if not window:
        return _mla_scores(qn, qr, kn, kr, scale, i, j, block_q, block_k)
    return _band_mask(_mla_raw_scores(qn, qr, kn, kr, scale), i, j, block_q,
                      block_k, window, diagonal, far)


def _mla_group_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                          lse_ref, m_scratch, l_scratch, acc_scratch, *,
                          scale, block_q, block_k, rep, window, kinds):
    i = pl.program_id(2)
    jj = pl.program_id(3)
    nk = pl.num_programs(3)
    # windowed: the grid's k entries end at the tile of the q block's
    # last query; an entry before the band's first tile is skipped
    j = _band_last_k(i, block_q, block_k) - (nk - 1) + jj if window else jj

    @pl.when(jj == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    if window:
        needed = j >= _band_first_k(i, block_q, block_k, window)
    else:
        needed = j * block_k <= i * block_q + block_q - 1

    def _compute(diagonal=True, far=True):
        kn, kr, v = kn_ref[0, 0, :, :], kr_ref[0, 0, :, :], v_ref[0, 0, :, :]
        for r in range(rep):
            s = _mla_tile_scores(qn_ref[0, r, :, :], qr_ref[0, r, :, :], kn,
                                 kr, scale, i, j, block_q, block_k, window,
                                 diagonal, far)
            m_prev = m_scratch[r, :, :1]
            l_prev = l_scratch[r, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a far tile hides all of itself from the rows whose window
            # starts later: see ``_flash_fwd_kernel``
            m_sub = (jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
                     if window and far else m_new)
            p = jnp.exp(s - m_sub)
            alpha = jnp.exp(m_prev - m_sub)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_scratch[r, :, :] = (
                acc_scratch[r, :, :] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            m_scratch[r, :, :] = jnp.broadcast_to(m_new,
                                                  m_scratch.shape[1:])
            l_scratch[r, :, :] = jnp.broadcast_to(l_new,
                                                  l_scratch.shape[1:])

    _when_tile(needed, i, j, block_q, block_k, window, kinds, _compute)

    @pl.when(jj == nk - 1)
    def _finalize():
        for r in range(rep):
            l = l_scratch[r, :, :1]
            o_ref[0, r, :, :] = (acc_scratch[r, :, :] / l).astype(
                o_ref.dtype)
            lse_ref[0, r, 0, :] = (m_scratch[r, :, :1] + jnp.log(l))[:, 0]


def _mla_group_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                  delta_ref, r, scale, i, j, block_q, block_k, window,
                  diagonal, far):
    """(p, ds) of one tile of the group's query head ``r``, recomputed
    from the saved logsumexp."""
    qn = qn_ref[0, r, :, :]
    s = _mla_tile_scores(qn, qr_ref[0, r, :, :], kn_ref[0, 0, :, :],
                         kr_ref[0, 0, :, :], scale, i, j, block_q, block_k,
                         window, diagonal, far)
    p = jnp.exp(s - lse_ref[0, r, 0, :][:, None])
    dp = jax.lax.dot_general(
        do_ref[0, r, :, :], v_ref[0, 0, :, :], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = (p * (dp - delta_ref[0, r, 0, :][:, None]) * scale).astype(
        qn.dtype)
    return p, ds


def _mla_group_dkv_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                          lse_ref, delta_ref, dkn_ref, dkr_ref, dv_ref,
                          dkn_scratch, dkr_scratch, dv_scratch, *, scale,
                          block_q, block_k, rep, window, q_blocks, kinds):
    # grid (batch, j, g, ii): g and ii are sequential, so the rotary
    # key's gradient accumulates over every group, head and q block of
    # this k block, a group's key's and value's over its heads and the
    # q blocks
    j = pl.program_id(1)
    g = pl.program_id(2)
    ii = pl.program_id(3)
    ng = pl.num_programs(2)
    nq = pl.num_programs(3)
    # windowed: the grid holds the q blocks whose band touches this k
    # tile; past the last of them the index maps clamp i and the entry
    # is skipped here
    i = _band_first_q(j, block_q, block_k) + ii if window else ii

    @pl.when(ii == 0)
    def _init():
        dkn_scratch[:] = jnp.zeros_like(dkn_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    @pl.when(jnp.logical_and(g == 0, ii == 0))
    def _init_shared():
        dkr_scratch[:] = jnp.zeros_like(dkr_scratch)

    if window:
        needed = i <= _band_last_q(j, block_q, block_k, window, q_blocks)
    else:
        needed = i * block_q + block_q - 1 >= j * block_k

    def _compute(diagonal=True, far=True):
        over_q = (((0,), (0,)), ((), ()))
        for r in range(rep):
            p, ds = _mla_group_ds(
                qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                delta_ref, r, scale, i, j, block_q, block_k, window,
                diagonal, far)
            do = do_ref[0, r, :, :]
            dv_scratch[:] = dv_scratch[:] + jax.lax.dot_general(
                p.astype(do.dtype), do, over_q,
                preferred_element_type=jnp.float32)
            dkn_scratch[:] = dkn_scratch[:] + jax.lax.dot_general(
                ds, qn_ref[0, r, :, :], over_q,
                preferred_element_type=jnp.float32)
            dkr_scratch[:] = dkr_scratch[:] + jax.lax.dot_general(
                ds, qr_ref[0, r, :, :], over_q,
                preferred_element_type=jnp.float32)

    _when_tile(needed, i, j, block_q, block_k, window, kinds, _compute)

    @pl.when(ii == nq - 1)
    def _finalize():
        dkn_ref[0, 0, :, :] = dkn_scratch[:].astype(dkn_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scratch[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(g == ng - 1, ii == nq - 1))
    def _finalize_shared():
        dkr_ref[0, 0, :, :] = dkr_scratch[:].astype(dkr_ref.dtype)


def _mla_group_dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                         lse_ref, delta_ref, dqn_ref, dqr_ref, dqn_scratch,
                         dqr_scratch, *, scale, block_q, block_k, rep,
                         window, kinds):
    i = pl.program_id(2)
    jj = pl.program_id(3)
    nk = pl.num_programs(3)
    j = _band_last_k(i, block_q, block_k) - (nk - 1) + jj if window else jj

    @pl.when(jj == 0)
    def _init():
        dqn_scratch[:] = jnp.zeros_like(dqn_scratch)
        dqr_scratch[:] = jnp.zeros_like(dqr_scratch)

    if window:
        needed = j >= _band_first_k(i, block_q, block_k, window)
    else:
        needed = j * block_k <= i * block_q + block_q - 1

    def _compute(diagonal=True, far=True):
        over_k = (((1,), (0,)), ((), ()))
        for r in range(rep):
            _, ds = _mla_group_ds(
                qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                delta_ref, r, scale, i, j, block_q, block_k, window,
                diagonal, far)
            dqn_scratch[r, :, :] = (
                dqn_scratch[r, :, :] + jax.lax.dot_general(
                    ds, kn_ref[0, 0, :, :], over_k,
                    preferred_element_type=jnp.float32))
            dqr_scratch[r, :, :] = (
                dqr_scratch[r, :, :] + jax.lax.dot_general(
                    ds, kr_ref[0, 0, :, :], over_k,
                    preferred_element_type=jnp.float32))

    _when_tile(needed, i, j, block_q, block_k, window, kinds, _compute)

    @pl.when(jj == nk - 1)
    def _finalize():
        dqn_ref[0, :, :, :] = dqn_scratch[:].astype(dqn_ref.dtype)
        dqr_ref[0, :, :, :] = dqr_scratch[:].astype(dqr_ref.dtype)


def _mla_group_plan(q_nope, q_rope, k_nope, k_rope, v, window, block_q,
                    block_k, interpret):
    """``(rep, block_q, block_k, the band's walk or None)`` of a grouped
    or windowed call, its shapes checked."""
    batch, heads, seq, dn = q_nope.shape
    groups, dr, dv = k_nope.shape[1], q_rope.shape[3], v.shape[3]
    want = {"q_rope": (batch, heads, seq, dr),
            "k_nope": (batch, groups, seq, dn),
            "k_rope": (batch, 1, seq, dr), "v": (batch, groups, seq, dv)}
    got = {"q_rope": q_rope.shape, "k_nope": k_nope.shape,
           "k_rope": k_rope.shape, "v": v.shape}
    if got != want or heads % groups or window < 0:
        raise ValueError(
            f"grouped latent attention of q_nope {q_nope.shape} wants "
            f"{want} with a head count that divides {heads} and a window "
            f"of 0 or more keys, got {got} and window {window}")
    bq, bk = _fit_block(block_q, seq), _fit_block(block_k, seq)
    _check_mosaic_lane_block(interpret, bq, seq, "block_q")
    return (heads // groups, bq, bk,
            band_walk(seq, window, bq, bk) if window else None)


def _mla_group_params(interpret):
    """The grouped kernels hold ``rep`` heads' blocks and accumulators a
    step: they ask Mosaic for ``flash_mla_bwd``'s limit."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_MLA_VMEM_LIMIT_BYTES)}


def _mla_group_forward(q_nope, q_rope, k_nope, k_rope, v, scale, window,
                       block_q, block_k, interpret):
    batch, heads, seq, dn = q_nope.shape
    groups, dr, dv = k_nope.shape[1], q_rope.shape[3], v.shape[3]
    rep, bq, bk, walk = _mla_group_plan(
        q_nope, q_rope, k_nope, k_rope, v, window, block_q, block_k,
        interpret)
    if walk:
        k_steps, kinds = walk.k_steps, walk.kinds
        kj = lambda i, j: jnp.maximum(  # noqa: E731
            _band_last_k(i, bq, bk) - (k_steps - 1) + j,
            _band_first_k(i, bq, bk, window))
    else:
        k_steps, kinds = seq // bk, ()
        kj = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)  # noqa: E731
    qi = lambda b, g, i, j: (b, g, i, 0)  # noqa: E731
    kg = lambda b, g, i, j: (b, g, kj(i, j), 0)  # noqa: E731
    k1 = lambda b, g, i, j: (b, 0, kj(i, j), 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_mla_group_fwd_kernel, scale=scale, block_q=bq,
                          block_k=bk, rep=rep, window=window, kinds=kinds),
        grid=(batch, groups, seq // bq, k_steps),
        in_specs=[
            pl.BlockSpec((1, rep, bq, dn), qi),
            pl.BlockSpec((1, rep, bq, dr), qi),
            pl.BlockSpec((1, 1, bk, dn), kg),
            pl.BlockSpec((1, 1, bk, dr), k1),
            pl.BlockSpec((1, 1, bk, dv), kg),
        ],
        out_specs=[
            pl.BlockSpec((1, rep, bq, dv), qi),
            pl.BlockSpec((1, rep, 1, bq), lambda b, g, i, j: (b, g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, seq, dv), q_nope.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, seq), jnp.float32),
        ],
        scratch_shapes=[_vmem((rep, bq, LANES)), _vmem((rep, bq, LANES)),
                        _vmem((rep, bq, dv))],
        interpret=interpret,
        name="flash_mla_win_fwd" if window else "flash_mla_fwd",
        **_mla_group_params(interpret),
    )(q_nope, q_rope, k_nope, k_rope, v)


def _mla_group_backward(q_nope, q_rope, k_nope, k_rope, v, out, lse4, do,
                        scale, window, block_q, block_k, interpret):
    batch, heads, seq, dn = q_nope.shape
    groups, dr, dv = k_nope.shape[1], q_rope.shape[3], v.shape[3]
    rep, bq, bk, walk = _mla_group_plan(
        q_nope, q_rope, k_nope, k_rope, v, window, block_q, block_k,
        interpret)
    nq, nk = seq // bq, seq // bk
    if walk:
        q_steps, k_steps, kinds = walk.q_steps, walk.k_steps, walk.kinds
        qi_of = lambda j, i: jnp.minimum(  # noqa: E731
            _band_first_q(j, bq, bk) + i,
            _band_last_q(j, bq, bk, window, nq))
        kj_of = lambda i, j: jnp.maximum(  # noqa: E731
            _band_last_k(i, bq, bk) - (k_steps - 1) + j,
            _band_first_k(i, bq, bk, window))
    else:
        q_steps, k_steps, kinds = nq, nk, ()
        qi_of = lambda j, i: jnp.maximum(i, (j * bk) // bq)  # noqa: E731
        kj_of = lambda i, j: jnp.minimum(  # noqa: E731
            j, (i * bq + bq - 1) // bk)
    f32 = jnp.float32
    delta4 = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1).reshape(
        batch, heads, 1, seq)
    operands = (q_nope, q_rope, k_nope, k_rope, v, do, lse4, delta4)
    kernel_args = dict(scale=scale, block_q=bq, block_k=bk, rep=rep,
                       window=window, kinds=kinds)
    prefix = "flash_mla_win_" if window else "flash_mla_"

    # dKV grid (b, j, g, ii)
    qg = lambda b, j, g, i: (b, g, qi_of(j, i), 0)  # noqa: E731
    kg = lambda b, j, g, i: (b, g, j, 0)  # noqa: E731
    k1 = lambda b, j, g, i: (b, 0, j, 0)  # noqa: E731
    row = lambda b, j, g, i: (b, g, 0, qi_of(j, i))  # noqa: E731
    dkn, dkr, dvv = pl.pallas_call(
        functools.partial(_mla_group_dkv_kernel, q_blocks=nq,
                          **kernel_args),
        grid=(batch, nk, groups, q_steps),
        in_specs=[
            pl.BlockSpec((1, rep, bq, dn), qg),
            pl.BlockSpec((1, rep, bq, dr), qg),
            pl.BlockSpec((1, 1, bk, dn), kg),
            pl.BlockSpec((1, 1, bk, dr), k1),
            pl.BlockSpec((1, 1, bk, dv), kg),
            pl.BlockSpec((1, rep, bq, dv), qg),
            pl.BlockSpec((1, rep, 1, bq), row),
            pl.BlockSpec((1, rep, 1, bq), row),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, dn), kg),
            pl.BlockSpec((1, 1, bk, dr), k1),
            pl.BlockSpec((1, 1, bk, dv), kg),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k_nope.shape, k_nope.dtype),
            jax.ShapeDtypeStruct(k_rope.shape, k_rope.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[_vmem((bk, dn)), _vmem((bk, dr)), _vmem((bk, dv))],
        interpret=interpret,
        name=prefix + "dkv",
        **_mla_group_params(interpret),
    )(*operands)

    # dQ grid (b, g, i, jj)
    qi = lambda b, g, i, j: (b, g, i, 0)  # noqa: E731
    kg = lambda b, g, i, j: (b, g, kj_of(i, j), 0)  # noqa: E731
    k1 = lambda b, g, i, j: (b, 0, kj_of(i, j), 0)  # noqa: E731
    ri = lambda b, g, i, j: (b, g, 0, i)  # noqa: E731
    dqn, dqr = pl.pallas_call(
        functools.partial(_mla_group_dq_kernel, **kernel_args),
        grid=(batch, groups, nq, k_steps),
        in_specs=[
            pl.BlockSpec((1, rep, bq, dn), qi),
            pl.BlockSpec((1, rep, bq, dr), qi),
            pl.BlockSpec((1, 1, bk, dn), kg),
            pl.BlockSpec((1, 1, bk, dr), k1),
            pl.BlockSpec((1, 1, bk, dv), kg),
            pl.BlockSpec((1, rep, bq, dv), qi),
            pl.BlockSpec((1, rep, 1, bq), ri),
            pl.BlockSpec((1, rep, 1, bq), ri),
        ],
        out_specs=[
            pl.BlockSpec((1, rep, bq, dn), qi),
            pl.BlockSpec((1, rep, bq, dr), qi),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
            jax.ShapeDtypeStruct(q_rope.shape, q_rope.dtype),
        ],
        scratch_shapes=[_vmem((rep, bq, dn)), _vmem((rep, bq, dr))],
        interpret=interpret,
        name=prefix + "dq",
        **_mla_group_params(interpret),
    )(*operands)
    return dqn, dqr, dkn, dkr, dvv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention_mla_grouped(
    q_nope: jax.Array,  # [B, H, S, Dn]
    q_rope: jax.Array,  # [B, H, S, Dr], rotated
    k_nope: jax.Array,  # [B, G, S, Dn], G divides H
    k_rope: jax.Array,  # [B, 1, S, Dr], rotated: one head for all
    v: jax.Array,  # [B, G, S, Dv]
    scale: Optional[float] = None,
    window: int = 0,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``flash_attention_mla`` with ``G <= H`` key and value heads
    (query heads ``rep * g`` to ``rep * g + rep - 1`` read head ``g``,
    ``rep = H / G``) and, with ``window`` > 0, over the band ``t -
    window < s <= t`` alone, on a grid of the band's tiles. Every query
    head keeps its own softmax and logsumexp. Kernels ``flash_mla_fwd``,
    ``flash_mla_dkv`` and ``flash_mla_dq``, under a window
    ``flash_mla_win_fwd``, ``_dkv`` and ``_dq``."""
    return _flash_mla_group_fwd(q_nope, q_rope, k_nope, k_rope, v, scale,
                                window, block_q, block_k, interpret)[0]


def _flash_mla_group_fwd(q_nope, q_rope, k_nope, k_rope, v, scale, window,
                         block_q, block_k, interpret):
    scale_v, interp = _resolve(
        scale, q_nope.shape[-1] + q_rope.shape[-1], interpret)
    out, lse4 = _kept(*_mla_group_forward(
        q_nope, q_rope, k_nope, k_rope, v, scale_v, window, block_q,
        block_k, interp))
    return out, (q_nope, q_rope, k_nope, k_rope, v, out, lse4)


def _flash_mla_group_bwd(scale, window, block_q, block_k, interpret,
                         residuals, do):
    q_nope, q_rope, k_nope, k_rope, v, out, lse4 = residuals
    scale_v, interp = _resolve(
        scale, q_nope.shape[-1] + q_rope.shape[-1], interpret)
    return _mla_group_backward(q_nope, q_rope, k_nope, k_rope, v, out,
                               lse4, do, scale_v, window, block_q, block_k,
                               interp)


flash_attention_mla_grouped.defvjp(_flash_mla_group_fwd,
                                   _flash_mla_group_bwd)


def mla_band_tile_counters(calls: int, seq: int, window: int,
                           block: int = 128) -> Dict[str, int]:
    """``band_tile_counters`` for ``calls`` one-head forward calls of
    the windowed latent kernel at square tiles of ``block``."""
    bq = _fit_block(block, seq)
    walk = band_walk(seq, window, bq, bq)
    return {StepCounter.ATTN_BAND_TILES: calls * walk.tiles,
            StepCounter.ATTN_BAND_TILES_UNMASKED: calls * walk.unmasked}


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def flash_attention_mla_by_kind(windowed, q_nope, q_rope, k_nope, k_rope, v,
                                scale, window, blocks, window_block,
                                interpret):
    """``flash_attention_mla_grouped`` over the window where the traced
    scalar ``windowed`` is not 0, else over every causal key: what a
    scan over layers of both kinds calls. The forward and the backward
    each branch once (``lax.switch``) and share one set of residuals; a
    ``cond`` differentiated by JAX would carry both kinds' residuals
    out of it (see ``ops.moe._held_rungs``)."""
    return _mla_by_kind_fwd(windowed, q_nope, q_rope, k_nope, k_rope, v,
                            scale, window, blocks, window_block,
                            interpret)[0]


def _mla_kind_args(window, blocks, window_block):
    return ((0, *blocks), (window, window_block, window_block))


def _mla_by_kind_fwd(windowed, q_nope, q_rope, k_nope, k_rope, v, scale,
                     window, blocks, window_block, interpret):
    operands = (q_nope, q_rope, k_nope, k_rope, v)
    scale_v, interp = _resolve(
        scale, q_nope.shape[-1] + q_rope.shape[-1], interpret)
    # the names stand on the switch's two results, at the level of the
    # enclosing checkpoint's own equations; the branches call the
    # kernel and name nothing
    out, lse4 = _kept(*jax.lax.switch(
        (windowed != 0).astype(jnp.int32),
        [lambda *a, k=kind: _mla_group_forward(*a, scale_v, *k, interp)
         for kind in _mla_kind_args(window, blocks, window_block)],
        *operands))
    return out, (windowed, *operands, out, lse4)


def _mla_by_kind_bwd(scale, window, blocks, window_block, interpret,
                     residuals, do):
    windowed, *saved = residuals
    grads = jax.lax.switch(
        (windowed != 0).astype(jnp.int32),
        [lambda saved, do, k=kind: _flash_mla_group_bwd(
            scale, *k, interpret, saved, do)
         for kind in _mla_kind_args(window, blocks, window_block)],
        tuple(saved), do)
    return (None, *grads)


flash_attention_mla_by_kind.defvjp(_mla_by_kind_fwd, _mla_by_kind_bwd)


def flash_attention_mla_auto(q_nope, q_rope, k_nope, k_rope, v,
                             scale: Optional[float] = None,
                             block_q: int = 512, block_k: int = 1024,
                             interpret: Optional[bool] = None,
                             window: int = 0, window_block: int = 128,
                             windowed=None):
    """``flash_attention_mla``, through ``shard_map`` under a mesh:
    batch on the data axes, heads on ``tensor``, the shared rotary key
    whole on every head shard (its gradient is summed over them by the
    ``shard_map``'s transpose). Fewer key and value heads than query
    heads, or a ``window``, take ``flash_attention_mla_grouped`` (the
    band in square tiles of ``window_block``); ``windowed``, a traced
    scalar, says a layer at a time whether the window applies
    (``flash_attention_mla_by_kind``), None that it does wherever
    ``window`` is set."""
    mesh = ambient_shard_mesh()
    grouped = window or k_nope.shape[1] != q_nope.shape[1]

    def body(qn, qr, kn, kr, vv, *kind):
        if kind:
            return flash_attention_mla_by_kind(
                kind[0], qn, qr, kn, kr, vv, scale, window,
                (block_q, block_k), window_block, interpret)
        if grouped:
            tiles = (window_block,) * 2 if window else (block_q, block_k)
            return flash_attention_mla_grouped(
                qn, qr, kn, kr, vv, scale, window, *tiles, interpret)
        return flash_attention_mla(qn, qr, kn, kr, vv, scale, block_q,
                                   block_k, interpret)

    kind = () if windowed is None or not window else (windowed,)
    if mesh is None:
        return body(q_nope, q_rope, k_nope, k_rope, v, *kind)
    from jax.sharding import PartitionSpec as P

    heads = P(("data", "fsdp"), "tensor", None, None)
    shared = P(("data", "fsdp"), None, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(heads, heads, heads, shared, heads) + (P(),) * len(kind),
        out_specs=heads, check_vma=False,
    )(q_nope, q_rope, k_nope, k_rope, v, *kind)
