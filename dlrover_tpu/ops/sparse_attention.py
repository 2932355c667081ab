"""Learned sparse attention: grouped-query attention over the keys a
small indexer selects for each query, and the indexer's own loss
(DeepSeek-Sparse-Attention's token-level selection on GQA, as
Keye-VL-2.0's ``sa_config`` publishes it). Training only.

Shapes: ``q`` ``[B, H, T, D]``, ``k``, ``v`` ``[B, H_kv, T, D]`` (a KV
head serves ``H / H_kv`` query heads and is never repeated in HBM);
the indexer's queries ``qi`` ``[B, J, T, E]`` (``J`` small heads), its
ONE key head ``ki`` ``[B, T, E]`` and its per-head weights ``w`` ``[B,
T, J]``.

* ``index_scores``: ``I[t, s] = (J E)^-1/2 sum_j w[t, j] relu(qi_j[t] .
  ki[s])`` in float32 (dense, for small rows and tests);
* ``select_topk``: for each query the ``topk`` causal keys of largest
  score, exactly, ties to the lower position, every causal key where
  there are no more than ``topk``. Nothing sorts: a query's threshold is
  found by bisection over the float32 bit pattern (32 counts) and the
  tie's position by 15 more, over the query block's whole score row in
  VMEM (kernel ``dsa_index_select``). Gives the ``Selection``: the set
  as an int8 mask, key tile major, the logsumexp of the selected scores
  and a query's count of selected keys a key tile;
* ``selected_attention``: the causal flash walk with the selection as a
  mask inside each tile, a tile skipped where no pair of it is selected
  (scalar-prefetched tile flags); float32 softmax; returns the
  logsumexp too. Kernels ``dsa_attn_fwd`` and one backward kernel,
  ``dsa_attn_bwd``: a tile's scores, mask, probabilities and ``ds``
  once, dQ, dK and dV out of them (five products), the gradients whole
  float32 rows in VMEM. Rows whose state does not fit there
  (``_win_row_state_bytes`` over ``_ATTN_ROW_STATE_BUDGET_BYTES``:
  beyond 16,384 at widths of 128 in bf16) run ``dsa_attn_dkv`` and
  ``dsa_attn_dq`` instead, each recomputing the tile (seven products);
  the gradients are the same bit for bit. The forward rule names its
  two results ``KEPT_NAMES``, as results and as residuals: a
  ``jax.checkpoint`` whose policy saves those names (``ops.remat.
  apply_remat``'s ``keep``) does not run ``dsa_attn_fwd`` again in its
  replay (``kept_bytes`` is what that holds);
* ``index_kl``: the rows' weighted sum (the mean by default) of a
  row's KL divergence from the head-mean of the main attention's
  probabilities to the softmax of the index scores over the selected
  set, and its gradient to ``qi``, ``ki``, ``w`` alone, both out of ONE
  visit of a tile (kernel ``dsa_index_kl``: the head-mean and the index
  scores are built once): the rows' weights are data, so the forward
  rule has the gradient beside the value, names the three
  ``INDEX_KEPT_NAMES`` as its residuals, and the backward rule is the
  scalar cotangent times each; a checkpoint that saves the names runs
  the kernel once a step (``index_kept_bytes`` is what that holds).
  With no gradient asked the same body runs with its gradient half
  statically off.

``use_kernels=False`` is the XLA form of each (dense ``[T, T]``
arrays: a CPU rehearsal). A ``pallas_call`` is traced once a process
and lowered once a program (``ops.trace_once.shared_call``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.flash_attention import (
    LANES,
    NEG_INF,
    _band_first_q,
    _band_last_k,
    _check_mosaic_lane_block,
    _fit_block,
    _group_size,
    _resolve,
    _win_row_state_bytes,
)
from dlrover_tpu.ops.trace_once import shared_call
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter

_INT_MIN = -(2 ** 31)
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
# the whole score row of a query block lives in VMEM while its
# thresholds are found: 4 bytes x block_q x T
_SELECT_ROW_BUDGET_BYTES = 48 * 1024 * 1024
# the selected attention's backward is one kernel where its whole-row
# state fits VMEM beside the tiles: a query head's dQ and a KV head's dK
# and dV in float32, and their whole-row output blocks twice (the
# pipeline's double buffer): ``flash_win_bwd``'s state and budget,
# 48 MiB at rows of 16,384 and widths of 128 in bf16
_ATTN_ROW_STATE_BUDGET_BYTES = 48 * 1024 * 1024
_F32 = jnp.float32
# the selected attention's output and logsumexp, as its forward rule
# names them (``jax.ad_checkpoint.checkpoint_name``): what a layer's
# checkpoint keeps so that its replay leaves the forward kernel out
KEPT_NAMES = ("dsa_attn_out", "dsa_attn_lse")
# the gradients of the indexer's loss to its three operands, as that
# loss's forward rule names them: kept beside the two above, a layer's
# replay has nothing of the loss's kernel left to run
INDEX_KEPT_NAMES = ("dsa_index_dqi", "dsa_index_dki", "dsa_index_dw")


class Selection(NamedTuple):
    """Which keys each query attends to."""
    # [B, T / bk, T, bk] int8, key tile major: 1 where (query, key) is
    # selected (``dense_mask`` gives [B, T, T]); the kernels' key tile
    # is this layout's ``bk``
    mask: jax.Array
    lse: jax.Array  # [B, T] float32: logsumexp of the selected scores
    # [B, T / bk, T] float32: a query's selected keys in each key tile;
    # the tile flags and the counters are sums of these, and nothing in
    # XLA reads the mask's T x T bytes
    counts: jax.Array


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _vmem(shape, dtype=_F32):
    return pltpu.VMEM(shape, dtype)


def _blocks(seq, block_q, block_k, interpret):
    """The tiles: the largest divisors of the row within the requested
    sides (a kernel that reads a mask takes the mask's key tile)."""
    bq, bk = _fit_block(block_q, seq), _fit_block(block_k, seq)
    _check_mosaic_lane_block(interpret, bq, seq, "block_q")
    _check_mosaic_lane_block(interpret, bk, seq, "block_k")
    if not interpret and bq != seq and bq % 32:
        raise ValueError(f"block_q={bq}: the int8 mask's tiles are 32 rows")
    return bq, bk


# -- the indexer's scores -----------------------------------------------------


def index_scale(heads: int, dim: int) -> float:
    return float(heads * dim) ** -0.5


def index_scores(qi, ki, w):
    """``I`` [B, T, T] in float32, every pair (the caller masks)."""
    scale = index_scale(qi.shape[1], qi.shape[3])
    acc = jnp.zeros((qi.shape[0], qi.shape[2], ki.shape[1]), _F32)
    for j in range(qi.shape[1]):  # the kernels' order of summation
        r = jnp.einsum("bte,bse->bts", qi[:, j], ki,
                       preferred_element_type=_F32)
        acc = acc + w[..., j:j + 1].astype(_F32) * jnp.maximum(r, 0.0)
    return acc * scale


def _score_tile(qi_ref, ki, w, scale):
    """A [bq, bk] tile of ``I`` from the block ``qi_ref`` [1, J, bq, E],
    the keys ``ki`` [bk, E] and the weights ``w`` [bq, J] (float32)."""
    acc = None
    for j in range(qi_ref.shape[1]):
        r = lax.dot_general(qi_ref[0, j], ki, (((1,), (1,)), ((), ())),
                            preferred_element_type=_F32)
        term = w[:, j:j + 1] * jnp.maximum(r, 0.0)
        acc = term if acc is None else acc + term
    # + 0.0: a sum of products with a zero factor may be -0.0, which
    # the bit pattern would order below +0.0
    return acc * scale + 0.0


def _sortable(scores):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _tile_positions(i, j, bq, bk):
    rows = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
    cols = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
    return rows, cols


# -- the selection ------------------------------------------------------------


def _select_kernel(qi_ref, ki_ref, w_ref, mask_ref, lse_ref, counts_ref,
                   keys_scr, *, scale, topk, bq, bk, seq):
    """One query block: its score row into VMEM as sortable keys (key
    tiles past the diagonal are never touched), the row's ``topk``-th
    largest key by bisection from the sign bit down, the position up to
    which keys equal to it are taken, then the mask and the selected
    scores' logsumexp."""
    i = pl.program_id(1)
    nk = seq // bk
    # key tiles that hold a causal pair of this block
    tiles = _band_last_k(i, bq, bk) + 1
    w = w_ref[0].astype(_F32)

    def fill(j, _):
        ki = ki_ref[0, pl.ds(pl.multiple_of(j * bk, bk), bk), :]
        rows, cols = _tile_positions(i, j, bq, bk)
        keys = _sortable(_score_tile(qi_ref, ki, w, scale))
        keys_scr[j] = jnp.where(cols <= rows, keys, jnp.int32(_INT_MIN))
        return 0

    lax.fori_loop(0, tiles, fill, 0)

    def count(pred):
        """[bq, 1] float32: a row's keys that ``pred(keys, cols)``."""

        def body(j, acc):
            cols = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
            return acc + jnp.sum(
                jnp.where(pred(keys_scr[j], cols), 1.0, 0.0), axis=1,
                keepdims=True)

        return lax.fori_loop(0, tiles, body, jnp.zeros((bq, 1), _F32))

    k = jnp.float32(topk)
    # the largest value with at least ``topk`` keys at or above it: the
    # sign bit first, then bit 30 down to bit 0; a row of fewer causal
    # keys keeps INT_MIN and takes them all
    enough = count(lambda keys, _: keys >= 0) >= k
    thr = jnp.where(enough, jnp.int32(0), jnp.int32(_INT_MIN))

    def value_bit(n, thr):
        trial = thr | (jnp.int32(1) << (30 - n))
        enough = count(lambda keys, _: keys >= trial) >= k
        return jnp.where(enough, trial, thr)

    thr = lax.fori_loop(0, 31, value_bit, thr)
    # of the keys equal to the threshold the lowest positions are taken:
    # ``need`` of them, the last at the largest ``pos`` with fewer than
    # ``need`` equal keys before it
    need = k - count(lambda keys, _: keys > thr)
    bits = max(seq - 1, 1).bit_length()

    def position_bit(n, pos):
        trial = pos | (jnp.int32(1) << (bits - 1 - n))
        fewer = count(
            lambda keys, cols: (keys == thr) & (cols < trial)) < need
        return jnp.where(fewer, trial, pos)

    pos = lax.fori_loop(0, bits, position_bit,
                        jnp.zeros((bq, 1), jnp.int32))

    def selected(j):
        rows, cols = _tile_positions(i, j, bq, bk)
        keys = keys_scr[j]
        return (cols <= rows) & ((keys > thr)
                                 | ((keys == thr) & (cols <= pos))), keys

    def unsortable(keys):
        return lax.bitcast_convert_type(
            jnp.where(keys < 0, keys ^ jnp.int32(0x7FFFFFFF), keys), _F32)

    def row_max(j, m):
        keep, keys = selected(j)
        return jnp.maximum(m, jnp.max(
            jnp.where(keep, unsortable(keys), NEG_INF), axis=1,
            keepdims=True))

    m = lax.fori_loop(0, tiles, row_max, jnp.full((bq, 1), NEG_INF, _F32))

    def write(j, total):
        keep, keys = selected(j)
        mask_ref[0, j] = keep.astype(jnp.int32).astype(jnp.int8)
        counts_ref[0, j, 0, :] = jnp.sum(
            jnp.where(keep, 1.0, 0.0), axis=1, keepdims=True)[:, 0]
        return total + jnp.sum(
            jnp.where(keep, jnp.exp(unsortable(keys) - m), 0.0), axis=1,
            keepdims=True)

    total = lax.fori_loop(0, tiles, write, jnp.zeros((bq, 1), _F32))

    def blank(j, _):
        mask_ref[0, j] = jnp.zeros((bq, bk), jnp.int8)
        counts_ref[0, j, 0, :] = jnp.zeros((bq,), _F32)
        return 0

    lax.fori_loop(tiles, nk, blank, 0)
    lse_ref[0, 0, :] = (m + jnp.log(total))[:, 0]


def _select_topk_kernels(qi, ki, w, topk, block_q, block_k, interpret):
    batch, heads, seq, dim = qi.shape
    bq, bk = _blocks(seq, block_q, block_k, interpret)
    while 4 * bq * seq > _SELECT_ROW_BUDGET_BYTES and bq % 256 == 0:
        bq //= 2
    if 4 * bq * seq > _SELECT_ROW_BUDGET_BYTES:
        raise ValueError(
            f"a row of {seq} keys does not fit VMEM beside a query block "
            f"of {bq}: the selection holds a block's whole score row")
    static = (index_scale(heads, dim), topk, bq, bk, seq, interpret)

    def build():
        return pl.pallas_call(
            functools.partial(_select_kernel, scale=static[0], topk=topk,
                              bq=bq, bk=bk, seq=seq),
            grid=(batch, seq // bq),
            in_specs=[
                pl.BlockSpec((1, heads, bq, dim), lambda b, i: (b, 0, i, 0)),
                pl.BlockSpec((1, seq, dim), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, bq, heads), lambda b, i: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, seq // bk, bq, bk),
                             lambda b, i: (b, 0, i, 0)),
                pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
                pl.BlockSpec((1, seq // bk, 1, bq),
                             lambda b, i: (b, 0, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((batch, seq // bk, seq, bk), jnp.int8),
                jax.ShapeDtypeStruct((batch, 1, seq), _F32),
                jax.ShapeDtypeStruct((batch, seq // bk, 1, seq), _F32),
            ],
            scratch_shapes=[_vmem((seq // bk, bq, bk), jnp.int32)],
            compiler_params=_params("parallel", "arbitrary"),
            interpret=interpret, name="dsa_index_select")

    mask, lse, counts = shared_call("dsa_index_select", DeviceScope.DSA_INDEX,
                                static, (qi, ki, w), build)
    return Selection(mask, lse[:, 0], counts[:, :, 0])


def select_from_scores(scores, topk):
    """The ``Selection`` of dense scores [B, T, T] (XLA: a stable
    ``top_k`` a row, so ties go to the lower position)."""
    seq = scores.shape[-1]
    t = jnp.arange(seq)
    causal = t[None, :] <= t[:, None]
    scores = jnp.where(causal, scores + 0.0, -jnp.inf)
    if topk < seq:
        values, at = lax.top_k(scores, topk)
        thr, pos = values[..., -1:], at[..., -1:]
        keep = causal & ((scores > thr) | ((scores == thr)
                                           & (t[None, None, :] <= pos)))
    else:
        keep = jnp.broadcast_to(causal, scores.shape)
    lse = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return Selection(keep.astype(jnp.int8)[:, None], lse,
                     jnp.sum(keep, axis=-1, dtype=_F32)[:, None])


def dense_mask(mask):
    """[B, T, T] of the key-tile-major mask."""
    batch, nk, seq, bk = mask.shape
    return mask.transpose(0, 2, 1, 3).reshape(batch, seq, nk * bk)


def select_topk(qi, ki, w, topk: int, use_kernels: bool = True,
                block_q: int = 128, block_k: int = 512,
                interpret: Optional[bool] = None) -> Selection:
    """For each query the ``topk`` causal keys of largest index score
    (all of them where there are no more), ties to the lower position.
    No gradient: the set is data."""
    qi, ki, w = (lax.stop_gradient(a) for a in (qi, ki, w))
    if not use_kernels:
        return select_from_scores(index_scores(qi, ki, w), topk)
    _, interp = _resolve(None, 1, interpret)
    return _select_topk_kernels(qi, ki, w, topk, block_q, block_k, interp)


def tile_flags(counts, bq: int):
    """[B, T/bq, T/bk] int32 of a ``Selection``'s ``counts``: 1 where a
    pair of the tile is selected."""
    batch, nk, seq = counts.shape
    return (jnp.sum(counts.reshape(batch, nk, seq // bq, bq), axis=-1)
            > 0).astype(jnp.int32).transpose(0, 2, 1)


def selection_counters(selection: Selection, heads: int, bq: int,
                       kernels: bool = True):
    """What a layer's selection is and what its forward kernel walks,
    for the loss function's aux: pairs selected and causal pairs (a
    query's, not a head's), tiles visited and causal tiles skipped over
    batch and heads (XLA's dense form walks no tile)."""
    batch, nk, seq = selection.counts.shape
    bq, bk = _fit_block(bq, seq), seq // nk
    visited = jnp.sum(tile_flags(selection.counts, bq).astype(_F32)) * float(
        kernels)
    i = jnp.arange(seq // bq)[:, None] * bq
    j = jnp.arange(nk)[None, :] * bk
    causal_tiles = float(batch * kernels) * jnp.sum(
        (j <= i + bq - 1).astype(_F32))
    return {
        # a query's count is whole and at most T: exact in float32
        StepCounter.DSA_PAIRS_SELECTED: jnp.sum(
            jnp.sum(selection.counts, axis=1)),
        StepCounter.DSA_PAIRS_CAUSAL: jnp.float32(
            batch * seq * (seq + 1) // 2),
        StepCounter.DSA_TILES_VISITED: heads * visited,
        StepCounter.DSA_TILES_SKIPPED: heads * (causal_tiles - visited),
    }


# -- attention over the selected pairs ----------------------------------------


def _fetched_k(bq, bk):
    """The key tile a grid step (i, j) fetches: past the diagonal the
    diagonal's own, which is there already, so nothing is copied for a
    tile the kernels skip."""
    return lambda i, j: jnp.minimum(j, _band_last_k(i, bq, bk))


def _keep(mask_ref):
    return mask_ref[0, 0].astype(jnp.int32) != 0


def _attn_fwd_kernel(flags_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                     lse_ref, m_scr, l_scr, acc_scr, *, scale, nq, nk):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(flags_ref[(b * nq + i) * nk + j] > 0)
    def _compute():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=_F32) * scale
        s = jnp.where(_keep(mask_ref), s, NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row may have no selected key in a visited tile, and none
        # yet: exp(NEG_INF - NEG_INF) would be 1
        m_sub = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
        p = jnp.exp(s - m_sub)
        alpha = jnp.exp(m_prev - m_sub)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0, 0, :] = (m_scr[:, :1] + jnp.log(l_safe))[:, 0]


def _attention_forward(q, k, v, mask, counts, scale, block_q, interpret):
    batch, heads, seq, d = q.shape
    dv = v.shape[3]
    group = _group_size(q, k)
    bq, bk = _blocks(seq, block_q, mask.shape[-1], interpret)
    nq, nk = seq // bq, seq // bk
    flags = tile_flags(counts, bq).reshape(-1)
    static = (scale, bq, bk, interpret)
    kj = _fetched_k(bq, bk)

    def build():
        return pl.pallas_call(
            functools.partial(_attn_fwd_kernel, scale=scale, nq=nq, nk=nk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(batch, heads, nq, nk),
                in_specs=[
                    pl.BlockSpec((1, 1, bq, d),
                                 lambda b, h, i, j, f: (b, h, i, 0)),
                    pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j, f: (
                        b, h // group, kj(i, j), 0)),
                    pl.BlockSpec((1, 1, bk, dv), lambda b, h, i, j, f: (
                        b, h // group, kj(i, j), 0)),
                    pl.BlockSpec((1, 1, bq, bk), lambda b, h, i, j, f: (
                        b, kj(i, j), i, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, bq, dv),
                                 lambda b, h, i, j, f: (b, h, i, 0)),
                    pl.BlockSpec((1, 1, 1, bq),
                                 lambda b, h, i, j, f: (b, h, 0, i)),
                ],
                scratch_shapes=[_vmem((bq, LANES)), _vmem((bq, LANES)),
                                _vmem((bq, dv))]),
            out_shape=[
                jax.ShapeDtypeStruct((batch, heads, seq, dv), q.dtype),
                jax.ShapeDtypeStruct((batch, heads, 1, seq), _F32),
            ],
            compiler_params=_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
            interpret=interpret, name="dsa_attn_fwd")

    out, lse = shared_call("dsa_attn_fwd", DeviceScope.ATTN_SPARSE, static,
                       (flags, q, k, v, mask), build)
    return out, lse.reshape(batch, heads, seq)


def _probabilities(q, k, lse, keep, scale):
    """The tile's probabilities from the saved logsumexp, 0 off the
    selection."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=_F32) * scale
    return jnp.where(keep, jnp.exp(s - lse[:, None]), 0.0)


def _attn_dkv_kernel(flags_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, mask_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                     *, scale, nq, nk):
    # grid (batch, kv_head, j, g, i): the query heads of the KV head's
    # group and the query blocks are the two innermost, sequential
    b, j = pl.program_id(0), pl.program_id(2)
    g, i = pl.program_id(3), pl.program_id(4)

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(flags_ref[(b * nq + i) * nk + j] > 0)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        p = _probabilities(q, k, lse_ref[0, 0, 0, :], _keep(mask_ref),
                           scale)
        over_q = (((0,), (0,)), ((), ()))
        dv_scr[:] = dv_scr[:] + lax.dot_general(
            p.astype(do.dtype), do, over_q, preferred_element_type=_F32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=_F32)
        ds = (p * (dp - delta_ref[0, 0, 0, :][:, None]) * scale).astype(
            q.dtype)
        dk_scr[:] = dk_scr[:] + lax.dot_general(
            ds, q, over_q, preferred_element_type=_F32)

    @pl.when(jnp.logical_and(g == pl.num_programs(3) - 1, i == nq - 1))
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _attn_dq_kernel(flags_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, mask_ref, dq_ref, dq_scr, *, scale, nq, nk):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(flags_ref[(b * nq + i) * nk + j] > 0)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        p = _probabilities(q, k, lse_ref[0, 0, 0, :], _keep(mask_ref),
                           scale)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=_F32)
        ds = (p * (dp - delta_ref[0, 0, 0, :][:, None]) * scale).astype(
            q.dtype)
        dq_scr[:] = dq_scr[:] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=_F32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _attn_bwd_kernel(flags_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, mask_ref, dq_ref, dk_ref, dv_ref, dq_scr,
                     dk_scr, dv_scr, *, scale, bq, bk, nq, nk):
    # grid (batch, kv_head, g, j, i), all sequential: the dKV kernel's
    # walk with the group's query heads outside the key tiles, so that a
    # head's dQ row is whole before the next head's begins. One (p, ds)
    # a tile feeds all three gradients. They are whole rows in VMEM: the
    # query's carried over the key tiles of a head, the key's and the
    # value's over the query blocks and the heads of the group. The sums
    # run in the two kernels' order.
    b, g = pl.program_id(0), pl.program_id(2)
    j, i = pl.program_id(3), pl.program_id(4)
    q_rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
    k_rows = pl.ds(pl.multiple_of(j * bk, bk), bk)

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _init_kv():
        dk_scr[k_rows, :] = jnp.zeros((bk, dk_scr.shape[1]), _F32)
        dv_scr[k_rows, :] = jnp.zeros((bk, dv_scr.shape[1]), _F32)

    @pl.when(j == 0)
    def _init_q():
        dq_scr[q_rows, :] = jnp.zeros((bq, dq_scr.shape[1]), _F32)

    @pl.when(flags_ref[(b * nq + i) * nk + j] > 0)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        p = _probabilities(q, k, lse_ref[0, 0, 0, :], _keep(mask_ref),
                           scale)
        over_q = (((0,), (0,)), ((), ()))
        dv_scr[k_rows, :] = dv_scr[k_rows, :] + lax.dot_general(
            p.astype(do.dtype), do, over_q, preferred_element_type=_F32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=_F32)
        ds = (p * (dp - delta_ref[0, 0, 0, :][:, None]) * scale).astype(
            q.dtype)
        dk_scr[k_rows, :] = dk_scr[k_rows, :] + lax.dot_general(
            ds, q, over_q, preferred_element_type=_F32)
        dq_scr[q_rows, :] = dq_scr[q_rows, :] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=_F32)

    @pl.when(j == _band_last_k(i, bq, bk))
    def _finalize_q():
        dq_ref[0, 0, q_rows, :] = dq_scr[q_rows, :].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(g == pl.num_programs(2) - 1, i == nq - 1))
    def _finalize_kv():
        dk_ref[0, 0, k_rows, :] = dk_scr[k_rows, :].astype(dk_ref.dtype)
        dv_ref[0, 0, k_rows, :] = dv_scr[k_rows, :].astype(dv_ref.dtype)


def _attention_backward(q, k, v, mask, counts, out, lse, do, dlse, scale,
                        block_q, interpret):
    batch, heads, seq, d = q.shape
    kv_heads, dv_dim = k.shape[1], v.shape[3]
    group = _group_size(q, k)
    bq, bk = _blocks(seq, block_q, mask.shape[-1], interpret)
    nq, nk = seq // bq, seq // bk
    flags = tile_flags(counts, bq).reshape(-1)
    delta4 = (jnp.sum(do.astype(_F32) * out.astype(_F32), axis=-1)
              - dlse.astype(_F32)).reshape(batch, heads, 1, seq)
    lse4 = lse.reshape(batch, heads, 1, seq)
    static = (scale, bq, bk, interpret)
    operands = (flags, q, k, v, do, lse4, delta4, mask)

    # nor is a query block before the key tile's first causal one
    qi = lambda j, i: jnp.maximum(  # noqa: E731
        i, _band_first_q(j, bq, bk))

    def build_bwd():
        # the gradients' blocks are whole rows, resident for a query
        # head (dQ) and for a KV head (dK, dV) and written back when
        # that index moves on
        qh = lambda b, hk, g, j, i, f: (  # noqa: E731
            b, hk * group + g, qi(j, i), 0)
        kvh = lambda b, hk, g, j, i, f: (b, hk, j, 0)  # noqa: E731
        row = lambda b, hk, g, j, i, f: (  # noqa: E731
            b, hk * group + g, 0, qi(j, i))
        kv_row = lambda b, hk, g, j, i, f: (b, hk, 0, 0)  # noqa: E731
        return pl.pallas_call(
            functools.partial(_attn_bwd_kernel, scale=scale, bq=bq, bk=bk,
                              nq=nq, nk=nk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(batch, kv_heads, group, nk, nq),
                in_specs=[
                    pl.BlockSpec((1, 1, bq, d), qh),
                    pl.BlockSpec((1, 1, bk, d), kvh),
                    pl.BlockSpec((1, 1, bk, dv_dim), kvh),
                    pl.BlockSpec((1, 1, bq, dv_dim), qh),
                    pl.BlockSpec((1, 1, 1, bq), row),
                    pl.BlockSpec((1, 1, 1, bq), row),
                    pl.BlockSpec((1, 1, bq, bk),
                                 lambda b, hk, g, j, i, f: (
                                     b, j, qi(j, i), 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, seq, d),
                                 lambda b, hk, g, j, i, f: (
                                     b, hk * group + g, 0, 0)),
                    pl.BlockSpec((1, 1, seq, d), kv_row),
                    pl.BlockSpec((1, 1, seq, dv_dim), kv_row),
                ],
                scratch_shapes=[_vmem((seq, d)), _vmem((seq, d)),
                                _vmem((seq, dv_dim))]),
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            compiler_params=_params(*5 * ("arbitrary",)),
            interpret=interpret, name="dsa_attn_bwd")

    if _win_row_state_bytes(seq, d, dv_dim, q.dtype.itemsize) <= (
            _ATTN_ROW_STATE_BUDGET_BYTES):
        return tuple(shared_call("dsa_attn_bwd", DeviceScope.ATTN_SPARSE,
                             static, operands, build_bwd))

    # longer rows: two kernels that hold a block of state each, and
    # each compute the tile
    kj = _fetched_k(bq, bk)

    def build_dkv():
        qh = lambda b, hk, j, g, i, f: (  # noqa: E731
            b, hk * group + g, qi(j, i), 0)
        kvh = lambda b, hk, j, g, i, f: (b, hk, j, 0)  # noqa: E731
        row = lambda b, hk, j, g, i, f: (  # noqa: E731
            b, hk * group + g, 0, qi(j, i))
        return pl.pallas_call(
            functools.partial(_attn_dkv_kernel, scale=scale, nq=nq, nk=nk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(batch, kv_heads, nk, group, nq),
                in_specs=[
                    pl.BlockSpec((1, 1, bq, d), qh),
                    pl.BlockSpec((1, 1, bk, d), kvh),
                    pl.BlockSpec((1, 1, bk, dv_dim), kvh),
                    pl.BlockSpec((1, 1, bq, dv_dim), qh),
                    pl.BlockSpec((1, 1, 1, bq), row),
                    pl.BlockSpec((1, 1, 1, bq), row),
                    pl.BlockSpec((1, 1, bq, bk),
                                 lambda b, hk, j, g, i, f: (
                                     b, j, qi(j, i), 0)),
                ],
                out_specs=[pl.BlockSpec((1, 1, bk, d), kvh),
                           pl.BlockSpec((1, 1, bk, dv_dim), kvh)],
                scratch_shapes=[_vmem((bk, d)), _vmem((bk, dv_dim))]),
            out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            compiler_params=_params("parallel", "parallel", "parallel",
                                    "arbitrary", "arbitrary"),
            interpret=interpret, name="dsa_attn_dkv")

    def build_dq():
        qb = lambda b, h, i, j, f: (b, h, i, 0)  # noqa: E731
        kb = lambda b, h, i, j, f: (  # noqa: E731
            b, h // group, kj(i, j), 0)
        row = lambda b, h, i, j, f: (b, h, 0, i)  # noqa: E731
        return pl.pallas_call(
            functools.partial(_attn_dq_kernel, scale=scale, nq=nq, nk=nk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(batch, heads, nq, nk),
                in_specs=[
                    pl.BlockSpec((1, 1, bq, d), qb),
                    pl.BlockSpec((1, 1, bk, d), kb),
                    pl.BlockSpec((1, 1, bk, dv_dim), kb),
                    pl.BlockSpec((1, 1, bq, dv_dim), qb),
                    pl.BlockSpec((1, 1, 1, bq), row),
                    pl.BlockSpec((1, 1, 1, bq), row),
                    pl.BlockSpec((1, 1, bq, bk), lambda b, h, i, j, f: (
                        b, kj(i, j), i, 0)),
                ],
                out_specs=[pl.BlockSpec((1, 1, bq, d), qb)],
                scratch_shapes=[_vmem((bq, d))]),
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
            compiler_params=_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
            interpret=interpret, name="dsa_attn_dq")

    dk, dv = shared_call("dsa_attn_dkv", DeviceScope.ATTN_SPARSE, static,
                     operands, build_dkv)
    (dq,) = shared_call("dsa_attn_dq", DeviceScope.ATTN_SPARSE, static,
                    operands, build_dq)
    return dq, dk, dv


def _dense_probabilities(q, k, mask, scale):
    """(probabilities [B, H, T, T] float32 over the selected keys,
    their logsumexp)."""
    group = _group_size(q, k)
    s = jnp.einsum("bhtd,bhsd->bhts", q, jnp.repeat(k, group, axis=1),
                   preferred_element_type=_F32) * scale
    s = jnp.where(dense_mask(mask)[:, None] != 0, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.exp(s - lse[..., None]), lse


def selected_attention_reference(q, k, v, mask, scale=None):
    """The XLA form: dense scores, float32 softmax over the selected
    keys. ``(out, lse)``."""
    scale, _ = _resolve(scale, q.shape[-1], True)
    p, lse = _dense_probabilities(q, k, mask, scale)
    out = jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype),
                     jnp.repeat(v, _group_size(q, k), axis=1))
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _selected_attention(q, k, v, mask, counts, scale, block_q, interpret):
    return _attention_forward(q, k, v, mask, counts, scale, block_q,
                              interpret)


def _selected_attention_fwd(q, k, v, mask, counts, scale, block_q,
                            interpret):
    out, lse = _attention_forward(q, k, v, mask, counts, scale, block_q,
                                  interpret)
    # named INSIDE the rule: the results and the residuals are then the
    # same named values, and a checkpoint that saves the names has
    # nothing of the kernel left to replay. Outside a checkpoint a name
    # is the identity.
    out, lse = (checkpoint_name(a, name)
                for a, name in zip((out, lse), KEPT_NAMES))
    return (out, lse), (q, k, v, mask, counts, out, lse)


def _selected_attention_bwd(scale, block_q, interpret, residuals,
                            cotangents):
    q, k, v, mask, counts, out, lse = residuals
    do, dlse = cotangents
    dq, dk, dv = _attention_backward(q, k, v, mask, counts, out, lse, do,
                                     dlse, scale, block_q, interpret)
    return dq, dk, dv, None, None


_selected_attention.defvjp(_selected_attention_fwd, _selected_attention_bwd)


def selected_attention(q, k, v, selection: Selection,
                       scale: Optional[float] = None,
                       use_kernels: bool = True, block_q: int = 512,
                       interpret: Optional[bool] = None):
    """``(out [B, H, T, Dv], lse [B, H, T])``: each query head's softmax
    attention over the keys ``selection`` holds for its query (the same
    keys for every head), differentiable in ``q``, ``k``, ``v`` through
    both outputs. The key tile is the selection's."""
    if not use_kernels:
        return selected_attention_reference(q, k, v, selection.mask, scale)
    scale, interp = _resolve(scale, q.shape[-1], interpret)
    return _selected_attention(
        q, k, v, selection.mask, lax.stop_gradient(selection.counts), scale,
        block_q, interp)


def kept_bytes(batch: int, heads: int, seq: int, dv: int, dtype) -> int:
    """The bytes of ``KEPT_NAMES`` of one call: ``out`` [B, H, T, Dv] in
    ``dtype`` and ``lse`` [B, H, T] in float32."""
    return batch * heads * seq * (dv * jnp.dtype(dtype).itemsize + 4)


# -- the indexer's loss -------------------------------------------------------
#
# A row's ``KL(pbar || softmax_S(I)) = sum_s pbar log pbar - sum_s pbar
# I + lse_I`` over its selected keys, ``pbar`` the mean over the query
# heads of the main attention's probabilities (they sum to one over the
# set). Its gradient to a selected score is ``softmax_S(I) - pbar``, to
# everything else nothing: ``pbar`` is data. The loss is the rows'
# weighted sum and the weights are data, so a tile's ``pbar`` and scores
# give the value and the gradient in ONE visit (``_kl_kernel``): the
# forward rule runs it, the backward rule multiplies what it kept.


def _head_mean_probabilities(q_ref, k_ref, lse_ref, keep, scale, group):
    """``pbar`` of a tile: q block [1, H, bq, D], k block [1, H_kv, bk,
    D], lse block [1, H, 1, bq]."""
    heads = q_ref.shape[1]
    total = None
    for h in range(heads):
        s = lax.dot_general(q_ref[0, h], k_ref[0, h // group],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=_F32) * scale
        p = jnp.exp(s - lse_ref[0, h, 0, :][:, None])
        total = p if total is None else total + p
    return jnp.where(keep, total * (1.0 / heads), 0.0)


def _kl_kernel(flags_ref, qi_ref, ki_ref, w_ref, q_ref, k_ref, lse_ref,
               lsei_ref, weight_ref, mask_ref, kl_ref, *rest, scale,
               index_scale, group, nq, nk, bk, gradients):
    """The rows' weighted KL and, with ``gradients``, the weighted sum's
    gradient to ``qi``, ``ki`` and ``w`` out of the same ``pbar`` and
    scores of each visited tile."""
    # grid (batch, i, j); with the gradients all sequential: the key
    # head's is a whole row resident in VMEM, summed over the query
    # blocks
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    heads = qi_ref.shape[1]
    if gradients:
        dqi_ref, dki_ref, dw_ref, acc_scr, dqi_scr, dw_scr = rest

        @pl.when(jnp.logical_and(i == 0, j == 0))
        def _init_keys():
            dki_ref[...] = jnp.zeros_like(dki_ref)
    else:
        (acc_scr,) = rest

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        if gradients:
            dqi_scr[:] = jnp.zeros_like(dqi_scr)
            dw_scr[:] = jnp.zeros_like(dw_scr)

    @pl.when(flags_ref[(b * nq + i) * nk + j] > 0)
    def _compute():
        keep = _keep(mask_ref)
        pbar = _head_mean_probabilities(q_ref, k_ref, lse_ref, keep, scale,
                                        group)
        ki, w = ki_ref[0], w_ref[0].astype(_F32)
        scores = _score_tile(qi_ref, ki, w, index_scale)
        log_pbar = jnp.log(jnp.where(pbar > 0.0, pbar, 1.0))
        acc_scr[:] = acc_scr[:] + jnp.sum(
            pbar * (log_pbar - scores), axis=1, keepdims=True)
        if not gradients:
            return
        soft = jnp.where(
            keep, jnp.exp(scores - lsei_ref[0, 0, :][:, None]), 0.0)
        # d loss / d score, the row's weight and the scale folded in
        ds = (soft - pbar) * (weight_ref[0, 0, :][:, None] * index_scale)
        lane = lax.broadcasted_iota(jnp.int32, dw_scr.shape, 1)
        k_rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        dki = jnp.zeros((bk, ki.shape[1]), _F32)
        for h in range(heads):
            qh = qi_ref[0, h]
            r = lax.dot_general(qh, ki, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32)
            dw_scr[:] = dw_scr[:] + jnp.where(
                lane == h,
                jnp.sum(ds * jnp.maximum(r, 0.0), axis=1, keepdims=True),
                0.0)
            dr = jnp.where(r > 0.0, ds * w[:, h:h + 1], 0.0).astype(
                ki.dtype)
            dqi_scr[h] = dqi_scr[h] + lax.dot_general(
                dr, ki, (((1,), (0,)), ((), ())),
                preferred_element_type=_F32)
            dki = dki + lax.dot_general(
                dr, qh, (((0,), (0,)), ((), ())),
                preferred_element_type=_F32)
        dki_ref[0, k_rows, :] = dki_ref[0, k_rows, :] + dki

    @pl.when(j == nk - 1)
    def _finalize():
        kl_ref[0, 0, :] = (acc_scr[:, 0] + lsei_ref[0, 0, :]) * (
            weight_ref[0, 0, :])
        if gradients:
            dqi_ref[0] = dqi_scr[:].astype(dqi_ref.dtype)
            dw_ref[0] = dw_scr[:, :heads].astype(dw_ref.dtype)


def _index_kl_call(qi, ki, w, q, k, lse, lse_index, weight, mask, counts,
                   scale, block_q, interpret, gradients):
    """``(loss,)`` or ``(loss, dqi, dki, dw)``: the one kernel's call,
    the gradient half statically on or off."""
    batch, heads_i, seq, dim_i = qi.shape
    heads, d = q.shape[1], q.shape[3]
    bq, bk = _blocks(seq, block_q, mask.shape[-1], interpret)
    nq, nk = seq // bq, seq // bk
    flags = tile_flags(counts, bq).reshape(-1)
    static = (scale, bq, bk, interpret, gradients)
    kj = _fetched_k(bq, bk)
    row = pl.BlockSpec((1, 1, bq), lambda b, i, j, f: (b, 0, i))
    qi_block = pl.BlockSpec((1, heads_i, bq, dim_i),
                            lambda b, i, j, f: (b, 0, i, 0))
    w_block = pl.BlockSpec((1, bq, heads_i), lambda b, i, j, f: (b, i, 0))
    out_specs, out_shape = [row], [
        jax.ShapeDtypeStruct((batch, 1, seq), _F32)]
    scratch = [_vmem((bq, 1))]
    if gradients:
        out_specs += [qi_block,
                      pl.BlockSpec((1, seq, dim_i),
                                   lambda b, i, j, f: (b, 0, 0)),
                      w_block]
        out_shape += [jax.ShapeDtypeStruct(qi.shape, qi.dtype),
                      jax.ShapeDtypeStruct(ki.shape, _F32),
                      jax.ShapeDtypeStruct(w.shape, w.dtype)]
        scratch += [_vmem((heads_i, bq, dim_i)), _vmem((bq, LANES))]

    def build():
        return pl.pallas_call(
            functools.partial(
                _kl_kernel, scale=scale,
                index_scale=index_scale(heads_i, dim_i),
                group=_group_size(q, k), nq=nq, nk=nk, bk=bk,
                gradients=gradients),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(batch, nq, nk),
                in_specs=[
                    qi_block,
                    pl.BlockSpec((1, bk, dim_i),
                                 lambda b, i, j, f: (b, kj(i, j), 0)),
                    w_block,
                    pl.BlockSpec((1, heads, bq, d),
                                 lambda b, i, j, f: (b, 0, i, 0)),
                    pl.BlockSpec((1, k.shape[1], bk, d),
                                 lambda b, i, j, f: (b, 0, kj(i, j), 0)),
                    pl.BlockSpec((1, heads, 1, bq),
                                 lambda b, i, j, f: (b, 0, 0, i)),
                    row, row,
                    pl.BlockSpec((1, 1, bq, bk),
                                 lambda b, i, j, f: (b, kj(i, j), i, 0)),
                ],
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=_params(*(
                3 * ("arbitrary",) if gradients
                else ("parallel", "parallel", "arbitrary"))),
            interpret=interpret, name="dsa_index_kl")

    rows, *grads = shared_call(
        "dsa_index_kl", DeviceScope.DSA_INDEX, static,
        (flags, qi, ki, w, q, k, lse.reshape(batch, -1, 1, seq),
         lse_index.reshape(batch, 1, seq), weight.reshape(batch, 1, seq),
         mask), build)
    return (jnp.sum(rows), *grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12))
def _index_kl(qi, ki, w, q, k, lse, lse_index, weight, mask, counts, scale,
              block_q, interpret):
    (loss,) = _index_kl_call(qi, ki, w, q, k, lse, lse_index, weight, mask,
                             counts, scale, block_q, interpret, False)
    return loss


def _index_kl_fwd(qi, ki, w, q, k, lse, lse_index, weight, mask, counts,
                  scale, block_q, interpret):
    loss, dqi, dki, dw = _index_kl_call(
        qi, ki, w, q, k, lse, lse_index, weight, mask, counts, scale,
        block_q, interpret, True)
    # a layer scan stacks what its checkpoint keeps, and the v5e's
    # compiler fuses that write into the call that makes a kept value:
    # the fusion has the default 16 MiB of scoped VMEM, not the kernel's
    # own limit, and this kernel's blocks are 65 MB at 64 index heads of
    # 128 (ISSUE 52's deviceless compile of axk2). The barrier keeps the
    # call whole; the stack's write is a copy after it.
    dqi, dki, dw = lax.optimization_barrier((dqi, dki, dw))
    # named INSIDE the rule, as the selected attention's: the residuals
    # are the gradients themselves, and a checkpoint that saves the
    # names has nothing of the kernel left to replay
    kept = tuple(checkpoint_name(a, name) for a, name in zip(
        (dqi, dki.astype(ki.dtype), dw), INDEX_KEPT_NAMES))
    return loss, kept


def _index_kl_bwd(scale, block_q, interpret, kept, g):
    return tuple((g * a).astype(a.dtype) for a in kept) + 7 * (None,)


_index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


def index_kept_bytes(batch: int, heads: int, seq: int, dim: int,
                     dtype) -> int:
    """The bytes of ``INDEX_KEPT_NAMES`` of one call: the gradients to
    ``qi`` [B, J, T, E], ``ki`` [B, T, E] and ``w`` [B, T, J] in
    ``dtype``."""
    return batch * seq * (heads * dim + dim + heads) * jnp.dtype(
        dtype).itemsize


def index_kl_reference(qi, ki, w, q, k, selection: Selection, scale=None):
    """The XLA form, differentiated by JAX, a row at a time [B, T]:
    dense ``pbar`` under ``stop_gradient``, the scores' log-softmax over
    the selected set."""
    scale, _ = _resolve(scale, q.shape[-1], True)
    keep = dense_mask(selection.mask) != 0
    p, _ = _dense_probabilities(lax.stop_gradient(q), lax.stop_gradient(k),
                                selection.mask, scale)
    pbar = jnp.mean(p, axis=1)
    scores = jnp.where(keep, index_scores(qi, ki, w), -jnp.inf)
    log_soft = scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True)
    log_pbar = jnp.log(jnp.where(pbar > 0.0, pbar, 1.0))
    return jnp.sum(jnp.where(keep, pbar * (log_pbar - log_soft), 0.0),
                   axis=-1)


def index_kl(qi, ki, w, q, k, lse, selection: Selection,
             scale: Optional[float] = None, use_kernels: bool = True,
             block_q: int = 256, interpret: Optional[bool] = None,
             weight=None):
    """A float32 scalar: the sum over the queries, each at its
    ``weight`` [B, T] (data; the mean, ``1 / (B T)``, where None), of
    ``KL(pbar || softmax_S(I))`` over the query's selected keys,
    ``pbar`` the mean over the query heads of the main attention's
    probabilities (from ``q``, ``k`` and its ``lse``, all data here).
    The gradient goes to ``qi``, ``ki``, ``w`` alone, and is made in the
    forward pass beside the value (kernel ``dsa_index_kl``): the forward
    rule names the three ``INDEX_KEPT_NAMES``, which a layer's
    checkpoint keeps (``index_kept_bytes``)."""
    batch, _, seq, _ = qi.shape
    if weight is None:
        weight = jnp.full((batch, seq), 1.0 / (batch * seq), _F32)
    weight = lax.stop_gradient(weight.astype(_F32))
    if not use_kernels:
        return jnp.sum(weight * index_kl_reference(
            qi, ki, w, q, k, selection, scale))
    scale, interp = _resolve(scale, q.shape[-1], interpret)
    q, k, lse = (lax.stop_gradient(a) for a in (q, k, lse))
    lse_index, counts = (lax.stop_gradient(a) for a in (
        selection.lse, selection.counts))
    return _index_kl(qi, ki, w, q, k, lse, lse_index, weight,
                     selection.mask, counts, scale, block_q, interp)


# -- the latent layout --------------------------------------------------------
#
# Latent attention scores a pair in two parts, ``q_nope_h . k_nope_h +
# q_rope_h . k_rope``, the rotary key ONE head for all, at one KV head a
# query head and values narrower than the scores. That sum is the one
# product of the parts side by side, so the kernels above serve it at
# ``H_kv = H`` and a contraction of ``nope + rope`` (192 for 128 + 64:
# the v5e's compiler takes it, ``tests/test_tpu_compile_axk2.py``); the
# shared key is written once a head on the way in, and its gradient is
# the heads' sum on the way out, both XLA's.


def latent_operands(q_nope, q_rope, k_nope, k_rope):
    """``(q, k)`` [B, H, T, nope + rope] of the two-part operands:
    ``q_nope``, ``k_nope`` [B, H, T, nope], ``q_rope`` [B, H, T, rope]
    and the shared ``k_rope`` [B, 1, T, rope]."""
    k_rope = jnp.broadcast_to(k_rope, q_rope.shape[:3] + k_rope.shape[3:])
    return (jnp.concatenate([q_nope, q_rope], axis=-1),
            jnp.concatenate([k_nope, k_rope], axis=-1))


def selected_attention_latent(q_nope, q_rope, k_nope, k_rope, v,
                              selection: Selection, scale: float, **how):
    """``selected_attention`` of latent heads: ``(out [B, H, T, Dv], lse
    [B, H, T])`` of the softmax over each query's selected keys of
    ``(q_nope . k_nope + q_rope . k_rope) * scale``, differentiable in
    all five; ``how`` is that function's (kernels, block, interpret)."""
    q, k = latent_operands(q_nope, q_rope, k_nope, k_rope)
    return selected_attention(q, k, v, selection, scale, **how)


def index_kl_latent(qi, ki, w, q_nope, q_rope, k_nope, k_rope, lse,
                    selection: Selection, scale: float, **how):
    """``index_kl`` against latent heads' probabilities (data here: the
    gradient goes to ``qi``, ``ki``, ``w`` alone); ``how`` is that
    function's (kernels, block, interpret, the rows' ``weight``)."""
    q, k = latent_operands(q_nope, q_rope, k_nope, k_rope)
    return index_kl(qi, ki, w, q, k, lse, selection, scale, **how)
