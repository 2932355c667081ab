"""The state-space dual of a Mamba-2 layer (arXiv:2405.21060) as one
differentiable op.

Per batch row and head (``H`` heads of ``P`` columns; a state of ``[P,
N]`` a head; ``B_t`` and ``C_t`` of ``[N]`` shared by the ``H / G``
heads of a group; one scalar decay rate ``A < 0`` and one skip ``D`` a
head)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T         S_0 = 0
    y_t = S_t C_t + D x_t

Token by token that is ``S`` dependent steps a row
(``ssd_reference``). The program computes the chunked form: over a
chunk of ``Q`` tokens, with ``a_i = dt_i A``, ``cum_i`` the sum of
``a`` up to token ``i`` of the chunk and ``H_c`` the state the chunk
starts from::

    L_ij   = exp(cum_i - cum_j)  for j <= i, else 0
    y_i    = sum_{j<=i} (C_i . B_j) L_ij dt_j x_j
             + exp(cum_i) H_c C_i + D x_i
    H_c+1  = exp(cum_Q) H_c + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T

Every ratio is ``exp`` of a difference that is at most 0: the
difference first, then the exponential, never ``exp(cum_i) *
exp(-cum_j)``, whose factors overflow where a head's decay underflows
inside a chunk. Every product of a chunk is a matrix product: ``C B^T``
(once a chunk and group), ``(L * C B^T * dt) x`` a head, the state's
read ``C H_c^T`` and its update ``(w x)^T B`` for all the heads of a
lane tile at once.

On a TPU the op is a pair of Pallas kernels whose instructions are
named ``ssd_fwd`` and ``ssd_bwd``, under one ``jax.custom_vjp``:

* ``x``, ``y`` and their gradients stay in the layer's own layout
  ``[B, S, H * P]``; a lane tile of 128 holds ``128 / P`` heads side by
  side (two of 64), a head is picked out of it by a mask on the lanes,
  and what the heads of a tile share (``C H^T``, ``B dH^T``, the
  state's update) is one product a tile;
* grid ``(batch, head blocks, chunks)``, the chunks innermost and
  sequential; a head block's states (``[heads * P, N]`` float32: 32 KB
  a head of 64 x 128) live in VMEM scratch and are carried from chunk
  to chunk;
* the forward also writes the state each chunk starts from
  (``[chunks, H * P, N]`` float32) as the backward's residual;
* the backward walks the chunks last to first with the adjoint state in
  VMEM, recomputes ``L`` and ``C B^T`` a chunk, and gives ``dx``, the
  gradients to ``dt`` and to ``cum`` (a row and a column form each: a
  sum over a ``[Q, Q]`` tile's rows lies along the lanes, one over its
  columns along the sublanes, and nothing in the kernel turns the one
  into the other; they are added outside), the head blocks' partial
  sums of ``dB`` and ``dC`` (added outside, as ``ssm_scan_bwd``'s
  channel blocks are) and ``dD`` a column. ``L`` never reaches HBM.

What XLA does around the kernels is under the scope ``ssd_chunk``:
``a = dt A``, its cumulative sum inside each chunk, the transposes of
``dt`` and ``cum`` to the row form, and the additions above; ``dA`` and
the whole ``ddt`` follow from those by autodiff.

float32: ``dt``, ``A``, ``cum``, ``L``, the carried state and its
adjoint, every accumulation. Operands enter the MXU in ``x``'s dtype
(``L * C B^T * dt`` and the state are rounded to it where a product
reads them, the carried copy is not).

The kernels take one group (``G = 1``, as Granite-4.0-H publishes) and
refuse more; ``ssd_chunked``, the same chunked form in ``jax.numpy``
(a ``lax.scan`` over chunks that autodiff differentiates: the CPU
rehearsal, ``use_kernels=False``), and ``ssd_reference`` take any ``G``
that divides ``H``. A row is a whole number of chunks, or the op
refuses it. A ``pallas_call`` is traced once a process and lowered
once a program (``ops.trace_once.shared_call``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from dlrover_tpu.ops.flash_attention import LANES, _vmem, ambient_shard_mesh
# a float32-accumulating product and its three contractions, as that
# module has them
from dlrover_tpu.ops.gated_delta import _NN, _NT, _TN, _dot
from dlrover_tpu.ops.selective_scan import _params, _resolve_interpret
from dlrover_tpu.ops.trace_once import shared_call
from dlrover_tpu.telemetry.names import DeviceScope

F32 = jnp.float32
CHUNK = 256  # Mamba-2's, and Granite-4.0-H's ``mamba_chunk_size``
# the most heads a grid step carries (my chip sweep, PR 57,
# ``benchmarks/ssd_bench.py``: 8 is slower, 32 is refused for VMEM)
HEADS_PER_PROGRAM = 16


def _check(x, dt, a, b, c, d, chunk):
    batch, s, h, _ = x.shape
    g = b.shape[2]
    if (dt.shape != (batch, s, h) or a.shape != (h,) or d.shape != (h,)
            or b.shape != c.shape or b.shape[:2] != (batch, s)):
        raise ValueError(
            f"x {x.shape} [B, S, H, P] goes with dt [B, S, H], A and D "
            f"[H], B and C [B, S, G, N]: dt {dt.shape}, A {a.shape}, "
            f"D {d.shape}, B {b.shape}, C {c.shape}")
    if h % g:
        raise ValueError(f"{g} groups do not divide {h} heads")
    if chunk is not None and s % chunk:
        raise ValueError(
            f"a row of {s} tokens is no whole number of chunks of {chunk}")


def ssd_reference(x, dt, a, b, c, d):
    """The recurrence token by token (``lax.scan`` over the row) in
    float32: ``x`` [B, S, H, P]; ``dt`` [B, S, H], after the softplus;
    ``a`` [H], negative; ``b``, ``c`` [B, S, G, N]; ``d`` [H]. Returns
    ``y`` [B, S, H, P] float32. The oracle of the tests."""
    _check(x, dt, a, b, c, d, None)
    x, dt, a, b, c, d = (t.astype(F32) for t in (x, dt, a, b, c, d))
    batch, _, h, p = x.shape
    g, n = b.shape[2:]
    hp = lax.Precision.HIGHEST

    def heads(t):  # [B, G, N] -> [B, H, N]
        return jnp.repeat(t, h // g, axis=1)

    def step(state, xs):  # state [B, H, P, N]
        x_t, dt_t, b_t, c_t = xs
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + jnp.einsum("bhp,bhn->bhpn", dt_t[..., None] * x_t,
                              heads(b_t), precision=hp))
        return state, jnp.einsum("bhpn,bhn->bhp", state, heads(c_t),
                                 precision=hp)

    _, y = lax.scan(step, jnp.zeros((batch, h, p, n), F32), tuple(
        t.swapaxes(0, 1) for t in (x, dt, b, c)))
    return y.swapaxes(0, 1) + d[:, None] * x


def _chunk_cumsum(a, chunk):
    """The running sum of ``a`` [B, S, H] inside each chunk."""
    batch, s, h = a.shape
    return jnp.cumsum(a.reshape(batch, s // chunk, chunk, h),
                      axis=2).reshape(batch, s, h)


def ssd_chunked(x, dt, a, b, c, d, chunk: int = CHUNK):
    """The chunked form of the module docstring in ``jax.numpy``: a
    ``lax.scan`` over the chunks with the float32 state as its carry,
    ``L`` a dense ``[B, H, Q, Q]`` array a chunk. ``y`` [B, S, H, P]
    in ``x``'s dtype; autodiff differentiates it."""
    _check(x, dt, a, b, c, d, chunk)
    batch, s, h, p = x.shape
    g, n = b.shape[2:]
    r, nc, cd = h // g, s // chunk, x.dtype
    dt, a = dt.astype(F32), a.astype(F32)
    cum = _chunk_cumsum(dt * a, chunk)

    def chunks(t, *tail):  # [B, S, ...] -> [chunks, B, Q, ...]
        return t.reshape((batch, nc, chunk) + tail).swapaxes(0, 1)

    tri = (lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))

    def step(state, xs):  # state [B, G, R, P, N] float32
        x_c, dt_c, cum_c, b_c, c_c = xs
        cb = jnp.einsum("bign,bjgn->bgij", c_c, b_c,
                        preferred_element_type=F32)
        diff = cum_c[:, :, None] - cum_c[:, None, :]  # [B, i, j, G, R]
        ell = jnp.exp(jnp.where(tri[None, :, :, None, None], diff,
                                -jnp.inf))
        m = (cb.transpose(0, 2, 3, 1)[..., None] * ell
             * dt_c[:, None]).astype(cd)
        y = jnp.einsum("bijgr,bjgrp->bigrp", m, x_c,
                       preferred_element_type=F32)
        y = y + jnp.exp(cum_c)[..., None] * jnp.einsum(
            "bgrpn,bign->bigrp", state.astype(cd), c_c,
            preferred_element_type=F32)
        last = cum_c[:, -1]  # [B, G, R]
        w = jnp.exp(last[:, None] - cum_c) * dt_c
        state = jnp.exp(last)[..., None, None] * state + jnp.einsum(
            "bjgrp,bjgn->bgrpn", (x_c * w[..., None]).astype(cd), b_c,
            preferred_element_type=F32)
        return state, y

    _, y = lax.scan(step, jnp.zeros((batch, g, r, p, n), F32), (
        chunks(x, g, r, p), chunks(dt, g, r), chunks(cum, g, r),
        chunks(b.astype(cd), g, n), chunks(c.astype(cd), g, n)))
    y = y.swapaxes(0, 1).reshape(batch, s, h, p)
    return (y + d.astype(F32)[:, None] * x).astype(cd)


# -- the kernels --------------------------------------------------------------


def _tile_heads(p: int, heads: int) -> int:
    """How many heads lie side by side in one lane tile: as many of
    ``p`` columns as 128 lanes hold, where that divides the block's
    heads (two of 64; at a toy size whatever fits)."""
    side = max(1, LANES // p)
    while heads % side:
        side -= 1
    return side


def _column(tile, lane, index):
    """Column ``index`` (a traced scalar) of ``tile`` [Q, H] as [Q, 1]:
    a mask on the lanes and a lane reduction, as
    ``selective_scan._column``."""
    return jnp.sum(jnp.where(lane == index, tile, 0.0), axis=1,
                   keepdims=True)


def _segment(value, which, k):
    """The sum over head ``k``'s lanes of ``value`` [Q, tile]: [Q, 1]."""
    return jnp.sum(jnp.where(which == k, value, 0.0), axis=1, keepdims=True)


def _triangle(q):
    return (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _head(natural, dtr_ref, cumr_ref, j, tri):
    """What both kernels read of head ``j`` of the block in a chunk:
    ``dt`` as a column [Q, 1] and as a row [1, Q], the decay matrix
    ``L`` [Q, Q], the decay ``rest`` [Q, 1] from a token to the chunk's
    end, and ``cum``'s last entry [1, 1] and column [Q, 1].
    ``natural`` is ``dt`` and ``cum`` of all the heads, [Q, H] each."""
    dt_nat, cum_nat = natural
    lane = lax.broadcasted_iota(jnp.int32, dt_nat.shape, 1)
    index = pl.program_id(1) * dtr_ref.shape[1] + j  # among all heads
    cum_col = _column(cum_nat, lane, index)
    q = cum_col.shape[0]
    last = cum_col[q - 1:q, :]
    ell = jnp.exp(jnp.where(tri, cum_col - cumr_ref[0, pl.ds(j, 1), :],
                            -jnp.inf))
    return (_column(dt_nat, lane, index), dtr_ref[0, pl.ds(j, 1), :], ell,
            jnp.exp(last - cum_col), last, cum_col)


def _ssd_fwd_kernel(x_ref, dtn_ref, cumn_ref, dtr_ref, cumr_ref, b_ref,
                    c_ref, d_ref,  # inputs
                    y_ref, start_ref,  # outputs
                    h_scratch, *, heads: int, p: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scratch[:] = jnp.zeros_like(h_scratch)

    start_ref[0, 0] = h_scratch[:]  # what this chunk starts from
    bm, cm = b_ref[0], c_ref[0]  # [Q, N]
    cd = x_ref.dtype
    q = bm.shape[0]
    side = _tile_heads(p, heads)
    width = side * p
    cb = _dot(cm, bm, _NT)  # [Q, Q]
    tri = _triangle(q)
    natural = dtn_ref[0], cumn_ref[0]  # [Q, H]
    which = lax.broadcasted_iota(jnp.int32, (q, width), 1) // p
    which_row = lax.broadcasted_iota(jnp.int32, (width, 1), 0) // p

    for t in range(heads // side):
        lanes = pl.ds(t * width, width)
        xt = x_ref[0, :, lanes]  # [Q, width]
        acc = d_ref[:, lanes] * xt.astype(F32)
        e_tile = jnp.zeros((q, width), F32)
        w_tile = jnp.zeros((q, width), F32)
        decay = jnp.zeros((width, 1), F32)
        for k in range(side):
            dt_col, dt_row, ell, rest, last, cum_col = _head(
                natural, dtr_ref, cumr_ref, t * side + k, tri)
            m = (cb * ell * dt_row).astype(cd)
            acc = acc + _dot(m, jnp.where(which == k, xt, 0).astype(cd),
                             _NN)
            e_tile = jnp.where(which == k, jnp.exp(cum_col), e_tile)
            w_tile = jnp.where(which == k, rest * dt_col, w_tile)
            decay = jnp.where(which_row == k, jnp.exp(last), decay)
        rows = pl.ds(t * width, width)
        hs = h_scratch[rows, :]  # [width, N]
        acc = acc + e_tile * _dot(cm, hs.astype(cd), _NT)
        y_ref[0, :, lanes] = acc.astype(y_ref.dtype)
        xw = (xt.astype(F32) * w_tile).astype(cd)
        h_scratch[rows, :] = decay * hs + _dot(xw, bm, _TN)


def _ssd_bwd_kernel(x_ref, dtn_ref, cumn_ref, dtr_ref, cumr_ref, b_ref,
                    c_ref, d_ref, start_ref, dy_ref,  # inputs
                    dx_ref, ddtr_ref, dcumr_ref, ddtc_ref, dcumc_ref,
                    db_ref, dc_ref, dd_ref,  # outputs
                    dh_scratch, *, heads: int, p: int):
    @pl.when(pl.program_id(2) == 0)  # the row's last chunk
    def _init():
        dh_scratch[:] = jnp.zeros_like(dh_scratch)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bm, cm = b_ref[0], c_ref[0]  # [Q, N]
    cd = x_ref.dtype
    q = bm.shape[0]
    side = _tile_heads(p, heads)
    width = side * p
    cb = _dot(cm, bm, _NT)  # [Q, Q]
    tri = _triangle(q)
    natural = dtn_ref[0], cumn_ref[0]  # [Q, H]
    which = lax.broadcasted_iota(jnp.int32, (q, width), 1) // p
    which_row = lax.broadcasted_iota(jnp.int32, (width, 1), 0) // p
    is_last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    lane_b = lax.broadcasted_iota(jnp.int32, (q, heads), 1)

    dcb = jnp.zeros((q, q), F32)
    dc_acc = jnp.zeros(cm.shape, F32)
    db_acc = jnp.zeros(bm.shape, F32)
    ddt_cols = jnp.zeros((q, heads), F32)
    dcum_cols = jnp.zeros((q, heads), F32)
    for t in range(heads // side):
        lanes = pl.ds(t * width, width)
        rows = pl.ds(t * width, width)
        xt, dyt = x_ref[0, :, lanes], dy_ref[0, :, lanes]  # [Q, width]
        xf, dyf = xt.astype(F32), dyt.astype(F32)
        hs = start_ref[0, 0, rows, :]  # [width, N] float32
        dhs = dh_scratch[rows, :]  # dL/d(the state the chunk ends in)
        hc, dhc = hs.astype(cd), dhs.astype(cd)
        dx = d_ref[:, lanes] * dyf
        e_tile = jnp.zeros((q, width), F32)
        w_tile = jnp.zeros((q, width), F32)
        decay = jnp.zeros((width, 1), F32)
        cols = []
        for k in range(side):
            j = t * side + k
            dt_col, dt_row, ell, rest, last, cum_col = _head(
                natural, dtr_ref, cumr_ref, j, tri)
            ml = cb * ell
            dyk = jnp.where(which == k, dyt, 0).astype(cd)
            dx = dx + _dot((ml * dt_row).astype(cd), dyk, _TN)  # M^T dy
            gl = _dot(dyk, xt, _NT) * ell  # (dy_i . x_j) L_ij
            dcb = dcb + gl * dt_row
            tp = gl * cb
            ddt_row = jnp.sum(tp, axis=0, keepdims=True)  # [1, Q]
            ddtr_ref[0, pl.ds(j, 1), :] = ddt_row
            dcumr_ref[0, pl.ds(j, 1), :] = -(ddt_row * dt_row)
            e_tile = jnp.where(which == k, jnp.exp(cum_col), e_tile)
            w_tile = jnp.where(which == k, rest * dt_col, w_tile)
            decay = jnp.where(which_row == k, jnp.exp(last), decay)
            cols.append((jnp.sum(tp * dt_row, axis=1, keepdims=True),
                         rest, rest * dt_col, jnp.exp(last)))
        edy = e_tile * dyf
        edc = edy.astype(cd)
        dc_acc = dc_acc + _dot(edc, hc, _NN)
        through_read = edy * _dot(cm, hc, _NT)  # dy_i . e_i H C_i a lane
        z = _dot(bm, dhc, _NT)  # [Q, width]: dH B_j
        dx = dx + w_tile * z
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        db_acc = db_acc + _dot((xf * w_tile).astype(cd), dhc, _NN)
        through_write = xf * z  # x_j . dH B_j a lane
        kept = jnp.sum(dhs * hs, axis=1, keepdims=True)  # [width, 1]
        for k, (dcum, rest, w, end) in enumerate(cols):
            j = t * side + k
            dw = _segment(through_write, which, k)  # [Q, 1]
            at_end = (jnp.sum(dw * w, axis=0, keepdims=True) + end * jnp.sum(
                jnp.where(which_row == k, kept, 0.0), axis=0, keepdims=True))
            dcum = (dcum + _segment(through_read, which, k) - dw * w
                    + jnp.where(is_last, at_end, 0.0))
            ddt_cols = jnp.where(lane_b == j, dw * rest, ddt_cols)
            dcum_cols = jnp.where(lane_b == j, dcum, dcum_cols)
        dh_scratch[rows, :] = decay * dhs + _dot(edc, cm, _TN)
        dd_ref[0, 0, :, lanes] = dd_ref[0, 0, :, lanes] + jnp.sum(
            dyf * xf, axis=0, keepdims=True)
    dcb_c = dcb.astype(cd)
    dc_ref[0, 0] = dc_acc + _dot(dcb_c, bm, _NN)
    db_ref[0, 0] = db_acc + _dot(dcb_c, cm, _TN)
    ddtc_ref[0, 0] = ddt_cols
    dcumc_ref[0, 0] = dcum_cols


def _fit_heads(requested: int, heads: int) -> int:
    """Heads a program: the largest multiple of 8 that divides
    ``heads`` and is at most ``requested`` (a block's heads lie on the
    sublanes of ``dt``'s row form); all of them where there is none."""
    fits = [n for n in range(8, min(requested, heads) + 1, 8)
            if heads % n == 0]
    return fits[-1] if fits else heads


def _rows_form(t):
    """[B, S, H] -> [B, H, S]: a head's tokens along the lanes."""
    return t.swapaxes(1, 2)


def _in_specs(q, hb, h, wide, n, order):
    """Block specs of what both kernels read: ``x``, ``dt`` and
    ``cum`` in both forms, ``B``, ``C`` and ``D``'s row."""
    natural = pl.BlockSpec((1, q, h), lambda i, j, k: (i, order(k), 0))
    by_head = pl.BlockSpec((1, hb, q), lambda i, j, k: (i, j, order(k)))
    shared = pl.BlockSpec((1, q, n), lambda i, j, k: (i, order(k), 0))
    return [pl.BlockSpec((1, q, wide), lambda i, j, k: (i, order(k), j)),
            natural, natural, by_head, by_head, shared, shared,
            pl.BlockSpec((1, wide), lambda i, j, k: (0, j))]


def _core_forward(x, dt, cum, b, c, d_row, chunk, hb, p, interpret):
    batch, s, hp = x.shape
    h, n = hp // p, b.shape[-1]
    chunks, wide = s // chunk, hb * p
    with jax.named_scope(DeviceScope.SSD_CHUNK):
        dt_rows, cum_rows = _rows_form(dt), _rows_form(cum)
    static = (chunk, hb, p, interpret)
    operands = (x, dt, cum, dt_rows, cum_rows, b, c, d_row)

    def build():
        return pl.pallas_call(
            functools.partial(_ssd_fwd_kernel, heads=hb, p=p),
            grid=(batch, h // hb, chunks),
            in_specs=_in_specs(chunk, hb, h, wide, n, lambda k: k),
            out_specs=[
                pl.BlockSpec((1, chunk, wide), lambda i, j, k: (i, k, j)),
                pl.BlockSpec((1, 1, wide, n), lambda i, j, k: (i, k, j, 0))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((batch, chunks, hp, n), F32)],
            scratch_shapes=[_vmem((wide, n))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="ssd_fwd",
        )

    return shared_call("ssd_fwd", DeviceScope.SSD, static, operands, build)


def _core_backward(x, dt, cum, b, c, d_row, starts, dy, chunk, hb, p,
                   interpret):
    batch, s, hp = x.shape
    h, n = hp // p, b.shape[-1]
    chunks, wide, blocks = s // chunk, hb * p, h // hb
    last = chunks - 1  # the chunks run last to first
    with jax.named_scope(DeviceScope.SSD_CHUNK):
        dt_rows, cum_rows = _rows_form(dt), _rows_form(cum)
    static = (chunk, hb, p, interpret)
    operands = (x, dt, cum, dt_rows, cum_rows, b, c, d_row, starts, dy)

    def order(k):
        return last - k

    def build():
        by_head = pl.BlockSpec((1, hb, chunk),
                               lambda i, j, k: (i, j, order(k)))
        by_block = pl.BlockSpec((1, 1, chunk, hb),
                                lambda i, j, k: (i, j, order(k), 0))
        partial = pl.BlockSpec((1, 1, chunk, n),
                               lambda i, j, k: (i, j, order(k), 0))
        rows = pl.BlockSpec((1, chunk, wide),
                            lambda i, j, k: (i, order(k), j))
        head_rows = jax.ShapeDtypeStruct((batch, h, s), F32)
        head_cols = jax.ShapeDtypeStruct((batch, blocks, s, hb), F32)
        sums = jax.ShapeDtypeStruct((batch, blocks, s, n), F32)
        return pl.pallas_call(
            functools.partial(_ssd_bwd_kernel, heads=hb, p=p),
            grid=(batch, blocks, chunks),
            in_specs=_in_specs(chunk, hb, h, wide, n, order) + [
                pl.BlockSpec((1, 1, wide, n),
                             lambda i, j, k: (i, order(k), j, 0)),
                rows],
            out_specs=[rows, by_head, by_head, by_block, by_block, partial,
                       partial,
                       pl.BlockSpec((1, 1, 1, wide),
                                    lambda i, j, k: (i, j, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), head_rows,
                       head_rows, head_cols, head_cols, sums, sums,
                       jax.ShapeDtypeStruct((batch, blocks, 1, wide), F32)],
            scratch_shapes=[_vmem((wide, n))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="ssd_bwd",
        )

    dx, ddt_r, dcum_r, ddt_c, dcum_c, db, dc, dd = shared_call(
        "ssd_bwd", DeviceScope.SSD, static, operands, build)
    with jax.named_scope(DeviceScope.SSD_CHUNK):

        def both(by_rows, by_cols):  # -> [B, S, H]
            return by_rows.swapaxes(1, 2) + by_cols.swapaxes(1, 2).reshape(
                batch, s, h)

        return (dx, both(ddt_r, ddt_c), both(dcum_r, dcum_c),
                db.sum(axis=1).astype(b.dtype), dc.sum(axis=1).astype(c.dtype),
                dd.sum(axis=0).reshape(1, hp))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _core(x, dt, cum, b, c, d_row, chunk, hb, p, interpret):
    """``y`` [B, S, H * P] of ``x`` [B, S, H * P], ``dt`` and ``cum``
    [B, S, H] float32, ``b`` and ``c`` [B, S, N] and ``d_row``
    [1, H * P] float32 (``D`` a column)."""
    return _core_forward(x, dt, cum, b, c, d_row, chunk, hb, p,
                         interpret)[0]


def _core_fwd(x, dt, cum, b, c, d_row, chunk, hb, p, interpret):
    y, starts = _core_forward(x, dt, cum, b, c, d_row, chunk, hb, p,
                              interpret)
    return y, (x, dt, cum, b, c, d_row, starts)


def _core_bwd(chunk, hb, p, interpret, residuals, dy):
    return _core_backward(*residuals, dy, chunk, hb, p, interpret)


_core.defvjp(_core_fwd, _core_bwd)


def ssd(
    x: jax.Array,  # [B, S, H, P]
    dt: jax.Array,  # [B, S, H], after the softplus
    a: jax.Array,  # [H], negative
    b: jax.Array,  # [B, S, G, N]
    c: jax.Array,  # [B, S, G, N]
    d: jax.Array,  # [H]
    chunk: int = CHUNK,
    use_kernels: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``y`` [B, S, H, P] in ``x``'s dtype of the recurrence in the
    module docstring, differentiable in all six arguments."""
    if not use_kernels:
        return ssd_chunked(x, dt, a, b, c, d, chunk)
    _check(x, dt, a, b, c, d, chunk)
    batch, s, h, p = x.shape
    if b.shape[2] != 1:
        raise ValueError(
            f"the ssd_* kernels take one group of B and C (Granite-4.0-H "
            f"publishes mamba_n_groups 1), not {b.shape[2]}: "
            "use_kernels=False computes any")
    if chunk % 8:
        raise ValueError(f"chunk {chunk} is not a multiple of 8")
    with jax.named_scope(DeviceScope.SSD_CHUNK):
        dt = dt.astype(F32)
        cum = _chunk_cumsum(dt * a.astype(F32), chunk)
        d_row = jnp.repeat(d.astype(F32), p)[None]
    y = _core(x.reshape(batch, s, h * p), dt, cum,
              b[:, :, 0].astype(x.dtype), c[:, :, 0].astype(x.dtype), d_row,
              chunk, _fit_heads(HEADS_PER_PROGRAM, h), p,
              _resolve_interpret(interpret))
    return y.reshape(batch, s, h, p)


def ssd_auto(x, dt, a, b, c, d, chunk: int = CHUNK, use_kernels: bool = True,
             interpret: Optional[bool] = None) -> jax.Array:
    """``ssd`` under whatever mesh is ambient: GSPMD cannot partition a
    Mosaic call, so under a mesh the op runs in a ``shard_map`` with
    the batch on the data axes and the heads on ``tensor``; ``b`` and
    ``c`` are whole on every shard and their gradients are summed over
    it."""
    from jax.sharding import PartitionSpec as P

    def run(*args):
        return ssd(*args, chunk=chunk, use_kernels=use_kernels,
                   interpret=interpret)

    mesh = ambient_shard_mesh()
    if mesh is None:
        return run(x, dt, a, b, c, d)
    rows = ("data", "fsdp")
    whole = P(rows, None, None, None)
    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(rows, None, "tensor", None), P(rows, None, "tensor"),
                  P("tensor"), whole, whole, P("tensor")),
        out_specs=P(rows, None, "tensor", None),
        check_vma=False,  # a pallas_call output carries no vma
    )(x, dt, a, b, c, d)
