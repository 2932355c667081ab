"""Manifold-constrained hyper-connections (DeepSeek, arXiv:2512.24880):
the residual stream of a token is ``n`` streams ``X`` in ``R^{n x C}``,
and a sublayer ``F`` reads a mix of them and writes back through two
more mappings, all three computed from the streams themselves::

    u      = RMSNorm_nC(vec(X))
    H_pre  = sigmoid(a_pre * (u @ phi_pre) + b_pre)              R^n
    H_post = 2 * sigmoid(a_post * (u @ phi_post) + b_post)       R^n
    M_0    = exp(clip(a_res * mat(u @ phi_res) + b_res, lo, hi)) R^{n x n}
    M_t    = rows_normalised(columns_normalised(M_{t-1}))        t = 1..iters
    x_in   = sum_j H_pre[j] X[j];   y = F(x_in)
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] * y,   H_res = M_iters

``H_res`` is made (nearly) doubly stochastic by the Sinkhorn iteration,
so the streams' mean is carried through a layer unchanged.

The passes over the streams are Pallas kernels where the shape allows
(``connect``, ``token_tile``), and the ``jax.numpy`` functions
``mappings``, ``mix_in``, ``mix_out`` elsewhere: the same arithmetic,
the fallback and the tests' reference. A kernel holds a tile of tokens
``[tile, n * C]`` in VMEM and reads each byte of the streams once a
pass (``PERF.md`` section 6, PR 45 and 46: XLA's separate fusions read
them four times a sublayer forward):

* ``hc_enter_fwd``: one read of ``X`` gives a token its sum of squares,
  its ``2n + n^2`` raw projections (one MXU product a tile), ``H_pre``
  from the first ``n`` and ``x_in``; it writes ``x_in`` and the normed
  projections. ``hc_leave_fwd``: one read of ``X`` and ``y``, one write
  of ``X'``. ``hc_leave_bwd``: one read of ``dX'``, ``X`` and ``y``;
  ``dX``, ``dy`` and a token's ``dH_post``, ``dH_res`` (inner products
  over ``C``, float32). ``hc_enter_bwd``: one read of ``X``, ``dx_in``
  and the cotangent ``hc_leave_bwd`` wrote for the same ``X`` (which
  ``_enter`` hands through so that it arrives here and is added in the
  one write, not in a pass of XLA's); the projection's gradient summed
  over the tiles in float32. Each is a ``jax.custom_vjp`` whose
  residuals are its arguments and a token's 24 + 20 floats;
* XLA keeps what is 24 floats a token: ``H_post``, ``M_0`` and the
  Sinkhorn rounds, on the tokens-minor form below, their backward by
  autodiff (``_post_res``, a checkpoint): 0.03 ms of a sublayer's 1.3
  forward, and a hand-written backward of 20 rounds would buy nothing;
* every call of a kernel goes through one shared jitted callable a
  kernel and operand shapes (``_call``), so a process traces each body
  once and a lowered program holds it once or twice, whatever the
  sublayers, scans, replays and programs that call it;
* the path follows what the code can see: the kernels run where ``C``
  is a multiple of 128 lanes and a token tile's blocks fit the VMEM
  budget beside the kernels (``_TILE_STATE_BUDGET_BYTES``), the
  functions anywhere else; no option. Off the TPU the kernels run in
  the Pallas interpreter.

The form both paths share:

* the streams are ONE flat array ``[B, S, n * C]`` wherever they exist,
  from ``enter`` to ``leave``: stream ``j`` is the slice ``[..., j * C:
  (j + 1) * C]``, the order ``vec(X)`` has, so ``norm/scale [n * C]``
  and ``phi``'s rows index it as it lies. With ``C`` a multiple of 128
  every slice is lane-aligned and ``C`` is the minor axis of a layer
  scan's carry by the form of the operations alone. (A stream axis,
  ``[B, S, n, C]``, would pad its 4 to a tile of 16 as a minor axis,
  so XLA puts it outermost, and the flat view the norm and the
  projection read is then a copy of the whole carry in every sublayer:
  forward, remat's replay and the cotangent: ``PERF.md`` section 6,
  PR 37);
* the three projections are ONE matmul ``[.., nC] @ [nC, 2n + n^2]``
  (``phi``'s columns are ``[pre | post | res]``, ``res`` row-major),
  its operands in the streams' dtype with a float32 result, as a
  router's scores are taken; the norm's division follows the matmul
  (``(x / r * g) @ phi = (x @ (g * phi)) / r``), so the streams are
  read once for the sum of squares and once for the product and no
  normed copy of them is written;
* everything after the matmul is float32 with the TOKENS on the minor
  axes (``[2n + n^2, B, S]``): the matmul's own output is written that
  way (``bsk,kf->fbs``, 24 x B x S values: the one small transpose),
  the Sinkhorn iteration is unrolled divisions of whole token vectors,
  never a ``[.., n, n]`` tile, and a mix broadcasts a ``[B, S]``
  weight along ``C``;
* the two mixes are unrolled multiply-adds over the streams' slices in
  float32, rounded once to the streams' dtype, ``mix_out``'s ``n``
  results written side by side on the minor axis: one pass over ``X``
  each;
* each of the three functions is a ``jax.checkpoint`` of its own, as
  each kernel is a ``custom_vjp``: what a backward pass keeps of them
  is their arguments (the streams in their own dtype, the mappings),
  not the float32 widenings autodiff would save (four streams widened
  are twice the streams).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.selective_scan import _resolve_interpret
from dlrover_tpu.ops.trace_once import shared_call
from dlrover_tpu.telemetry.names import DeviceScope


# The gates and biases at the start: the model's config does not give
# them. A gate of about 1 lets the projection, of unit variance a
# column, move each mapping by about a unit, so the mappings differ by
# token and ``M_0`` is far from doubly stochastic from the first step.
INIT_STD = 0.5


def init(key, lead: Tuple[int, ...], n: int, width: int, dtype) -> Dict:
    """One sublayer's hyper-connection: the norm's scale over ``n *
    width``, ``phi`` (fan-in scaled, columns ``[pre | post | res]``),
    the gates ``alpha`` (pre, post, res) normal around 1 and the biases
    normal around 0, both at ``INIT_STD``."""
    k = jax.random.split(key, 3)
    cols = 2 * n + n * n
    return {
        "norm": {"scale": jnp.ones(lead + (n * width,), dtype)},
        "phi": {"kernel": jax.random.normal(
            k[0], lead + (n * width, cols), dtype) / (n * width) ** 0.5},
        "alpha": 1.0 + INIT_STD * jax.random.normal(k[1], lead + (3,),
                                                   dtype),
        "bias": INIT_STD * jax.random.normal(k[2], lead + (cols,), dtype),
    }


def sinkhorn(m: jax.Array, iters: int) -> jax.Array:
    """``iters`` rounds of column then row normalisation of the
    positive ``m [n, n, ...]`` (rows on axis 0, columns on axis 1)."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=0, keepdims=True)  # a column sums to 1
        m = m / jnp.sum(m, axis=1, keepdims=True)  # a row sums to 1
    return m


def enter(x: jax.Array, n: int) -> jax.Array:
    """The streams at the start, ``[B, S, n * C]``: every one is the
    token's ``x [B, S, C]``."""
    return jnp.concatenate([x] * n, axis=-1)


def split(x: jax.Array, n: int):
    """The ``n`` streams of the flat ``x [..., n * C]``, each a slice
    ``[..., C]`` of the minor axis."""
    width = x.shape[-1] // n
    return [x[..., j * width:(j + 1) * width] for j in range(n)]


def leave(x: jax.Array, n: int) -> jax.Array:
    """The streams at the end, summed: ``[B, S, C]`` in ``x``'s dtype
    (a float32 sum rounded once, as ``jnp.sum`` over a stream axis)."""
    streams = [stream.astype(jnp.float32) for stream in split(x, n)]
    return sum(streams[1:], streams[0]).astype(x.dtype)


def _gates(z: jax.Array, p: Dict, n: int, iters: int,
           clamp: Tuple[float, float]):
    """The three mappings from the normed projection ``z [2n + n^2, B,
    S]``, float32, tokens minor."""
    _, b, s = z.shape
    alpha = p["alpha"].astype(jnp.float32)
    bias = p["bias"].astype(jnp.float32)[:, None, None]
    pre = jax.nn.sigmoid(alpha[0] * z[:n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[n:2 * n] + bias[n:2 * n])
    res = jnp.exp(jnp.clip(alpha[2] * z[2 * n:] + bias[2 * n:], *clamp))
    return pre, post, sinkhorn(res.reshape(n, n, b, s), iters)


def _projection(p: Dict, dtype) -> jax.Array:
    """``g * phi [n * C, 2n + n^2]``: the norm's scale folded into the
    projection, in the streams' dtype."""
    return (p["norm"]["scale"].astype(jnp.float32)[:, None]
            * p["phi"]["kernel"].astype(jnp.float32)).astype(dtype)


@jax.named_scope(DeviceScope.HC_MAP)
@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4, 5))
def mappings(x: jax.Array, p: Dict, n: int, iters: int,
             clamp: Tuple[float, float], eps: float):
    """The three mappings of the ``n`` streams ``x [B, S, n * C]``,
    float32, tokens minor: ``(H_pre [n, B, S], H_post [n, B, S], H_res
    [n, n, B, S])``."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1) + eps)  # [B, S]
    z = jnp.einsum("bsk,kf->fbs", x, _projection(p, x.dtype),
                   preferred_element_type=jnp.float32) * inv
    return _gates(z, p, n, iters, clamp)


def res_defect(res: jax.Array) -> jax.Array:
    """The mean over tokens of the largest ``|row or column sum - 1|``
    of ``H_res [n, n, B, S]``: what the iterations left."""
    rows = jnp.abs(jnp.sum(res, axis=1) - 1.0).max(axis=0)
    cols = jnp.abs(jnp.sum(res, axis=0) - 1.0).max(axis=0)
    return jnp.mean(jnp.maximum(rows, cols))


@jax.named_scope(DeviceScope.HC_MIX)
@jax.checkpoint
def mix_in(x: jax.Array, pre: jax.Array) -> jax.Array:
    """``sum_j H_pre[j] X[j]`` of the streams ``x [B, S, n * C]``:
    ``[B, S, C]`` in ``x``'s dtype."""
    streams = split(x, pre.shape[0])
    acc = pre[0][..., None] * streams[0].astype(jnp.float32)
    for j in range(1, len(streams)):
        acc = acc + pre[j][..., None] * streams[j].astype(jnp.float32)
    return acc.astype(x.dtype)


@jax.named_scope(DeviceScope.HC_MIX)
@jax.checkpoint
def mix_out(x: jax.Array, y: jax.Array, post: jax.Array,
            res: jax.Array) -> jax.Array:
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``: ``[B, S, n *
    C]`` in ``x``'s dtype, the streams side by side."""
    n = post.shape[0]
    streams = [stream.astype(jnp.float32) for stream in split(x, n)]
    yf = y.astype(jnp.float32)
    out = []
    for i in range(n):
        acc = post[i][..., None] * yf
        for j in range(n):
            acc = acc + res[i, j][..., None] * streams[j]
        out.append(acc.astype(x.dtype))
    return jnp.concatenate(out, axis=-1)


# -- the kernels ------------------------------------------------------------

LANES = 128
# The kernels hold a tile of 128 tokens' streams in VMEM (a block of
# the xing4 cell's ``[128, 4 x 3584]`` bf16 is 3.7 MB in one piece; 256
# read the same to a percent on the chip and compile twice as long, 64
# a few percent slower: ``PERF.md`` section 7). A token's 24 or 20
# floats cross a kernel's edge with the TOKENS minor, ``[B, 24, S]``,
# the form XLA's part keeps them in, and are turned in VMEM, a ``[128,
# 128]`` float32 transpose a tile. ``hc_enter_bwd`` pipelines three
# stream blocks ``[128, n * C]`` and one of ``[128, C]``,
# ``hc_leave_bwd`` three and two, every block twice over (the pallas
# pipeline double-buffers), beside the projection ``[128, n * C]`` and
# its float32 gradient. Mosaic is asked for the budget and a quarter
# more, for the float32 temporaries of a row block, of the v5e's 128
# MiB. A shape that does not fit (a ``C`` or a row that is no multiple
# of 128, streams too wide for the budget) takes the ``jax.numpy``
# functions above.
_TOKEN_TILE = 128
_TILE_STATE_BUDGET_BYTES = 80 * 1024 * 1024
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
_ROWS = 32  # tokens of a kernel's inner step: four float32 sublane tiles


def _tile_state_bytes(n: int, width: int, itemsize: int) -> int:
    """VMEM bytes of the dearest kernel's pipelined blocks: the token
    blocks of ``hc_leave_bwd`` (it has one more than ``hc_enter_bwd``)
    and the projection blocks of ``hc_enter_bwd``."""
    token_blocks = 2 * (3 * n + 2) * _TOKEN_TILE * width * itemsize
    projection = 2 * LANES * n * width * (itemsize + 4)
    return token_blocks + projection


def token_tile(shape: Tuple[int, ...], dtype, n: int) -> int:
    """The tokens a program of the kernels holds for streams ``[B, S,
    n * C]`` of ``dtype``, or 0: the ``jax.numpy`` functions."""
    seq, width = shape[-2], shape[-1] // n
    itemsize = jnp.dtype(dtype).itemsize
    fits = (width % LANES == 0 and seq % _TOKEN_TILE == 0
            and itemsize in (2, 4) and 2 * n + n * n < LANES
            and _tile_state_bytes(n, width, itemsize)
            <= _TILE_STATE_BUDGET_BYTES)
    return _TOKEN_TILE if fits else 0


def _sublanes(count: int) -> int:
    """``count`` rows as whole float32 sublane tiles."""
    return -(-count // 8) * 8


def _row_blocks(body) -> None:
    """``body(rows)`` over the tile's row blocks, ``rows`` a slice of
    ``_ROWS`` tokens."""

    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS))
        return carry

    lax.fori_loop(0, _TOKEN_TILE // _ROWS, step, 0)


def _scatter_lanes(columns, lane) -> jax.Array:
    """``[rows, 128]`` whose lane ``k`` is ``columns[k] [rows, 1]`` and
    whose other lanes are 0."""
    out = jnp.zeros(lane.shape, jnp.float32)
    for k, column in enumerate(columns):
        out = jnp.where(lane == k, column, out)
    return out


def _tokens_major(minor: jax.Array) -> jax.Array:
    """A tile's ``[k, tile]`` floats, tokens minor, as ``[tile, 128]``:
    a token's ``k`` in lanes ``0..k - 1``, zeros past them."""
    k, tile = minor.shape
    return jnp.concatenate(
        [minor, jnp.zeros((LANES - k, tile), jnp.float32)]).T


def _hc_enter_fwd_kernel(x_ref, w_ref, a_ref, b_ref, xin_ref, zi_ref, zt_ref,
                         r_scr, *, n, cols, eps):
    # grid (batch, token tile). One read of the tile: its raw
    # projections on the MXU, then a row block at a time the sum of
    # squares, the normed projections (lane ``cols`` carries 1 / rms for
    # the backward), ``H_pre`` from the first ``n`` and the mix; the
    # projections leave a second time with the tokens minor, for XLA.
    width = xin_ref.shape[-1]
    r_scr[...] = jnp.dot(x_ref[0], w_ref[...],
                         preferred_element_type=jnp.float32)

    def block(rows):
        lane = lax.broadcasted_iota(jnp.int32, (rows.size, LANES), 1)
        xs = [x_ref[0, rows, j * width:(j + 1) * width].astype(jnp.float32)
              for j in range(n)]
        squares = xs[0] * xs[0]
        for xf in xs[1:]:
            squares = squares + xf * xf
        mean = jnp.sum(squares, axis=-1, keepdims=True) / (n * width)
        inv = lax.rsqrt(mean + eps)
        z = r_scr[rows, :] * inv
        zi_ref[0, rows, :] = jnp.where(lane == cols, inv, z)
        pre = jax.nn.sigmoid(a_ref[...] * z + b_ref[...])
        acc = pre[:, 0:1] * xs[0]
        for j in range(1, n):
            acc = acc + pre[:, j:j + 1] * xs[j]
        xin_ref[0, rows, :] = acc.astype(xin_ref.dtype)

    _row_blocks(block)
    zt_ref[0] = zi_ref[0].T[:zt_ref.shape[1]]


def _hc_enter_bwd_kernel(x_ref, dxs_ref, dxin_ref, dzt_ref, zi_ref, wt_ref,
                         a_ref, b_ref, dx_ref, dwt_ref, dab_ref, dr_scr,
                         co_scr, *, n, cols):
    # grid (batch, token tile), the tiles of a batch row in turn: the
    # projection's gradient and the gate's and bias's sum over them.
    # A row block at a time the tile gives ``dH_pre`` (inner products
    # with ``dx_in``) and from it the projections' cotangent; then the
    # MXU takes that back to the streams, and one pass adds the mix's,
    # the norm's and the cotangent that came by ``hc_leave``.
    width = dxin_ref.shape[-1]

    @pl.when(pl.program_id(1) == 0)
    def _zero():
        dwt_ref[...] = jnp.zeros_like(dwt_ref)
        dab_ref[...] = jnp.zeros_like(dab_ref)

    co_scr[...] = _tokens_major(dzt_ref[0])  # until a row block takes it

    def block(rows):
        lane = lax.broadcasted_iota(jnp.int32, (rows.size, LANES), 1)
        zi = zi_ref[0, rows, :]
        inv = zi[:, cols:cols + 1]
        z = jnp.where(lane < cols, zi, 0.0)
        pre = jax.nn.sigmoid(a_ref[...] * z + b_ref[...])
        d = dxin_ref[0, rows, :].astype(jnp.float32)
        dpre = _scatter_lanes([jnp.sum(d * x_ref[
            0, rows, j * width:(j + 1) * width].astype(jnp.float32),
            axis=-1, keepdims=True) for j in range(n)], lane)
        g = dpre * pre * (1.0 - pre)  # 0 past lane n, where dpre is
        dz = jnp.where(lane < cols, co_scr[rows, :], 0.0) + a_ref[...] * g
        q = jnp.sum(dz * z, axis=-1, keepdims=True)
        dr_scr[rows, :] = (dz * inv).astype(dr_scr.dtype)
        # lanes 0..n-1 H_pre, lane n the norm's share of the cotangent
        co_scr[rows, :] = jnp.where(lane == n,
                                    -q * inv * inv / (n * width), pre)
        dab_ref[0, 0:1, :] += jnp.sum(g * z, axis=0, keepdims=True)
        dab_ref[0, 1:2, :] += jnp.sum(g, axis=0, keepdims=True)

    _row_blocks(block)
    dr = dr_scr[...]
    co = co_scr[...]
    norm = co[:, n:n + 1]
    d = dxin_ref[0].astype(jnp.float32)
    for j in range(n):
        at = slice(j * width, (j + 1) * width)
        dwt_ref[0, :, at] += lax.dot_general(
            dr, x_ref[0, :, at], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dx = jnp.dot(dr, wt_ref[:, at], preferred_element_type=jnp.float32)
        dx = dx + co[:, j:j + 1] * d
        dx = dx + norm * x_ref[0, :, at].astype(jnp.float32)
        dx = dx + dxs_ref[0, :, at].astype(jnp.float32)
        dx_ref[0, :, at] = dx.astype(dx_ref.dtype)


def _coefficients(coef, n):
    """``H_post [n]`` and ``H_res [n][n]`` of a row block's ``coef
    [rows, 128]`` (lanes ``0..n + n^2 - 1``), each ``[rows, 1]``."""
    post = [coef[:, i:i + 1] for i in range(n)]
    res = [[coef[:, n + i * n + j:n + i * n + j + 1] for j in range(n)]
           for i in range(n)]
    return post, res


def _hc_leave_fwd_kernel(x_ref, y_ref, coef_ref, out_ref, co_scr, *, n):
    # grid (batch, token tile): one read of the streams and of y, one
    # write of the streams; mix_out's multiply-adds in its order.
    width = y_ref.shape[-1]
    co_scr[...] = _tokens_major(coef_ref[0])

    def block(rows):
        post, res = _coefficients(co_scr[rows, :], n)
        yf = y_ref[0, rows, :].astype(jnp.float32)
        xs = [x_ref[0, rows, j * width:(j + 1) * width].astype(jnp.float32)
              for j in range(n)]
        for i in range(n):
            acc = post[i] * yf
            for j in range(n):
                acc = acc + res[i][j] * xs[j]
            out_ref[0, rows, i * width:(i + 1) * width] = acc.astype(
                out_ref.dtype)

    _row_blocks(block)


def _hc_leave_bwd_kernel(g_ref, x_ref, y_ref, coef_ref, dx_ref, dy_ref,
                         dcoef_ref, co_scr, dco_scr, *, n):
    # grid (batch, token tile): one read of the cotangent, the streams
    # and y; the streams' and y's cotangents written once, the
    # mappings' (inner products over C, float32) a token in rows
    # 0..n + n^2 - 1 of ``dcoef``, tokens minor.
    width = y_ref.shape[-1]
    co_scr[...] = _tokens_major(coef_ref[0])

    def block(rows):
        lane = lax.broadcasted_iota(jnp.int32, (rows.size, LANES), 1)
        post, res = _coefficients(co_scr[rows, :], n)
        yf = y_ref[0, rows, :].astype(jnp.float32)
        xs = [x_ref[0, rows, j * width:(j + 1) * width].astype(jnp.float32)
              for j in range(n)]
        gs = [g_ref[0, rows, i * width:(i + 1) * width].astype(jnp.float32)
              for i in range(n)]
        dy = post[0] * gs[0]
        for i in range(1, n):
            dy = dy + post[i] * gs[i]
        dy_ref[0, rows, :] = dy.astype(dy_ref.dtype)
        for j in range(n):
            dx = res[0][j] * gs[0]
            for i in range(1, n):
                dx = dx + res[i][j] * gs[i]
            dx_ref[0, rows, j * width:(j + 1) * width] = dx.astype(
                dx_ref.dtype)
        over = [gs[i] * yf for i in range(n)] + [
            gs[i] * xs[j] for i in range(n) for j in range(n)]
        dco_scr[rows, :] = _scatter_lanes(
            [jnp.sum(v, axis=-1, keepdims=True) for v in over], lane)

    _row_blocks(block)
    dcoef_ref[0] = dco_scr[...].T[:dcoef_ref.shape[1]]


def _call(kernel, static, name, scope, operands, in_specs, out_specs,
          out_shape, scratch, semantics, interpret, aliases=()):
    """``kernel(..., **static)`` over grid (batch, token tile) of the
    streams ``operands[0]``, under ``scope``, traced once a process
    (``ops.trace_once``)."""
    x = operands[0]

    def build():
        return pl.pallas_call(
            functools.partial(kernel, **static),
            grid=(x.shape[0], x.shape[1] // _TOKEN_TILE),
            in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            input_output_aliases=dict(aliases),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics,
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret, name=name)

    return shared_call(
        name, scope,
        (tuple(sorted(static.items())), semantics, interpret, aliases),
        operands, build)


def _tokens(width):
    """The block of a ``[B, S, width]`` operand at grid (batch, token
    tile)."""
    return pl.BlockSpec((1, _TOKEN_TILE, width), lambda i, k: (i, k, 0))


def _tokens_minor(count):
    """The block of a ``[B, count, S]`` operand at that grid."""
    return pl.BlockSpec((1, count, _TOKEN_TILE), lambda i, k: (i, 0, k))


def _whole(shape):
    return pl.BlockSpec(shape, lambda i, k: (0,) * len(shape))


def _scratch(dtype=jnp.float32):
    return pltpu.VMEM((_TOKEN_TILE, LANES), dtype)


def _enter_forward(x, w, a, b, n, cols, eps, interpret):
    batch, seq, wide = x.shape
    width = wide // n
    return _call(
        _hc_enter_fwd_kernel, dict(n=n, cols=cols, eps=eps),
        "hc_enter_fwd", DeviceScope.HC_MAP, (x, w, a, b),
        [_tokens(wide), _whole(w.shape), _whole(a.shape), _whole(b.shape)],
        [_tokens(width), _tokens(LANES), _tokens_minor(_sublanes(cols))],
        [jax.ShapeDtypeStruct((batch, seq, width), x.dtype),
         jax.ShapeDtypeStruct((batch, seq, LANES), jnp.float32),
         jax.ShapeDtypeStruct((batch, _sublanes(cols), seq), jnp.float32)],
        [_scratch()], ("parallel", "parallel"), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _enter(x, w, a, b, n, cols, eps, interpret):
    """(``x_in [B, S, C]``; the normed projections ``[B, 24, S]``,
    float32, tokens minor; ``x`` itself, so that what leaves by
    ``hc_leave`` brings its cotangent back through this function's
    backward, which adds it in its one write). ``w [n * C, 128]`` is
    the projection, zero past ``cols``; ``a`` and ``b [1, 128]`` the
    gate and bias of ``H_pre``."""
    return _enter_fwd(x, w, a, b, n, cols, eps, interpret)[0]


def _enter_fwd(x, w, a, b, n, cols, eps, interpret):
    # zi: the projections a second time, a token's 128 lanes minor as
    # the backward kernel reads them, 1 / rms in lane ``cols``
    x_in, zi, zt = _enter_forward(x, w, a, b, n, cols, eps, interpret)
    return (x_in, zt, x), (x, w, a, b, zi)


def _enter_bwd(n, cols, eps, interpret, residuals, cotangents):
    del eps  # in zi's lane ``cols``
    x, w, a, b, zi = residuals
    dxin, dzt, dxs = cotangents
    batch, seq, wide = x.shape
    width = wide // n
    dx, dwt, dab = _call(
        _hc_enter_bwd_kernel, dict(n=n, cols=cols),
        "hc_enter_bwd", DeviceScope.HC_MAP, (x, dxs, dxin, dzt, zi, w.T, a, b),
        [_tokens(wide), _tokens(wide), _tokens(width),
         _tokens_minor(dzt.shape[1]), _tokens(LANES),
         _whole(w.T.shape), _whole(a.shape), _whole(b.shape)],
        [_tokens(wide),
         pl.BlockSpec((1, LANES, wide), lambda i, k: (i, 0, 0)),
         pl.BlockSpec((1, 8, LANES), lambda i, k: (i, 0, 0))],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct((batch, LANES, wide), jnp.float32),
         jax.ShapeDtypeStruct((batch, 8, LANES), jnp.float32)],
        [_scratch(x.dtype), _scratch()],
        ("parallel", "arbitrary"), interpret, ((1, 0),))
    dab = dab.sum(axis=0)
    return (dx, dwt.sum(axis=0).T.astype(w.dtype), dab[0:1].astype(a.dtype),
            dab[1:2].astype(b.dtype))


_enter.defvjp(_enter_fwd, _enter_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _leave(x, y, coef, interpret):
    """``mix_out`` of the streams ``x``, ``y [B, S, C]`` and ``coef [B,
    24, S]`` (``H_post``, then ``H_res`` row-major, then zeros),
    float32, tokens minor."""
    wide = x.shape[-1]
    return _call(
        _hc_leave_fwd_kernel, dict(n=wide // y.shape[-1]),
        "hc_leave_fwd", DeviceScope.HC_MIX, (x, y, coef),
        [_tokens(wide), _tokens(y.shape[-1]),
         _tokens_minor(coef.shape[1])],
        _tokens(wide), jax.ShapeDtypeStruct(x.shape, x.dtype),
        [_scratch()], ("parallel", "parallel"), interpret)


def _leave_fwd(x, y, coef, interpret):
    return _leave(x, y, coef, interpret), (x, y, coef)


def _leave_bwd(interpret, residuals, g):
    x, y, coef = residuals
    wide, width = x.shape[-1], y.shape[-1]
    return tuple(_call(
        _hc_leave_bwd_kernel, dict(n=wide // width),
        "hc_leave_bwd", DeviceScope.HC_MIX, (g, x, y, coef),
        [_tokens(wide), _tokens(wide), _tokens(width),
         _tokens_minor(coef.shape[1])],
        [_tokens(wide), _tokens(width),
         _tokens_minor(coef.shape[1])],
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (x, y, coef)],
        [_scratch(), _scratch()],
        ("parallel", "parallel"), interpret, ((0, 0),)))


_leave.defvjp(_leave_fwd, _leave_bwd)


@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4))
def _post_res(zt: jax.Array, p: Dict, n: int, iters: int,
              clamp: Tuple[float, float]):
    """From ``hc_enter``'s projections ``zt [B, 24, S]``: ``H_res [n,
    n, B, S]`` and ``[H_post | H_res | zeros] [B, 24, S]`` as
    ``hc_leave`` reads them. XLA's, on the tokens-minor form: 24 floats
    a token and 20 rounds of them."""
    b, _, s = zt.shape
    z = jnp.moveaxis(zt[:, :2 * n + n * n], 1, 0)
    _, post, res = _gates(z, p, n, iters, clamp)
    coef = jnp.concatenate(
        [post, res.reshape(n * n, b, s),
         jnp.zeros((_sublanes(n + n * n) - n - n * n, b, s), jnp.float32)])
    return res, jnp.moveaxis(coef, 0, 1)


def connect(x: jax.Array, p: Dict, f, n: int, iters: int,
            clamp: Tuple[float, float], eps: float, kernels: bool = True,
            interpret=None):
    """The sublayer ``f(x_in) -> (y, out)`` under its hyper-connection
    ``p``, on the streams ``x [B, S, n * C]``: ``(X', out, the defect
    of H_res, 1 if the kernels ran it and 0 if the jax.numpy
    functions)``. The path follows the shapes (``token_tile``);
    ``kernels`` False is a model that runs no kernel at all."""
    if not (kernels and token_tile(x.shape, x.dtype, n)):
        pre, post, res = mappings(x, p, n, iters, clamp, eps)
        y, out = f(mix_in(x, pre))
        return mix_out(x, y, post, res), out, res_defect(res), 0
    interpret = _resolve_interpret(interpret)
    cols = 2 * n + n * n
    with jax.named_scope(DeviceScope.HC_MAP):
        w = jnp.pad(_projection(p, x.dtype), ((0, 0), (0, LANES - cols)))
        gate = jnp.zeros((1, LANES), jnp.float32)
        a = gate.at[0, :n].set(p["alpha"][0].astype(jnp.float32))
        b = gate.at[0, :n].set(p["bias"][:n].astype(jnp.float32))
        x_in, zt, x = _enter(x, w, a, b, n, cols, eps, interpret)
        res, coef = _post_res(zt, p, n, iters, clamp)
    y, out = f(x_in)
    return _leave(x, y, coef, interpret), out, res_defect(res), 1
