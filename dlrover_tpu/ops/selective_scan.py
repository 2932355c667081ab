"""The selective scan of a Mamba-1 state-space layer as one
differentiable op.

Per batch row, channel ``c`` and state ``n`` (``N`` states a channel)::

    h_t[c, n] = exp(dt_t[c] * A[c, n]) * h_{t-1}[c, n]
                + dt_t[c] * u_t[c] * B_t[n]
    y_t[c]    = sum_n h_t[c, n] * C_t[n] + D[c] * u_t[c]

``h`` is ``[S, C, N]`` float32 over a row (2.7 GB a layer at 8192
tokens, 5120 channels, 16 states) and is never materialised, in either
direction. On a TPU the recurrence is a pair of Pallas kernels whose
instructions are named ``ssm_scan_fwd`` and ``ssm_scan_bwd``:

* grid ``(batch, channel blocks, chunks of the row)``, the chunks
  innermost and sequential; ``h`` of one channel block (``[N, block_c]``
  float32: states on sublanes, channels on lanes) lives in VMEM and is
  carried from chunk to chunk;
* the forward also writes the state each chunk starts from, a
  ``[chunks, N, C]`` residual (1/chunk of the full history);
* the backward walks the chunks last to first: it replays one chunk's
  states from that residual into VMEM, then runs the adjoint recurrence
  over the chunk in reverse. The sums over channels that ``dB`` and
  ``dC`` need are kept lane-wide in VMEM (one ``[N, 128]`` tile a step)
  and reduced once a chunk; the partial sums of the channel blocks are
  added outside the kernel.

``B_t`` and ``C_t`` arrive with the states on lanes (``[chunk, N]``
blocks); a step turns row ``t`` into an ``[N, 1]`` column through an
identity mask and a lane reduction, which needs nothing of Mosaic
beyond what the flash kernels already use.

Everything is float32: ``dt``, ``A`` and ``h`` by the architecture's
definition, and ``u``/``y`` because a step reads and writes single
rows (a packed 16-bit row cannot be addressed alone). Off the TPU the
same kernels run in the Pallas interpreter (``interpret``), and
``selective_scan_reference`` is the token-by-token ``lax.scan`` the
tests hold them to.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from dlrover_tpu.ops.flash_attention import LANES, _vmem, ambient_shard_mesh


def selective_scan_reference(u, dt, a, b, c, d):
    """The recurrence token by token (``lax.scan`` over the row):
    ``u``, ``dt`` [B, S, C]; ``a`` [C, N]; ``b``, ``c`` [B, S, N];
    ``d`` [C]. Float32 throughout. The CPU path of the models and the
    oracle of the kernels' tests."""
    f32 = jnp.float32
    u, dt, a, b, c, d = (t.astype(f32) for t in (u, dt, a, b, c, d))

    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs  # [B, C], [B, C], [B, N], [B, N]
        decay = jnp.exp(dt_t[:, :, None] * a[None])
        h = decay * h + (dt_t * u_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c_t)

    h0 = jnp.zeros((u.shape[0], u.shape[2], a.shape[1]), f32)
    _, y = lax.scan(step, h0, tuple(
        t.swapaxes(0, 1) for t in (u, dt, b, c)))
    return y.swapaxes(0, 1) + d * u


def _column(ref, t, eye):
    """Row ``t`` of a ``[1, chunk, N]`` block (states on lanes) as an
    ``[N, 1]`` column (states on sublanes)."""
    n = eye.shape[0]
    row = jnp.broadcast_to(ref[0, pl.ds(t, 1), :], (n, n))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _eye(n):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _ssm_fwd_kernel(u_ref, dt_ref, at_ref, b_ref, c_ref,  # inputs
                    y_ref, start_ref,  # outputs
                    h_scratch, *, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scratch[:] = jnp.zeros_like(h_scratch)

    start_ref[0, 0] = h_scratch[:]  # what this chunk starts from
    at = at_ref[:]  # [N, Cb]
    eye = _eye(at.shape[0])

    def step(t, h):
        dt_row = dt_ref[0, pl.ds(t, 1), :]  # [1, Cb]
        dtu = dt_row * u_ref[0, pl.ds(t, 1), :]
        h = jnp.exp(dt_row * at) * h + dtu * _column(b_ref, t, eye)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(
            h * _column(c_ref, t, eye), axis=0, keepdims=True)
        return h

    h_scratch[:] = lax.fori_loop(0, chunk, step, h_scratch[:])


def _ssm_bwd_kernel(u_ref, dt_ref, at_ref, b_ref, c_ref, start_ref,
                    dy_ref,  # inputs
                    du_ref, ddt_ref, dat_ref, db_ref, dc_ref,  # outputs
                    g_scratch, dat_scratch, hist, pb, pc, *,
                    chunk: int, lanes: int):
    @pl.when(pl.program_id(2) == 0)  # the row's last chunk
    def _init():
        g_scratch[:] = jnp.zeros_like(g_scratch)
        dat_scratch[:] = jnp.zeros_like(dat_scratch)

    at = at_ref[:]  # [N, Cb]
    n, cb = at.shape
    eye = _eye(n)

    def fold(x):  # [N, Cb] -> [N, lanes]: the channel groups added
        out = x[:, :lanes]
        for g in range(1, cb // lanes):
            out = out + x[:, g * lanes:(g + 1) * lanes]
        return out

    # replay the chunk's states: hist[t] is h before step t
    hist[0] = start_ref[0, 0]

    def replay(t, h):
        dt_row = dt_ref[0, pl.ds(t, 1), :]
        dtu = dt_row * u_ref[0, pl.ds(t, 1), :]
        h = jnp.exp(dt_row * at) * h + dtu * _column(b_ref, t, eye)
        hist[t + 1] = h
        return h

    lax.fori_loop(0, chunk, replay, hist[0])

    def back(i, carry):
        # ``later`` is dL/dh_t through h_{t+1}: decay_{t+1} * g_{t+1}
        later, dat = carry
        t = chunk - 1 - i
        dt_row = dt_ref[0, pl.ds(t, 1), :]
        u_row = u_ref[0, pl.ds(t, 1), :]
        dy_row = dy_ref[0, pl.ds(t, 1), :]
        g = dy_row * _column(c_ref, t, eye) + later  # dL/dh_t
        pc[t] = fold(dy_row * hist[t + 1])
        pb[t] = fold(g * (dt_row * u_row))
        ddtu = jnp.sum(g * _column(b_ref, t, eye), axis=0, keepdims=True)
        decay = jnp.exp(dt_row * at)
        ddecay = g * hist[t] * decay  # dL/d(dt A), [N, Cb]
        ddt_ref[0, pl.ds(t, 1), :] = u_row * ddtu + jnp.sum(
            ddecay * at, axis=0, keepdims=True)
        du_ref[0, pl.ds(t, 1), :] = dt_row * ddtu
        return decay * g, dat + ddecay * dt_row

    later, dat = lax.fori_loop(0, chunk, back,
                               (g_scratch[:], dat_scratch[:]))
    g_scratch[:] = later
    dat_scratch[:] = dat
    dat_ref[0] = dat  # the block stays put: its last write is the sum
    # one lane reduction a chunk; [chunk * N, 1] -> lanes, as the flash
    # kernels write their logsumexp
    db_ref[0, 0, 0, :] = jnp.sum(
        pb[:].reshape(chunk * n, lanes), axis=1, keepdims=True)[:, 0]
    dc_ref[0, 0, 0, :] = jnp.sum(
        pc[:].reshape(chunk * n, lanes), axis=1, keepdims=True)[:, 0]


def _fit_channels(requested: int, channels: int) -> int:
    """The largest multiple of 128 that divides ``channels`` and is at
    most ``requested``; all the channels where 128 does not divide them
    (a toy size: a block that covers the whole dim is always legal)."""
    if channels % LANES:
        return channels
    best = LANES
    for groups in range(1, channels // LANES + 1):
        if channels % (groups * LANES) == 0 and groups * LANES <= requested:
            best = groups * LANES
    return best


def _resolve_interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _params(semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics)


def _scan_forward(u, dt, at, b, c, chunk, block_c, interpret):
    batch, s, channels = u.shape
    n = at.shape[0]
    cb = _fit_channels(block_c, channels)
    chunks = s // chunk
    row = pl.BlockSpec((1, chunk, cb), lambda i, j, k: (i, k, j))
    state = pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, 0))
    return pl.pallas_call(
        functools.partial(_ssm_fwd_kernel, chunk=chunk),
        grid=(batch, channels // cb, chunks),
        in_specs=[row, row, pl.BlockSpec((n, cb), lambda i, j, k: (0, j)),
                  state, state],
        out_specs=[row, pl.BlockSpec((1, 1, n, cb),
                                     lambda i, j, k: (i, k, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32),
                   jax.ShapeDtypeStruct((batch, chunks, n, channels),
                                        jnp.float32)],
        scratch_shapes=[_vmem((n, cb))],
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(u, dt, at, b, c)


def _scan_backward(u, dt, at, b, c, starts, dy, chunk, block_c, interpret):
    batch, s, channels = u.shape
    n = at.shape[0]
    cb = _fit_channels(block_c, channels)
    lanes = LANES if cb % LANES == 0 else cb
    chunks = s // chunk
    last = chunks - 1  # the chunks run last to first
    row = pl.BlockSpec((1, chunk, cb), lambda i, j, k: (i, last - k, j))
    state = pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, last - k, 0))
    by_block = pl.BlockSpec((1, 1, 1, chunk * n),
                            lambda i, j, k: (i, j, 0, last - k))
    partial = jax.ShapeDtypeStruct((batch, channels // cb, 1, s * n),
                                   jnp.float32)
    du, ddt, dat, db, dc = pl.pallas_call(
        functools.partial(_ssm_bwd_kernel, chunk=chunk, lanes=lanes),
        grid=(batch, channels // cb, chunks),
        in_specs=[row, row, pl.BlockSpec((n, cb), lambda i, j, k: (0, j)),
                  state, state,
                  pl.BlockSpec((1, 1, n, cb),
                               lambda i, j, k: (i, last - k, 0, j)),
                  row],
        out_specs=[row, row,
                   pl.BlockSpec((1, n, cb), lambda i, j, k: (i, 0, j)),
                   by_block, by_block],
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32),
                   jax.ShapeDtypeStruct(u.shape, jnp.float32),
                   jax.ShapeDtypeStruct((batch, n, channels), jnp.float32),
                   partial, partial],
        scratch_shapes=[_vmem((n, cb)), _vmem((n, cb)),
                        _vmem((chunk + 1, n, cb)),
                        _vmem((chunk, n, lanes)), _vmem((chunk, n, lanes))],
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(u, dt, at, b, c, starts, dy)
    db = db.sum(axis=1).reshape(batch, s, n)
    dc = dc.sum(axis=1).reshape(batch, s, n)
    return du, ddt, dat.sum(axis=0), db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(u, dt, at, b, c, chunk, block_c, interpret):
    return _scan_forward(u, dt, at, b, c, chunk, block_c, interpret)[0]


def _scan_fwd(u, dt, at, b, c, chunk, block_c, interpret):
    y, starts = _scan_forward(u, dt, at, b, c, chunk, block_c, interpret)
    return y, (u, dt, at, b, c, starts)


def _scan_bwd(chunk, block_c, interpret, residuals, dy):
    return _scan_backward(*residuals, dy, chunk, block_c, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(
    u: jax.Array,  # [B, S, C]
    dt: jax.Array,  # [B, S, C], after the softplus
    a: jax.Array,  # [C, N], negative
    b: jax.Array,  # [B, S, N]
    c: jax.Array,  # [B, S, N]
    d: jax.Array,  # [C]
    chunk: int = 32,
    block_c: int = 2560,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``y`` [B, S, C] float32 of the recurrence in the module
    docstring, differentiable in all six arguments. ``chunk`` (a
    multiple of 8) is how many tokens the kernels hold in VMEM at a
    time; a row that is no multiple of it is padded with steps that
    leave the state as it is (``dt`` 0). ``block_c`` bounds the
    channels a grid step carries (``[N, block_c]`` float32 of state).
    The backward holds ``chunk + 1`` states of a block in VMEM, so the
    two trade against each other under the 16 MB a kernel may use; a
    step's time is its chain of dependent operations until the block
    is wide enough to fill the vector units (on the v5e, 8192 x 5120 x
    16 forward and backward: 25.7 ms at 128 x 640, 16.2 at 64 x 1280,
    13.5 at 32 x 2560, 13.8 at 16 x 5120; my chip runs, PR 29)."""
    if chunk % 8:
        raise ValueError(f"chunk {chunk} is not a multiple of 8")
    f32 = jnp.float32
    u, dt, b, c = (t.astype(f32) for t in (u, dt, b, c))
    s = u.shape[1]
    pad = -s % chunk
    padded = (u, dt, b, c)
    if pad:
        padded = tuple(jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                       for t in padded)
    y = _scan(padded[0], padded[1], a.astype(f32).T, padded[2], padded[3],
              chunk, block_c, _resolve_interpret(interpret))
    return y[:, :s] + d.astype(f32) * u


def selective_scan_auto(u, dt, a, b, c, d, chunk: int = 32,
                        block_c: int = 2560,
                        interpret: Optional[bool] = None) -> jax.Array:
    """``selective_scan`` under whatever mesh is ambient: GSPMD cannot
    partition a Mosaic call, so under a mesh the kernels run in a
    ``shard_map`` with the batch on the data axes and the channels, the
    one axis ``tensor`` may split, on ``tensor``; ``b`` and ``c`` are
    whole on every shard and their gradients are summed over it."""
    from jax.sharding import PartitionSpec as P

    mesh = ambient_shard_mesh()
    if mesh is None:
        return selective_scan(u, dt, a, b, c, d, chunk, block_c, interpret)
    rows = P(("data", "fsdp"), None, "tensor")
    whole = P(("data", "fsdp"), None, None)
    return jax.shard_map(
        lambda *args: selective_scan(*args, chunk, block_c, interpret),
        mesh=mesh,
        in_specs=(rows, rows, P("tensor", None), whole, whole,
                  P("tensor")),
        out_specs=rows,
        check_vma=False,  # a pallas_call output carries no vma
    )(u, dt, a, b, c, d)
