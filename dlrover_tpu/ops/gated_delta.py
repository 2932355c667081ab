"""The gated delta rule (Gated DeltaNet's linear attention) as one
differentiable op.

Per batch row and head, with a matrix state ``H`` of ``[dk, dv]`` (the
transpose of the ``S`` of the papers), ``alpha_t = exp(g_t)`` in
``(0, 1]`` and ``beta_t`` in ``(0, 2)``::

    H_t = alpha_t (I - beta_t k_t k_t^T) H_{t-1} + beta_t k_t v_t^T
    o_t = H_t^T q_t

i.e. decay, erase along ``k_t``, then write ``v_t`` there. Token by
token that is ``S`` dependent steps a row. The program computes the
chunked (WY) form: over a chunk of ``C`` tokens, with ``G_i`` the sum
of ``g`` up to token ``i`` of the chunk, ``gamma = exp(G)``, and
``H0`` the state the chunk starts from::

    A[i, j] = beta_i exp(G_i - G_j) (k_i . k_j)   j < i, else 0
    T       = (I + A)^-1                          unit lower triangular
    Ubar    = T diag(beta) V          W  = T diag(beta gamma) K
    P       = M * (Q K^T),  M[i, j] = exp(G_i - G_j)  j <= i, else 0
    Qg      = diag(gamma) Q           Kd = diag(gamma_C / gamma) K
    ----------------------------------------------------------------
    U       = Ubar - W H0
    O       = Qg H0 + P U
    H_C     = gamma_C H0 + Kd^T U

Every ratio is ``exp(G_i - G_j)`` with ``j <= i``: nothing overflows.
What stands above the line is local to a chunk: float32 for ``g``, its
sums, the ratios, ``beta`` and the inverse, operands in the inputs'
dtype into the MXU with float32 accumulation. The inverse is exact
block substitution by doubling (a block-diagonal inverse of block ``s``
gives the one of ``2s`` in two ``[C, C]`` products), so it is as stable
as forward substitution and lane-dense; a Neumann product over the
whole chunk is not, since ``beta`` up to 2 lets the powers of ``A``
grow where ``T`` stays bounded. The three lines below it are the chain:
``S / C`` dependent steps, each three small matmuls against a float32
state. What runs where:

* **The forward pass: one kernel**, ``gdn_rule_fwd``
  (``gdn_forward``). Grid ``(batch, head blocks, chunks)``, the chunks
  innermost and sequential, the state of a block's heads (``[dk, dv]``
  float32 each) in VMEM scratch. A grid step reads one chunk of q, k
  and v a head (``[B, H, N, C, d]``: a block's last dimension is the
  array's own, since keys of 96 and values of 192 are no lane tiles
  and a head cut out of the layer's ``[B, S, H d]`` would be a
  relayout in the kernel; the transposes to and from that layout are
  XLA's) and of ``g`` and ``beta`` as the layer has them (``[B, S,
  H]``, a head's column by a mask on the lanes), computes in VMEM what
  stands above the line (``_rule_chunk``: the sums of ``g`` on the VPU
  as a row against a triangular mask, the ratio ONE number a pair
  outside the contraction, so the pairs are plain products in the
  inputs' dtype with no sub-chunks, ``A``, its inverse by doubling in
  float32 as six-piece bf16 products, ``Ubar``, ``W``, ``P``, ``Qg``,
  ``Kd``, each rounded where ``_prepare`` rounds it), chains it and
  writes ``O``; the final state at the last chunk. Nothing prepared and
  no chunk start state reaches HBM; every stage runs for all the
  block's heads at once.
* **The backward: two kernels** on the same grid and layout, all the
  layer's heads in one call each (``gdn_backward``). The states pass,
  ``gdn_rule_starts``, is the forward kernel without the queries: it
  writes the float32 state each chunk starts from and the chunk's
  inverse ``T``, the two things the backward cannot prepare again
  cheaply, and nothing else. The backward pass, ``gdn_rule_bwd``,
  walks the chunks LAST TO FIRST with ``dH`` carried in VMEM: a grid
  step prepares the chunk again (``_rule_chunk`` with ``T`` handed in:
  the same stages, the same roundings, no doubling), runs the chain's
  derivative (``_gdn_bwd_kernel``'s products) and then the
  preparation's own by hand (``_rule_chunk_bwd``): ``T^T dUbar``,
  ``T^T dW`` and ``dT``; the inverse's ``dA = -T^T dT T^T`` kept
  strictly lower, in six-piece float32 products; the pairs' (the
  gradients of ``k k^T`` and of ``q k^T`` with the ratio on them, a
  row's share and a column's in one product each); ``g``'s without a
  product of its own, ``dG_i = q_i . dq_i + k_i . (dk_i's terms that
  carry exp(G) less those that carry exp(-G))``, and ``dg`` the sums of
  ``dG`` from a row to the chunk's end. It writes the gradients of q,
  k, v (their dtype) a head and chunk and those of ``g`` and ``beta``
  (float32) a head block. Nothing of the rule is XLA's.
* **The two steps** (``gated_delta_rule``): the differentiable op with
  the state handed in and out, which no model's program calls (PR 66):
  the oracle of the kernels above and, with ``use_kernels=False``, the
  CPU path. The preparation is plain ``jax.numpy`` batched over ``batch
  x heads x chunks`` that XLA differentiates (``_prepare``, scope
  ``gdn_chunk``; the inverse with its own gradient). The chain is a
  pair of Pallas kernels named ``gdn_fwd`` and ``gdn_bwd`` under one
  ``jax.custom_vjp``, on the same grid: ``gdn_fwd`` also writes the
  state each chunk starts from as the residual; ``gdn_bwd`` walks the
  chunks last to first with ``dH`` carried in VMEM and returns the
  gradients of ``Qg``, ``Kd``, ``W``, ``Ubar``, ``P`` and the chunk's
  decay (which reaches the kernels as a ``[1, dv]`` row, the scalar
  ``gamma_C`` repeated: a row multiplies the state without a scalar
  load); those of ``q``, ``k``, ``v``, ``g`` and ``beta`` follow
  through ``_prepare`` by autodiff.

``gated_delta_rule_grouped``, what a layer calls, joins the forward
kernel and the backward's two under a ``jax.custom_vjp`` whose
residuals are the op's inputs, and names its output (``KEPT_NAMES``).
The three kernels' calls go through one shared ``jax.jit`` a kernel and
shape (``ops.trace_once.shared_call``).

The chunk and the heads a program are ``chain_tiles``'s, from the
shape. Off the TPU the same kernels run in the Pallas interpreter;
``use_kernels=False`` runs the chain as a ``lax.scan`` over chunks (the
path the CPU tests differentiate by autodiff and hold the kernels to),
and ``gated_delta_rule_reference`` is the token-by-token recurrence.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from dlrover_tpu.ops.flash_attention import _vmem, ambient_shard_mesh
from dlrover_tpu.ops.selective_scan import _params, _resolve_interpret
from dlrover_tpu.ops.trace_once import shared_call
from dlrover_tpu.telemetry.names import DeviceScope

F32 = jnp.float32
# what ``gated_delta_rule_grouped`` names its output (``jax.
# ad_checkpoint.checkpoint_name``): a layer's checkpoint that keeps it
# (``ops.remat.apply_remat(layer, policy, keep=KEPT_NAMES)``) does not
# run the rule's forward again in its replay. The op's residuals are
# its INPUTS, so with the output kept nothing of the replayed forward
# is read and the compiler drops it: a step runs ``gdn_rule_fwd`` once
# a layer (the forward pass) and ``gdn_rule_starts`` and
# ``gdn_rule_bwd`` once (the backward), none a second time in the
# layer's replay. [B, S, H, dv] in the compute dtype a layer
KEPT_NAMES = ("gdn_out",)


def gated_delta_rule_reference(q, k, v, g, beta, initial_state=None):
    """The recurrence token by token (``lax.scan`` over the row), in
    float32: ``q``, ``k`` [B, S, H, dk]; ``v`` [B, S, H, dv]; ``g``
    (the log of the decay, <= 0) and ``beta`` [B, S, H]. Returns
    ``(o [B, S, H, dv], the final state [B, H, dk, dv])``. The oracle
    of the tests."""
    q, k, v, g, beta = (t.astype(F32) for t in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    dv = v.shape[-1]
    hp = lax.Precision.HIGHEST

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [B, H, .]
        state = jnp.exp(g_t)[..., None, None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=hp)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - seen),
            precision=hp)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state, precision=hp)

    h0 = (jnp.zeros((b, h, dk, dv), F32) if initial_state is None
          else initial_state.astype(F32))
    final, o = lax.scan(step, h0, tuple(
        t.swapaxes(0, 1) for t in (q, k, v, g, beta)))
    return o.swapaxes(0, 1), final


# -- the chunk-local preparation ---------------------------------------------


def _doubling_inverse(a):
    c = a.shape[-1]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    t = jnp.broadcast_to(jnp.eye(c, dtype=F32), a.shape)
    s = 1
    while s < c:
        # rows of the lower half of a 2s block, columns of its upper
        below = (i // (2 * s) == j // (2 * s)) & (i // s > j // s)
        off = jnp.where(below, a, 0.0)
        if s == 1:
            t = t - off  # T_1 = I on both sides
        else:
            t = t - jnp.matmul(jnp.matmul(t, off, precision="highest"), t,
                               precision="highest")
        s *= 2
    return t


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower triangular ``a`` [..., C, C]
    (``C`` a power of two), float32: block substitution by doubling.
    With ``T_s`` the inverse of the diagonal blocks of size ``s``,
    ``T_2s = T_s - T_s (a on the sub-diagonal blocks of each 2s block)
    T_s``; ``T_1 = I``. Its gradient is the inverse's own, ``-T^T dT
    T^T`` (the caller's mask keeps the strictly lower part), so the
    backward keeps ``T`` and not every level of the doubling."""
    return _doubling_inverse(a)


def _inverse_fwd(a):
    t = _doubling_inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(jnp.matmul(tt, dt, precision="highest"), tt,
                        precision="highest"),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _prepare(q, k, v, g, beta):
    """What is local to a chunk. ``q``, ``k`` [B, H, N, C, dk]; ``v``
    [B, H, N, C, dv]; ``g``, ``beta`` [B, H, N, C] float32. Returns
    ``(Qg, Kd, W, Ubar, P, decay)``, the first five in ``q``'s dtype
    and ``decay`` = ``gamma_C`` [B, H, N] float32."""
    cd = q.dtype
    c = q.shape[-2]
    big_g = jnp.cumsum(g, axis=-1)
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # masked before the exponential: above the diagonal the difference
    # is positive and may overflow
    ratio = jnp.exp(jnp.where(
        j <= i, big_g[..., :, None] - big_g[..., None, :], -jnp.inf))
    gamma = jnp.exp(big_g)
    to_end = jnp.exp(big_g[..., -1:] - big_g)

    def pairs(x, y):  # x y^T over the chunk
        return jnp.einsum("...ik,...jk->...ij", x, y,
                          preferred_element_type=F32)

    a = jnp.where(j < i, beta[..., :, None] * ratio * pairs(k, k), 0.0)
    t = _unit_lower_inverse(a).astype(cd)

    def solve(rows):  # T rows
        return jnp.einsum("...ij,...jd->...id", t, rows.astype(cd),
                          preferred_element_type=F32).astype(cd)

    kf, vf = k.astype(F32), v.astype(F32)
    ubar = solve(beta[..., None] * vf)
    w = solve((beta * gamma)[..., None] * kf)
    p = (ratio * pairs(q, k)).astype(cd)
    qg = (gamma[..., None] * q.astype(F32)).astype(cd)
    kd = (to_end[..., None] * kf).astype(cd)
    return qg, kd, w, ubar, p, gamma[..., -1]


# -- the chain ----------------------------------------------------------------


def _dot(x, y, contract):
    return lax.dot_general(x, y, ((contract[0], contract[1]), ((), ())),
                           preferred_element_type=F32)


_NN = ((1,), (0,))  # x y
_NT = ((1,), (1,))  # x y^T
_TN = ((0,), (0,))  # x^T y


def _chain_step(h, qg, kd, w, ubar, p, decay):
    """One chunk of one head: ``(O, the next state)`` from the state
    ``h`` [dk, dv] float32 the chunk starts from. ``decay`` is
    ``[1, dv]`` (or a scalar). The forward kernel and the scan both
    run it."""
    cd = w.dtype
    hc = h.astype(cd)
    u = ubar.astype(F32) - _dot(w, hc, _NN)
    uc = u.astype(cd)
    o = _dot(qg, hc, _NN) + _dot(p, uc, _NN)
    return o, decay * h + _dot(kd, uc, _TN)


def _chain_scan(qg, kd, w, ubar, p, decay, h0):
    """The chain as a ``lax.scan`` over the chunks. Operands
    [B, H, N, C, .], ``decay`` [B, H, N], ``h0`` [B, H, dk, dv]
    float32. Returns ``(O [B, H, N, C, dv] float32, the final
    state)``."""
    step = jax.vmap(jax.vmap(_chain_step))  # over batch, heads

    def body(h, xs):
        o, h = step(h, *xs)
        return h, o

    final, o = lax.scan(body, h0, tuple(
        jnp.moveaxis(t, 2, 0) for t in (qg, kd, w, ubar, p, decay)))
    return jnp.moveaxis(o, 0, 2), final


def _gdn_fwd_kernel(qg_ref, kd_ref, w_ref, ubar_ref, p_ref, decay_ref,
                    h0_ref,  # inputs
                    o_ref, start_ref, final_ref,  # outputs
                    h_scratch, *, heads: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        h_scratch[:] = h0_ref[0]

    for j in range(heads):
        h = h_scratch[j]
        start_ref[0, j, 0] = h  # what this chunk starts from
        o, h = _chain_step(h, qg_ref[0, j, 0], kd_ref[0, j, 0],
                           w_ref[0, j, 0], ubar_ref[0, j, 0],
                           p_ref[0, j, 0], decay_ref[0, j, 0])
        o_ref[0, j, 0] = o.astype(o_ref.dtype)
        h_scratch[j] = h

    @pl.when(n == pl.num_programs(2) - 1)
    def _final():
        final_ref[0] = h_scratch[:]


def _gdn_bwd_kernel(qg_ref, kd_ref, w_ref, ubar_ref, p_ref, decay_ref,
                    start_ref, do_ref, dfinal_ref,  # inputs
                    dqg_ref, dkd_ref, dw_ref, dubar_ref, dp_ref,
                    ddecay_ref, dh0_ref,  # outputs
                    dh_scratch, *, heads: int):
    n = pl.program_id(2)  # the chunks run last to first

    @pl.when(n == 0)
    def _init():
        dh_scratch[:] = dfinal_ref[0]

    for j in range(heads):
        qg, kd, w, p = (r[0, j, 0] for r in (qg_ref, kd_ref, w_ref, p_ref))
        cd = w.dtype
        decay = decay_ref[0, j, 0]  # [1, dv]
        h = start_ref[0, j, 0]  # [dk, dv] float32
        hc = h.astype(cd)
        uc = (ubar_ref[0, j, 0].astype(F32) - _dot(w, hc, _NN)).astype(cd)
        do = do_ref[0, j, 0].astype(cd)
        dh = dh_scratch[j]  # dL/d(the state the chunk ends in)
        dhc = dh.astype(cd)
        du = _dot(p, do, _TN) + _dot(kd, dhc, _NN)  # [C, dv]
        duc = du.astype(cd)
        dqg_ref[0, j, 0] = _dot(do, hc, _NT).astype(dqg_ref.dtype)
        dp_ref[0, j, 0] = _dot(do, uc, _NT).astype(dp_ref.dtype)
        dkd_ref[0, j, 0] = _dot(uc, dhc, _NT).astype(dkd_ref.dtype)
        dubar_ref[0, j, 0] = duc.astype(dubar_ref.dtype)
        dw_ref[0, j, 0] = (-_dot(duc, hc, _NT)).astype(dw_ref.dtype)
        ddecay_ref[0, j, 0] = jnp.sum(dh * h, axis=0, keepdims=True)
        dh_scratch[j] = (decay * dh + _dot(qg, do, _TN)
                         - _dot(w, duc, _TN))

    @pl.when(n == pl.num_programs(2) - 1)
    def _first():
        dh0_ref[0] = dh_scratch[:]


def _specs(operands, hb, order):
    """Block specs of per-chunk operands [B, H, N, rows, cols]: a
    group of ``hb`` heads, one chunk; ``order`` maps the grid's chunk
    index to the chunk."""
    return [pl.BlockSpec((1, hb, 1) + t.shape[3:],
                         lambda b, hg, n: (b, hg, order(n), 0, 0))
            for t in operands]


def _state_spec(hb, dk, dv):
    return pl.BlockSpec((1, hb, dk, dv), lambda b, hg, n: (b, hg, 0, 0))


def _chain_forward(qg, kd, w, ubar, p, decay, h0, hb, interpret):
    b, h, n, c, dk = qg.shape
    dv = ubar.shape[-1]
    operands = (qg, kd, w, ubar, p, decay)
    o_shape = jax.ShapeDtypeStruct((b, h, n, c, dv), qg.dtype)
    starts = jax.ShapeDtypeStruct((b, h, n, dk, dv), F32)
    final = jax.ShapeDtypeStruct((b, h, dk, dv), F32)
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, heads=hb),
        grid=(b, h // hb, n),
        in_specs=_specs(operands, hb, lambda i: i)
        + [_state_spec(hb, dk, dv)],
        out_specs=_specs((o_shape, starts), hb, lambda i: i)
        + [_state_spec(hb, dk, dv)],
        out_shape=[o_shape, starts, final],
        scratch_shapes=[_vmem((hb, dk, dv))],
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_fwd",
    )(*operands, h0)


def _chain_backward(qg, kd, w, ubar, p, decay, starts, do, dfinal, hb,
                    interpret):
    b, h, n, c, dk = qg.shape
    dv = ubar.shape[-1]
    operands = (qg, kd, w, ubar, p, decay, starts, do)
    grads = [jax.ShapeDtypeStruct(t.shape, t.dtype)
             for t in (qg, kd, w, ubar, p, decay)]
    last = n - 1
    return pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, heads=hb),
        grid=(b, h // hb, n),
        in_specs=_specs(operands, hb, lambda i: last - i)
        + [_state_spec(hb, dk, dv)],
        out_specs=_specs(grads, hb, lambda i: last - i)
        + [_state_spec(hb, dk, dv)],
        out_shape=grads + [jax.ShapeDtypeStruct((b, h, dk, dv), F32)],
        scratch_shapes=[_vmem((hb, dk, dv))],
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_bwd",
    )(*operands, dfinal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _chain(qg, kd, w, ubar, p, decay, h0, hb, interpret):
    """The chain through the kernels; ``decay`` is [B, H, N, 1, dv]."""
    o, _, final = _chain_forward(qg, kd, w, ubar, p, decay, h0, hb,
                                 interpret)
    return o, final


def _chain_fwd(qg, kd, w, ubar, p, decay, h0, hb, interpret):
    o, starts, final = _chain_forward(qg, kd, w, ubar, p, decay, h0, hb,
                                      interpret)
    return (o, final), (qg, kd, w, ubar, p, decay, starts)


def _chain_bwd(hb, interpret, residuals, cotangents):
    do, dfinal = cotangents
    return tuple(_chain_backward(*residuals, do, dfinal, hb, interpret))


_chain.defvjp(_chain_fwd, _chain_bwd)


# -- the whole rule as three kernels ------------------------------------------
# a chunk is prepared in VMEM and chained there, forward and backward:
# nothing prepared reaches HBM


def _pieces(x):
    """A float32 tile as three bf16 tiles whose sum it is (8 bits of
    mantissa each, 24 together: every bit of a float32 in bf16's
    range)."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(F32)).astype(jnp.bfloat16)


# the pairs of pieces XLA's ``highest`` multiplies on this chip
# (bf16_6x), the smallest first: lo x mid, mid x lo and lo x lo, under
# 2^-32 of the result, are left out
_SIX = ((1, 1), (2, 0), (0, 2), (1, 0), (0, 1), (0, 0))


def _dots(x, y, contract):
    """``_dot`` a head: ``x`` and ``y`` [heads, rows, columns]."""
    return lax.dot_general(
        x, y, (((contract[0][0] + 1,), (contract[1][0] + 1,)), ((0,), (0,))),
        preferred_element_type=F32)


def _dots_f32(a, b, contract):
    """The float32 products a head of two stacks of tiles given as
    ``_pieces``: the six products of ``_SIX`` as ONE, the pieces side
    by side along the contraction, so that the MXU adds all six in its
    float32 accumulator and the result is read once
    (``precision=highest`` issues six products and adds their results
    on the VPU: the whole kernel 2.83 ms against 2.14, 2 x 8192 x 8
    heads; my chip runs, PR 63, TPU v5 lite)."""
    lhs = jnp.concatenate([a[x] for x, _ in _SIX], axis=contract[0][0] + 1)
    rhs = jnp.concatenate([b[y] for _, y in _SIX], axis=contract[1][0] + 1)
    return _dots(lhs, rhs, contract)


def _head_columns(tile, heads):
    """The program's heads' columns [heads, C, 1] of the layer's
    [C, H] tile (``g`` or ``beta``): a head's column by a mask on the
    lanes."""
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    first = pl.program_id(1) * heads
    return jnp.stack([
        jnp.sum(jnp.where(lane == first + h, tile, 0.0), axis=1,
                keepdims=True) for h in range(heads)])


def _head_lanes(columns, width):
    """[heads, C, 1] columns side by side, a [C, width] tile: the
    inverse of ``_head_columns`` on a head block's own tile."""
    lane = lax.broadcasted_iota(jnp.int32, (columns.shape[1], width), 1)
    return sum(jnp.where(lane == h, column, 0.0)
               for h, column in enumerate(columns))


def _pair_indices(c):
    """``(i, j)``: the row and the column of a [C, C] tile's pairs."""
    return (lax.broadcasted_iota(jnp.int32, (c, c), 0),
            lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _turned(columns):
    """[heads, C, 1] columns as [heads, 1, C] rows, to the bit: each
    value alone on its diagonal, summed down."""
    i, j = _pair_indices(columns.shape[1])
    return jnp.sum(jnp.where(i == j, columns, 0.0), axis=1, keepdims=True)


def _sums(columns, upward=False):
    """The sums of [heads, C, 1] columns down the chunk (``upward``:
    from a row to the chunk's end), float32 on the VPU: the scalar a
    token is a row against a triangular mask, no product."""
    i, j = _pair_indices(columns.shape[1])
    return jnp.sum(jnp.where((j >= i) if upward else (j <= i),
                             _turned(columns), 0.0), axis=2, keepdims=True)


def _sum_of_tiles(x):
    """[heads, rows, columns] -> [heads, 1, 1]: a tile's sum."""
    return jnp.sum(jnp.sum(x, axis=2, keepdims=True), axis=1, keepdims=True)


def _rule_chunk(q, k, v, g, beta, t=None):
    """One chunk of a block of heads prepared in VMEM: ``_prepare``'s
    formulas at its precisions on whole tiles, every stage for all the
    block's heads at once. ``q`` (or None: nothing of the queries' is
    prepared), ``k`` [heads, C, dk] and ``v`` [heads, C, dv] in the
    inputs' dtype; ``g`` and ``beta`` [heads, C, 1] float32; ``t``
    [heads, C, C] float32 the inverse where the caller has it. The
    ratio ``exp(G_i - G_j)`` is one number a pair OUTSIDE the
    contraction, so the pairs are plain products in the inputs' dtype;
    the inverse alone is float32 (``ops.kda._chunk_inverse``: this
    file's doubling in six-piece products)."""
    from dlrover_tpu.ops.kda import _chunk_inverse  # it imports this file

    cd = k.dtype
    c = k.shape[1]
    kf, vf = k.astype(F32), v.astype(F32)
    i, j = _pair_indices(c)
    big_g = _sums(g)
    # masked before the exponential: above the diagonal the difference
    # is positive and may overflow
    ratio = jnp.exp(jnp.where(j <= i, big_g - _turned(big_g), -jnp.inf))
    gamma = jnp.exp(big_g)
    to_end = jnp.exp(big_g[:, c - 1:] - big_g)
    # the rows of the keys, then the queries', over those of the keys:
    # one product
    rows = k if q is None else jnp.concatenate([k, q], axis=1)
    pairs = _dots(rows, k, _NT)
    pairs_k = pairs[:, :c]
    if t is None:
        t = _chunk_inverse(jnp.where(j < i, beta * ratio * pairs_k, 0.0))
    ch = SimpleNamespace(
        kf=kf, vf=vf, rows=rows, ratio=ratio, pairs_k=pairs_k, t32=t,
        t=t.astype(cd),
        gamma=gamma, to_end=to_end, decay=gamma[:, c - 1:],
        bv=(beta * vf).astype(cd), bgk=(beta * gamma * kf).astype(cd),
        kd=(to_end * kf).astype(cd))
    ch.ubar = _dots(ch.t, ch.bv, _NN).astype(cd)
    ch.w = _dots(ch.t, ch.bgk, _NN).astype(cd)
    if q is not None:
        ch.p = (ratio * pairs[:, c:]).astype(cd)
        ch.qg = (gamma * q.astype(F32)).astype(cd)
    return ch


def _chunk_reads(h, ch):
    """The chain's first line for a block of heads: the states ``h``
    [heads, dk, dv] float32 the chunk starts from in the operands'
    dtype, and ``U`` [heads, C, dv] in it."""
    hc = h.astype(ch.w.dtype)
    return hc, (ch.ubar.astype(F32) - _dots(ch.w, hc, _NN)).astype(hc.dtype)


def _chunk_next(h, uc, ch):
    """The third line: the states the chunk ends in."""
    return ch.decay * h + _dots(ch.kd, uc, _TN)


def _rule_chunk_bwd(q, k, v, g, beta, do, start, t, dh):
    """The chunk's backward in VMEM, all the block's heads at once:
    the chunk prepared again (``_rule_chunk`` with the inverse handed
    in), ``_gdn_bwd_kernel``'s products from the states ``start``
    [heads, dk, dv] float32 the chunk starts from and ``dh`` the
    gradient of those it ends in, then the preparation's own derivative
    by hand. ``do`` [heads, C, dv] in the inputs' dtype. Returns the
    gradients of ``q``, ``k``, ``v``, ``g`` and ``beta`` (the last two
    [heads, C, 1]), float32, and the gradient of ``start``."""
    cd = k.dtype
    c = k.shape[1]
    ch = _rule_chunk(q, k, v, g, beta, t)
    qf, kf, gamma = q.astype(F32), ch.kf, ch.gamma
    i, j = _pair_indices(c)
    # the chain's
    hc, uc = _chunk_reads(start, ch)
    dhc = dh.astype(cd)
    duc = (_dots(ch.p, do, _TN) + _dots(ch.kd, dhc, _NN)).astype(cd)
    dqg = _dots(do, hc, _NT)
    dp = _dots(do, uc, _NT)
    dkd = ch.to_end * _dots(uc, dhc, _NT)
    dwc = (-_dots(duc, hc, _NT)).astype(cd)
    ddecay = _sum_of_tiles(dh * start)
    dh = ch.decay * dh + _dots(ch.qg, do, _TN) - _dots(ch.w, duc, _TN)
    # Ubar = T (beta V) and W = T (beta gamma K)
    dbv = _dots(ch.t, duc, _TN)
    dbgk = _dots(ch.t, dwc, _TN)
    dt = _dots(duc, ch.bv, _NT) + _dots(dwc, ch.bgk, _NT)
    # the inverse's own (``_inverse_bwd``): -T^T dT T^T, strictly lower
    t_pieces = _pieces(ch.t32)
    da = jnp.where(j < i, -_dots_f32(
        _pieces(_dots_f32(t_pieces, _pieces(dt), _TN)), t_pieces, _NT), 0.0)
    # the pairs': the gradients of k k^T (through A) over those of
    # q k^T (through P), both with the ratio on them and 0 above the
    # diagonal; a row's share, then a column's
    da_ratio = ch.ratio * da
    m = jnp.concatenate([beta * da_ratio, ch.ratio * dp], axis=1).astype(cd)
    rows = _dots(m, k, _NN)
    dk_cols = _dots(m, ch.rows, _TN)
    dq = rows[:, c:] + gamma * dqg
    # dk's terms by the sign with which the gate's sums feel them: W
    # and the pairs' rows carry exp(G), the pairs' columns and Kd
    # exp(-G)
    dk_up = rows[:, :c] + beta * gamma * dbgk
    dk_down = dk_cols + dkd
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    dbig_g = jnp.sum(qf * dq + kf * (dk_up - dk_down), axis=2,
                     keepdims=True) + jnp.where(
        row == c - 1, _sum_of_tiles(kf * dkd) + ch.decay * ddecay, 0.0)
    dbeta = (jnp.sum(da_ratio * ch.pairs_k, axis=2, keepdims=True)
             + jnp.sum(dbv * ch.vf, axis=2, keepdims=True)
             + jnp.sum(dbgk * gamma * kf, axis=2, keepdims=True))
    # a sum of g is felt by every g up to its row: the sums of its
    # gradient from a row to the chunk's end
    return (dq, dk_up + dk_down, beta * dbv, _sums(dbig_g, upward=True),
            dbeta, dh)


def _chunk_stacks(heads, g_ref, beta_ref, *refs):
    """A program's chunk: ``refs``' [heads, C, columns] stacks, then
    ``g`` and ``beta`` a head."""
    return (*(ref[0, :, 0] for ref in refs),
            _head_columns(g_ref[0], heads), _head_columns(beta_ref[0], heads))


def _gdn_rule_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, h0_ref,
                         o_ref, final_ref,  # outputs
                         h_scratch, *, heads: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        h_scratch[:] = h0_ref[0]

    ch = _rule_chunk(*_chunk_stacks(heads, g_ref, beta_ref, q_ref, k_ref,
                                    v_ref))
    h = h_scratch[:]
    hc, uc = _chunk_reads(h, ch)
    o_ref[0, :, 0] = (_dots(ch.qg, hc, _NN)
                      + _dots(ch.p, uc, _NN)).astype(o_ref.dtype)
    h_scratch[:] = _chunk_next(h, uc, ch)

    @pl.when(n == pl.num_programs(2) - 1)
    def _final():
        final_ref[0] = h_scratch[:]


def _gdn_rule_starts_kernel(k_ref, v_ref, g_ref, beta_ref,
                            start_ref, t_ref,  # outputs
                            h_scratch, *, heads: int):
    """``_gdn_rule_fwd_kernel`` without the queries, from no state: in
    place of ``o`` the state each chunk starts from and the chunk's
    inverse, which is what the backward cannot prepare again at a
    forward's price (the state) or at a third of it (the doubling)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scratch[:] = jnp.zeros_like(h_scratch)

    ch = _rule_chunk(None, *_chunk_stacks(heads, g_ref, beta_ref, k_ref,
                                          v_ref))
    t_ref[0, :, 0] = ch.t32
    h = h_scratch[:]
    start_ref[0, :, 0] = h
    h_scratch[:] = _chunk_next(h, _chunk_reads(h, ch)[1], ch)


def _gdn_rule_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref,
                         start_ref, t_ref,  # inputs
                         dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                         dh_scratch, *, heads: int):
    @pl.when(pl.program_id(2) == 0)  # the chunks run last to first
    def _init():
        dh_scratch[:] = jnp.zeros_like(dh_scratch)

    dq, dk, dv, dg, dbeta, dh = _rule_chunk_bwd(
        *_chunk_stacks(heads, g_ref, beta_ref, q_ref, k_ref, v_ref),
        do_ref[0, :, 0], start_ref[0, :, 0], t_ref[0, :, 0], dh_scratch[:])
    dh_scratch[:] = dh
    for ref, grad in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv)):
        ref[0, :, 0] = grad.astype(ref.dtype)
    # a head's column of the block's own [C, heads] tile
    dg_ref[0, 0] = _head_lanes(dg, heads)
    dbeta_ref[0, 0] = _head_lanes(dbeta, heads)


def _scalar_spec(chunk, h, order=lambda n: n):
    """A chunk of the layer's ``g`` or ``beta`` [B, S, H], all heads."""
    return pl.BlockSpec((1, chunk, h), lambda b, hg, n: (b, order(n), 0))


def _rule_forward(q, k, v, g, beta, h0, hb, interpret):
    """The forward kernel on whole chunks: ``q``, ``k`` [B, H, N, C,
    dk] and ``v`` [B, H, N, C, dv] a head (a block's last dimension is
    the array's own: 96 and 192 are no lane tiles, see
    ``chain_tiles``), ``g`` and ``beta`` [B, N C, H] as the layer has
    them, ``h0`` [B, H, dk, dv]. Returns ``(o as v, the final
    state)``."""
    b, h, n, c, dk = q.shape
    dv = v.shape[-1]

    def build():
        return pl.pallas_call(
            functools.partial(_gdn_rule_fwd_kernel, heads=hb),
            grid=(b, h // hb, n),
            in_specs=_specs((q, k, v), hb, lambda i: i)
            + [_scalar_spec(c, h), _scalar_spec(c, h),
               _state_spec(hb, dk, dv)],
            out_specs=_specs((v,), hb, lambda i: i)
            + [_state_spec(hb, dk, dv)],
            out_shape=[jax.ShapeDtypeStruct(v.shape, q.dtype),
                       jax.ShapeDtypeStruct((b, h, dk, dv), F32)],
            scratch_shapes=[_vmem((hb, dk, dv))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="gdn_rule_fwd",
        )

    return shared_call("gdn_rule_fwd", DeviceScope.GDN, (hb, interpret),
                       (q, k, v, g, beta, h0), build)


def _rule_starts(k, v, g, beta, hb, interpret):
    """The states pass on ``_rule_forward``'s operands, from no state:
    ``(the state each chunk starts from [B, H, N, dk, dv], the chunks'
    inverses [B, H, N, C, C])``, float32."""
    b, h, n, c, dk = k.shape
    dv = v.shape[-1]
    outs = [jax.ShapeDtypeStruct((b, h, n, dk, dv), F32),
            jax.ShapeDtypeStruct((b, h, n, c, c), F32)]

    def build():
        return pl.pallas_call(
            functools.partial(_gdn_rule_starts_kernel, heads=hb),
            grid=(b, h // hb, n),
            in_specs=_specs((k, v), hb, lambda i: i)
            + [_scalar_spec(c, h), _scalar_spec(c, h)],
            out_specs=_specs(outs, hb, lambda i: i),
            out_shape=outs,
            scratch_shapes=[_vmem((hb, dk, dv))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="gdn_rule_starts",
        )

    return shared_call("gdn_rule_starts", DeviceScope.GDN, (hb, interpret),
                       (k, v, g, beta), build)


def _rule_backward(q, k, v, g, beta, do, starts, t, hb, interpret):
    """The backward pass on ``_rule_forward``'s operands, ``do`` as
    ``o`` and ``_rule_starts``'s two results: the gradients of ``q``,
    ``k``, ``v`` (their dtype and layout) and those of ``g`` and
    ``beta`` [B, H / hb, N C, hb] float32: a block of its own a head
    block, since two programs of a ``parallel`` axis must not share an
    output block."""
    b, h, n, c, _ = q.shape
    dk, dv = starts.shape[-2:]
    back = lambda i: n - 1 - i  # noqa: E731
    narrow = jax.ShapeDtypeStruct((b, h // hb, n * c, hb), F32)
    narrow_spec = pl.BlockSpec((1, 1, c, hb),
                               lambda i, hg, j: (i, hg, back(j), 0))

    def build():
        return pl.pallas_call(
            functools.partial(_gdn_rule_bwd_kernel, heads=hb),
            grid=(b, h // hb, n),
            in_specs=_specs((q, k, v), hb, back)
            + [_scalar_spec(c, h, back), _scalar_spec(c, h, back)]
            + _specs((do, starts, t), hb, back),
            out_specs=_specs((q, k, v), hb, back)
            + [narrow_spec, narrow_spec],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in (q, k, v)] + [narrow, narrow],
            scratch_shapes=[_vmem((hb, dk, dv))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="gdn_rule_bwd",
        )

    return shared_call("gdn_rule_bwd", DeviceScope.GDN, (hb, interpret),
                       (q, k, v, g, beta, do, starts, t), build)


def chain_tiles(seq: int, heads: int) -> Tuple[int, int]:
    """``(chunk, heads a program)`` of the chain for a row of ``seq``
    tokens and ``heads`` heads on this shard: a chunk of 64 (the
    largest power of two up to it that divides the row) and the largest
    divisor of the heads up to 10.

    The sweep behind it (my chip runs, PR 43, TPU v5 lite,
    ``benchmarks/gdn_bench.py``: 1 x 8192 x 30 heads of 96 / 192, bf16,
    ms a call): a grid step's fixed work is shared by its heads, so the
    chain's forward falls from 4.16 at one head a program to 2.91 at 5,
    2.80 at 6, 2.74 at 10 and 2.75 at 15 (forward and backward 7.70,
    5.48, 5.29, 5.12, 5.07; all 30 do not fit VMEM); at a chunk of 128
    the chain is faster (2.28 and 4.16 at 10; 15 no longer fits) but
    the preparation around it, whose inverse and ``[C, C]`` tiles grow
    with the chunk, costs more than that gains: the whole op forward
    and backward 23.11 at 64 x 10 against 24.49 at 128 x 10. At the 10
    heads of a head group: 6.45 at 64 x 10, 6.55 at 64 x 5, 6.80 at
    128 x 10.

    The whole rule's three kernels take the same tiles (my chip runs,
    PR 66, TPU v5 lite, the same bench and shape, ms a call in the
    Mosaic call alone from the device trace; q, k and v a head and
    chunk, the one layout tried: the chain kernels', known to lower at
    96 / 192). ``gdn_rule_fwd`` / ``gdn_rule_starts`` / ``gdn_rule_bwd``
    at 1, 2, 3, 5, 6, 10 heads a program: 9.09 / 8.71 / 5.74, 5.12 /
    4.85 / 4.00, 3.81 / 3.50 / 3.43, 3.22 / 2.94 / 2.79, 2.69 / 2.49 /
    2.69, **2.71 / 2.46 / 2.30** (7.47 together against 7.87 at 6, where
    an earlier call read the backward pass at 2.50); at
    15 the first two read 3.00 / 2.53 and the backward pass does not
    fit VMEM (20.4 MB of the 16 a kernel may use), at 30 none does. The
    op a layer calls, XLA's transposes to a head and chunk and back
    with it: 3.70 forward and 10.09 forward and backward, where the two
    steps are 12.05 and 24.25 (and ran their forward three times a
    step); the same three calls inside the olmohybrid step read 2.72 /
    2.46 / 2.30."""
    chunk = 64
    while chunk > 8 and seq % chunk:
        chunk //= 2
    group = max(d for d in range(1, min(heads, 10) + 1) if heads % d == 0)
    return chunk, group


def _tiles(s, h, chunk, heads_per_program):
    """``(chunk, heads a program, the row's padding)``: ``chain_tiles``'s
    unless the caller says, the row padded to whole chunks."""
    tile_c, tile_h = chain_tiles(s, h)
    chunk = chunk or tile_c
    hb = heads_per_program or tile_h
    if chunk & (chunk - 1) or h % hb:
        raise ValueError(f"chunk {chunk} is no power of two, or "
                         f"{hb} heads a program do not divide {h}")
    return chunk, hb, -s % chunk


def _start_state(initial_state, b, h, dk, dv):
    """The state [B, H, dk, dv] float32 a row starts from."""
    return (jnp.zeros((b, h, dk, dv), F32) if initial_state is None
            else initial_state.astype(F32))


def _padded(t, pad):
    """A row [B, S, ...] with ``pad`` tokens more that leave the state
    as it is (``g`` 0, ``beta`` 0)."""
    if not pad:
        return t
    return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))


def _chunks(t, chunk, pad):
    """[B, S, H, ...] -> [B, H, N, C, ...]."""
    t = jnp.moveaxis(_padded(t, pad), 2, 1)
    return t.reshape(t.shape[:2] + (-1, chunk) + t.shape[3:])


def _tokens(t, s):
    """[B, H, N, C, d] -> [B, S, H, d], the padding off."""
    b, h, n, c, d = t.shape
    return jnp.moveaxis(t.reshape(b, h, n * c, d), 1, 2)[:, :s]


def gated_delta_rule(
    q: jax.Array,  # [B, S, H, dk], l2-normalised and scaled by the caller
    k: jax.Array,  # [B, S, H, dk], l2-normalised
    v: jax.Array,  # [B, S, H, dv]
    g: jax.Array,  # [B, S, H], the log of the decay, <= 0
    beta: jax.Array,  # [B, S, H], in (0, 2)
    initial_state: Optional[jax.Array] = None,  # [B, H, dk, dv]
    use_kernels: bool = True,
    interpret: Optional[bool] = None,
    chunk: Optional[int] = None,
    heads_per_program: Optional[int] = None,
):
    """``(o [B, S, H, dv] in q's dtype, the final state [B, H, dk, dv]
    float32)`` of the recurrence in the module docstring,
    differentiable in ``q``, ``k``, ``v``, ``g``, ``beta`` and
    ``initial_state``: the preparation in XLA and the chain. ``chunk``
    (a power of two) and ``heads_per_program`` default to
    ``chain_tiles``'s; a row that is no multiple of the chunk is padded
    with tokens that leave the state as it is (``g`` 0, ``beta`` 0)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    chunk, hb, pad = _tiles(s, h, chunk, heads_per_program)
    chunks = functools.partial(_chunks, chunk=chunk, pad=pad)
    with jax.named_scope(DeviceScope.GDN_CHUNK):
        qg, kd, w, ubar, p, decay = _prepare(
            chunks(q), chunks(k), chunks(v.astype(q.dtype)),
            chunks(g.astype(F32)), chunks(beta.astype(F32)))
    h0 = _start_state(initial_state, b, h, dk, dv)
    if use_kernels:
        row = jnp.broadcast_to(decay[..., None, None], decay.shape + (1, dv))
        o, final = _chain(qg, kd, w, ubar, p, row, h0, hb,
                          _resolve_interpret(interpret))
    else:
        o, final = _chain_scan(qg, kd, w, ubar, p, decay, h0)
    return _tokens(o, s).astype(q.dtype), final


def gdn_forward(q, k, v, g, beta, initial_state=None,
                interpret: Optional[bool] = None,
                chunk: Optional[int] = None,
                heads_per_program: Optional[int] = None):
    """``gated_delta_rule``'s two results by the ``gdn_rule_fwd`` kernel
    alone, with no derivative of its own (``gated_delta_rule_grouped``
    gives it ``gdn_backward``): q, k and v go in a head and chunk,
    ``g`` and ``beta`` as the layer has them, and nothing of the
    preparation is written out."""
    b, s, h, dk = q.shape
    chunk, hb, pad = _tiles(s, h, chunk, heads_per_program)
    chunks = functools.partial(_chunks, chunk=chunk, pad=pad)
    o, final = _rule_forward(
        chunks(q), chunks(k), chunks(v.astype(q.dtype)),
        _padded(g.astype(F32), pad), _padded(beta.astype(F32), pad),
        _start_state(initial_state, b, h, dk, v.shape[-1]), hb,
        _resolve_interpret(interpret))
    return _tokens(o, s), final


def gdn_backward(q, k, v, g, beta, do,
                 interpret: Optional[bool] = None,
                 chunk: Optional[int] = None,
                 heads_per_program: Optional[int] = None):
    """The gradients of ``q``, ``k``, ``v``, ``g`` and ``beta`` (each
    in its shape and dtype) from the gradient ``do`` of
    ``gdn_forward``'s output ``o`` on a row that starts from no state
    and whose final state nothing reads, by two kernels on all the
    heads: ``gdn_rule_starts`` (the state each chunk starts from and
    the chunk's inverse, float32, the only things of the rule that
    reach HBM) and ``gdn_rule_bwd`` (the chunks last to first: a chunk
    prepared again in VMEM, the chain's derivative and the
    preparation's)."""
    b, s, h, _ = q.shape
    chunk, hb, pad = _tiles(s, h, chunk, heads_per_program)
    interpret = _resolve_interpret(interpret)
    chunks = functools.partial(_chunks, chunk=chunk, pad=pad)
    operands = (chunks(q), chunks(k), chunks(v.astype(q.dtype)),
                _padded(g.astype(F32), pad), _padded(beta.astype(F32), pad))
    starts, t = _rule_starts(*operands[1:], hb, interpret)
    *wide, dg, dbeta = _rule_backward(
        *operands, chunks(do.astype(q.dtype)), starts, t, hb, interpret)
    # [B, H / hb, N C, hb] -> [B, S, H]
    narrow = [jnp.moveaxis(t, 1, 2).reshape(b, -1, h)[:, :s]
              for t in (dg, dbeta)]
    return tuple(grad.astype(like.dtype) for grad, like in zip(
        [_tokens(t, s) for t in wide] + narrow, (q, k, v, g, beta)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    """The rule's output on the kernels, ``gdn_forward``'s, with
    ``gdn_backward`` for its derivative: the residuals are the inputs
    as the layer has them."""
    return gdn_forward(q, k, v, g, beta, interpret=interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    return _rule(q, k, v, g, beta, interpret), (q, k, v, g, beta)


def _rule_bwd(interpret, inputs, do):
    return gdn_backward(*inputs, do, interpret=interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule_grouped(q, k, v, g, beta, use_kernels: bool = True,
                             interpret: Optional[bool] = None) -> jax.Array:
    """``gated_delta_rule``'s output from a row that starts from no
    state, named ``KEPT_NAMES``: what a layer calls. On the kernels
    the forward pass is ``gdn_forward`` and keeps its inputs alone, the
    backward ``gdn_backward``, both on all the heads at once; with
    ``use_kernels=False`` forward and backward are
    ``gated_delta_rule``'s scan over chunks and its autodiff."""
    if use_kernels:
        o = _rule(q, k, v, g, beta, interpret)
    else:
        o = gated_delta_rule(q, k, v, g, beta, use_kernels=False)[0]
    return checkpoint_name(o, KEPT_NAMES[0])


def gated_delta_rule_auto(q, k, v, g, beta, use_kernels: bool = True,
                          interpret: Optional[bool] = None) -> jax.Array:
    """``gated_delta_rule_grouped`` under whatever mesh is ambient:
    GSPMD cannot partition a Mosaic call, so under a mesh the op runs in
    a ``shard_map`` with the batch on the data axes and the heads on
    ``tensor``; a head's recurrence needs nothing of another's."""
    from jax.sharding import PartitionSpec as P

    def run(*args):
        return gated_delta_rule_grouped(*args, use_kernels=use_kernels,
                                        interpret=interpret)

    mesh = ambient_shard_mesh()
    if mesh is None:
        return run(q, k, v, g, beta)
    wide = P(("data", "fsdp"), None, "tensor", None)
    narrow = P(("data", "fsdp"), None, "tensor")
    return jax.shard_map(
        run, mesh=mesh, in_specs=(wide, wide, wide, narrow, narrow),
        out_specs=wide,
        check_vma=False,  # a pallas_call output carries no vma
    )(q, k, v, g, beta)
