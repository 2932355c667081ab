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
What stands above the line is local to a chunk, batched over ``batch x
heads x chunks``, plain ``jax.numpy`` that XLA differentiates
(``_prepare``): float32 for ``g``, its sums, the ratios, ``beta`` and
the inverse, operands in the inputs' dtype into the MXU with float32
accumulation. The inverse is exact block substitution by doubling (a
block-diagonal inverse of block ``s`` gives the one of ``2s`` in two
``[C, C]`` products), so it is as stable as forward substitution and
lane-dense; a Neumann product over the whole chunk is not, since
``beta`` up to 2 lets the powers of ``A`` grow where ``T`` stays
bounded.

The three lines below it are the chain: ``S / C`` dependent steps,
each three small matmuls against a float32 state. On a TPU they are a
pair of Pallas kernels whose instructions are named ``gdn_fwd`` and
``gdn_bwd``, under one ``jax.custom_vjp``:

* grid ``(batch, head groups, chunks)``, the chunks innermost and
  sequential; the state of a group's heads (``[dk, dv]`` float32 each)
  lives in VMEM scratch and is carried from chunk to chunk;
* the forward also writes the state each chunk starts from
  (``[chunks, dk, dv]`` float32 a head) as the residual;
* the backward walks the chunks last to first with ``dH`` carried in
  VMEM, recomputes ``U`` from that residual, and returns the gradients
  of ``Qg``, ``Kd``, ``W``, ``Ubar``, ``P`` and the chunk's decay; the
  gradients of ``q``, ``k``, ``v``, ``g`` and ``beta`` follow through
  ``_prepare`` by autodiff.

The decay of a chunk reaches the kernels as a ``[1, dv]`` row (the
scalar ``gamma_C`` repeated): a row multiplies the state without a
scalar load, and the sum over it that the scalar's gradient needs is
the transpose of that broadcast.

The chunk and the heads a program are ``chain_tiles``'s, from the
shape. Off the TPU the same kernels run in the Pallas interpreter;
``use_kernels=False`` runs the chain as a ``lax.scan`` over chunks (the
path the CPU tests differentiate by autodiff and hold the kernels to),
and ``gated_delta_rule_reference`` is the token-by-token recurrence.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from dlrover_tpu.ops.flash_attention import _vmem, ambient_shard_mesh
from dlrover_tpu.ops.selective_scan import _params, _resolve_interpret
from dlrover_tpu.telemetry.names import DeviceScope

F32 = jnp.float32


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
    128 x 10."""
    chunk = 64
    while chunk > 8 and seq % chunk:
        chunk //= 2
    group = max(d for d in range(1, min(heads, 10) + 1) if heads % d == 0)
    return chunk, group


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
    ``initial_state``. ``chunk`` (a power of two) and
    ``heads_per_program`` default to ``chain_tiles``'s; a row that is
    no multiple of the chunk is padded with tokens that leave the state
    as it is (``g`` 0, ``beta`` 0)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    tile_c, tile_h = chain_tiles(s, h)
    chunk = chunk or tile_c
    hb = heads_per_program or tile_h
    if chunk & (chunk - 1) or h % hb:
        raise ValueError(f"chunk {chunk} is no power of two, or "
                         f"{hb} heads a program do not divide {h}")
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(t):  # [B, S, H, ...] -> [B, H, N, C, ...]
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t, 2, 1).reshape(
            (b, h, n, chunk) + t.shape[3:])

    with jax.named_scope(DeviceScope.GDN_CHUNK):
        qg, kd, w, ubar, p, decay = _prepare(
            chunks(q), chunks(k), chunks(v.astype(q.dtype)),
            chunks(g.astype(F32)), chunks(beta.astype(F32)))
    h0 = (jnp.zeros((b, h, dk, dv), F32) if initial_state is None
          else initial_state.astype(F32))
    if use_kernels:
        row = jnp.broadcast_to(decay[..., None, None], (b, h, n, 1, dv))
        o, final = _chain(qg, kd, w, ubar, p, row, h0, hb,
                          _resolve_interpret(interpret))
    else:
        o, final = _chain_scan(qg, kd, w, ubar, p, decay, h0)
    o = jnp.moveaxis(o.reshape(b, h, n * chunk, dv), 1, 2)[:, :s]
    return o.astype(q.dtype), final


# what the op's backward holds while it runs, a token, head and column
# of a key or a value: the prepared operands and their gradients, the
# float32 pieces of the preparation, the state a chunk starts from
# (2.3 GB of the compiler's estimate for 8192 x 30 at 96 + 192)
_BYTES_A_COLUMN = 33
_GROUP_BYTES = 1 << 30


def head_groups(batch: int, seq: int, heads: int, dk: int, dv: int) -> int:
    """Into how many groups of heads, run one after another, the op
    splits so that a group's backward holds at most a gigabyte: the
    smallest divisor of ``heads`` that does (3 for 8192 x 30; 1 at a
    toy size)."""
    whole = batch * seq * heads * (dk + dv) * _BYTES_A_COLUMN
    return next(g for g in range(1, heads + 1)
                if heads % g == 0 and (whole <= g * _GROUP_BYTES
                                       or g == heads))


def gated_delta_rule_grouped(q, k, v, g, beta, use_kernels: bool = True,
                             interpret: Optional[bool] = None) -> jax.Array:
    """``gated_delta_rule``'s output, the heads in ``head_groups``
    groups one after another (``lax.map``), each group its own
    checkpoint: what the preparation and the chain keep for their
    backward is then one group's at a time and not the layer's, at the
    price of a group's forward run again in its backward. A head's
    recurrence needs nothing of another's, and one chip runs the groups
    in sequence whatever the grid."""
    b, s, h, dk = q.shape
    groups = head_groups(b, s, h, dk, v.shape[-1])

    def run(*args):
        return gated_delta_rule(*args, use_kernels=use_kernels,
                                interpret=interpret)[0]

    if groups == 1:
        return run(q, k, v, g, beta)

    def split(t):  # [B, S, H, ...] -> [groups, B, S, H / groups, ...]
        return jnp.moveaxis(
            t.reshape(t.shape[:2] + (groups, h // groups) + t.shape[3:]),
            2, 0)

    o = lax.map(lambda xs: jax.checkpoint(run)(*xs),
                tuple(split(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2).reshape(b, s, h, -1)


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
