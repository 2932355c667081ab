"""Kimi delta attention's rule (Kimi Linear, arXiv:2510.26692): the
delta rule under a DIAGONAL decay, as one differentiable op.

Per batch row and head, with a matrix state ``H`` of ``[dk, dv]``,
``alpha_t = exp(g_t)`` a VECTOR over the state's ``dk`` rows (``g_t``
in ``[-5, 0]``: the bounded gate, see below) and ``beta_t`` in
``(0, 1)``::

    H_t = (I - beta_t k_t k_t^T) Diag(alpha_t) H_{t-1} + beta_t k_t v_t^T
    o_t = H_t^T q_t

i.e. decay each row, erase along ``k_t``, then write ``v_t`` there:
``ops/gated_delta.py``'s order, whose ``alpha_t`` is one scalar a head.
The program computes the chunked (WY) form. Over a chunk of ``C``
tokens, with ``G_i`` ``[dk]`` the sum of ``g`` up to token ``i`` of the
chunk, ``Gamma = exp(G)`` and ``H0`` the state the chunk starts from::

    A[i, j] = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])    j < i
    T       = (I + A)^-1                          unit lower triangular
    Ubar    = T diag(beta) V          W  = T diag(beta) (K * Gamma)
    P[i, j] = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])           j <= i
    Qg      = Q * Gamma               Kd = K * exp(G_C - G)
    ----------------------------------------------------------------
    U       = Ubar - W H0
    O       = Qg H0 + P U
    H_C     = Diag(Gamma_C) H0 + Kd^T U

The scalar rule's ratio ``exp(G_i - G_j)`` leaves the dot product; here
it sits INSIDE the contraction over ``c``, so ``A`` and ``P`` are
products of operands that carry it: ``(k_i * exp(G_i - G_r)) . (k_j *
exp(G_r - G_j))`` for a reference row ``r``. A chunk is cut into
sub-chunks of ``SUB`` = 16 tokens. Between a sub-chunk and an EARLIER
one, ``r`` is the later one's first row and both factors are at most 1:
operands in the inputs' dtype. Inside a sub-chunk ``r`` is its first
row, the first factor is at most 1 and the second at most ``exp(15 x
5) = e^75``, under float32's ``e^88``: float32 operands at the highest
precision, a sixteenth of the pairs. **That is what the gate's bound is
for**: ``g >= -5`` is the caller's to keep (Kimi Linear's
``kda_lower_bound`` with ``kda_safe_gate``); a gate far under it
overflows the second factor.

What stands above the line is local to a chunk, batched over ``batch x
heads x chunks``, plain ``jax.numpy`` that XLA differentiates
(``_prepare``, scope ``kda_chunk``): float32 for ``g``, its sums, every
ratio, ``beta`` and the inverse (``gated_delta``'s exact block
substitution by doubling, with its own gradient).

The three lines below it are the chain: ``S / C`` dependent steps, each
three small matmuls against a float32 state, in a pair of Pallas
kernels named ``kda_fwd`` and ``kda_bwd`` under one ``jax.custom_vjp``.
The kernels keep the state TRANSPOSED, ``[dv, dk]``: the chunk's decay
scales the state's ``dk`` rows, which is a ``[1, dk]`` row times the
transposed state's columns (a lane-dense operand and no scalar load,
where a ``[dk, 1]`` column would pad every value to a lane tile), and
the three products keep forms the MXU has (``x y``, ``x y^T``,
``x^T y``). Grid ``(batch, head groups, chunks)``, the chunks innermost
and sequential; the forward also writes the state each chunk starts
from as the residual; the backward walks the chunks last to first with
``dH`` carried in VMEM and returns the gradients of ``Qg``, ``Kd``,
``W``, ``Ubar``, ``P`` and the chunk's decay; those of ``q``, ``k``,
``v``, ``g`` and ``beta`` follow through ``_prepare`` by autodiff.
Every call goes through one shared ``jax.jit`` a kernel and shape
(``ops.trace_once.shared_call``).

Off the TPU the same kernels run in the Pallas interpreter;
``use_kernels=False`` runs the chain as a ``lax.scan`` over chunks (the
path the CPU tests differentiate by autodiff and hold the kernels to),
and ``kda_reference`` is the token-by-token recurrence.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from dlrover_tpu.ops.flash_attention import _vmem, ambient_shard_mesh
from dlrover_tpu.ops.gated_delta import (
    _NN,
    _NT,
    _TN,
    _dot,
    _specs,
    _state_spec,
    _unit_lower_inverse,
)
from dlrover_tpu.ops.selective_scan import _params, _resolve_interpret
from dlrover_tpu.ops.trace_once import shared_call
from dlrover_tpu.telemetry.names import DeviceScope

F32 = jnp.float32
# what ``kda_grouped`` names its output (``jax.ad_checkpoint.
# checkpoint_name``): a layer's checkpoint that keeps it
# (``ops.remat.apply_remat(layer, policy, keep=KEPT_NAMES)``) does not
# run the rule's forward again in its replay. Each head group is a
# checkpoint of its own whose residuals are its INPUTS, so with the
# output kept nothing of the replayed forward is read and the compiler
# drops it: the preparation and ``kda_fwd`` run twice a step (the
# forward pass, the group's own replay before ``kda_bwd``), not three
# times. [B, S, H, dv] in the compute dtype a layer
KEPT_NAMES = ("kda_out",)
# tokens of a sub-chunk: inside one the second factor of a pair is at
# most exp((SUB - 1) x 5) for a gate bounded at -5
SUB = 16


def kda_reference(q, k, v, g, beta, initial_state=None):
    """The recurrence token by token (``lax.scan`` over the row), in
    float32: ``q``, ``k`` [B, S, H, dk]; ``v`` [B, S, H, dv]; ``g`` (the
    log of the decay a key channel, <= 0) [B, S, H, dk]; ``beta``
    [B, S, H]. Returns ``(o [B, S, H, dv], the final state [B, H, dk,
    dv])``. The oracle of the tests."""
    q, k, v, g, beta = (t.astype(F32) for t in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    dv = v.shape[-1]
    hp = lax.Precision.HIGHEST

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [B, H, .]
        state = jnp.exp(g_t)[..., None] * state
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


def _prepare(q, k, v, g, beta):
    """What is local to a chunk. ``q``, ``k`` [B, H, N, C, dk]; ``v``
    [B, H, N, C, dv]; ``g`` [B, H, N, C, dk] and ``beta`` [B, H, N, C]
    float32. Returns ``(Qg, Kd, W, Ubar, P, decay)``, the first five in
    ``q``'s dtype and ``decay`` = ``Gamma_C`` [B, H, N, dk] float32."""
    cd = q.dtype
    lead, (c, dk) = q.shape[:-2], q.shape[-2:]
    s = min(SUB, c)
    m = c // s
    big_g = jnp.cumsum(g, axis=-2)
    gamma = jnp.exp(big_g)
    to_end = jnp.exp(big_g[..., -1:, :] - big_g)
    qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)

    def by_sub(t):  # [..., C, dk] -> [..., m, s, dk]
        return t.reshape(lead + (m, s, dk))

    gs = by_sub(big_g)
    ref = gs[..., :1, :]  # a sub-chunk's first row
    down = jnp.exp(gs - ref)  # at most 1
    # the columns of a row's own sub-chunk, float32: up to e^75
    k_own = by_sub(kf) * jnp.exp(ref - gs)
    # the columns of the sub-chunks before a row's, for each row
    # sub-chunk ``a`` [..., a, C, dk]: at most 1, masked before the
    # exponential (from the row's own sub-chunk on the difference is
    # positive and may overflow)
    col = lax.broadcasted_iota(jnp.int32, (m, c, 1), 1)
    first = s * lax.broadcasted_iota(jnp.int32, (m, c, 1), 0)
    k_before = (kf[..., None, :, :] * jnp.exp(jnp.where(
        col < first, ref - big_g[..., None, :, :], -jnp.inf))).astype(cd)
    own_block = (jnp.eye(m, dtype=F32)[:, None, :, None]
                 * jnp.ones((1, s, 1, s), F32))

    def pairs(x):  # sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c]), [..., C, C]
        x = by_sub(x) * down
        before = jnp.einsum("...aik,...ajk->...aij", x.astype(cd), k_before,
                            preferred_element_type=F32)
        own = jnp.einsum("...aik,...ajk->...aij", x, k_own,
                         precision="highest")
        own = own[..., :, :, None, :] * own_block  # [..., m, s, m, s]
        return before.reshape(lead + (c, c)) + own.reshape(lead + (c, c))

    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    a = jnp.where(j < i, beta[..., :, None] * pairs(kf), 0.0)
    t = _unit_lower_inverse(a).astype(cd)

    def solve(rows):  # T rows
        return jnp.einsum("...ij,...jd->...id", t, rows.astype(cd),
                          preferred_element_type=F32).astype(cd)

    ubar = solve(beta[..., None] * vf)
    w = solve(beta[..., None] * gamma * kf)
    p = jnp.where(j <= i, pairs(qf), 0.0).astype(cd)
    qg = (gamma * qf).astype(cd)
    kd = (to_end * kf).astype(cd)
    return qg, kd, w, ubar, p, gamma[..., -1, :]


# -- the chain ----------------------------------------------------------------
# on the transposed state ``s`` = ``H^T`` [dv, dk]


def _chain_step(s, qg, kd, w, ubar, p, decay):
    """One chunk of one head: ``(O, the next state)`` from the state
    ``s`` [dv, dk] float32 the chunk starts from. ``decay`` is
    ``[1, dk]`` (or ``[dk]``). The forward kernel and the scan both run
    it."""
    cd = w.dtype
    sc = s.astype(cd)
    u = ubar.astype(F32) - _dot(w, sc, _NT)
    uc = u.astype(cd)
    o = _dot(qg, sc, _NT) + _dot(p, uc, _NN)
    return o, decay * s + _dot(uc, kd, _TN)


def _chain_scan(qg, kd, w, ubar, p, decay, s0):
    """The chain as a ``lax.scan`` over the chunks. Operands
    [B, H, N, C, .], ``decay`` [B, H, N, dk], ``s0`` [B, H, dv, dk]
    float32. Returns ``(O [B, H, N, C, dv] float32, the final
    state)``."""
    step = jax.vmap(jax.vmap(_chain_step))  # over batch, heads

    def body(s, xs):
        o, s = step(s, *xs)
        return s, o

    final, o = lax.scan(body, s0, tuple(
        jnp.moveaxis(t, 2, 0) for t in (qg, kd, w, ubar, p, decay)))
    return jnp.moveaxis(o, 0, 2), final


def _kda_fwd_kernel(qg_ref, kd_ref, w_ref, ubar_ref, p_ref, decay_ref,
                    s0_ref,  # inputs
                    o_ref, start_ref, final_ref,  # outputs
                    s_scratch, *, heads: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        s_scratch[:] = s0_ref[0]

    for j in range(heads):
        s = s_scratch[j]
        start_ref[0, j, 0] = s  # what this chunk starts from
        o, s = _chain_step(s, qg_ref[0, j, 0], kd_ref[0, j, 0],
                           w_ref[0, j, 0], ubar_ref[0, j, 0],
                           p_ref[0, j, 0], decay_ref[0, j, 0])
        o_ref[0, j, 0] = o.astype(o_ref.dtype)
        s_scratch[j] = s

    @pl.when(n == pl.num_programs(2) - 1)
    def _final():
        final_ref[0] = s_scratch[:]


def _kda_bwd_kernel(qg_ref, kd_ref, w_ref, ubar_ref, p_ref, decay_ref,
                    start_ref, do_ref, dfinal_ref,  # inputs
                    dqg_ref, dkd_ref, dw_ref, dubar_ref, dp_ref,
                    ddecay_ref, ds0_ref,  # outputs
                    ds_scratch, *, heads: int):
    n = pl.program_id(2)  # the chunks run last to first

    @pl.when(n == 0)
    def _init():
        ds_scratch[:] = dfinal_ref[0]

    for j in range(heads):
        qg, kd, w, p = (r[0, j, 0] for r in (qg_ref, kd_ref, w_ref, p_ref))
        cd = w.dtype
        decay = decay_ref[0, j, 0]  # [1, dk]
        s = start_ref[0, j, 0]  # [dv, dk] float32
        sc = s.astype(cd)
        uc = (ubar_ref[0, j, 0].astype(F32) - _dot(w, sc, _NT)).astype(cd)
        do = do_ref[0, j, 0].astype(cd)
        ds = ds_scratch[j]  # dL/d(the state the chunk ends in)
        dsc = ds.astype(cd)
        du = _dot(p, do, _TN) + _dot(kd, dsc, _NT)  # [C, dv]
        duc = du.astype(cd)
        dqg_ref[0, j, 0] = _dot(do, sc, _NN).astype(dqg_ref.dtype)
        dp_ref[0, j, 0] = _dot(do, uc, _NT).astype(dp_ref.dtype)
        dkd_ref[0, j, 0] = _dot(uc, dsc, _NN).astype(dkd_ref.dtype)
        dubar_ref[0, j, 0] = duc.astype(dubar_ref.dtype)
        dw_ref[0, j, 0] = (-_dot(duc, sc, _NN)).astype(dw_ref.dtype)
        ddecay_ref[0, j, 0] = jnp.sum(ds * s, axis=0, keepdims=True)
        ds_scratch[j] = (decay * ds + _dot(do, qg, _TN)
                         - _dot(duc, w, _TN))

    @pl.when(n == pl.num_programs(2) - 1)
    def _first():
        ds0_ref[0] = ds_scratch[:]


def _chain_forward(qg, kd, w, ubar, p, decay, s0, hb, interpret):
    b, h, n, c, dk = qg.shape
    dv = ubar.shape[-1]
    operands = (qg, kd, w, ubar, p, decay)
    o_shape = jax.ShapeDtypeStruct((b, h, n, c, dv), qg.dtype)
    starts = jax.ShapeDtypeStruct((b, h, n, dv, dk), F32)
    final = jax.ShapeDtypeStruct((b, h, dv, dk), F32)

    def build():
        return pl.pallas_call(
            functools.partial(_kda_fwd_kernel, heads=hb),
            grid=(b, h // hb, n),
            in_specs=_specs(operands, hb, lambda i: i)
            + [_state_spec(hb, dv, dk)],
            out_specs=_specs((o_shape, starts), hb, lambda i: i)
            + [_state_spec(hb, dv, dk)],
            out_shape=[o_shape, starts, final],
            scratch_shapes=[_vmem((hb, dv, dk))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="kda_fwd",
        )

    return shared_call("kda_fwd", DeviceScope.KDA, (hb, interpret),
                       operands + (s0,), build)


def _chain_backward(qg, kd, w, ubar, p, decay, starts, do, dfinal, hb,
                    interpret):
    b, h, n, c, dk = qg.shape
    dv = ubar.shape[-1]
    operands = (qg, kd, w, ubar, p, decay, starts, do)
    grads = [jax.ShapeDtypeStruct(t.shape, t.dtype)
             for t in (qg, kd, w, ubar, p, decay)]
    last = n - 1

    def build():
        return pl.pallas_call(
            functools.partial(_kda_bwd_kernel, heads=hb),
            grid=(b, h // hb, n),
            in_specs=_specs(operands, hb, lambda i: last - i)
            + [_state_spec(hb, dv, dk)],
            out_specs=_specs(grads, hb, lambda i: last - i)
            + [_state_spec(hb, dv, dk)],
            out_shape=grads + [jax.ShapeDtypeStruct((b, h, dv, dk), F32)],
            scratch_shapes=[_vmem((hb, dv, dk))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="kda_bwd",
        )

    return shared_call("kda_bwd", DeviceScope.KDA, (hb, interpret),
                       operands + (dfinal,), build)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _chain(qg, kd, w, ubar, p, decay, s0, hb, interpret):
    """The chain through the kernels; ``decay`` is [B, H, N, 1, dk] and
    the states [B, H, dv, dk]."""
    o, _, final = _chain_forward(qg, kd, w, ubar, p, decay, s0, hb,
                                 interpret)
    return o, final


def _chain_fwd(qg, kd, w, ubar, p, decay, s0, hb, interpret):
    o, starts, final = _chain_forward(qg, kd, w, ubar, p, decay, s0, hb,
                                      interpret)
    return (o, final), (qg, kd, w, ubar, p, decay, starts)


def _chain_bwd(hb, interpret, residuals, cotangents):
    do, dfinal = cotangents
    return tuple(_chain_backward(*residuals, do, dfinal, hb, interpret))


_chain.defvjp(_chain_fwd, _chain_bwd)


def chain_tiles(seq: int, heads: int) -> Tuple[int, int]:
    """``(chunk, heads a program)`` of the chain for a row of ``seq``
    tokens and ``heads`` heads on this shard: a chunk of 64 (the largest
    power of two up to it that divides the row) and the largest divisor
    of the heads up to 8.

    The sweep behind it (my chip runs, PR 62, TPU v5 lite,
    ``benchmarks/kda_bench.py``: 2 x 8192 x 8 heads of 128 / 128, bf16,
    ms a call by the host's clock over ten calls): a grid step's fixed
    work is shared by its heads, so the chain's forward falls from 1.11
    at two heads a program to 0.80 at 4 and 0.69 at 8 (forward and
    backward 2.21, 1.71, 1.49); at a chunk of 128 the chain is faster
    (0.48 and 0.98 at 8) but the preparation around it, whose inverse
    and ``[C, C]`` tiles grow with the chunk, costs more than that
    gains (8.63 ms forward and 17.68 with its backward at 64, 10.52 and
    21.49 at 128): the whole op forward and backward 19.45 at 64 x 8
    against 22.58 at 128 x 8, and 20.08 at 64 x 2, the heads a program
    of the cell's head groups of two (``head_groups``: what a smaller
    group gains outweighs it)."""
    chunk = 64
    while chunk > 8 and seq % chunk:
        chunk //= 2
    group = max(d for d in range(1, min(heads, 8) + 1) if heads % d == 0)
    return chunk, group


def kda(
    q: jax.Array,  # [B, S, H, dk], l2-normalised and scaled by the caller
    k: jax.Array,  # [B, S, H, dk], l2-normalised
    v: jax.Array,  # [B, S, H, dv]
    g: jax.Array,  # [B, S, H, dk], the log of the decay, in [-5, 0]
    beta: jax.Array,  # [B, S, H], in (0, 1)
    initial_state: Optional[jax.Array] = None,  # [B, H, dk, dv]
    use_kernels: bool = True,
    interpret: Optional[bool] = None,
    chunk: Optional[int] = None,
    heads_per_program: Optional[int] = None,
):
    """``(o [B, S, H, dv] in q's dtype, the final state [B, H, dk, dv]
    float32)`` of the recurrence in the module docstring, differentiable
    in ``q``, ``k``, ``v``, ``g``, ``beta`` and ``initial_state``.
    ``chunk`` (a power of two) and ``heads_per_program`` default to
    ``chain_tiles``'s; a row that is no multiple of the chunk is padded
    with tokens that leave the state as it is (``g`` 0, ``beta`` 0)."""
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

    with jax.named_scope(DeviceScope.KDA_CHUNK):
        qg, kd, w, ubar, p, decay = _prepare(
            chunks(q), chunks(k), chunks(v.astype(q.dtype)),
            chunks(g.astype(F32)), chunks(beta.astype(F32)))
        s0 = (jnp.zeros((b, h, dv, dk), F32) if initial_state is None
              else initial_state.astype(F32).swapaxes(-1, -2))
    if use_kernels:
        o, final = _chain(qg, kd, w, ubar, p, decay[..., None, :], s0, hb,
                          _resolve_interpret(interpret))
    else:
        o, final = _chain_scan(qg, kd, w, ubar, p, decay, s0)
    o = jnp.moveaxis(o.reshape(b, h, n * chunk, dv), 1, 2)[:, :s]
    return o.astype(q.dtype), final.swapaxes(-1, -2)


# what the op's backward holds while it runs, a token, head and column
# of a key or a value: ``gated_delta``'s 33 (the prepared operands and
# their gradients, the float32 pieces of the preparation, the state a
# chunk starts from) and, a key column, the float32 gate, its sums and
# ratios and the columns ``k_before`` of each later sub-chunk. Half a
# gigabyte a group, because a smaller group is the faster one (my chip
# runs, PR 62, TPU v5 lite, ``benchmarks/kda_bench.py``, one layer's op
# at 2 x 8192 x 32 heads, forward and backward, ms a call: 54.9 in
# sixteen groups of two heads, 69.5 in eight of four, 74.7 in four,
# 86.0 in two, 89.7 in one; forward alone 22.6, 25.6, 36.0, 41.5, 46.9:
# a smaller group's float32 pieces are written and read back sooner)
_BYTES_A_COLUMN = 48
_GROUP_BYTES = 1 << 29


def head_groups(batch: int, seq: int, heads: int, dk: int, dv: int) -> int:
    """Into how many groups of heads, run one after another, the op
    splits so that a group's backward holds about half a gigabyte: the
    smallest divisor of ``heads`` that does (16 for 2 x 8192 x 32 heads
    of 128; 1 at a toy size)."""
    whole = batch * seq * heads * (dk + dv) * _BYTES_A_COLUMN
    return next(g for g in range(1, heads + 1)
                if heads % g == 0 and (whole <= g * _GROUP_BYTES
                                       or g == heads))


def kda_grouped(q, k, v, g, beta, use_kernels: bool = True,
                interpret: Optional[bool] = None) -> jax.Array:
    """``kda``'s output, the heads in ``head_groups`` groups one after
    another (``lax.map``), each group its own checkpoint: what the
    preparation and the chain keep for their backward is then one
    group's at a time and not the layer's, at the price of a group's
    forward run again in its backward (``gated_delta_rule_grouped``'s
    form). A head's recurrence needs nothing of another's."""
    b, s, h, dk = q.shape
    groups = head_groups(b, s, h, dk, v.shape[-1])

    def run(*args):
        return kda(*args, use_kernels=use_kernels, interpret=interpret)[0]

    if groups == 1:  # one group keeps the chain's own residuals
        return run(q, k, v, g, beta)

    def split(t):  # [B, S, H, ...] -> [groups, B, S, H / groups, ...]
        return jnp.moveaxis(
            t.reshape(t.shape[:2] + (groups, h // groups) + t.shape[3:]),
            2, 0)

    o = lax.map(lambda xs: jax.checkpoint(run)(*xs),
                tuple(split(t) for t in (q, k, v, g, beta)))
    return checkpoint_name(jnp.moveaxis(o, 0, 2).reshape(b, s, h, -1),
                           KEPT_NAMES[0])


def kda_auto(q, k, v, g, beta, use_kernels: bool = True,
             interpret: Optional[bool] = None) -> jax.Array:
    """``kda_grouped`` under whatever mesh is ambient: GSPMD cannot
    partition a Mosaic call, so under a mesh the op runs in a
    ``shard_map`` with the batch on the data axes and the heads on
    ``tensor``; a head's recurrence needs nothing of another's."""
    from jax.sharding import PartitionSpec as P

    def run(*args):
        return kda_grouped(*args, use_kernels=use_kernels,
                           interpret=interpret)

    mesh = ambient_shard_mesh()
    if mesh is None:
        return run(q, k, v, g, beta)
    wide = P(("data", "fsdp"), None, "tensor", None)
    narrow = P(("data", "fsdp"), None, "tensor")
    return jax.shard_map(
        run, mesh=mesh, in_specs=(wide, wide, wide, wide, narrow),
        out_specs=wide,
        check_vma=False,  # a pallas_call output carries no vma
    )(q, k, v, g, beta)
