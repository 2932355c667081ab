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

What stands above the line is local to a chunk; the three lines below
it are the chain: ``S / C`` dependent steps, each three small matmuls
against a float32 state. The kernels keep that state TRANSPOSED,
``[dv, dk]``: the chunk's decay scales the state's ``dk`` rows, which
is a ``[1, dk]`` row times the transposed state's columns (a lane-dense
operand and no scalar load, where a ``[dk, 1]`` column would pad every
value to a lane tile), and the three products keep forms the MXU has
(``x y``, ``x y^T``, ``x^T y``). What runs where:

* **The forward pass: one kernel**, ``kda_rule_fwd``
  (``kda_forward``). Grid ``(batch, head blocks, chunks)``, the chunks
  innermost and sequential, the state in VMEM scratch. A grid step
  reads one chunk of q, k, v, ``g`` and ``beta`` as the layer has them
  (``[B, S, H x columns]``: a head is a lane tile of the block),
  computes in VMEM what stands above the line (``_rule_chunk``: the
  sums of ``g`` as a triangular product, the sub-chunks' pairs, ``A``,
  its inverse by doubling, ``Ubar``, ``W``, ``P``, ``Qg``, ``Kd``, each
  rounded where ``_prepare`` rounds it), chains it and writes ``O``;
  the final state at the last chunk. Nothing prepared and no chunk
  start state reaches HBM. Its float32 products are three bf16 pieces
  an operand and the six products of pieces that ``highest`` is on this
  chip, as one product (``_dots_f32``); every stage runs for all the
  block's heads at once.
* **The backward: two kernels** on the same grid and layout, all the
  layer's heads in one call each (``kda_backward``). The states pass,
  ``kda_rule_starts``, is the forward kernel without the queries: it
  writes the float32 state each chunk starts from and the chunk's
  inverse ``T``, the two things the backward cannot prepare again
  cheaply, and nothing else. The backward pass, ``kda_rule_bwd``,
  walks the chunks LAST TO FIRST with ``dH`` carried in VMEM: a grid
  step prepares the chunk again (``_rule_chunk`` with ``T`` handed in:
  the same stages, the same roundings, no doubling), runs the chain's
  derivative (``_kda_bwd_kernel``'s seven products) and then the
  preparation's own by hand (``_rule_chunk_bwd``): ``T^T dUbar``,
  ``T^T dW`` and ``dT``; the inverse's ``dA = -T^T dT T^T`` kept
  strictly lower, in six-piece float32 products; the pairs' derivative
  with the forward's split (``_chunk_pairs_bwd``: inside a sub-chunk
  float32 pieces about its first row, between sub-chunks the inputs'
  dtype with both factors at most 1, so nothing larger than the
  forward's ``e^75`` is formed); the gate's without a product of its
  own, ``dG_i[c] = x_i[c] dx_i[c] - k_i[c] dk_i[c]`` over every term
  that carries ``exp(G)`` or ``exp(-G)``, and ``dg`` the sums of ``dG``
  from a row to the chunk's end, an upper-triangular product. It writes
  the gradients of q, k, v (their dtype) and ``g`` (float32) in the
  layer's layout and ``beta``'s a head block. Nothing of the rule is
  XLA's.
* **The two steps** (``kda``): the differentiable op with the state
  handed in and out, which no model's program calls (PR 64): the
  oracle of the kernels above and, with ``use_kernels=False``, the CPU
  path. The preparation is plain ``jax.numpy`` batched over ``batch x
  heads x chunks`` that XLA differentiates (``_prepare``, scope
  ``kda_chunk``): float32 for ``g``, its sums, every ratio, ``beta``
  and the inverse (``gated_delta``'s exact block substitution by
  doubling, with its own gradient). The chain is a pair of Pallas
  kernels named ``kda_fwd`` and ``kda_bwd`` under one
  ``jax.custom_vjp``, on the same grid: ``kda_fwd`` also writes the
  state each chunk starts from as the residual; ``kda_bwd`` walks the
  chunks last to first with ``dH`` carried in VMEM and returns the
  gradients of ``Qg``, ``Kd``, ``W``, ``Ubar``, ``P`` and the chunk's
  decay; those of ``q``, ``k``, ``v``, ``g`` and ``beta`` follow
  through ``_prepare`` by autodiff.

``kda_grouped``, what a layer calls, joins the forward kernel and the
backward's two under a ``jax.custom_vjp`` whose residuals are the op's
inputs. Every call goes through one shared ``jax.jit`` a kernel and
shape (``ops.trace_once.shared_call``).

Off the TPU the same kernels run in the Pallas interpreter;
``use_kernels=False`` runs the chain as a ``lax.scan`` over chunks (the
path the CPU tests differentiate by autodiff and hold the kernels to),
and ``kda_reference`` is the token-by-token recurrence.
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
from dlrover_tpu.ops.gated_delta import (
    _NN,
    _NT,
    _TN,
    _dot,
    _dots,
    _dots_f32,
    _pieces,
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
# run the rule's forward again in its replay. The op's residuals are
# its INPUTS, so with the output kept nothing of the replayed forward is
# read and the compiler drops it: a step runs ``kda_rule_fwd`` once a
# layer (the forward pass) and ``kda_rule_starts`` and ``kda_rule_bwd``
# once (the backward), none a second time in the layer's replay.
# [B, S, H, dv] in the compute dtype a layer
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


def _chain_reads(s, w, ubar):
    """The first line of the chain: the state ``s`` [dv, dk] float32 a
    chunk starts from in the operands' dtype, and ``U`` [C, dv]
    float32."""
    sc = s.astype(w.dtype)
    return sc, ubar.astype(F32) - _dot(w, sc, _NT)


def _chain_next(s, uc, kd, decay):
    """The third line: the state the chunk ends in."""
    return decay * s + _dot(uc, kd, _TN)


def _chain_writes(s, sc, u, qg, kd, p, decay):
    """The two other lines: ``(O, the next state)``."""
    uc = u.astype(sc.dtype)
    o = _dot(qg, sc, _NT) + _dot(p, uc, _NN)
    return o, _chain_next(s, uc, kd, decay)


def _chain_step(s, qg, kd, w, ubar, p, decay):
    """One chunk of one head: ``(O, the next state)`` from the state
    ``s`` [dv, dk] float32 the chunk starts from. ``decay`` is
    ``[1, dk]`` (or ``[dk]``). The chain's forward kernel and the scan
    run it; the whole rule's kernel runs its two halves a stage each."""
    sc, u = _chain_reads(s, w, ubar)
    return _chain_writes(s, sc, u, qg, kd, p, decay)


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


# -- the whole rule's forward as one kernel -----------------------------------
# a chunk is prepared in VMEM and chained there: nothing but ``o`` and
# the final state reaches HBM


def _sub_chunks(c):
    """``(the tokens of a sub-chunk, the later ones' first rows)``."""
    s = min(SUB, c)
    return s, range(s, c, s)


def _sub_rows(t, r, s, count):
    """The rows of the sub-chunk that starts at ``r`` in each of the
    ``count`` stacks of C rows that ``t`` [heads, count C, .] holds."""
    c = t.shape[1] // count
    return jnp.concatenate(
        [t[:, n * c + r:n * c + r + s] for n in range(count)], axis=1)


def _chunk_pairs(xs, kf, big_g, cd):
    """``sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c])`` [heads, C, C]
    float32 for each ``x`` of ``xs`` (the queries and the keys, or the
    keys alone: [heads, C, dk] float32 as ``kf``), by ``_prepare``'s
    split, and what their derivative reads again. Rows are cut at
    multiples of a sub-chunk alone (whole sublane tiles), lanes never:
    a pair's mask takes the place of ``_prepare``'s reshape by
    sub-chunks. A pair above the diagonal is finite and means nothing."""
    heads, c, dk = kf.shape
    s, starts = _sub_chunks(c)
    count = len(xs)
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    ref = big_g[:, :1]  # a sub-chunk's first row, on each of its rows
    for r in starts:
        ref = jnp.where(row >= r, big_g[:, r:r + 1], ref)
    down = jnp.exp(big_g - ref)  # at most 1
    up = jnp.exp(ref - big_g)  # up to e^75
    # the rows of every x over those of the keys: one product each
    x = jnp.concatenate([t * down for t in xs], axis=1)
    kept = SimpleNamespace(
        down=down, up=up, xc=x.astype(cd), x=_pieces(x),
        k_up=_pieces(kf * up), before={}, k_before={})
    # inside a sub-chunk, float32: the second factor is up to e^75; a
    # pair of two sub-chunks is finite and masked
    own = _dots_f32(kept.x, kept.k_up, _NT)  # [heads, count C, C]
    # a later sub-chunk's rows against the columns before it, both
    # factors at most 1, in the inputs' dtype
    parts = [[jnp.zeros((heads, s, c), F32)]  # nothing before the first
             for _ in xs]
    for r in starts:
        kept.before[r] = jnp.concatenate(
            [jnp.exp(big_g[:, r:r + 1] - big_g[:, :r]),
             jnp.zeros((heads, c - r, dk), F32)], axis=1)
        kept.k_before[r] = (kf * kept.before[r]).astype(cd)
        pair = _dots(_sub_rows(kept.xc, r, s, count), kept.k_before[r],
                     _NT)  # [heads, count SUB, C]
        for n, part in enumerate(parts):
            part.append(pair[:, n * s:(n + 1) * s])
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = i // s == j // s
    return [jnp.where(same, own[:, n * c:(n + 1) * c],
                      jnp.concatenate(part, axis=1))
            for n, part in enumerate(parts)], kept


def _chunk_pairs_bwd(ms, kf, kept):
    """``_chunk_pairs``'s derivative by hand: from the gradient ``m``
    [heads, C, C] float32 of each x's pairs (0 where the pair is not
    read, j > i), ``(each x's [heads, C, dk], the keys' as the pairs'
    columns)``: ``dx_i[c] = sum_j m_ij k_j[c] exp(G_i[c] - G_j[c])``
    and ``dk_j[c] = sum_i m_ij x_i[c] exp(G_i[c] - G_j[c])`` with the
    forward's split, so that nothing larger than its ``e^75`` is
    formed: inside a sub-chunk float32 pieces about its first row,
    between sub-chunks the inputs' dtype with both factors at most 1."""
    heads, c, dk = kf.shape
    s, starts = _sub_chunks(c)
    count = len(ms)
    m = jnp.concatenate(ms, axis=1)  # [heads, count C, C]
    i = lax.broadcasted_iota(jnp.int32, m.shape[1:], 0) & (c - 1)
    j = lax.broadcasted_iota(jnp.int32, m.shape[1:], 1)
    own = _pieces(jnp.where(i // s == j // s, m, 0.0))
    dx = _dots_f32(own, kept.k_up, _NN)  # [heads, count C, dk]
    dk_cols = _dots_f32(own, kept.x, _TN) * kept.up  # [heads, C, dk]
    mc = m.astype(kept.xc.dtype)
    parts = [[jnp.zeros((heads, s, dk), F32)] for _ in ms]
    for r in starts:
        rows = _sub_rows(mc, r, s, count)  # [heads, count SUB, C]
        before = _dots(rows, kept.k_before[r], _NN)
        for n, part in enumerate(parts):
            part.append(before[:, n * s:(n + 1) * s])
        dk_cols = dk_cols + kept.before[r] * _dots(
            rows, _sub_rows(kept.xc, r, s, count), _TN)
    dx = (dx + jnp.concatenate([t for part in parts for t in part], axis=1)
          ) * jnp.concatenate([kept.down] * count, axis=1)
    return [dx[:, n * c:(n + 1) * c] for n in range(count)], dk_cols


def _chunk_inverse(a):
    """``(I + a)^-1`` of strictly lower ``a`` [heads, C, C] float32 by
    ``gated_delta._doubling_inverse``'s block substitution: with
    ``below`` the rows of the lower half of a block of 2n and the
    columns of its upper half, T_2n = T_n - T_n (A below(n)) T_n, and
    T_2 = I - (A below(1))."""
    c = a.shape[-1]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def below(n):
        return (i // (2 * n) == j // (2 * n)) & (i // n > j // n)

    a_pieces = _pieces(a)
    t = jnp.where(i == j, 1.0, 0.0) - jnp.where(below(1), a, 0.0)
    n = 2
    while n < c:
        t_pieces = _pieces(t)
        step = _dots_f32(tuple(jnp.where(below(n), piece, 0)
                               for piece in a_pieces), t_pieces, _NN)
        t = t - _dots_f32(t_pieces, _pieces(step), _NN)
        n *= 2
    return t


def _rule_chunk(q, k, v, big_g, beta, t=None):
    """One chunk of a block of heads prepared in VMEM: ``_prepare``'s
    formulas at its precisions on whole tiles. ``q`` (or None: nothing
    of the queries' is prepared), ``k`` [heads, C, dk] and ``v``
    [heads, C, dv] in the inputs' dtype; ``big_g`` [heads, C, dk] the
    sums of ``g`` down the chunk and ``beta`` [heads, C, 1], float32;
    ``t`` [heads, C, C] float32 the inverse where the caller has it.

    Every stage is written for all the block's heads at once, so that
    in the kernel's program the heads' products of one stage stand
    together and hide each other's way through the MXU; the compiler
    keeps the order it is given (a head's whole chunk after another's:
    4.43 ms the kernel where a level of the inverse for all heads
    before the next gives 2.83; as above)."""
    cd = k.dtype
    c = k.shape[1]
    kf, vf = k.astype(F32), v.astype(F32)
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    pairs, kept = _chunk_pairs(
        ([] if q is None else [q.astype(F32)]) + [kf], kf, big_g, cd)
    if t is None:
        t = _chunk_inverse(jnp.where(j < i, beta * pairs[-1], 0.0))
    gamma = jnp.exp(big_g)
    to_end = jnp.exp(big_g[:, c - 1:] - big_g)
    ch = SimpleNamespace(
        kf=kf, vf=vf, pairs_k=pairs[-1], kept=kept, t32=t, t=t.astype(cd),
        gamma=gamma, to_end=to_end, decay=gamma[:, c - 1:],
        bv=(beta * vf).astype(cd), bgk=(beta * gamma * kf).astype(cd),
        kd=(to_end * kf).astype(cd))
    ch.ubar = _dots(ch.t, ch.bv, _NN).astype(cd)
    ch.w = _dots(ch.t, ch.bgk, _NN).astype(cd)
    if q is not None:
        ch.p = jnp.where(j <= i, pairs[0], 0.0).astype(cd)
        ch.qg = (gamma * q.astype(F32)).astype(cd)
    return ch


def _rule_chunk_bwd(q, k, v, big_g, beta, do, start, t, ds):
    """The chunk's backward in VMEM, all the block's heads at once:
    the chunk prepared again (``_rule_chunk`` with the inverse handed
    in), ``_kda_bwd_kernel``'s seven products from the state ``start``
    [heads, dv, dk] float32 the chunk starts from and ``ds`` the
    gradient of the one it ends in, then the preparation's own
    derivative by hand. ``do`` [heads, C, dv] in the inputs' dtype.
    Returns the gradients of ``q``, ``k``, ``v``, of ``big_g`` (the
    SUMS of g) and of ``beta`` [heads, C, 1], float32, and the gradient
    of ``start``."""
    cd = k.dtype
    c = k.shape[1]
    ch = _rule_chunk(q, k, v, big_g, beta, t)
    qf, kf, gamma, to_end = q.astype(F32), ch.kf, ch.gamma, ch.to_end
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # the chain's
    sc, dsc = start.astype(cd), ds.astype(cd)
    uc = (ch.ubar.astype(F32) - _dots(ch.w, sc, _NT)).astype(cd)
    duc = (_dots(ch.p, do, _TN) + _dots(ch.kd, dsc, _NT)).astype(cd)
    dqg = _dots(do, sc, _NN)
    dp = _dots(do, uc, _NT)
    dkd = _dots(uc, dsc, _NN)
    dwc = (-_dots(duc, sc, _NN)).astype(cd)
    ddecay = jnp.sum(ds * start, axis=1, keepdims=True)  # [heads, 1, dk]
    ds = ch.decay * ds + _dots(do, ch.qg, _TN) - _dots(duc, ch.w, _TN)
    # Ubar = T (beta V) and W = T (beta Gamma K)
    dbv = _dots(ch.t, duc, _TN)
    dbgk = _dots(ch.t, dwc, _TN)
    dt = _dots(duc, ch.bv, _NT) + _dots(dwc, ch.bgk, _NT)
    # the inverse's own (``gated_delta._inverse_bwd``): -T^T dT T^T,
    # strictly lower
    t_pieces = _pieces(ch.t32)
    da = jnp.where(j < i, -_dots_f32(
        _pieces(_dots_f32(t_pieces, _pieces(dt), _TN)), t_pieces, _NT), 0.0)
    (dq, dk_rows), dk_cols = _chunk_pairs_bwd(
        [jnp.where(j <= i, dp, 0.0), beta * da], kf, ch.kept)
    dq = dq + gamma * dqg
    # dk's terms by the sign with which the gate's sums feel them: W
    # and the pairs' rows carry exp(G), the pairs' columns and Kd
    # exp(-G)
    dk_up = dk_rows + beta * gamma * dbgk
    dk_down = dk_cols + to_end * dkd
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    dbig_g = qf * dq + kf * (dk_up - dk_down) + jnp.where(
        row == c - 1, jnp.sum(ch.kd.astype(F32) * dkd, axis=1, keepdims=True)
        + ch.decay * ddecay, 0.0)
    dbeta = (jnp.sum(da * ch.pairs_k, axis=2, keepdims=True)
             + jnp.sum(dbv * ch.vf, axis=2, keepdims=True)
             + jnp.sum(dbgk * gamma * kf, axis=2, keepdims=True))
    return dq, dk_up + dk_down, beta * dbv, dbig_g, dbeta, ds


def _by_head(tile, heads):
    """[C, heads x columns] -> [heads, C, columns]."""
    d = tile.shape[1] // heads
    return jnp.stack([tile[:, h * d:(h + 1) * d] for h in range(heads)])


def _gate_sums(g, upward=False):
    """The sums of ``g`` [..., C, columns] float32 down the chunk
    (``upward``: from a row to the chunk's end) as a triangular
    product: a one is exact in bf16, so three products of pieces add
    the float32 values."""
    c = g.shape[-2]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    ones = ((j >= i) if upward else (j <= i)).astype(jnp.bfloat16)
    ones = jnp.concatenate([ones, ones, ones], axis=1)
    pieces = jnp.concatenate(_pieces(g)[::-1], axis=-2)
    if g.ndim == 2:
        return _dot(ones, pieces, _NN)
    return _dots(jnp.broadcast_to(ones, g.shape[:1] + ones.shape), pieces,
                 _NN)


def _beta_columns(beta_ref, heads):
    """``beta`` of the program's heads [heads, C, 1] from the layer's
    [C, H] tile: a head's column by a mask on the lanes."""
    betas = beta_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    first = pl.program_id(1) * heads
    return jnp.stack([
        jnp.sum(jnp.where(lane == first + h, betas, 0.0), axis=1,
                keepdims=True) for h in range(heads)])


def _chunk_stacks(heads, g_ref, beta_ref, *refs):
    """A program's chunk a head: ``refs``' tiles as [heads, C, columns]
    stacks, then the sums of g (a product for all the heads' columns at
    once) and ``beta``."""
    return (*(_by_head(ref[0], heads) for ref in refs),
            _by_head(_gate_sums(g_ref[0]), heads),
            _beta_columns(beta_ref, heads))


def _kda_rule_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                         o_ref, final_ref,  # outputs
                         s_scratch, *, heads: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        s_scratch[:] = s0_ref[0]

    dv = v_ref.shape[2] // heads
    ch = _rule_chunk(*_chunk_stacks(heads, g_ref, beta_ref, q_ref, k_ref,
                                    v_ref))
    # the chain, a head's two halves a stage each
    states = [s_scratch[h] for h in range(heads)]
    reads = [_chain_reads(state, ch.w[h], ch.ubar[h])
             for h, state in enumerate(states)]
    done = [_chain_writes(state, sc, u, ch.qg[h], ch.kd[h], ch.p[h],
                          ch.decay[h])
            for h, (state, (sc, u)) in enumerate(zip(states, reads))]
    for h, (o, s) in enumerate(done):
        o_ref[0, :, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
        s_scratch[h] = s

    @pl.when(n == pl.num_programs(2) - 1)
    def _final():
        final_ref[0] = s_scratch[:]


def _kda_rule_starts_kernel(k_ref, v_ref, g_ref, beta_ref, s0_ref,
                            start_ref, t_ref,  # outputs
                            s_scratch, *, heads: int):
    """``_kda_rule_fwd_kernel`` without the queries: in place of ``o``
    the state each chunk starts from and the chunk's inverse, which is
    what the backward cannot prepare again at a forward's price (the
    state) or at a third of it (the doubling)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scratch[:] = s0_ref[0]

    ch = _rule_chunk(None, *_chunk_stacks(heads, g_ref, beta_ref, k_ref,
                                          v_ref))
    t_ref[0, :, 0] = ch.t32
    states = [s_scratch[h] for h in range(heads)]
    reads = [_chain_reads(state, ch.w[h], ch.ubar[h])
             for h, state in enumerate(states)]
    for h, (state, (_, u)) in enumerate(zip(states, reads)):
        start_ref[0, h, 0] = state
        s_scratch[h] = _chain_next(state, u.astype(ch.kd.dtype), ch.kd[h],
                                   ch.decay[h])


def _kda_rule_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref,
                         start_ref, t_ref,  # inputs
                         dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                         ds_scratch, *, heads: int):
    @pl.when(pl.program_id(2) == 0)  # the chunks run last to first
    def _init():
        ds_scratch[:] = jnp.zeros_like(ds_scratch)

    dq, dk, dv, dbig_g, dbeta, ds = _rule_chunk_bwd(
        *_chunk_stacks(heads, g_ref, beta_ref, q_ref, k_ref, v_ref),
        _by_head(do_ref[0], heads), start_ref[0, :, 0], t_ref[0, :, 0],
        ds_scratch[:])
    ds_scratch[:] = ds
    # a sum of g is felt by every g up to its row: the sums of its
    # gradient from a row to the chunk's end
    dg = _gate_sums(dbig_g, upward=True)
    for ref, grad in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv), (dg_ref, dg)):
        d = grad.shape[2]
        for h in range(heads):
            ref[0, :, h * d:(h + 1) * d] = grad[h].astype(ref.dtype)
    # a head's column of the block's own [C, heads] tile by a mask
    lane = lax.broadcasted_iota(jnp.int32, dbeta_ref.shape[2:], 1)
    dbeta_ref[0, 0] = sum(jnp.where(lane == h, dbeta[h], 0.0)
                          for h in range(heads))


def _wide_spec(chunk, hb, d, order=lambda n: n):
    """A chunk of a head block's columns of [B, S, H d]."""
    return pl.BlockSpec((1, chunk, hb * d),
                        lambda i, hg, n: (i, order(n), hg))


def _beta_spec(chunk, h, order=lambda n: n):
    return pl.BlockSpec((1, chunk, h), lambda i, hg, n: (i, order(n), 0))


def _rule_forward(q, k, v, g, beta, s0, chunk, hb, interpret):
    """The kernel on a row of whole chunks in the layer's own layout:
    ``q``, ``k``, ``g`` [B, S, H dk], ``v`` [B, S, H dv], ``beta``
    [B, S, H], ``s0`` [B, H, dv, dk]. Returns ``(o [B, S, H dv], the
    final state)``."""
    b, s, h = beta.shape
    dk, dv = s0.shape[-1], s0.shape[-2]

    def build():
        return pl.pallas_call(
            functools.partial(_kda_rule_fwd_kernel, heads=hb),
            grid=(b, h // hb, s // chunk),
            in_specs=[_wide_spec(chunk, hb, dk), _wide_spec(chunk, hb, dk),
                      _wide_spec(chunk, hb, dv), _wide_spec(chunk, hb, dk),
                      _beta_spec(chunk, h), _state_spec(hb, dv, dk)],
            out_specs=[_wide_spec(chunk, hb, dv), _state_spec(hb, dv, dk)],
            out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
                       jax.ShapeDtypeStruct((b, h, dv, dk), F32)],
            scratch_shapes=[_vmem((hb, dv, dk))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="kda_rule_fwd",
        )

    return shared_call("kda_rule_fwd", DeviceScope.KDA,
                       (chunk, hb, interpret), (q, k, v, g, beta, s0), build)


def _rule_starts(k, v, g, beta, s0, chunk, hb, interpret):
    """The states pass on ``_rule_forward``'s operands: ``(the state
    each chunk starts from [B, H, N, dv, dk], the chunks' inverses
    [B, H, N, C, C])``, float32."""
    b, s, h = beta.shape
    dk, dv = s0.shape[-1], s0.shape[-2]
    n = s // chunk
    outs = [jax.ShapeDtypeStruct((b, h, n, dv, dk), F32),
            jax.ShapeDtypeStruct((b, h, n, chunk, chunk), F32)]

    def build():
        return pl.pallas_call(
            functools.partial(_kda_rule_starts_kernel, heads=hb),
            grid=(b, h // hb, n),
            in_specs=[_wide_spec(chunk, hb, dk), _wide_spec(chunk, hb, dv),
                      _wide_spec(chunk, hb, dk), _beta_spec(chunk, h),
                      _state_spec(hb, dv, dk)],
            out_specs=_specs(outs, hb, lambda i: i),
            out_shape=outs,
            scratch_shapes=[_vmem((hb, dv, dk))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="kda_rule_starts",
        )

    return shared_call("kda_rule_starts", DeviceScope.KDA,
                       (chunk, hb, interpret), (k, v, g, beta, s0), build)


def _rule_backward(q, k, v, g, beta, do, starts, t, chunk, hb, interpret):
    """The backward pass on ``_rule_forward``'s operands, ``do`` as
    ``o`` and ``_rule_starts``'s two results: the gradients of ``q``,
    ``k``, ``v`` (their dtype) and ``g`` (float32) in their layout, and
    ``beta``'s [B, H / hb, S, hb] float32: a block of its own a head
    block, since two programs of a ``parallel`` axis must not share an
    output block."""
    b, s, h = beta.shape
    dk, dv = starts.shape[-1], starts.shape[-2]
    last = s // chunk - 1
    back = lambda n: last - n  # noqa: E731
    wide = functools.partial(_wide_spec, chunk, hb, order=back)

    def build():
        return pl.pallas_call(
            functools.partial(_kda_rule_bwd_kernel, heads=hb),
            grid=(b, h // hb, last + 1),
            in_specs=[wide(dk), wide(dk), wide(dv), wide(dk),
                      _beta_spec(chunk, h, back), wide(dv)]
            + _specs((starts, t), hb, back),
            out_specs=[wide(dk), wide(dk), wide(dv), wide(dk),
                       pl.BlockSpec((1, 1, chunk, hb),
                                    lambda i, hg, n: (i, hg, back(n), 0))],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct(g.shape, F32),
                       jax.ShapeDtypeStruct((b, h // hb, s, hb), F32)],
            scratch_shapes=[_vmem((hb, dv, dk))],
            compiler_params=_params(("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="kda_rule_bwd",
        )

    return shared_call("kda_rule_bwd", DeviceScope.KDA,
                       (chunk, hb, interpret),
                       (q, k, v, g, beta, do, starts, t), build)


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
    against 22.58 at 128 x 8, and 20.08 at 64 x 2 (the heads a program
    of the head groups of two in which the two steps ran while a
    model's program called them, PR 62 and 63).

    The whole rule's forward kernel takes the same tiles (my chip runs,
    PR 63, TPU v5 lite, the same bench and shape): ``kda_rule_fwd``
    3.05 / 1.91 / 1.52 ms at 2 / 4 / 8 heads a program, where the
    preparation and the chain's forward it takes the place of are 8.62
    + 0.70; on a layer's 32 heads 12.17 / 7.52 / 6.09.

    So do the backward's two (my chip runs, PR 64, TPU v5 lite, the
    same bench at 2 x 8192 x 32 heads, the operands in the layer's
    layout): the states pass ``kda_rule_starts`` 11.73 / 7.14 / 5.71 ms
    and the backward pass ``kda_rule_bwd`` 10.71 / 7.75 / 6.79 at 2 /
    4 / 8 heads a program, 12.50 together where the two steps'
    backward they take the place of, sixteen head groups of two one
    after another, is 55.39."""
    chunk = 64
    while chunk > 8 and seq % chunk:
        chunk //= 2
    group = max(d for d in range(1, min(heads, 8) + 1) if heads % d == 0)
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
    """The transposed state [B, H, dv, dk] float32 a row starts from."""
    return (jnp.zeros((b, h, dv, dk), F32) if initial_state is None
            else initial_state.astype(F32).swapaxes(-1, -2))


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
    in ``q``, ``k``, ``v``, ``g``, ``beta`` and ``initial_state``: the
    preparation in XLA and the chain. ``chunk`` (a power of two) and
    ``heads_per_program`` default to ``chain_tiles``'s; a row that is
    no multiple of the chunk is padded with tokens that leave the state
    as it is (``g`` 0, ``beta`` 0)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    chunk, hb, pad = _tiles(s, h, chunk, heads_per_program)
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
        s0 = _start_state(initial_state, b, h, dk, dv)
    if use_kernels:
        o, final = _chain(qg, kd, w, ubar, p, decay[..., None, :], s0, hb,
                          _resolve_interpret(interpret))
    else:
        o, final = _chain_scan(qg, kd, w, ubar, p, decay, s0)
    o = jnp.moveaxis(o.reshape(b, h, n * chunk, dv), 1, 2)[:, :s]
    return o.astype(q.dtype), final.swapaxes(-1, -2)


def _rows(t, pad):
    """[B, S, H, ...] -> [B, S + pad, H x columns], the layer's layout."""
    t = t.reshape(t.shape[:2] + (-1,))
    return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t


def kda_forward(q, k, v, g, beta, initial_state=None,
                interpret: Optional[bool] = None,
                chunk: Optional[int] = None,
                heads_per_program: Optional[int] = None):
    """``kda``'s two results by the ``kda_rule_fwd`` kernel alone, with
    no derivative of its own (``kda_grouped`` gives it
    ``kda_backward``): the operands go in as the layer has them,
    [B, S, H x columns], and nothing of the preparation is written
    out."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    chunk, hb, pad = _tiles(s, h, chunk, heads_per_program)
    o, final = _rule_forward(
        _rows(q, pad), _rows(k, pad), _rows(v.astype(q.dtype), pad),
        _rows(g.astype(F32), pad), _rows(beta.astype(F32), pad),
        _start_state(initial_state, b, h, dk, dv),
        chunk, hb, _resolve_interpret(interpret))
    return o[:, :s].reshape(b, s, h, dv), final.swapaxes(-1, -2)


def kda_backward(q, k, v, g, beta, do,
                 interpret: Optional[bool] = None,
                 chunk: Optional[int] = None,
                 heads_per_program: Optional[int] = None):
    """The gradients of ``q``, ``k``, ``v``, ``g`` and ``beta`` (each
    in its shape and dtype) from the gradient ``do`` of
    ``kda_forward``'s output ``o`` on a row that starts from no state
    and whose final state nothing reads, by two kernels on all the
    heads in the layer's layout: ``kda_rule_starts`` (the state each
    chunk starts from and the chunk's inverse, float32, the only things
    of the rule that reach HBM) and ``kda_rule_bwd`` (the chunks last
    to first: a chunk prepared again in VMEM, the chain's derivative
    and the preparation's)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    chunk, hb, pad = _tiles(s, h, chunk, heads_per_program)
    interpret = _resolve_interpret(interpret)
    flat = (_rows(q, pad), _rows(k, pad), _rows(v.astype(q.dtype), pad),
            _rows(g.astype(F32), pad), _rows(beta.astype(F32), pad))
    starts, t = _rule_starts(*flat[1:], _start_state(None, b, h, dk, dv),
                             chunk, hb, interpret)
    *grads, dbeta = _rule_backward(
        *flat, _rows(do.astype(q.dtype), pad), starts, t, chunk, hb,
        interpret)
    # [B, H / hb, S, hb] -> [B, S, H]
    grads.append(jnp.moveaxis(dbeta, 1, 2))
    return tuple(grad[:, :s].reshape(like.shape).astype(like.dtype)
                 for grad, like in zip(grads, (q, k, v, g, beta)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    """The rule's output on the kernels, ``kda_forward``'s, with
    ``kda_backward`` for its derivative: the residuals are the inputs
    as the layer has them."""
    return kda_forward(q, k, v, g, beta, interpret=interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    return _rule(q, k, v, g, beta, interpret), (q, k, v, g, beta)


def _rule_bwd(interpret, inputs, do):
    return kda_backward(*inputs, do, interpret=interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda_grouped(q, k, v, g, beta, use_kernels: bool = True,
                interpret: Optional[bool] = None) -> jax.Array:
    """``kda``'s output from a row that starts from no state, named
    ``KEPT_NAMES``: what a layer calls. On the kernels the forward
    pass is ``kda_forward`` and keeps its inputs alone, the backward
    ``kda_backward``, both on all the heads at once in the layer's
    layout; with ``use_kernels=False`` forward and backward are
    ``kda``'s scan over chunks and its autodiff."""
    if use_kernels:
        o = _rule(q, k, v, g, beta, interpret)
    else:
        o = kda(q, k, v, g, beta, use_kernels=False)[0]
    return checkpoint_name(o, KEPT_NAMES[0])


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
