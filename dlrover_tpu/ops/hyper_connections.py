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

Plain ``jax.numpy``, written for XLA to fuse (no kernel here):

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
* each of the three is a ``jax.checkpoint`` of its own: what a backward
  pass keeps of them is their arguments (the streams in their own
  dtype, the mappings), not the float32 widenings autodiff would save
  (four streams widened are twice the streams).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

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


@jax.named_scope(DeviceScope.HC_MAP)
@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4, 5))
def mappings(x: jax.Array, p: Dict, n: int, iters: int,
             clamp: Tuple[float, float], eps: float):
    """The three mappings of the ``n`` streams ``x [B, S, n * C]``,
    float32, tokens minor: ``(H_pre [n, B, S], H_post [n, B, S], H_res
    [n, n, B, S])``."""
    b, s, _ = x.shape
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1) + eps)  # [B, S]
    w = (p["norm"]["scale"].astype(jnp.float32)[:, None]
         * p["phi"]["kernel"].astype(jnp.float32)).astype(x.dtype)
    z = jnp.einsum("bsk,kf->fbs", x, w,
                   preferred_element_type=jnp.float32) * inv
    alpha = p["alpha"].astype(jnp.float32)
    bias = p["bias"].astype(jnp.float32)[:, None, None]
    pre = jax.nn.sigmoid(alpha[0] * z[:n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[n:2 * n] + bias[n:2 * n])
    res = jnp.exp(jnp.clip(alpha[2] * z[2 * n:] + bias[2 * n:], *clamp))
    return pre, post, sinkhorn(res.reshape(n, n, b, s), iters)


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
