"""Shared building blocks for the model families."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def dense_init(rng, shape, dtype, scale=None):
    """Fan-in-scaled normal initializer (scale defaults to
    1/sqrt(fan_in), fan_in = second-to-last dim)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
    return jax.random.normal(rng, shape, dtype) * scale


def layer_norm(x, scale, bias, eps):
    """LayerNorm with f32 statistics regardless of compute dtype. The
    scale/bias params are cast to x's dtype so the output dtype is
    stable under scan even when params are f32 and compute is bf16."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(axis=-1, keepdims=True)
    normed = (xf - mean) * lax.rsqrt(var + eps)
    return normed.astype(x.dtype) * scale.astype(x.dtype) + bias.astype(
        x.dtype
    )


def rms_norm(x, scale, eps):
    """RMSNorm with f32 statistics (llama-family); output keeps x's
    dtype (see layer_norm)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(
        x.dtype
    )


def norm_init(lead, d, dtype, bias=False):
    """The parameters of a norm over ``d`` columns, stacked over
    ``lead``: a scale of ones and, with ``bias``, a bias of zeros."""
    out = {"scale": jnp.ones(lead + (d,), dtype)}
    if bias:
        out["bias"] = jnp.zeros(lead + (d,), dtype)
    return out


def causal_conv(u, kernel, bias):
    """Depthwise: out[t] = sum_k kernel[k] * u[t - (K - 1) + k] + bias."""
    width, s = kernel.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    out = bias
    for k in range(width):
        out = out + kernel[k] * padded[:, k:k + s]
    return out


def cast_floats(tree, dtype):
    """Cast floating leaves to ``dtype`` (params stored f32, computed
    bf16 — the mixed-precision pattern); non-float leaves pass through."""
    return jax.tree.map(
        lambda w: w.astype(dtype)
        if jnp.issubdtype(w.dtype, jnp.floating) else w,
        tree,
    )


def param_count(init_fn) -> int:
    """Total parameter count of ``init_fn(rng)`` via abstract eval."""
    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    return sum(
        math.prod(int(s) for s in leaf.shape)
        for leaf in jax.tree.leaves(abstract)
    )


def segment_positions(segment_ids):
    """[B, S] segment ids -> position WITHIN each segment (positional
    encodings must restart per packed document, or later documents see
    phantom long distances). Shared by every packed-capable family."""
    b, s = segment_ids.shape
    idx = jnp.arange(s)[None, :]
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool),
         segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1,
    )
    starts = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    return idx - starts


def make_init_fn(init, config, layer_kinds, passes=None):
    """``init(rng, config=config)`` as the trainer takes it, with the
    model's layers by kind on it and, where the stack runs more than
    once a step, its ``passes``: ``ElasticTrainer`` puts them on its
    ``trainer_ready`` event."""
    init_fn = partial(init, config=config)
    init_fn.layer_kinds = layer_kinds
    if passes is not None:
        init_fn.passes = passes
    return init_fn


# -- the period-stacked decoder ---------------------------------------------
#
# A decoder whose layers are of several kinds, with different parameter
# trees, by a published per-layer list: the list's smallest period ``p``
# is found, the parameters are stacked by position in the period
# (``layers/<j>/`` holds position ``j`` of every period, ``[num_layers /
# p, ...]``: layer ``l`` is ``layers[str(l % p)]`` at ``l // p``), and
# the stack is ONE ``lax.scan`` over periods with the period's ``p``
# layers unrolled in its body, each a checkpoint of its own: a layer's
# slice of the scanned stack is then what its checkpoint keeps, and its
# gradient lands where it belongs without a copy of the period's other
# layers (what took SmallThinker's step from 18.21 to 13.68 GB,
# ``PERF.md`` PR 41). The model says its kinds, each kind's init and
# apply and what a checkpoint keeps; the layout is known here alone.


def period_of(kinds, num_layers: int, what: str) -> int:
    """The smallest ``p`` at which the per-layer list ``kinds`` (``what``
    names it to the reader of an error) repeats. Refuses a list shorter
    than the depth and a depth that is no whole number of periods."""
    kinds = list(kinds)
    if not 0 < num_layers <= len(kinds):
        raise ValueError(
            f"{what} ({len(kinds)} entries) gives each of {num_layers} "
            "layers its kind: at least as long as the depth")
    period = next(p for p in range(1, len(kinds) + 1)
                  if kinds[p:] == kinds[:-p])
    if num_layers % period:
        raise ValueError(
            f"{num_layers} layers is no whole number of periods: {what} "
            f"repeats every {period} layers, and the layers are stacked "
            "and scanned by the period")
    return period


def stacked_init(key, plan, layer_init):
    """``{str(j): layer_init(key_j, plan[j])}`` over one period's
    ``plan``: ``layer_init`` gives the layers at position ``j`` of every
    period, stacked over the periods."""
    return {str(j): layer_init(k, kind) for j, (kind, k) in enumerate(
        zip(plan, jax.random.split(key, len(plan))))}


def layer_slot(index: int, period: int):
    """Where layer ``index`` lies in ``stacked_init``'s tree: (its key,
    its place on the leaves' leading axis)."""
    return str(index % period), index // period


def scan_periods(layers, x, stacked):
    """``x`` through the whole stack: one ``lax.scan`` over the periods
    of ``stacked`` (``stacked_init``'s tree), ``layers[j](x, p) -> (x,
    out)`` in order in its body. Returns (``x``, the ``out`` trees
    summed leaf by leaf over a period's layers, ``[periods, ...]``)."""

    def period(x, p):
        outs = []
        for j, layer in enumerate(layers):
            x, out = layer(x, p[str(j)])
            outs.append(out)
        return x, jax.tree.map(lambda *a: sum(a), *outs)

    return lax.scan(period, x, stacked)
