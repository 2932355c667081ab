"""Shared ppermute ring-step helpers.

The two comm/compute-overlap paths in this tree move data around a mesh
axis with ``lax.ppermute`` rings:

  * ``ops.ring_attention`` rotates KV shards one neighbor per step (the
    classic ring schedule — one ICI hop per step on a TPU torus);
  * ``ops.moe``'s chunked ``grouped_ep`` dispatch decomposes its row
    all-to-all into distance-``s`` permutes so each chunk's exchange can
    overlap the grouped GEMM on the previous chunk's rows.

Both build their permutation tables HERE so the ring mechanics cannot
fork between the call sites.

Why a distance-``s`` permute ring instead of a hop-by-hop relay for the
all-to-all: relaying block ``j`` through every intermediate shard would
put each block on the wire ``dist(i, j)`` times — O(P^2) blocks total —
while one ``ppermute`` per distance moves every block exactly once, so
the ring's total bytes equal the one-shot ``all_to_all``'s minus the
local (diagonal) block that never needs the wire. The G106 byte audit
relies on exactly this parity (``docs/static_analysis.md``).
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def neighbor_perm(n: int) -> List[Tuple[int, int]]:
    """The single-hop ring permutation (shard i -> i+1): what the KV
    rotation uses every step."""
    return [(i, (i + 1) % n) for i in range(n)]


def shifted_perm(n: int, shift: int) -> List[Tuple[int, int]]:
    """The distance-``shift`` permutation (shard i -> i+shift): one step
    of the ring all-to-all decomposition."""
    return [(i, (i + shift) % n) for i in range(n)]


def ring_shift(x, axis_name, n: int):
    """Rotate ``x`` one neighbor around the ring (a single ICI hop)."""
    return lax.ppermute(x, axis_name, neighbor_perm(n))


def ring_all_to_all(x: jax.Array, axis_name, n: int) -> jax.Array:
    """An ``all_to_all`` over the leading axis, decomposed into ``n-1``
    distance-``s`` ``ppermute`` steps.

    ``x``: ``[n, ...]`` where block ``j`` is the data THIS shard sends
    to shard ``j``. Returns ``[n, ...]`` where block ``j`` is the data
    shard ``j`` sent to THIS shard — the same contract as
    ``lax.all_to_all(x, axis_name, 0, 0)`` with the axis already split.

    The diagonal block (self -> self) never touches the wire; each of
    the other ``n-1`` blocks rides exactly one permute, so total wire
    bytes match the one-shot collective. Because each step's permute has
    no data dependency on any other step, a caller that interleaves
    these exchanges with independent compute (the chunked MoE dispatch)
    gives XLA's latency-hiding scheduler real overlap to find — the
    one-shot ``all_to_all`` is an opaque single op it cannot split.

    Differentiable: ``ppermute`` transposes to the inverse permutation,
    so the backward runs the mirrored ring for free.
    """
    i = lax.axis_index(axis_name)
    # local (diagonal) block: a dynamic slice, no wire traffic
    mine = lax.dynamic_slice_in_dim(x, i, 1, axis=0)
    out = jnp.zeros_like(x)
    out = lax.dynamic_update_slice_in_dim(out, mine, i, axis=0)
    for s in range(1, n):
        # send the block destined to shard (i+s); receive the block
        # shard (i-s) destined to me
        send = lax.dynamic_slice_in_dim(x, (i + s) % n, 1, axis=0)
        recv = lax.ppermute(send, axis_name, shifted_perm(n, s))
        out = lax.dynamic_update_slice_in_dim(
            out, recv, (i - s) % n, axis=0
        )
    return out
