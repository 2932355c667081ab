"""Mixture-of-Experts: router, capacity-based dispatch, expert parallelism.

Role parity: ``atorch/atorch/modules/moe/moe_layer.py:22-565`` (expert
process groups + ``_AllToAll`` autograd + ``Experts``) and
``switch_gating.py:24-195`` (top-1 gating with capacity and load-balance
aux loss). TPU-first: expert weights live on the expert submesh and XLA
inserts the all-to-alls from shardings — no hand-written autograd
collective is needed.

Four dispatch implementations share one routing core (``_routing``):

- ``"gather"`` (default, the fast path): a slot->token index map built
  from tiny int32 scatters turns dispatch into a pure gather of the
  token matrix and combine into a gather of the expert outputs. Data
  movement is O(T*D); the only O(T*E) work is the router's position
  bookkeeping. This replaces the reference's fastmoe/CUDA delegation
  (``moe_layer.py:511``) — on TPU the win comes from NOT materializing
  capacity-shaped dense compute, not from a custom kernel.
- ``"einsum"`` (the reference check): one-hot [T,E,C] dispatch/combine
  einsums, numerically transparent and GSPMD-friendly, but the einsums
  cost T*E*C*D = capacity_factor*T^2*D FLOPs — quadratic in tokens, so
  dispatch dominates expert FLOPs at practical T. Kept as the oracle
  the fast paths are tested against (``tests/test_ops.py``).
- ``"grouped"`` (DROPLESS, per-shard): the Pallas grouped-matmul kernel
  (``ops.grouped_matmul``) — megablocks-style. No capacity and no
  dropped tokens: rows sort by expert, groups pad to the row-tile, and
  the expert FFN runs as grouped GEMMs with the per-tile expert index
  on scalar prefetch; the row buffer holds every assignment plus a
  tile of padding an expert, and the tiles past the last group are
  skipped. The data-parallel-experts hot path; the kernel is
  opaque to GSPMD, so EP submesh sharding of its operands would force
  replication.
- ``"grouped_ep"`` (DROPLESS, expert-parallel): a ``shard_map`` over the
  expert submesh wrapping the same grouped kernel with EXPLICIT
  collectives — the TPU rendering of the reference's ``_AllToAll``
  expert process groups (``moe_layer.py:87``). Each shard routes its
  local tokens, exchanges per-(shard, expert) COUNTS with a tiny
  ``all_to_all`` so row padding stays tile-aligned and static-shaped,
  exchanges the token rows themselves with a second ``all_to_all``,
  runs the dropless grouped GEMMs on its local experts, and returns
  outputs through the reverse ``all_to_all`` and local combine. MoE
  FLOPs stay linear in tokens even with experts on different chips;
  the price is two all-to-alls each way, which ``parallel.planner``
  estimates against the capacity paths' quadratic dispatch.

Beside the switch-FFN above (``moe_ffn``: ``up``/``down`` experts,
softmax top-1/2, capacity or dropless), the GATED experts of which a
chip holds a set (``models/mla_moe.py``; the second half of this file):
``sigmoid_topk_routing`` scores every expert by a sigmoid, takes the
top-k of all of them and renormalises and scales their scores;
``held_expert_ffn`` computes, for the experts HELD here (an argument:
which of the layer's experts these weights are), ``down(silu(gate x) *
up x)`` through ``grouped_matmul``. It gathers and sorts only
the assignments to held experts, into a row buffer with a static bound
(``held_row_bound``: a multiple of what uniform routing sends the held
experts, not ``T * k``), skips the tiles past the last real group
instead of computing them, runs the XLA operations around the kernels
at the smallest rung of ``held_row_ladder`` that holds the rows that
arrived, has no capacity, and counts the assignments that fell past
the bound; what the experts held elsewhere would add is
left out (a chip's share of an expert-parallel layer, without the
exchange that one chip does not have). It is not a fifth ``dispatch``
string: a model that has ``experts_held`` takes it.

Planner guidance (``parallel/planner.py`` prices all four): "grouped" on
a per-shard (no-EP) mesh; "grouped_ep" when experts shard across chips
and per-chip token counts are large (all-to-all comm is linear in T
where the capacity fallback's dispatch is quadratic); "gather" for
small-token EP configs; "einsum" only as the testing oracle.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import (
    Any,
    Callable,
    Dict,
    List,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# metric keys surfaced to callers of ``moe_ffn``; _routing may carry
# additional internal entries (per-expert routing fractions the EP path
# pmean-reduces to reproduce the GLOBAL aux loss exactly)
PUBLIC_METRICS = ("dropped_frac", "expert_load")


@dataclass
class MoEConfig:
    num_experts: int
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    top_k: int = 1  # 1 = switch routing, 2 = gshard-style
    aux_loss_weight: float = 0.01
    router_jitter: float = 0.0  # multiplicative logit noise during training
    # "gather" (fast, capacity-based) | "einsum" (reference oracle) |
    # "grouped" (DROPLESS Pallas grouped matmul — per-shard experts) |
    # "grouped_ep" (DROPLESS + expert-parallel: shard_map + all_to_all
    # around the grouped kernel — experts sharded over ``ep_axes``)
    dispatch: str = "gather"
    # grouped-dispatch kernel mode: None = auto (interpreter off TPU),
    # False forces Mosaic (the deviceless-AOT contract)
    kernel_interpret: Optional[bool] = None
    # "grouped_ep" only: mesh axis name(s) forming the expert submesh
    # (tokens shard their batch dim and expert weights their expert dim
    # over these axes). The default matches the canonical rule sets'
    # (data x fsdp) expert submesh (``sharding_rules.moe_rules``).
    ep_axes: Tuple[str, ...] = ("data", "fsdp")
    # "grouped_ep" only: explicit mesh; None = the AMBIENT mesh
    # (``jax.sharding.set_mesh``, what accelerate establishes while
    # tracing) — rebuilt by every accelerate, so elastic-safe.
    mesh: Any = None
    # "grouped_ep" only: split the [P, n, D] row exchange into this many
    # static chunks driven by a ppermute ring (``ops.ring``), with the
    # grouped GEMM on already-arrived chunks overlapping the in-flight
    # exchange (double-buffered). 1 = the one-shot ``all_to_all``
    # (serial exchange -> GEMM -> exchange). 0 = resolve the global
    # Context knob (``dispatch_chunks``) at TRACE time, which is what
    # lets ``ElasticTrainer.retune`` re-chunk a running job through the
    # program cache with zero recompiles on a prewarmed value.
    dispatch_chunks: int = 0
    # "grouped_ep" only: the WIRE precision of the row exchanges
    # (``ops.quantize``). "bf16" = the exchange carries the compute
    # dtype unchanged; "fp8" = rows quantize to block-scaled e4m3
    # (values + f32 per-block scales, both exchanged — ~0.56x the
    # bytes) BEFORE the all_to_all / ppermute ring, forward rows AND
    # backward cotangents, with the up-projection consuming the wire
    # rows through the dequant-in-kernel grouped matmul; "fp8_qdq" =
    # the reference oracle (quantize->dequantize locally, wire at full
    # precision — bitwise identical outputs to "fp8", used by tests
    # and for isolating transport from numerics). "" = resolve the
    # Context knob (``moe_precision``) at TRACE time — the same
    # retune-without-rebuild contract as ``dispatch_chunks``. Falls
    # back to "bf16" (logged) when the backend fails the fp8
    # capability probe (``shard_compat.fp8_wire_supported``).
    precision: str = ""


def _capacity(num_tokens: int, num_experts: int, factor: float,
              top_k: int = 1) -> int:
    """Per-expert queue length, gshard convention: capacity scales with
    top_k (k assignments per token means k*T total demand — a k=2
    config at factor 1.25 would otherwise drop >= 37.5% of assignments
    by construction, under perfectly uniform routing)."""
    return max(1, int(math.ceil(
        num_tokens * top_k * factor / num_experts
    )))


def _routing(
    logits: jax.Array,  # [T, E]
    capacity: int,
    top_k: int,
    rng: Optional[jax.Array],
    jitter: float,
) -> Tuple[List[Tuple[jax.Array, ...]], jax.Array, Dict[str, jax.Array]]:
    """Shared routing core: per-round (expert, position, keep, gate).

    Round-by-round filling (all k=0 choices claim queue positions
    before any k=1 choice) with arrival-order priority inside a round —
    the switch/gshard semantics both dispatch paths must agree on.
    Everything here is [T] or [T, E]; the capacity axis never
    materializes. Returns (rounds, aux_loss, metrics) where each round
    is (expert_idx [T]i32, pos [T]i32, keep [T]f32, gate [T]f32) and
    metrics carries the load-balance observability signals
    (``switch_gating.py:24-195`` parity: capacity-overflow accounting).
    """
    t, e = logits.shape
    if rng is not None and jitter > 0.0:
        noise = jax.random.uniform(
            rng, logits.shape, minval=1.0 - jitter, maxval=1.0 + jitter
        )
        logits = logits * noise
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]

    remaining = probs
    expert_fill = jnp.zeros((e,), jnp.int32)
    total_onehot = jnp.zeros((t, e), jnp.float32)
    kept_per_expert = jnp.zeros((e,), jnp.float32)
    rounds = []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)  # [T]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [T, E]
        # position of each token within its expert's queue (arrival order)
        pos_in_expert = (
            jnp.cumsum(onehot, axis=0) - onehot
        ) * onehot  # [T, E]
        pos_in_expert = pos_in_expert + expert_fill[None, :] * onehot
        within = (pos_in_expert < capacity).astype(jnp.float32) * onehot
        pos = pos_in_expert.sum(axis=-1).astype(jnp.int32)  # [T]
        keep = within.sum(axis=-1)  # [T] 1.0 = assigned a queue slot
        gate = (probs * onehot).sum(axis=-1)  # [T]
        rounds.append((idx, pos, keep, gate))
        expert_fill = expert_fill + within.sum(axis=0).astype(jnp.int32)
        kept_per_expert = kept_per_expert + within.sum(axis=0)
        total_onehot = total_onehot + onehot
        remaining = remaining * (1.0 - onehot)

    # load-balance auxiliary loss (switch transformer eq. 4)
    frac_tokens = total_onehot.mean(axis=0)  # [E]
    frac_probs = probs.mean(axis=0)  # [E]
    aux_loss = e * jnp.sum(frac_tokens * frac_probs) / max(1, top_k)
    routed = total_onehot.sum(axis=0)  # [E] pre-drop demand per expert
    metrics = {
        # fraction of (token, round) assignments that overflowed capacity
        "dropped_frac": 1.0 - kept_per_expert.sum() / float(t * top_k),
        # pre-drop routing demand per expert, as a fraction of tokens;
        # uniform = 1/E. This is the signal the aux loss regularizes.
        "expert_load": routed / float(t * top_k),
        # internal (not in PUBLIC_METRICS): the aux loss's two per-expert
        # fraction vectors. The expert-parallel path pmean-reduces these
        # across token shards — means of equal-sized local means ARE the
        # global means, so the reduced aux equals the single-shard oracle
        "frac_tokens": frac_tokens,
        "frac_probs": frac_probs,
    }
    return rounds, aux_loss, metrics


def router_dispatch(
    logits: jax.Array,  # [T, E]
    capacity: int,
    top_k: int = 1,
    rng: Optional[jax.Array] = None,
    jitter: float = 0.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compute (dispatch_mask [T,E,C], combine_weights [T,E,C], aux_loss).

    The reference-path materialization of ``_routing``: each token goes
    to its top-k experts, subject to a per-expert capacity; overflowing
    tokens are dropped (their combine weight is zero, so the residual
    path carries them).
    """
    t, e = logits.shape
    rounds, aux_loss, _ = _routing(logits, capacity, top_k, rng, jitter)
    dispatch, combine = _materialize(rounds, t, e, capacity)
    return dispatch, combine, aux_loss


def _materialize(rounds, t: int, e: int, capacity: int):
    """[T,E,C] one-hot dispatch/combine from routing rounds — the single
    source both ``router_dispatch`` and the einsum oracle build on."""
    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    for idx, pos, keep, gate in rounds:
        within = jax.nn.one_hot(idx, e, dtype=jnp.float32) * keep[:, None]
        pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
        dispatch = dispatch + within[:, :, None] * pos_oh[:, None, :]
        combine = combine + (
            gate[:, None, None] * within[:, :, None] * pos_oh[:, None, :]
        )
    return dispatch, combine


def _moe_compute_einsum(params, xt, rounds, capacity, e, activation):
    """[T,E,C] one-hot dispatch/combine (the reference check)."""
    t = xt.shape[0]
    dispatch, combine = _materialize(rounds, t, e, capacity)
    # all-to-all #1: tokens -> expert queues (XLA inserts the collective
    # when experts are mesh-sharded). The SPMD partitioner may log an
    # "involuntary full rematerialization" for the [T,1,1] gate broadcast
    # when dispatch/combine consumers want different T shardings — that
    # tensor is tokens*4 bytes, so the replicate-and-repartition it falls
    # back to is noise, not a bandwidth problem.
    expert_in = jnp.einsum(
        "tec,td->ecd", dispatch.astype(xt.dtype), xt
    )  # [E, C, D]
    h = activation(jnp.einsum(
        "ecd,edf->ecf", expert_in, params["experts"]["up"]["kernel"]
    ))
    expert_out = jnp.einsum(
        "ecf,efd->ecd", h, params["experts"]["down"]["kernel"]
    )  # [E, C, D]
    # all-to-all #2: expert queues -> tokens
    return jnp.einsum("tec,ecd->td", combine.astype(xt.dtype), expert_out)


def _moe_compute_gather(params, xt, rounds, capacity, e, activation):
    """Slot-indexed dispatch/combine (the fast path).

    A [E*C+1] int32 slot->token map is built with scatters whose
    operand is tokens*4 bytes (dropped tokens write the sentinel slot);
    the [E,C,D] expert input is then a single gather of the token
    matrix, and combine is a gather of the expert outputs weighted by
    the gates. Identical routing semantics to the einsum path by
    construction — both consume the same ``_routing`` rounds.
    """
    t, d = xt.shape
    n_slots = e * capacity
    token_ids = jnp.arange(t, dtype=jnp.int32)
    # sentinel slot n_slots absorbs dropped tokens; sentinel token t
    # backs empty slots with a zero row
    slot_token = jnp.full((n_slots + 1,), t, jnp.int32)
    for idx, pos, keep, _gate in rounds:
        flat = jnp.where(keep > 0, idx * capacity + pos, n_slots)
        slot_token = slot_token.at[flat].set(token_ids)
    x_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    expert_in = x_pad[slot_token[:n_slots]].reshape(e, capacity, d)
    h = activation(jnp.einsum(
        "ecd,edf->ecf", expert_in, params["experts"]["up"]["kernel"]
    ))
    expert_out = jnp.einsum(
        "ecf,efd->ecd", h, params["experts"]["down"]["kernel"]
    ).reshape(n_slots, d)
    out = jnp.zeros((t, d), xt.dtype)
    for idx, pos, keep, gate in rounds:
        flat = jnp.clip(idx * capacity + pos, 0, n_slots - 1)
        weight = (gate * keep).astype(xt.dtype)[:, None]
        out = out + expert_out[flat] * weight
    return out


def _moe_compute_grouped(params, xt, rounds, e, activation,
                         block_t: int = 128,
                         interpret: Optional[bool] = None):
    """DROPLESS dispatch via the grouped-matmul Pallas kernel
    (``ops.grouped_matmul``) — megablocks-style: NO capacity, NO
    dropped tokens.

    Every (token, round) assignment is served: rows are sorted by
    expert with each group padded up to the row-tile, and the expert
    FFN runs as two grouped matmuls whose per-tile expert index rides
    scalar prefetch. Static shapes throughout — padded rows are the
    upper bound ceil(T*k / bt)*bt + E*bt, so XLA sees one program
    regardless of the routing. Pad overhead is at most E*(block_t-1)
    rows vs the capacity approach's (factor-1)*T slots plus overflow
    drops.

    Scope: the per-shard (data-parallel experts) hot path. With experts
    sharded over an expert submesh (EP), use the "gather"/"einsum"
    dispatches — the kernel is opaque to GSPMD, so EP sharding of its
    operands would force replication instead of all-to-alls.
    """
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    t, d = xt.shape
    k = len(rounds)
    n = t * k
    # assignments in round-major arrival order (matches _routing's
    # queue discipline: every k=0 choice precedes any k=1 choice)
    expert_a = jnp.concatenate([r[0] for r in rounds])  # [n] int32
    gate_a = jnp.concatenate([r[3] for r in rounds])  # [n] f32
    token_a = jnp.tile(jnp.arange(t, dtype=jnp.int32), k)
    # with capacity == T nothing overflows, so _routing's queue
    # positions ARE each assignment's within-expert arrival rank
    # (cross-round fill included) — no second [n, E] cumsum needed
    rank = jnp.concatenate([r[1] for r in rounds])  # [n] int32
    counts = jnp.zeros((e,), jnp.int32).at[expert_a].add(1)  # [E]
    # every expert gets AT LEAST one tile, even with zero routed
    # tokens: its sentinel-zero rows make the dw kernel INITIALIZE that
    # expert's gradient block to zero — an unvisited output block would
    # be uninitialized garbage on real TPU (interpret mode zero-fills,
    # which would mask the bug)
    padded = jnp.maximum(
        ((counts + block_t - 1) // block_t), 1
    ) * block_t  # [E]
    ends = jnp.cumsum(padded).astype(jnp.int32)  # [E]
    offsets = ends - padded.astype(jnp.int32)  # [E] exclusive
    row = offsets[expert_a] + rank  # [n] destination row, unique
    # static padded-row bound: every group full + its tile padding
    tp = ((n + block_t - 1) // block_t) * block_t + e * block_t
    # row -> token map; pad rows read the zero sentinel row of x_pad
    row_token = jnp.full((tp,), t, jnp.int32).at[row].set(token_a)
    x_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    x_sorted = x_pad[row_token]
    # tile i belongs to the expert whose [offset, end) span covers it;
    # tiles past the last real group clip to the final expert and are
    # skipped by the kernels (``num_tiles``): no product, zeros
    tile_start = jnp.arange(tp // block_t, dtype=jnp.int32) * block_t
    tile_expert = jnp.clip(
        jnp.searchsorted(ends, tile_start, side="right"), 0, e - 1
    ).astype(jnp.int32)
    num_tiles = (ends[-1] // block_t).astype(jnp.int32).reshape(1)

    h = activation(grouped_matmul(
        x_sorted, params["experts"]["up"]["kernel"], tile_expert,
        block_t, 512, interpret, num_tiles,
    ))
    y_sorted = grouped_matmul(
        h, params["experts"]["down"]["kernel"], tile_expert,
        block_t, 512, interpret, num_tiles,
    )
    # combine: unsort + gate weight, summing each token's k rounds
    y_a = y_sorted[row] * gate_a[:, None].astype(y_sorted.dtype)
    return jnp.zeros((t, d), xt.dtype).at[token_a].add(
        y_a.astype(xt.dtype)
    )


# -- gated experts of which this chip holds a set ---------------------------


def sigmoid_topk_routing(logits: jax.Array, top_k: int,
                         renormalise: bool = True, scale: float = 1.0,
                         selection_bias: Optional[jax.Array] = None):
    """Score every expert by ``sigmoid(logits)`` in float32, select the
    ``top_k`` best of all of them (no capacity, no groups) and weigh
    each selected expert by its score, over the selected scores' sum if
    ``renormalise``, times ``scale``. ``selection_bias [E]`` (the
    balancing bias of DeepSeek-V3's ``noaux_tc``) is added to the
    scores for the selection alone: it moves which experts are chosen,
    never a weight, and takes no gradient. A.X-K1 passes none. Returns
    ``(experts [T, k] int32, weights [T, k] float32, scores [T, E]
    float32)``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    if selection_bias is None:
        top_s, top_i = lax.top_k(scores, top_k)
    else:
        _, top_i = lax.top_k(scores + lax.stop_gradient(
            selection_bias.astype(jnp.float32)), top_k)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if renormalise:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_i.astype(jnp.int32), top_s * scale, scores


def expert_load(top_i: jax.Array, num_experts: int) -> jax.Array:
    """The tokens each of ``num_experts`` experts was selected by:
    ``top_i [T, k]`` to ``[E]`` float32 (a comparison and a sum; a
    scatter inside a layer scan is what the v5e's compiler refuses,
    see ``held_expert_ffn``)."""
    return jnp.sum(top_i[:, :, None] == jnp.arange(
        num_experts, dtype=top_i.dtype), axis=(0, 1), dtype=jnp.float32)


def selection_bias_update(bias: jax.Array, load: jax.Array,
                          rate: float) -> jax.Array:
    """The selection bias after a step, by the load alone and no
    gradient (DeepSeek-V3's auxiliary-loss-free balancing as
    torchtitan's ``load_balance_coeff`` runs it): ``delta = rate *
    sign(mean(load) - load)`` an expert, so an expert under the mean is
    raised and one over it lowered, and ``bias + delta - mean(delta)``,
    so the bias keeps its mean. ``bias`` and ``load`` ``[..., E]``, a
    layer a row; float32."""
    load = load.astype(jnp.float32)
    delta = rate * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
    return bias + delta - jnp.mean(delta, axis=-1, keepdims=True)


def topk_softmax_routing(logits: jax.Array, top_k: int,
                         renormalise: bool = True):
    """Score every expert by ``softmax(logits)`` over ALL of them in
    float32, select the ``top_k`` largest (no capacity, no groups) and
    weigh each selected expert by its probability, over the selected
    ones' sum if ``renormalise``: that is the softmax of the selected
    logits alone, and it is computed so. No scale, no bias. Returns
    ``sigmoid_topk_routing``'s triple: ``(experts [T, k] int32, weights
    [T, k] float32, scores [T, E] float32)``."""
    logits = logits.astype(jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    top_l, top_i = lax.top_k(logits, top_k)
    top_w = (jax.nn.softmax(top_l, axis=-1) if renormalise
             else jnp.take_along_axis(scores, top_i, axis=-1))
    return top_i.astype(jnp.int32), top_w, scores


def sequence_balance_loss(scores: jax.Array, top_i: jax.Array,
                          batch: int) -> jax.Array:
    """The sequence-wise balance loss of a layer, before its
    coefficient: over each sequence ``sum_i f_i P_i`` with ``f_i`` the
    share of the sequence's selections that chose expert ``i`` times
    the number of experts, and ``P_i`` the mean over its tokens of the
    score ``s_i / sum_j s_j``; then the mean over sequences. 1 where
    routing is uniform. ``scores [B*S, E]``, ``top_i [B*S, k]``."""
    t, e = scores.shape
    k = top_i.shape[1]
    seq = t // batch
    chosen = jnp.zeros((batch, e), jnp.float32).at[
        jnp.repeat(jnp.arange(batch), seq * k), top_i.reshape(-1)].add(1.0)
    f = chosen * (e / (seq * k))
    p = (scores / jnp.sum(scores, axis=-1, keepdims=True)).reshape(
        batch, seq, e).mean(axis=1)
    return jnp.mean(jnp.sum(f * p, axis=-1))


def held_row_bound(num_tokens: int, top_k: int, num_experts: int,
                   held: int, factor: float, block_t: int = 128) -> int:
    """The static row buffer of ``held_expert_ffn``: ``factor`` times
    the rows uniform routing sends to ``held`` of ``num_experts``
    experts (``T * k * held / E``), and never more than every
    assignment; plus a tile of padding a held expert, in whole tiles."""
    expected = num_tokens * top_k * held / num_experts
    rows = min(int(math.ceil(expected * factor)), num_tokens * top_k)
    return (-(-rows // block_t) + held) * block_t


def held_row_ladder(num_tokens: int, top_k: int, num_experts: int,
                    held: int, factor: float,
                    block_t: int = 128) -> Tuple[int, ...]:
    """The static row counts at which ``held_expert_ffn`` exists,
    ascending: ``held_row_bound`` last, before it its halvings in whole
    tiles, none smaller than what uniform routing sends (the bound at
    a factor of 1). A layer runs at the smallest that holds the rows
    that arrived, so the bound is what a skewed step may take and not
    what every step pays. Each rung is one more copy of the expert
    section to compile."""
    args = (num_tokens, top_k, num_experts, held)
    floor = held_row_bound(*args, min(factor, 1.0), block_t)
    ladder = [held_row_bound(*args, factor, block_t)]
    while True:
        half = -(-ladder[0] // (2 * block_t)) * block_t
        if half < floor or half == ladder[0]:
            return tuple(ladder)
        ladder.insert(0, half)


class _HeldRows:
    """Gather to combine of ``held_expert_ffn`` on the first ``n`` rows
    of its layout, in the pieces its backward is made of: the XLA
    operations (``gather``, ``gate``, ``combine``) and, between them,
    the grouped matmuls (``gmm``)."""

    def __init__(self, n, block_t, interpret, activation, tokens, layout):
        row_token, tile_expert, self.num_tiles = layout
        self.n, self.block_t, self.interpret = n, block_t, interpret
        self.activation = activation
        self.tokens = tokens  # [T, D]: shape and dtype alone
        self.row_token = row_token[:n]
        self.tile_expert = tile_expert[:n // block_t]

    @classmethod
    def at_each(cls, ladder, block_t, interpret, activation, method):
        """``method`` at every rung: the branches of a ``switch`` over
        ``(layout, experts, xt, ...)``."""
        def at(n):
            return lambda layout, experts, xt, *rest: method(
                cls(n, block_t, interpret, activation, xt, layout),
                experts, xt, *rest)

        return [at(n) for n in ladder]

    def gather(self, xt):
        # a pad row's token is T, past the last: it reads zeros here
        # and ``combine`` leaves it out
        return xt.at[self.row_token].get(mode="fill", fill_value=0)

    def gmm(self, rows, kernel):
        from dlrover_tpu.ops.grouped_matmul import grouped_matmul

        return grouped_matmul(rows, kernel, self.tile_expert, self.block_t,
                              interpret=self.interpret,
                              num_tiles=self.num_tiles)

    def gate(self, gate, up, row_weight, act=None):
        # the down projection is linear: a row's weight goes in before
        # it, on the narrow side, and the combine is a plain sum into
        # the token. ``act``: the parameters of an activation that has
        # some (``experts["act"]``), which is then ``activation(rows,
        # act)`` over a row's columns and not elementwise
        hidden = (self.activation(gate) if act is None
                  else self.activation(gate, act)) * up
        return hidden * row_weight[:self.n, None].astype(hidden.dtype)

    def combine(self, y):
        return jnp.zeros(self.tokens.shape, jnp.float32).at[
            self.row_token].add(y.astype(jnp.float32), mode="drop").astype(
                self.tokens.dtype)

    def forward(self, experts, xt, row_weight):
        x_sorted = self.gather(xt)
        hidden = self.gate(self.gmm(x_sorted, experts["gate"]["kernel"]),
                           self.gmm(x_sorted, experts["up"]["kernel"]),
                           row_weight, *_act_of(experts))
        return self.combine(self.gmm(hidden, experts["down"]["kernel"]))

    def backward(self, experts, xt, row_weight, d_out):
        """The cotangents of ``forward``'s arguments. The XLA pieces
        are transposed by ``jax.vjp``; the grouped matmuls by their own
        rule called as it stands (``grouped_matmul._gm_bwd``), because
        under a ``jax.vjp`` in here their instructions would be named
        ``transpose(jvp(gmm_dx))`` and no longer ``gmm_dx``, ``gmm_dw``,
        by which a trace's readers find them."""
        from dlrover_tpu.ops.grouped_matmul import _auto_interpret, _gm_bwd

        def gmm_bwd(rows, name, d_rows):
            d_in, d_kernel, _, _ = _gm_bwd(  # 2048: the default block_f
                self.block_t, 2048, _auto_interpret(self.interpret),
                (rows, experts[name]["kernel"], self.tile_expert,
                 self.num_tiles), d_rows)
            return d_in, {"kernel": d_kernel}

        x_sorted, gather_t = jax.vjp(self.gather, xt)
        hidden, gate_t = jax.vjp(
            self.gate, self.gmm(x_sorted, experts["gate"]["kernel"]),
            self.gmm(x_sorted, experts["up"]["kernel"]), row_weight,
            *_act_of(experts))
        (d_y,) = jax.linear_transpose(self.combine, jax.ShapeDtypeStruct(
            (self.n, xt.shape[1]), xt.dtype))(d_out)
        d_hidden, d_down = gmm_bwd(hidden, "down", d_y)
        d_gate_rows, d_up_rows, d_row_weight, *d_act = gate_t(d_hidden)
        d_x_gate, d_gate = gmm_bwd(x_sorted, "gate", d_gate_rows)
        d_x_up, d_up = gmm_bwd(x_sorted, "up", d_up_rows)
        (d_xt,) = gather_t(d_x_gate + d_x_up)
        d_experts = {"gate": d_gate, "up": d_up, "down": d_down}
        if d_act:
            d_experts["act"] = d_act[0]
        return d_experts, d_xt, d_row_weight


def _act_of(experts):
    """The gate stage's activation parameters, where the experts have
    some: ``(experts["act"],)`` or ``()``."""
    return (experts["act"],) if "act" in experts else ()


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _held_rungs(ladder, block_t, interpret, activation, experts, xt,
                row_weight, layout):
    """``_HeldRows.forward`` at ``ladder[rung]`` rows, ``layout`` being
    ``(row_token, tile_expert, num_tiles, rung)``. The backward
    branches as the forward did and keeps the inputs alone, computing
    the rung's forward again: the gradient of a ``cond`` would carry
    every rung's residuals out of the branch, zeros for the rungs not
    taken (1.85 GB more on the A.X-K1 step; PERF.md section 6, PR 35),
    and a layer under full remat, as the models here run, replays its
    forward in the backward anyway, where this one replaces that."""
    *layout, rung = layout
    return lax.switch(
        rung, _HeldRows.at_each(ladder, block_t, interpret, activation,
                                _HeldRows.forward),
        layout, experts, xt, row_weight)


def _held_rungs_fwd(ladder, block_t, interpret, activation, experts, xt,
                    row_weight, layout):
    out = _held_rungs(ladder, block_t, interpret, activation, experts, xt,
                      row_weight, layout)
    return out, (experts, xt, row_weight, layout)


def _held_rungs_bwd(ladder, block_t, interpret, activation, saved, d_out):
    experts, xt, row_weight, (*layout, rung) = saved
    grads = lax.switch(
        rung, _HeldRows.at_each(ladder, block_t, interpret, activation,
                                _HeldRows.backward),
        layout, experts, xt, row_weight, d_out)
    return (*grads, None)


_held_rungs.defvjp(_held_rungs_fwd, _held_rungs_bwd)


def held_expert_ffn(experts: dict, xt: jax.Array, top_i: jax.Array,
                    top_w: jax.Array, held: Tuple[int, ...],
                    rows: Union[int, Sequence[int]], block_t: int = 128,
                    interpret: Optional[bool] = None,
                    activation=jax.nn.silu):
    """The part of a routed expert layer that the experts HELD here
    give: ``out[t] = sum over the selected experts e of token t that
    are in held of top_w[t, e] * down_e(act(gate_e x_t) * up_e x_t)``,
    ``act`` the static elementwise ``activation`` (SiLU: SwiGLU experts;
    ``jax.nn.relu``: ReGLU). Where ``experts`` holds ``act``, a tree of
    trained parameters all the held experts share, the gate stage is
    ``activation(gate rows [n, F], experts["act"])``, free to read a
    whole row (PolyNorm normalises over an expert's ``F`` gate columns),
    and ``act``'s gradient comes back with the kernels'; a pad row's
    gate columns are zeros and its ``up`` columns too.
    The router is whole (``top_i`` indexes all the layer's experts,
    ``top_w`` was normalised over all the selected ones); what the
    experts held elsewhere would add is left out, as on a chip of an
    expert-parallel deployment before the exchange that is not here.

    ``experts``: ``gate``/``up`` ``[H, D, F]`` and ``down`` ``[H, F,
    D]`` kernels (and ``act``, above), slot ``h`` being expert
    ``held[h]``. Only assignments
    to held experts are gathered and sorted (by slot, each group padded
    to the row tile), into a buffer of ``rows`` rows
    (``held_row_bound``); the grouped matmuls skip the tiles past the
    last group (``grouped_matmul``'s ``num_tiles``). No capacity: an
    assignment is left out only where the buffer is full (every held
    expert keeps a tile of it), and ``rows_dropped`` counts those,
    which a caller that promises none checks.

    ``rows`` may be a ladder of row counts, ascending
    (``held_row_ladder``): the rows are laid out against the last, the
    bound, and everything from the gather to the combine (the XLA
    operations have static shapes, and so do their transposes) runs on
    the first ``n`` rows of that layout, ``n`` the smallest rung at or
    past the last group's end. The rows beyond it are then all pad
    rows, which read zeros and are left out of the sum: the same sums
    in the same order, at the cost of the rows that arrived.

    Returns ``(out [T, D] in xt's dtype, {"rows_held", "rows_max",
    "rows_dropped", "rows_buffered"})``: assignments to held experts,
    the most one expert got, those past the bound, and the rung that
    ran, as float32 scalars."""
    t = xt.shape[0]
    k = top_i.shape[1]
    h = len(held)
    ladder = (rows,) if isinstance(rows, int) else tuple(rows)
    row_bound = ladder[-1]
    if (any(n % block_t or n < h * block_t for n in ladder)
            or list(ladder) != sorted(set(ladder))):
        raise ValueError(f"rows {ladder}: ascending whole tiles of "
                         f"{block_t}, at least one a held expert ({h})")
    num_experts = max(held) + 1
    # expert index -> slot here, or -1; a constant of the trace: built
    # on the device (a scatter of h elements) inside a model's layer
    # scan it stops the v5e's compiler (scatter_emitter.cc, PR 34)
    table = np.full((num_experts,), -1, np.int32)
    table[list(held)] = np.arange(h, dtype=np.int32)
    slot_of = jnp.asarray(table)
    # assignments in token order; a selected expert beyond the table
    # (held elsewhere) has no slot
    expert_a = top_i.reshape(-1)
    slot_a = jnp.where(expert_a < num_experts,
                       slot_of[jnp.minimum(expert_a, num_experts - 1)], -1)
    token_a = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    here = slot_a[:, None] == jnp.arange(h, dtype=jnp.int32)  # [T k, H]
    arrived = jnp.cumsum(here.astype(jnp.int32), axis=0)
    counts = arrived[-1]  # [H]
    rank = jnp.sum(jnp.where(here, arrived, 0), axis=1) - 1
    # every held expert owns at least one tile, so that the dw kernel
    # initialises its block (see ``grouped_matmul``), also where the
    # buffer is full: a group ends no later than leaves a tile for each
    # expert after it, and the assignments past its end are left out
    want = jnp.maximum(-(-counts // block_t), 1) * block_t
    ends = jnp.minimum(
        jnp.cumsum(want),
        row_bound - (h - 1 - jnp.arange(h, dtype=jnp.int32)) * block_t)
    padded = jnp.diff(ends, prepend=0)
    slot = jnp.maximum(slot_a, 0)
    fits = jnp.logical_and(slot_a >= 0, rank < padded[slot])
    dropped = jnp.sum(jnp.logical_and(slot_a >= 0, ~fits))
    # out of range: left out below
    row = jnp.where(fits, (ends - padded)[slot] + rank, row_bound)
    row_token = jnp.full((row_bound,), t, jnp.int32).at[row].set(
        token_a, mode="drop")  # a pad row's token is T: no token
    row_weight = jnp.zeros((row_bound,), jnp.float32).at[row].set(
        top_w.reshape(-1).astype(jnp.float32), mode="drop")
    tiles = row_bound // block_t
    tile_expert = jnp.clip(jnp.searchsorted(
        ends, jnp.arange(tiles, dtype=jnp.int32) * block_t, side="right"),
        0, h - 1).astype(jnp.int32)
    num_tiles = (ends[-1] // block_t).astype(jnp.int32).reshape(1)

    # a rung under the bound holds every group only where no clamp of
    # ``ends`` has bitten: each group has its whole tiles and the rows
    # past the rung are pad rows
    rung = jnp.sum(ends[-1] > jnp.asarray(ladder[:-1], jnp.int32))
    out = _held_rungs(ladder, block_t, interpret, activation, experts, xt,
                      row_weight, (row_token, tile_expert, num_tiles, rung))
    f32 = jnp.float32
    return out, {
        "rows_held": jnp.sum(counts).astype(f32),
        "rows_max": jnp.max(counts).astype(f32),
        "rows_dropped": dropped.astype(f32),
        "rows_buffered": jnp.asarray(ladder, f32)[rung]}


def held_expert_ffn_reference(experts, xt, top_i, top_w, held,
                              activation=jax.nn.silu):
    """``held_expert_ffn`` as dense einsums over every (token, held
    expert) pair: the oracle of the tests."""
    sel = (top_i[:, :, None] == jnp.asarray(held, jnp.int32)).astype(
        jnp.float32)  # [T, k, H]
    weight = jnp.einsum("tk,tkh->th", top_w.astype(jnp.float32), sel)
    gate = jnp.einsum("td,hdf->thf", xt, experts["gate"]["kernel"])
    up = jnp.einsum("td,hdf->thf", xt, experts["up"]["kernel"])
    hidden = activation(gate, *_act_of(experts)) * up
    y = jnp.einsum("thf,hfd->thd", hidden, experts["down"]["kernel"])
    return jnp.einsum("thd,th->td", y.astype(jnp.float32),
                      weight).astype(xt.dtype)


def ambient_ep_mesh(axes: Tuple[str, ...]):
    """The ambient mesh (``shard_compat.ambient_mesh`` — what
    ``accelerate`` establishes while tracing) when it
    carries every axis in ``axes`` with none of them already manual;
    else None.

    Mirrors ``ops.ring_attention.ambient_ring_mesh``: a mesh frozen into
    a config at startup would survive ``on_world_change``'s
    re-accelerate and make the shard_map reference departed devices; the
    ambient mesh is rebuilt with each accelerate, so ``dispatch=
    "grouped_ep"`` stays elastic-safe with ``mesh=None``.
    """
    from dlrover_tpu.ops.shard_compat import ambient_mesh_with_axes

    return ambient_mesh_with_axes(axes)


def _resolve_ep_mesh(config: "MoEConfig"):
    """(mesh, axes, ep_degree) for ``dispatch="grouped_ep"``.

    ``(None, axes, 1)`` when no usable expert submesh exists — the
    caller degrades to the per-shard "grouped" path (identical math;
    the elastic world may legitimately have shrunk the submesh to 1).
    """
    axes = tuple(config.ep_axes)
    mesh = config.mesh
    if mesh is None:
        mesh = ambient_ep_mesh(axes)
        if mesh is None:
            return None, axes, 1
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    missing = [a for a in axes if a not in sizes]
    if missing:
        raise ValueError(
            f"grouped_ep: mesh {tuple(mesh.axis_names)} lacks expert "
            f"submesh axes {missing}"
        )
    ep = math.prod(sizes[a] for a in axes)
    return (mesh, axes, ep) if ep > 1 else (None, axes, 1)


def resolve_dispatch_chunks(config: "MoEConfig") -> int:
    """The effective ``dispatch_chunks`` for a config: an explicit
    positive value wins; 0 resolves the global Context knob at TRACE
    time (``Context.dispatch_chunks``), which is how the runtime
    optimizer's chosen chunking reaches a re-traced program without
    rebuilding the model config."""
    c = int(getattr(config, "dispatch_chunks", 0) or 0)
    if c > 0:
        return c
    from dlrover_tpu.common.config import get_context

    return max(1, int(getattr(get_context(), "dispatch_chunks", 1)))


def resolve_moe_precision(config: "MoEConfig") -> str:
    """The effective wire precision for a config at TRACE time: an
    explicit ``config.precision`` wins; "" resolves the global Context
    knob (``moe_precision``) — which is how the runtime optimizer's
    chosen precision reaches a re-traced program without rebuilding the
    model config (the ``dispatch_chunks`` pattern). A quantized choice
    degrades to "bf16" (logged) when the backend fails the fp8
    capability probe."""
    p = (getattr(config, "precision", "") or "").strip()
    if not p:
        from dlrover_tpu.common.config import get_context

        p = str(getattr(get_context(), "moe_precision", "bf16") or
                "bf16").strip()
    from dlrover_tpu.ops.quantize import PRECISIONS

    if p not in PRECISIONS:
        raise ValueError(
            f"unknown MoE precision {p!r}; choose one of {PRECISIONS}"
        )
    if p != "bf16":
        from dlrover_tpu.ops.shard_compat import fp8_wire_supported

        if not fp8_wire_supported():
            from dlrover_tpu.common.log import get_logger

            get_logger("ops.moe").warning(
                "moe precision %r requested but the backend fails the "
                "fp8 capability probe; running the bf16 wire", p,
            )
            return "bf16"
    return p


def _regroup_window(recv, lo, nc, up_l, down_l, *, x_chunk=None,
                    v_chunk=None, s_chunk=None, ep: int, el: int,
                    block_t: int, interpret, activation, out_dtype):
    """Received block rows [lo, lo+nc) from every source -> expert
    outputs in the same layout (invalid slots zero).

    All index math comes from the exchanged counts (``recv`` [P, el]),
    so every shape is static; at lo=0, nc=n this IS the unchunked
    regroup (chunk-window clips are no-ops). The rows arrive either at
    full precision (``x_chunk`` [P, nc, D]) or at wire precision
    (``v_chunk`` [P, nc, D] e4m3 + ``s_chunk`` [P, nc, D/B] f32 scales,
    the ``ops.quantize`` layout) — the quantized form feeds the
    up-projection through the dequant-in-kernel grouped matmul, bitwise
    equal to dequantizing first (the exchange buffer is never
    re-materialized at full width just to enter the GEMM). Module-level
    (no closures) so the quantized dispatch's custom_vjp boundary can
    call it with everything explicit.
    """
    from dlrover_tpu.ops.grouped_matmul import (
        grouped_matmul,
        grouped_matmul_quantized,
    )

    quantized = v_chunk is not None
    rows = v_chunk if quantized else x_chunk
    d = rows.shape[-1]
    csum = jnp.cumsum(recv, axis=1)  # [P, el]
    tot = csum[:, -1]  # [P] real rows per source block
    group_start = csum - recv  # [P, el] within-block group starts

    r_idx = lo + jnp.arange(nc, dtype=jnp.int32)
    le_r = jax.vmap(
        lambda c, r: jnp.searchsorted(c, r, side="right")
    )(csum, jnp.broadcast_to(r_idx, (ep, nc)))  # [P, nc]
    valid = r_idx[None, :] < tot[:, None]  # [P, nc]
    le_r = jnp.clip(le_r, 0, el - 1).astype(jnp.int32)
    src_rows = jnp.arange(ep, dtype=jnp.int32)[:, None]
    # rows of each (source, local-expert) group that fall in this
    # chunk's window, and the group's start within it
    cnt = jnp.clip(
        jnp.minimum(csum, lo + nc)
        - jnp.maximum(group_start, lo), 0, nc
    )  # [P, el]
    start = jnp.maximum(group_start[src_rows, le_r], lo)
    pre = jnp.cumsum(cnt, axis=0) - cnt  # earlier sources
    rank_r = pre[src_rows, le_r] + (r_idx[None, :] - start)
    m_le = cnt.sum(axis=0)  # [el] chunk rows per local expert
    padded = jnp.maximum(
        (m_le + block_t - 1) // block_t, 1
    ) * block_t
    ends = jnp.cumsum(padded).astype(jnp.int32)
    offs = (ends - padded).astype(jnp.int32)
    # static bound: every group full + its tile padding (and every
    # zero-row expert still owns one sentinel tile — dw init, see
    # grouped_matmul)
    tp = (
        ((ep * nc + block_t - 1) // block_t) * block_t
        + el * block_t
    )
    dest_row = jnp.where(valid, offs[le_r] + rank_r, tp)
    q_flat = jnp.arange(ep * nc, dtype=jnp.int32)
    row_src = jnp.full((tp + 1,), ep * nc, jnp.int32).at[
        dest_row.reshape(-1)
    ].set(q_flat)[:tp]
    tile_start = jnp.arange(
        tp // block_t, dtype=jnp.int32
    ) * block_t
    tile_expert = jnp.clip(
        jnp.searchsorted(ends, tile_start, side="right"),
        0, el - 1,
    ).astype(jnp.int32)
    if quantized:
        # gather values AND scales by the same row map; pad rows read
        # zero sentinel rows on both sides (zero values decode to zero
        # under any scale)
        nb = s_chunk.shape[-1]
        v_pad = jnp.concatenate(
            [v_chunk.reshape(ep * nc, d),
             jnp.zeros((1, d), v_chunk.dtype)], axis=0
        )
        s_pad = jnp.concatenate(
            [s_chunk.reshape(ep * nc, nb),
             jnp.zeros((1, nb), s_chunk.dtype)], axis=0
        )
        h = activation(grouped_matmul_quantized(
            v_pad[row_src], s_pad[row_src], up_l, tile_expert,
            block_t, 512, interpret, jnp.float32,
        ))
    else:
        x_pad_c = jnp.concatenate(
            [x_chunk.reshape(ep * nc, d),
             jnp.zeros((1, d), x_chunk.dtype)], axis=0
        )
        h = activation(grouped_matmul(
            x_pad_c[row_src], up_l, tile_expert, block_t, 512,
            interpret,
        ))
    y_sorted = grouped_matmul(
        h, down_l, tile_expert, block_t, 512, interpret,
    )
    # back to the chunk's recv layout (invalid slots zero)
    y_flat = y_sorted[
        jnp.clip(dest_row, 0, tp - 1).reshape(-1)
    ]
    y_flat = jnp.where(
        valid.reshape(-1)[:, None], y_flat, 0
    ).astype(out_dtype)
    return y_flat.reshape(ep, nc, d)


def _quantized_dispatch_fwd_impl(x_send3, up_l, down_l, recv,
                                 axes, ep, el, chunks, block_t,
                                 interpret, precision, activation):
    """Forward of the quantized row dispatch: quantize -> exchange ->
    grouped GEMMs -> quantize -> reverse exchange -> dequantize.

    Returns (y_ret, (v_recv, s_recv)) — the received wire rows are the
    backward residual (at 1.125 bytes/element they are the CHEAPEST
    exact record of what the GEMMs consumed).

    "fp8" exchanges the (values, scales) pair — the wire carries ~0.56x
    the bf16 bytes; "fp8_qdq" applies the identical quantize->
    dequantize at the SOURCE of every exchange and wires full precision
    — bitwise the same result, because quantization is per-row and the
    exchange is a pure row permutation (the commuting square the exact
    tests pin). Chunked (C > 1) keeps PR 10's double-buffered ring
    schedule: chunk c+1's value+scale rings are issued before chunk c's
    GEMMs."""
    from dlrover_tpu.ops.quantize import (
        dequantize_block_scaled,
        quantize_block_scaled,
    )

    n = x_send3.shape[1]
    wire_fp8 = precision == "fp8"
    v, s = quantize_block_scaled(x_send3)

    def exch(a):
        return lax.all_to_all(a, axes, 0, 0)

    def gemms(vc, sc, xc, lo, nc):
        return _regroup_window(
            recv, lo, nc, up_l, down_l,
            x_chunk=xc, v_chunk=vc, s_chunk=sc,
            ep=ep, el=el, block_t=block_t, interpret=interpret,
            activation=activation, out_dtype=jnp.float32,
        )

    # the backward residual is the received wire rows: (values, scales)
    # for the fp8 wire, the received dequantized rows themselves for
    # the qdq reference — bitwise the same dequant-space array (the
    # exchange commutes with the per-row decode), and the form each
    # mode already holds. Re-encoding the reference's received rows
    # would NOT be bitwise (448 is not a power of two, so
    # quantize(dequantize(q, s)) reproduces neither q nor s exactly).
    if chunks <= 1:
        if wire_fp8:
            vr, sr = exch(v), exch(s)
            y = gemms(vr, sr, None, 0, n)
            residual = (vr, sr)
        else:
            xr = exch(dequantize_block_scaled(v, s))
            y = gemms(None, None, xr, 0, n)
            residual = (xr, jnp.zeros((0,), jnp.float32))
        wv, ws = quantize_block_scaled(y)
        if wire_fp8:
            y_ret = dequantize_block_scaled(exch(wv), exch(ws))
        else:
            y_ret = exch(dequantize_block_scaled(wv, ws))
        return y_ret, residual

    from dlrover_tpu.ops.ring import ring_all_to_all

    def ring(a):
        return ring_all_to_all(a, axes, ep)

    nc = n // chunks

    def wire_in(c):
        """Issue chunk c's exchange (the double-buffered prefetch)."""
        lo, hi = c * nc, (c + 1) * nc
        if wire_fp8:
            return (ring(v[:, lo:hi]), ring(s[:, lo:hi]))
        xq = dequantize_block_scaled(v[:, lo:hi], s[:, lo:hi])
        return (ring(xq),)

    cur = wire_in(0)
    parts, res_a, res_b = [], [], []
    for c in range(chunks):
        nxt = wire_in(c + 1) if c + 1 < chunks else None
        if wire_fp8:
            vr_c, sr_c = cur
            y_c = gemms(vr_c, sr_c, None, c * nc, nc)
            res_a.append(vr_c)
            res_b.append(sr_c)
        else:
            (xr_c,) = cur
            y_c = gemms(None, None, xr_c, c * nc, nc)
            res_a.append(xr_c)
        wv, ws = quantize_block_scaled(y_c)
        if wire_fp8:
            parts.append((ring(wv), ring(ws)))
        else:
            parts.append(dequantize_block_scaled(wv, ws))
        cur = nxt
    if wire_fp8:
        y_ret = jnp.concatenate(
            [dequantize_block_scaled(pv, ps) for pv, ps in parts],
            axis=1,
        )
        residual = (jnp.concatenate(res_a, axis=1),
                    jnp.concatenate(res_b, axis=1))
    else:
        y_ret = jnp.concatenate([ring(p) for p in parts], axis=1)
        residual = (jnp.concatenate(res_a, axis=1),
                    jnp.zeros((0,), jnp.float32))
    return y_ret, residual


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9,
                                                    10, 11))
def _quantized_dispatch(x_send3, up_l, down_l, recv,
                        axes, ep, el, chunks, block_t, interpret,
                        precision, activation):
    """The quantized row dispatch, differentiable end to end: the wire
    carries block-scaled fp8 in BOTH directions (forward rows and
    backward cotangents — that is what halves the all-to-all bytes the
    G106 audit counts, not just the forward leg).

    Autodiff cannot run through an fp8 primal (the cotangent of an e4m3
    array is e4m3 — gradients would be destroyed at 2 decimal digits),
    so the boundary is a custom VJP: the backward re-derives the GEMM
    gradients by ``jax.vjp`` over the DEQUANT-SPACE compute on the
    saved wire rows (a remat-style forward replay — the fp8 residual is
    8x smaller than saving ``h``), and wires each cotangent exchange
    through the same quantize -> exchange -> dequantize transform as
    the forward (straight-through at the quantize step). The reference
    oracle ("fp8_qdq") shares this exact code path with the wire left
    at full precision, which is why the equality the tests pin is
    bitwise and not approximate."""
    y, _res = _quantized_dispatch_fwd_impl(
        x_send3, up_l, down_l, recv, axes, ep, el, chunks, block_t,
        interpret, precision, activation,
    )
    return y


def _qd_fwd(x_send3, up_l, down_l, recv, axes, ep, el, chunks, block_t,
            interpret, precision, activation):
    y, (res_a, res_b) = _quantized_dispatch_fwd_impl(
        x_send3, up_l, down_l, recv, axes, ep, el, chunks, block_t,
        interpret, precision, activation,
    )
    # the empty array exists only to carry x_send3's dtype into the
    # backward (a bare numpy dtype is not a valid residual leaf)
    return y, (res_a, res_b, up_l, down_l, recv,
               jnp.zeros((0,), x_send3.dtype))


def _qd_bwd(axes, ep, el, chunks, block_t, interpret, precision,
            activation, res, g):
    from dlrover_tpu.ops.quantize import (
        dequantize_block_scaled,
        quantize_block_scaled,
    )

    res_a, res_b, up_l, down_l, recv, x_proto = res
    x_dtype = x_proto.dtype
    n = g.shape[1]
    wire_fp8 = precision == "fp8"
    if chunks > 1:
        from dlrover_tpu.ops.ring import ring_all_to_all

        def exch(a):
            # same wire as the forward (ring: the diagonal block stays
            # off the wire); chunk windows act per row, so one
            # full-array ring is bitwise the per-chunk concatenation
            return ring_all_to_all(a, axes, ep)
    else:
        def exch(a):
            return lax.all_to_all(a, axes, 0, 0)

    def wire(a):
        """One backward cotangent exchange: quantized at the source
        exactly like the forward rows (or qdq'd locally with a
        full-precision wire in the reference mode)."""
        gv, gs = quantize_block_scaled(a)
        if wire_fp8:
            return dequantize_block_scaled(exch(gv), exch(gs))
        return exch(dequantize_block_scaled(gv, gs))

    # the return exchange's backward: send layout -> recv layout (the
    # exchange operator is an involution, so the same op routes it)
    g_y = wire(g.astype(jnp.float32))

    def inner(xd, up, down):
        # the dequant-space compute the forward is bitwise equal to;
        # mirrored per chunk window so the vjp sees the same GEMM
        # partitioning
        if chunks <= 1:
            return _regroup_window(
                recv, 0, n, up, down, x_chunk=xd,
                ep=ep, el=el, block_t=block_t, interpret=interpret,
                activation=activation, out_dtype=jnp.float32,
            )
        nc = n // chunks
        return jnp.concatenate([
            _regroup_window(
                recv, c * nc, nc, up, down,
                x_chunk=xd[:, c * nc:(c + 1) * nc],
                ep=ep, el=el, block_t=block_t, interpret=interpret,
                activation=activation, out_dtype=jnp.float32,
            ) for c in range(chunks)
        ], axis=1)

    # the dequant-space input the forward consumed: decode the fp8
    # residual, or the qdq reference's received rows as-is (bitwise the
    # same array — the commuting square again)
    x_deq = (dequantize_block_scaled(res_a, res_b) if wire_fp8
             else res_a)
    _y_replay, vjp_fn = jax.vjp(inner, x_deq, up_l, down_l)
    gx_deq, dup, ddown = vjp_fn(g_y)
    # the row exchange's backward: recv layout -> send layout
    gx = wire(gx_deq).astype(x_dtype)
    return gx, dup, ddown, None


_quantized_dispatch.defvjp(_qd_fwd, _qd_bwd)


def _moe_compute_grouped_ep(params, xt, config: "MoEConfig", activation,
                            mesh, axes: Tuple[str, ...], ep: int,
                            rng, jitter: float,
                            block_t: int = 128,
                            chunks: int = 1,
                            precision: str = "bf16"):
    """DROPLESS dispatch with experts SHARDED over the ``axes`` submesh:
    shard_map + two ``lax.all_to_all`` exchanges around the grouped
    Pallas kernel — megablocks-style droplessness with MoE FLOPs linear
    in tokens even when experts live on different chips.

    Per shard (P = ep shards, el = E/P local experts, Tl local tokens,
    n = Tl * top_k local assignments):

      1. route the LOCAL tokens over all E experts (router replicated);
         aux-loss fractions pmean across shards so the loss equals the
         single-shard oracle exactly;
      2. exchange per-(dest shard, local expert) COUNTS with a tiny
         int32 all_to_all — the receiver can then compute every row's
         tile-aligned destination locally, so all row buffers keep
         STATIC shapes (zero recompiles across steps);
      3. exchange token rows with a [P, n, D] all_to_all (block s =
         rows destined to shard s, grouped by that shard's local
         experts in local arrival order). n is the static worst case —
         all local assignments to one shard — which is what droplessness
         without dynamic shapes costs; the planner prices exactly these
         bytes (``planner`` "moe_disp_comm_s");
      4. regroup received rows by local expert, pad each group to the
         row tile, run the two grouped GEMMs (the per-shard kernel,
         unchanged — every local expert owns >= 1 tile so dw blocks
         initialize, see ``grouped_matmul``);
      5. reverse all_to_all and combine locally (unsort + gate, summing
         each token's top_k rounds).

    ``chunks`` > 1 (the comm/compute-overlap mode): the [P, n, D] row
    exchange of steps 3/5 is split into C static chunks of n/C rows
    per block, each exchanged by a ppermute ring (``ops.ring``) instead
    of the opaque one-shot ``all_to_all``, DOUBLE-BUFFERED — chunk
    c+1's exchange is issued before chunk c's grouped GEMMs, and chunk
    c's reverse exchange before chunk c+1's GEMMs, so XLA's
    latency-hiding scheduler can run the in-flight exchange under the
    compute on already-arrived rows. Per-row math is unchanged (each
    row's output is x_row @ W of its expert, independent of chunking),
    so C is a pure schedule knob: outputs are exactly the C=1 path's,
    total wire bytes stay the all_to_all's (minus the diagonal block
    that never needed the wire — the G106 audit's parity contract),
    shapes stay static per C, and droplessness is untouched. n % C != 0
    degrades to C=1 at trace time (logged).

    Differentiable end to end: the collectives transpose to their
    reverses and the kernel brings its custom VJP, so the backward runs
    the same exchanges (all-to-alls, or the mirrored ppermute ring) in
    the opposite direction.

    Returns (out [T, D], aux_loss, metrics) — metrics are the pmean'd
    global load-balance signals, ``dropped_frac`` identically 0.
    """
    from jax.sharding import PartitionSpec as P

    t, d = xt.shape
    e = config.num_experts
    top_k = config.top_k
    if e % ep:
        raise ValueError(
            f"grouped_ep: num_experts={e} not divisible by the expert "
            f"submesh of {ep} shards ({axes})"
        )
    if t % ep:
        raise ValueError(
            f"grouped_ep: {t} tokens not divisible by the expert "
            f"submesh of {ep} shards ({axes})"
        )
    el = e // ep
    interpret = config.kernel_interpret
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    # chunk validation happens at TRACE time (shapes are static): an
    # indivisible row count degrades to the one-shot exchange rather
    # than changing the row layout
    chunks = max(1, int(chunks))
    n_static = (t // ep) * config.top_k
    if chunks > 1 and (n_static % chunks or chunks > n_static):
        from dlrover_tpu.common.log import get_logger

        get_logger("ops.moe").warning(
            "grouped_ep: dispatch_chunks=%d does not divide the %d "
            "local assignment rows; running unchunked (C=1)",
            chunks, n_static,
        )
        chunks = 1

    def body(xt_l, router_k, up_l, down_l, rng_l):
        tl = xt_l.shape[0]
        shard = lax.axis_index(axes)
        # decorrelate router jitter across token shards
        rng_s = jax.random.fold_in(rng_l, shard)
        logits = xt_l @ router_k  # [Tl, E]
        # capacity = Tl: dropless — nothing can overflow, and the
        # round positions ARE per-expert local arrival ranks
        rounds, _, metrics_l = _routing(logits, tl, top_k, rng_s, jitter)

        k = len(rounds)
        n = tl * k
        expert_a = jnp.concatenate([r[0] for r in rounds])  # [n] i32
        gate_a = jnp.concatenate([r[3] for r in rounds])  # [n] f32
        rank_a = jnp.concatenate([r[1] for r in rounds])  # [n] i32
        token_a = jnp.tile(jnp.arange(tl, dtype=jnp.int32), k)
        # contiguous expert ownership: expert g lives on shard g // el
        # as local expert g % el — exactly how PartitionSpec shards the
        # leading [E] dim over the (row-major) combined axis index
        dest = expert_a // el  # [n] owner shard
        le_a = expert_a % el  # [n] owner's local expert
        counts = jnp.zeros((ep, el), jnp.int32).at[dest, le_a].add(1)
        # send layout: per-dest block of n rows; within a block, rows
        # group by the dest's local expert in local arrival order
        block_off = jnp.cumsum(counts, axis=1) - counts  # [P, el]
        send_pos = dest * n + block_off[dest, le_a] + rank_a  # unique
        send_token = jnp.full((ep * n,), tl, jnp.int32).at[send_pos].set(
            token_a
        )
        x_pad = jnp.concatenate(
            [xt_l, jnp.zeros((1, d), xt_l.dtype)], axis=0
        )
        x_send = x_pad[send_token]  # [P*n, D]; pad rows = zero sentinel

        # all-to-all #1 (tiny): counts — recv[s, le] = rows shard s is
        # sending for my local expert le. Never quantized: the regroup
        # index math must be exact, and [P, el] int32 is wire noise.
        recv = lax.all_to_all(counts, axes, 0, 0)  # [P, el]

        def regroup_gemm(x_chunk, lo, nc):
            return _regroup_window(
                recv, lo, nc, up_l, down_l, x_chunk=x_chunk,
                ep=ep, el=el, block_t=block_t, interpret=interpret,
                activation=activation, out_dtype=xt_l.dtype,
            )

        x_send3 = x_send.reshape(ep, n, d)
        if precision != "bf16":
            # the LOW-PRECISION wire: rows quantize to block-scaled
            # e4m3 BEFORE the exchange (values + f32 scales both ride
            # the wire — ~0.56x the bf16 bytes the planner prices and
            # G106 audits), the up-projection consumes them through
            # the dequant-in-kernel grouped matmul, and the backward
            # cotangent exchanges quantize the same way through the
            # custom VJP boundary. "fp8_qdq" is the bitwise reference
            # with the wire left at full precision.
            y_ret = _quantized_dispatch(
                x_send3, up_l, down_l, recv,
                axes, ep, el, chunks, block_t, interpret,
                precision, activation,
            ).astype(xt_l.dtype)
        elif chunks <= 1:
            # all-to-all #2: the token rows, one shot (serial)
            x_recv = lax.all_to_all(x_send3, axes, 0, 0)
            y_ret = lax.all_to_all(
                regroup_gemm(x_recv, 0, n), axes, 0, 0
            )  # [P, n, D]
        else:
            # chunked double-buffered exchange: chunk c+1's ring
            # permutes (and chunk c's reverse ring) carry no data
            # dependency on chunk c's GEMMs, so the scheduler can run
            # them under the compute — the overlap the one-shot
            # all_to_all structurally forbids
            from dlrover_tpu.ops.ring import ring_all_to_all

            nc = n // chunks
            cur = ring_all_to_all(x_send3[:, :nc], axes, ep)
            parts = []
            for c in range(chunks):
                nxt = (
                    ring_all_to_all(
                        x_send3[:, (c + 1) * nc:(c + 2) * nc],
                        axes, ep,
                    ) if c + 1 < chunks else None
                )
                y_c = regroup_gemm(cur, c * nc, nc)
                parts.append(ring_all_to_all(y_c, axes, ep))
                cur = nxt
            y_ret = jnp.concatenate(parts, axis=1)  # [P, n, D]
        # combine: each assignment's result sits at its own send_pos
        y_a = y_ret.reshape(ep * n, d)[send_pos]  # [n, D]
        out_l = jnp.zeros((tl, d), xt_l.dtype).at[token_a].add(
            (y_a * gate_a[:, None].astype(y_a.dtype)).astype(xt_l.dtype)
        )

        # aux loss from GLOBAL routing fractions: pmean of equal-sized
        # local means == the global mean, so this equals the oracle
        ft = lax.pmean(metrics_l["frac_tokens"], axes)
        fp = lax.pmean(metrics_l["frac_probs"], axes)
        aux = e * jnp.sum(ft * fp) / max(1, top_k)
        load = lax.pmean(metrics_l["expert_load"], axes)
        return out_l, aux, load

    spec_tok = P(axes)  # dim 0 over the combined expert submesh
    spec_exp = P(axes)  # weights: expert dim over the same submesh
    rep = P()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_tok, rep, spec_exp, spec_exp, rep),
        out_specs=(spec_tok, rep, rep),
        check_vma=False,  # a pallas_call output carries no vma
    )
    out, aux, load = fn(
        xt, params["router"]["kernel"],
        params["experts"]["up"]["kernel"],
        params["experts"]["down"]["kernel"],
        rng,
    )
    metrics = {
        "dropped_frac": jnp.zeros((), jnp.float32),  # dropless
        "expert_load": load,
    }
    return out, aux.astype(jnp.float32), metrics


def moe_ffn(
    params: dict,
    x: jax.Array,  # [B, S, D]
    config: MoEConfig,
    activation: Callable = jax.nn.gelu,
    train: bool = True,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Switch-FFN block. params:
      router/kernel: [D, E]
      experts/up/kernel:   [E, D, F]
      experts/down/kernel: [E, F, D]
    Returns (output [B,S,D], aux_loss scalar, metrics dict) where
    metrics = {"dropped_frac" scalar, "expert_load" [E]} — the
    load-balance observability signals, computed by the router at
    negligible cost and surfaced as step metrics by the trainer.
    """
    dispatch = config.dispatch
    if dispatch not in ("gather", "einsum", "grouped", "grouped_ep"):
        raise ValueError(
            f"unknown MoE dispatch {config.dispatch!r}; choose "
            f"'gather' (fast, capacity), 'einsum' (reference oracle), "
            f"'grouped' (dropless Pallas kernel, per-shard experts) or "
            f"'grouped_ep' (dropless + expert-parallel all-to-all)"
        )
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    jitter = config.router_jitter if train else 0.0
    if dispatch == "grouped_ep":
        mesh, axes, ep = _resolve_ep_mesh(config)
        if ep > 1:
            # routing happens INSIDE the shard_map (per local shard) so
            # the two all-to-alls move rows straight to owner experts
            out, aux, metrics = _moe_compute_grouped_ep(
                params, xt, config, activation, mesh, axes, ep,
                rng, jitter,
                chunks=resolve_dispatch_chunks(config),
                precision=resolve_moe_precision(config),
            )
            return out.reshape(b, s, d), aux, metrics
        # no usable expert submesh (single shard, elastic shrink, or no
        # mesh context): the per-shard dropless path is the same math
        dispatch = "grouped"
    logits = xt @ params["router"]["kernel"]  # [T, E]
    factor = config.capacity_factor if train else config.eval_capacity_factor
    if dispatch == "grouped":
        # DROPLESS: no capacity limit — every assignment is served, so
        # route with capacity = T (nothing can overflow) and the
        # metrics honestly report dropped_frac == 0
        capacity = t
    else:
        capacity = _capacity(t, config.num_experts, factor,
                             config.top_k)
    rounds, aux, metrics = _routing(
        logits, capacity, config.top_k, rng, jitter,
    )
    metrics = {k: metrics[k] for k in PUBLIC_METRICS}
    if dispatch == "grouped":
        out = _moe_compute_grouped(
            params, xt, rounds, config.num_experts, activation,
            interpret=config.kernel_interpret,
        )
    else:
        compute = (_moe_compute_einsum if dispatch == "einsum"
                   else _moe_compute_gather)
        out = compute(params, xt, rounds, capacity, config.num_experts,
                      activation)
    return out.reshape(b, s, d), aux.astype(jnp.float32), metrics


def init_moe_params(rng, d_model: int, d_ff: int, num_experts: int,
                    dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)
    return {
        "router": {
            "kernel": jax.random.normal(k1, (d_model, num_experts),
                                        dtype) * scale_in,
        },
        "experts": {
            "up": {"kernel": jax.random.normal(
                k2, (num_experts, d_model, d_ff), dtype) * scale_in},
            "down": {"kernel": jax.random.normal(
                k3, (num_experts, d_ff, d_model), dtype) * scale_out},
        },
    }


# -- group-limited selection --------------------------------------------------


def top_groups(scores: jax.Array, n_group: int, topk_group: int):
    """``[T, n_group]`` bool: of the ``n_group`` equal runs of the
    experts' ``scores`` [T, E] (group ``k`` is experts ``k E / n_group``
    onward), the ``topk_group`` whose two largest scores add up to the
    most, ties to the lower group (DeepSeek-V3's group mark)."""
    rows, experts = scores.shape
    if experts % n_group or not 0 < topk_group <= n_group or (
            experts // n_group < 2):
        raise ValueError(f"{experts} experts in {n_group} groups of at "
                         f"least 2, {topk_group} of them kept")
    mark = jnp.sum(lax.top_k(scores.reshape(
        rows, n_group, experts // n_group), 2)[0], axis=-1)
    _, kept = lax.top_k(mark, topk_group)
    return jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)


def group_limited_routing(logits: jax.Array, top_k: int, n_group: int,
                          topk_group: int, renormalise: bool = True,
                          scale: float = 1.0,
                          selection_bias: Optional[jax.Array] = None):
    """``sigmoid_topk_routing`` under DeepSeek-V3's group limit: a token
    keeps the ``topk_group`` of ``n_group`` groups of experts that
    ``top_groups`` marks (on the biased scores, as the selection) and
    selects its ``top_k`` among their experts alone: an expert of a
    group left out is never chosen, whatever its score. The weights are
    the unbiased scores of the selected, as there. Returns that
    function's triple and the kept groups ``[T, n_group]`` bool."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    bias = jnp.float32(0.0) if selection_bias is None else (
        selection_bias.astype(jnp.float32))
    groups = top_groups(scores + bias, n_group, topk_group)
    allowed = jnp.repeat(groups, scores.shape[1] // n_group, axis=1)
    # an expert of a group left out is biased out of the selection
    return sigmoid_topk_routing(
        logits, top_k, renormalise, scale,
        jnp.where(allowed, bias, -jnp.inf)) + (groups,)
