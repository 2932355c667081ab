"""GPipe-style pipeline parallelism over the "pipe" mesh axis.

Role parity: atorch's PiPPy compiler stack (``atorch/atorch/modules/
distributed_modules/compilers/pipe_compiler/distributed_pippy_compiler.py:90-378``
— FX graph split into stages, torch RPC drivers, interleaver). The TPU
formulation needs none of that machinery: stages are a *leading array
dimension* sharded on the "pipe" mesh axis, the whole schedule is a
``lax.scan`` over pipeline ticks, and the per-tick shift of activations to
the next stage (``jnp.roll`` over the stage dim) lowers to an XLA
collective-permute over ICI/DCN. Because this is plain GSPMD (no manual
``shard_map``), it composes freely with the data/fsdp/seq/tensor axes —
tensor-parallel matmuls inside a stage still get their collectives from
the partitioner.

Schedule: GPipe. With M microbatches and P stages the bubble fraction is
(P-1)/(M+P-1); backward runs the reverse schedule automatically because
``jax.grad`` transposes the scan and the collective-permute.

Contract: ``stage_fn(stage_params, state) -> state`` must be
shape/dtype-preserving on ``state`` (homogeneous stages — the transformer
block case); heterogeneous embed/head layers stay *outside* the pipeline
in the surrounding GSPMD program.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


def _context_has_axis(axis_name: str) -> bool:
    """Sharding constraints only resolve under a mesh context
    (``jax.sharding.set_mesh`` — what ``accelerate`` establishes;
    ``shard_compat.ambient_mesh``); skip them when running unsharded."""
    from dlrover_tpu.ops.shard_compat import ambient_mesh

    mesh = ambient_mesh()
    return mesh is not None and axis_name in mesh.axis_names


def pipe_batch_constraint(
    x: jax.Array,
    axis_name: str = "pipe",
    batch_axes: Tuple = ("data", "fsdp"),
) -> jax.Array:
    """Spread dim 0 of a post-pipeline activation over the pipe axis too.

    The surrounding GSPMD program (embed / final-norm / lm head) has no
    operand sharded on "pipe", so XLA replicates that compute across
    every pipe group — at scale the head is a large fraction of a
    stage's FLOPs. Constraining the batch dim over (batch_axes + pipe)
    is comm-free at this point (replicated -> sharded lowers to a local
    slice) and cuts the outer compute by the pipe degree; the backward
    pays one activation-size all-gather over pipe to re-replicate the
    gradient entering the pipeline. No-op without a pipe mesh axis.
    """
    if not _context_has_axis(axis_name):
        return x
    from jax.sharding import PartitionSpec as P

    return lax.with_sharding_constraint(
        x,
        P((*batch_axes, axis_name),
          *(P.UNCONSTRAINED for _ in range(x.ndim - 1))),
    )


def split_microbatches(tree: PyTree, num_microbatches: int) -> PyTree:
    """[B, ...] leaves -> [M, B/M, ...] microbatch-stacked leaves."""

    def split(x):
        b = x.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"batch of {b} rows not divisible into "
                f"{num_microbatches} microbatches"
            )
        return x.reshape((num_microbatches, b // num_microbatches)
                         + x.shape[1:])

    return jax.tree.map(split, tree)


def merge_microbatches(tree: PyTree) -> PyTree:
    """[M, mb, ...] -> [M*mb, ...] (inverse of split_microbatches)."""
    return jax.tree.map(
        lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), tree
    )


def _stage_constraint(tree: PyTree, axis_name: str,
                      batch_axes: Optional[Tuple]) -> PyTree:
    """Pin the leading (stage) dim of every leaf on the pipe axis and the
    microbatch dim on the data axes, leaving trailing dims to XLA."""
    from jax.sharding import PartitionSpec as P

    unconstrained = P.UNCONSTRAINED

    def constrain(x):
        spec = [axis_name]
        if x.ndim > 1:
            spec.append(batch_axes)
        spec.extend(unconstrained for _ in range(x.ndim - len(spec)))
        return lax.with_sharding_constraint(x, P(*spec))

    return jax.tree.map(constrain, tree)


def pipeline_apply(
    stage_fn: Callable[[PyTree, PyTree], PyTree],
    stage_params: PyTree,  # leaves [num_stages, ...], pipe-sharded on dim 0
    x_mb: PyTree,  # microbatch-stacked inputs, leaves [M, ...]
    axis_name: str = "pipe",
    batch_axes: Optional[Tuple] = ("data", "fsdp"),
    constrain: bool = True,
    remat_stage: bool = False,
) -> PyTree:
    """Run M microbatches through P homogeneous stages; returns outputs
    with the same [M, ...] layout as ``x_mb``.

    ``stage_fn`` sees one stage's params (dim 0 of ``stage_params``
    stripped by vmap) and one microbatch-shaped ``state``.

    ``remat_stage``: checkpoint each stage application so the tick
    scan's backward stores one stage-boundary state per tick instead
    of every inner layer-scan carry. The stage params here are a scan
    constant, so the checkpoint's saved inputs do not stack per tick.
    """
    stage_leaves = jax.tree.leaves(stage_params)
    if not stage_leaves:
        raise ValueError("stage_params is empty")
    num_stages = stage_leaves[0].shape[0]
    constrain = constrain and _context_has_axis(axis_name)
    if constrain:
        from jax.sharding import PartitionSpec as P

        stage_params = jax.tree.map(
            lambda w: lax.with_sharding_constraint(
                w,
                P(axis_name, *(P.UNCONSTRAINED for _ in range(w.ndim - 1))),
            ),
            stage_params,
        )
    x_leaves = jax.tree.leaves(x_mb)
    num_mb = x_leaves[0].shape[0]
    num_ticks = num_mb + num_stages - 1

    if remat_stage:
        stage_fn = jax.checkpoint(stage_fn)
    vstage = jax.vmap(stage_fn)

    def maybe_constrain(tree):
        if not constrain:
            return tree
        return _stage_constraint(tree, axis_name, batch_axes)

    # state: one in-flight microbatch per stage, [P, mb, ...]
    state0 = jax.tree.map(
        lambda x: jnp.zeros((num_stages,) + x.shape[1:], x.dtype), x_mb
    )
    outs0 = jax.tree.map(jnp.zeros_like, x_mb)

    def tick(carry, t):
        state, outs = carry
        # feed the next microbatch into stage 0 (garbage during drain)
        inp = jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, num_mb - 1), 0, keepdims=False
            ),
            x_mb,
        )
        state = jax.tree.map(
            lambda s, i: lax.dynamic_update_index_in_dim(s, i, 0, 0),
            state, inp,
        )
        state = maybe_constrain(state)
        y = vstage(stage_params, state)
        y = maybe_constrain(y)
        # stage P-1 finished microbatch t-(P-1): collect it
        out_idx = t - (num_stages - 1)
        valid = jnp.logical_and(out_idx >= 0, out_idx < num_mb)
        idx = jnp.clip(out_idx, 0, num_mb - 1)
        outs = jax.tree.map(
            lambda o, yy: jnp.where(
                valid,
                lax.dynamic_update_index_in_dim(o, yy[-1], idx, 0),
                o,
            ),
            outs, y,
        )
        # shift every stage's output to its successor: one collective
        # permute around the pipe ring (slot 0 is overwritten next tick)
        state = jax.tree.map(lambda yy: jnp.roll(yy, 1, axis=0), y)
        return (state, outs), None

    (_, outs), _ = lax.scan(
        tick, (state0, outs0), jnp.arange(num_ticks)
    )
    return outs


def stack_stages(layer_params: PyTree, num_stages: int) -> PyTree:
    """[L, ...] scan-stacked layer params -> [P, L/P, ...] stage chunks."""

    def restack(x):
        layers = x.shape[0]
        if layers % num_stages:
            raise ValueError(
                f"{layers} layers not divisible into {num_stages} stages"
            )
        return x.reshape((num_stages, layers // num_stages) + x.shape[1:])

    return jax.tree.map(restack, layer_params)


def stack_stages_uneven(
    layer_params: PyTree, depths
) -> Tuple[PyTree, jax.Array]:
    """[L, ...] scan-stacked layer params -> ([P, Lmax, ...] zero-padded
    stage chunks, [P, Lmax] float validity mask).

    Per-stage layer counts (``depths``, summing to L) express UNEQUAL
    stage splits — a deliberately lighter first/last stage, or a layer
    count that doesn't divide by the stage count. Role parity: the
    reference's uneven stage placement
    (``atorch/atorch/auto/opt_lib/shard_planners/base_stage_planner.py:125``).

    Cost model: any lockstep pipeline ticks at the HEAVIEST stage's
    cost, so running every stage over Lmax = max(depths) padded slots
    costs the same wall-clock as a ragged implementation would — the
    light stages' padded slots burn cycles the tick-barrier would waste
    anyway. The real overheads are (P*Lmax - L)/L extra parameter
    memory and the masked slots' energy. The caller's ``stage_fn`` must
    skip masked slots (carry the state through where mask == 0).
    """
    depths = tuple(int(d) for d in depths)
    if not depths or any(d <= 0 for d in depths):
        raise ValueError(f"stage depths must be positive: {depths}")
    lmax = max(depths)
    offsets = [0]
    for d in depths:
        offsets.append(offsets[-1] + d)
    total = offsets[-1]

    def restack(x):
        if x.shape[0] != total:
            raise ValueError(
                f"{x.shape[0]} layers != sum(depths) = {total}"
            )
        chunks = []
        for p, d in enumerate(depths):
            chunk = lax.slice_in_dim(x, offsets[p], offsets[p] + d, axis=0)
            if d < lmax:
                pad = jnp.zeros((lmax - d,) + x.shape[1:], x.dtype)
                chunk = jnp.concatenate([chunk, pad], axis=0)
            chunks.append(chunk)
        return jnp.stack(chunks)

    mask = jnp.asarray(
        [[1.0 if j < d else 0.0 for j in range(lmax)] for d in depths],
        jnp.float32,
    )
    return jax.tree.map(restack, layer_params), mask


def stack_stages_interleaved_uneven(
    layer_params: PyTree, num_stages: int, num_virtual: int, depths
) -> Tuple[PyTree, jax.Array]:
    """[L, ...] -> ([V, P, Lmax, ...] zero-padded chunks, [V, P, Lmax]
    mask) for the circular schedule with per-chunk layer counts.

    ``depths`` has V*P entries in VISIT order — round 0 stages 0..P-1,
    then round 1 stages 0..P-1, ... — matching the logical layer order
    of ``stack_stages_interleaved``. Physical stage p's total layer load
    is ``sum(depths[r*P + p] for r in range(V))``; a lighter first/last
    stage means making those column sums smaller at the ends.
    """
    depths = tuple(int(d) for d in depths)
    if len(depths) != num_stages * num_virtual:
        raise ValueError(
            f"need {num_virtual}x{num_stages} = "
            f"{num_virtual * num_stages} depths, got {len(depths)}"
        )
    stacked, mask = stack_stages_uneven(layer_params, depths)

    def to_vp(x):
        return x.reshape((num_virtual, num_stages) + x.shape[1:])

    return jax.tree.map(to_vp, stacked), to_vp(mask)


def dispatch_pipeline(
    stage_fn: Callable[[PyTree, PyTree], PyTree],
    layer_params: PyTree,
    state_mb: PyTree,
    num_stages: int,
    num_virtual: int = 1,
    stage_depths=None,
    remat_stage: bool = False,
) -> PyTree:
    """Shared stacking + schedule dispatch for model ``apply_pipelined``
    implementations: picks gpipe vs interleaved vs their uneven-depth
    variants, stacks ``layer_params`` accordingly, and runs the
    schedule. ``stage_fn((layers_chunk, mask), state)`` receives
    ``mask=None`` on the even paths (None is an empty pytree, so vmap
    passes it through untouched); with a mask it must skip masked slots
    (carry the state through where mask == 0, e.g. via
    ``masked_layer_scan``).

    ``remat_stage``: checkpoint each stage application so the tick
    scan's backward saves only STAGE-BOUNDARY activations (one state
    per tick), not every inner layer-scan carry — without it a deep
    stage saves ticks x layers-per-stage residuals, which at 70B scale
    is tens of GB per device and OOMs where plain PP activation math
    (microbatches x stage boundaries) fits comfortably. The checkpoint
    is applied INSIDE the schedules (around the round-selection in the
    interleaved case) so the saved inputs are the loop-INVARIANT
    params plus the per-tick state — wrapping the stage fn itself
    would stack the dynamically-selected param chunk per tick, ~20 GB
    of param copies at 70B. The model's per-layer remat policy still
    shapes the recompute inside the stage."""
    if stage_depths is not None:
        if num_virtual > 1:
            stage_params = stack_stages_interleaved_uneven(
                layer_params, num_stages, num_virtual, stage_depths
            )
            return pipeline_apply_interleaved(
                stage_fn, stage_params, state_mb,
                remat_stage=remat_stage,
            )
        if len(stage_depths) != num_stages:
            raise ValueError(
                f"stage_depths has {len(stage_depths)} entries "
                f"for {num_stages} stages"
            )
        stage_params = stack_stages_uneven(layer_params, stage_depths)
        return pipeline_apply(stage_fn, stage_params, state_mb,
                              remat_stage=remat_stage)
    if num_virtual > 1:
        stage_params = (stack_stages_interleaved(
            layer_params, num_stages, num_virtual
        ), None)
        return pipeline_apply_interleaved(stage_fn, stage_params, state_mb,
                                          remat_stage=remat_stage)
    stage_params = (stack_stages(layer_params, num_stages), None)
    return pipeline_apply(stage_fn, stage_params, state_mb,
                          remat_stage=remat_stage)


def masked_layer_scan(
    block: Callable, x: jax.Array, layers_chunk: PyTree,
    mask: Optional[jax.Array],
) -> jax.Array:
    """Scan ``block(carry, layer) -> (new_carry, _)`` over a stage
    chunk. ``mask=None`` (even split) is a plain scan; with a mask
    (zero-padded uneven chunk) masked slots carry the state through
    untouched (the zero params keep the masked branch finite, so it
    cannot poison the selected branch's gradient). For blocks whose
    carry is the activation alone; models with richer carries write
    their own slot loop."""
    if mask is None:
        x, _ = lax.scan(block, x, layers_chunk)
        return x

    def slot(carry, inp):
        layer, valid = inp
        new_x, _ = block(carry, layer)
        return jnp.where(valid > 0, new_x, carry), None

    x, _ = lax.scan(slot, x, (layers_chunk, mask))
    return x


def stack_stages_interleaved(
    layer_params: PyTree, num_stages: int, num_virtual: int
) -> PyTree:
    """[L, ...] -> [V, P, L/(V*P), ...] chunks for the circular schedule.

    Logical layer order: a microbatch visits device 0..P-1 with round-0
    chunks, wraps, visits 0..P-1 with round-1 chunks, ... — so layer
    ``l`` lands in chunk (round r = l // (P*per), device p = (l // per)
    % P).
    """

    def restack(x):
        layers = x.shape[0]
        total = num_stages * num_virtual
        if layers % total:
            raise ValueError(
                f"{layers} layers not divisible into {num_virtual}x"
                f"{num_stages} virtual stages"
            )
        per = layers // total
        return x.reshape(
            (num_virtual, num_stages, per) + x.shape[1:]
        )

    return jax.tree.map(restack, layer_params)


def pipeline_apply_interleaved(
    stage_fn: Callable[[PyTree, PyTree], PyTree],
    stage_params: PyTree,  # leaves [V, P, ...]; dim 1 pipe-sharded
    x_mb: PyTree,  # microbatch-stacked inputs, leaves [M, ...]
    axis_name: str = "pipe",
    batch_axes: Optional[Tuple] = ("data", "fsdp"),
    constrain: bool = True,
    remat_stage: bool = False,
) -> PyTree:
    """Circular (interleaved virtual stage) schedule.

    Role parity: PiPPy's ``StageInterleaver`` / Megatron interleaved
    virtual stages. Each physical stage holds V parameter chunks; a
    microbatch circles the pipe ring V times, taking chunk r on round r.
    With M microbatches the bubble shrinks from (P-1)/(M+P-1) to
    (P-1)/(V*M+P-1) — the V-fold reduction interleaving buys — at the
    cost of V-1 extra ring wraps of activation traffic.

    Scheduling invariant (device 0 is busy with wrapped microbatches as
    soon as round 1 begins): requires M >= P.
    """
    stage_leaves = jax.tree.leaves(stage_params)
    if not stage_leaves:
        raise ValueError("stage_params is empty")
    num_virtual, num_stages = stage_leaves[0].shape[:2]
    x_leaves = jax.tree.leaves(x_mb)
    num_mb = x_leaves[0].shape[0]
    if num_mb < num_stages:
        raise ValueError(
            f"circular schedule needs microbatches >= stages "
            f"(got M={num_mb} < P={num_stages})"
        )
    constrain = constrain and _context_has_axis(axis_name)

    if constrain:
        from jax.sharding import PartitionSpec as P

        stage_params = jax.tree.map(
            lambda w: lax.with_sharding_constraint(
                w,
                P(None, axis_name,
                  *(P.UNCONSTRAINED for _ in range(w.ndim - 2))),
            ),
            stage_params,
        )

    def maybe_constrain(tree):
        if not constrain:
            return tree
        return _stage_constraint(tree, axis_name, batch_axes)

    # stage p at tick t works on (round (t-p)//M, microbatch (t-p)%M)
    def chunk_select(params_v, round_idx, state):
        chunk = jax.tree.map(
            lambda w: lax.dynamic_index_in_dim(
                w, round_idx, 0, keepdims=False
            ),
            params_v,
        )
        return stage_fn(chunk, state)

    if remat_stage:
        # checkpoint OUTSIDE the round selection: the saved inputs are
        # then the loop-invariant [V, ...] params (a scan constant, not
        # stacked per tick) + the scalar round + the per-tick state —
        # checkpointing stage_fn itself would stack the dynamically
        # selected param chunk for every tick (~20 GB at 70B)
        chunk_select = jax.checkpoint(chunk_select)

    # vmap over stages: params [V, P, ...] -> per-stage [V, ...]
    vstage = jax.vmap(chunk_select, in_axes=(1, 0, 0))

    stage_ids = jnp.arange(num_stages)
    num_ticks = num_virtual * num_mb + num_stages - 1
    # a wrap activation leaves stage P-1 at tick m+P-1 but stage 0 only
    # consumes it at tick M+m (it processes all round-r jobs before any
    # round-r+1 job): a FIFO of M-P+1 slots provides exactly that delay
    fifo_len = num_mb - num_stages + 1

    state0 = jax.tree.map(
        lambda x: jnp.zeros((num_stages,) + x.shape[1:], x.dtype), x_mb
    )
    fifo0 = jax.tree.map(
        lambda x: jnp.zeros((fifo_len,) + x.shape[1:], x.dtype), x_mb
    )
    outs0 = jax.tree.map(jnp.zeros_like, x_mb)

    def tick(carry, t):
        state, fifo, outs = carry
        # stage 0 input: fresh microbatch during round 0, else the FIFO
        # head (the wrap that left stage P-1 exactly M-P+1 ticks ago)
        feed_fresh = t < num_mb
        fresh = jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, num_mb - 1), 0, keepdims=False
            ),
            x_mb,
        )
        inp = jax.tree.map(
            lambda f, q: jnp.where(feed_fresh, f, q[0]), fresh, fifo
        )
        state = jax.tree.map(
            lambda s, i: lax.dynamic_update_index_in_dim(s, i, 0, 0),
            state, inp,
        )
        state = maybe_constrain(state)
        rounds = jnp.clip((t - stage_ids) // num_mb, 0, num_virtual - 1)
        y = vstage(stage_params, rounds, state)
        y = maybe_constrain(y)

        # last stage finishes microbatch m of the FINAL round at tick
        # (V-1)*M + m + (P-1)
        fin = t - (num_stages - 1) - (num_virtual - 1) * num_mb
        valid = jnp.logical_and(fin >= 0, fin < num_mb)
        idx = jnp.clip(fin, 0, num_mb - 1)
        outs = jax.tree.map(
            lambda o, yy: jnp.where(
                valid,
                lax.dynamic_update_index_in_dim(o, yy[-1], idx, 0),
                o,
            ),
            outs, y,
        )
        # push this tick's wrap (stage P-1 output) onto the FIFO tail;
        # slot 0 of the ring shift is overwritten next tick anyway
        fifo = jax.tree.map(
            lambda q, yy: lax.dynamic_update_index_in_dim(
                jnp.roll(q, -1, axis=0), yy[-1], fifo_len - 1, 0
            ),
            fifo, y,
        )
        state = jax.tree.map(lambda yy: jnp.roll(yy, 1, axis=0), y)
        return (state, fifo, outs), None

    (_, _, outs), _ = lax.scan(
        tick, (state0, fifo0, outs0), jnp.arange(num_ticks)
    )
    return outs
