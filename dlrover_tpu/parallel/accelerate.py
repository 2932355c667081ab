"""``accelerate`` — one call from (model, optimizer, strategy) to a
sharded, compiled, elastic-ready train step.

Role parity: ``auto_accelerate`` (``atorch/atorch/auto/accelerate.py:395``).
Where the reference mutates the model through a stack of wrappers
(DDP/FSDP/TP rewrites/AMP/checkpoint), the TPU version is purely
functional: parameters and optimizer state get ``NamedSharding``s from the
strategy's rules, the train step is ``jit``-ed with those shardings, and
XLA's SPMD partitioner inserts every collective. Gradient accumulation (the
fixed-global-batch elasticity lever) is a ``lax.scan`` over microbatches.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import flax.struct

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.sharding_rules import batch_sharding
from dlrover_tpu.parallel.strategy import Strategy

logger = get_logger("parallel.accelerate")

# loss_fn contract: (params, batch, rng) -> (scalar_loss, aux_dict)
LossFn = Callable[[Any, Any, Any], Tuple[jnp.ndarray, dict]]


@dataclass(frozen=True)
class StepBuffers:
    """State the step itself moves, by no gradient, from what its loss
    function counted (a router's selection bias moved by the experts'
    load). A loss function that has some carries this as its
    ``step_buffers`` attribute and takes the buffers as a fourth
    argument, ``loss_fn(params, batch, rng, buffers)``; ``accelerate``
    keeps them in ``TrainState.buffers`` and calls ``update`` in the
    compiled step after the optimizer. They take no gradient (the loss
    function reads them as data) and the optimizer never sees them."""

    # params -> the buffers at the start (a pytree of arrays; its paths
    # under ``buffers/`` are what the sharding rules match)
    init: Callable[[Any], Any]
    # (buffers, aux) -> (the buffers after this step, the aux that goes
    # on as metrics: what was counted for the update alone taken out)
    update: Callable[[Any, dict], Tuple[Any, dict]]


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    opt_state: Any
    # error-feedback residual of the low-precision gradient path
    # (``grad_precision`` != "bf16"): the decompression error of the
    # last step's quantized gradients, param-shaped and param-sharded,
    # added back before the next quantize so the error telescopes
    # instead of accumulating. Part of the TRAINING STATE proper — it
    # rides HostSnapshot, checkpoint save/restore and live reshard
    # exactly like optimizer moments. None when the gradient wire is
    # exact (the default), so existing checkpoints and states are
    # structurally unchanged.
    wire_residual: Any = None
    # what the step moves from its own counters (``StepBuffers``): not
    # a parameter and not an optimizer moment, but training state like
    # them: it rides HostSnapshot, checkpoint save and restore and live
    # reshard, sharded by the rules under ``buffers/``. None, and so no
    # leaf and no instruction, for a loss function without
    # ``step_buffers``.
    buffers: Any = None


@dataclass
class AccelerateResult:
    train_step: Callable  # (state, batch, rng) -> (state, metrics)
    eval_step: Callable  # (state, batch) -> metrics
    init_fn: Callable  # (rng) -> sharded TrainState
    mesh: Any
    state_sharding: Any
    batch_spec: Any
    strategy: Strategy

    def compiled_cache_size(self) -> int:
        """Executables held by this result's jitted train step. A loop
        that ran N steps with an unchanged delta here recompiled
        nothing — the zero-recompile gate of the warm-restart /
        live-reshard paths and of the tests' stepped loops."""
        inner = getattr(self.train_step, "__wrapped__", self.train_step)
        size = getattr(inner, "_cache_size", None)
        return int(size()) if callable(size) else 0

    def shard_batch(self, batch):
        """Host batch -> mesh-sharded global batch.

        Fully-addressable mesh (single process, or a local-subset
        mesh): ``batch`` is the whole global batch. Multi-host mesh:
        each process passes its PROCESS-LOCAL rows — the shard its
        data loader owns under the master's data-sharding service —
        and the global array is assembled across hosts
        (``put_global_batch``). This is the multi-host data plane the
        reference reaches via per-rank torch DataLoader sharding +
        NCCL.
        """
        return put_global_batch(batch, self.batch_spec,
                                self.strategy.global_batch_size)


def put_global_batch(batch, sharding, global_rows: int = 0):
    """Host rows -> a sharded global batch.

    A fully-addressable sharding (single process, or a mesh of only
    this process's devices) goes through plain ``device_put`` with the
    batch as the whole global batch. A sharding spanning OTHER
    processes' devices — the real multi-host case, where ``device_put``
    raises on non-addressable devices — assembles the global array
    from each process's PROCESS-LOCAL rows
    (``jax.make_array_from_process_local_data``). When ``global_rows``
    is known, the local row count is validated loudly: feeding the
    global batch on the multi-host path would otherwise silently
    assemble a process_count-times larger batch of duplicated rows.
    """
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(batch, sharding)
    import numpy as np

    rows = jax.tree.leaves(batch)[0].shape[0]
    expected = global_rows // jax.process_count() if global_rows else 0
    if expected and rows != expected:
        raise ValueError(
            f"a multi-host sharding takes PROCESS-LOCAL rows: expected "
            f"{expected} rows/process (global batch {global_rows} over "
            f"{jax.process_count()} processes), got {rows}"
        )
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x)
        ),
        batch,
    )


def _remat_wrap(loss_fn: LossFn, policy_name: str) -> LossFn:
    from dlrover_tpu.ops.remat import apply_remat

    return apply_remat(loss_fn, policy_name or "none")


def resolve_grad_precision(requested: Optional[str] = None) -> str:
    """The effective gradient-path precision at BUILD time: an explicit
    request wins, else the Context knob (``grad_precision``). A
    quantized choice degrades to "bf16" (logged, never raised) when
    the backend fails the fp8 probe. Build-time — not trace-time like
    the dense gathers — because a quantized gradient path changes the
    STRUCTURE of TrainState (the error-feedback residual), which a
    live retune cannot swap under a running state."""
    from dlrover_tpu.common.config import get_context
    from dlrover_tpu.ops.quantize import GRAD_PRECISIONS

    p = (requested or "").strip()
    if not p:
        p = str(getattr(get_context(), "grad_precision", "bf16")
                or "bf16").strip() or "bf16"
    if p not in GRAD_PRECISIONS:
        raise ValueError(
            f"unknown grad precision {p!r}; choose one of "
            f"{GRAD_PRECISIONS}"
        )
    if p != "bf16":
        from dlrover_tpu.ops.shard_compat import fp8_wire_supported

        if not fp8_wire_supported():
            logger.warning(
                "grad precision %r requested but the backend fails the "
                "fp8 probe; gradients stay exact (bf16 path)", p,
            )
            return "bf16"
    return p


def _apply_grad_wire(grads, residual, grad_precision: str):
    """(effective grads, new residual): the error-feedback quantized
    gradient path, per float leaf (blocks along each leaf's last dim,
    computed SHARDWISE — the transform is elementwise over the
    param-sharded gradient tree, so it adds zero collective traffic).
    Non-float leaves pass through untouched."""
    from dlrover_tpu.ops.quantize import error_feedback_qdq

    feedback = grad_precision != "fp8_nofb"

    def one(g, r):
        if (r is None or getattr(g, "ndim", 0) == 0
                or not jnp.issubdtype(jnp.asarray(g).dtype,
                                      jnp.floating)):
            return g, r
        gq, nr = error_feedback_qdq(g, r, feedback=feedback)
        return gq, nr

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residual)
    pairs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    new_g = jax.tree_util.tree_unflatten(treedef, [p[0] for p in pairs])
    new_r = jax.tree_util.tree_unflatten(treedef, [p[1] for p in pairs])
    return new_g, new_r


def accelerate(
    init_fn: Callable[[Any], Any],
    loss_fn: LossFn,
    optimizer,
    example_batch: Any,
    strategy: Optional[Strategy] = None,
    rng: Optional[jax.Array] = None,
    devices: Optional[Sequence] = None,
    extra_metrics_fn: Optional[Callable] = None,
    grad_precision: Optional[str] = None,
) -> AccelerateResult:
    """Build the sharded training program.

    Args:
      init_fn: rng -> params pytree (abstractly evaluated; params are
        materialized directly into their shardings, so 100B-scale models
        never exist unsharded — the ``meta_model_utils`` parity).
      loss_fn: (params, batch, rng) -> (loss, aux dict).
      optimizer: an optax GradientTransformation.
      example_batch: host-local example with GLOBAL batch dimension.
      strategy: mesh/rules/remat/dtype/accum decisions (default: all-fsdp).
      grad_precision: "bf16" (exact, default) | "fp8" — quantize the
        per-shard gradient tree with an ERROR-FEEDBACK residual
        carried in ``TrainState.wire_residual`` (zeros at init,
        param-shaped/-sharded). None resolves the Context knob
        (``grad_precision``). Resolved at BUILD time: the residual
        changes the TrainState structure, so it cannot flip under a
        live retune the way the dense-gather wire can.
    """
    from dlrover_tpu.common.config import get_context
    from dlrover_tpu.utils.compile_cache import enable_compile_cache

    # make every train-step compile land in the persistent cache so a
    # restarted (preempted/rescaled) job warm-starts its compiles
    enable_compile_cache()
    if get_context().jax_debug_nans:
        # opt-in NaN trap (DLROVER_TPU_JAX_DEBUG_NANS=1): jit re-runs the
        # offending op un-jitted and raises at the first NaN — the
        # debug-flag counterpart of the reference's error monitor
        jax.config.update("jax_debug_nans", True)

    strategy = strategy or Strategy()
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    batch_rows = jax.tree.leaves(example_batch)[0].shape[0]
    if strategy.global_batch_size and strategy.global_batch_size != batch_rows:
        raise ValueError(
            f"strategy.global_batch_size={strategy.global_batch_size} but "
            f"the example batch has {batch_rows} rows"
        )
    if batch_rows % max(1, strategy.grad_accum_steps):
        raise ValueError(
            f"grad_accum_steps={strategy.grad_accum_steps} does not divide "
            f"the global batch of {batch_rows} rows"
        )
    strategy = dataclasses.replace(strategy, global_batch_size=batch_rows)

    mesh = strategy.mesh.build(devices)
    rules = strategy.rules()
    step_buffers: Optional[StepBuffers] = getattr(
        loss_fn, "step_buffers", None)
    loss_fn = _remat_wrap(loss_fn, strategy.remat_policy)

    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())
    batch_spec = batch_sharding(mesh)

    grad_precision = resolve_grad_precision(grad_precision)

    def make_state(r) -> TrainState:
        params = init_fn(r)
        residual = None
        if grad_precision != "bf16":
            # error-feedback residual: zeros, param-shaped — sharded
            # like the params (the rules match the mirrored
            # wire_residual/... paths), so it reshards with them
            residual = jax.tree.map(jnp.zeros_like, params)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
            wire_residual=residual,
            buffers=step_buffers.init(params) if step_buffers else None,
        )

    abstract_state = jax.eval_shape(make_state, rng)
    state_sharding = rules.tree_shardings(mesh, abstract_state)

    sharded_init = jax.jit(make_state, out_shardings=state_sharding)

    accum = max(1, strategy.grad_accum_steps)

    def grad_fn(params, batch, step_rng, *buffers):
        """``jax.value_and_grad(loss_fn, has_aux=True)`` with its two
        halves under the names a profiler trace shows (the same
        jaxpr: a scope is metadata of the operations in it).
        ``buffers``: the state's, where the loss function reads some."""
        with jax.named_scope("forward"):
            loss, vjp, aux = jax.vjp(
                lambda p: loss_fn(p, batch, step_rng, *buffers), params,
                has_aux=True)
        with jax.named_scope("backward"):
            (grads,) = vjp(jnp.ones_like(loss))
        return (loss, aux), grads

    def _accumulate_grads(params, batch, step_rng, *buffers):
        """Microbatch scan keeping the global batch semantics fixed."""
        def split_mb(x):
            b = x.shape[0]
            return x.reshape((accum, b // accum) + x.shape[1:])

        microbatches = jax.tree.map(split_mb, batch)
        rngs = jax.random.split(step_rng, accum)

        def body(carry, mb_rng):
            grad_sum, loss_sum = carry
            mb, r = mb_rng
            (loss, aux), grads = grad_fn(params, mb, r, *buffers)
            carry = (
                jax.tree.map(jnp.add, grad_sum, grads),
                loss_sum + loss,
            )
            return carry, aux

        zeros = jax.tree.map(jnp.zeros_like, params)
        (grad_sum, loss_sum), aux_stack = lax.scan(
            body, (zeros, jnp.zeros(())), (microbatches, rngs)
        )
        grads = jax.tree.map(lambda g: g / accum, grad_sum)
        aux = jax.tree.map(lambda a: a.mean(axis=0), aux_stack)
        return grads, loss_sum / accum, aux

    def train_step(state: TrainState, batch, step_rng):
        buffers = (state.buffers,) if step_buffers else ()
        if accum == 1:
            (loss, aux), grads = grad_fn(state.params, batch, step_rng,
                                         *buffers)
        else:
            grads, loss, aux = _accumulate_grads(
                state.params, batch, step_rng, *buffers
            )
        new_residual = state.wire_residual
        if state.wire_residual is not None and grad_precision != "bf16":
            # low-precision gradient path with error feedback: the
            # optimizer (and everything downstream — norm, finite
            # gate) consumes the decompressed gradients the quantized
            # wire delivers; the decompression error rides forward in
            # the state so it telescopes instead of compounding
            grads, new_residual = _apply_grad_wire(
                grads, state.wire_residual, grad_precision
            )
        import optax

        with jax.named_scope("optimizer"):
            if hasattr(optimizer, "update_with_grad_fn"):
                # two-gradient optimizers (WSAM/SAM family): hand them a
                # full forward/backward at arbitrary params on this same
                # batch
                def full_grad_fn(p):
                    if accum == 1:
                        return grad_fn(p, batch, step_rng, *buffers)[1]
                    return _accumulate_grads(p, batch, step_rng,
                                             *buffers)[0]

                updates, new_opt_state = optimizer.update_with_grad_fn(
                    grads, state.opt_state, state.params, full_grad_fn
                )
            else:
                updates, new_opt_state = optimizer.update(
                    grads, state.opt_state, state.params
                )
            new_params = optax.apply_updates(state.params, updates)
        new_buffers = state.buffers
        if step_buffers:
            # after the optimizer, from this step's own counters (over
            # microbatches their mean, whose order against the mean
            # load is the sum's)
            new_buffers, aux = step_buffers.update(state.buffers, aux)
        grad_norm = optax.global_norm(grads)
        metrics = {
            # loss_fn aux entries (e.g. the MoE load-balance signals
            # moe_dropped_frac / moe_expert_load) ride the step metrics;
            # reserved keys below win on collision
            **aux,
            "loss": loss,
            "grad_norm": grad_norm,
            # NaN/overflow guardrail (reference: the error monitor's
            # silent-NaN failure class): any non-finite grad propagates
            # into the global norm, so this is a free full-tree check
            # the executor routes to report_failure
            "finite": jnp.isfinite(loss) & jnp.isfinite(grad_norm),
            "step": state.step + 1,
        }
        if extra_metrics_fn is not None:
            metrics.update(extra_metrics_fn(state.params, grads))
        new_state = TrainState(
            step=state.step + 1, params=new_params,
            opt_state=new_opt_state, wire_residual=new_residual,
            buffers=new_buffers,
        )
        return new_state, metrics

    def eval_step(state: TrainState, batch):
        buffers = (state.buffers,) if step_buffers else ()
        loss, aux = loss_fn(state.params, batch, jax.random.PRNGKey(0),
                            *buffers)
        if step_buffers:  # the metrics' form of the aux; nothing moves
            _, aux = step_buffers.update(state.buffers, aux)
        return {"loss": loss, **aux}

    def _under_mesh(fn):
        """Trace under a mesh context so in-model sharding constraints
        (pipeline stages, manual annotations) resolve against our mesh."""
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            try:
                ctx = jax.sharding.set_mesh(mesh)
            except ValueError:
                # already inside a trace (e.g. eval_shape over init_fn):
                # the caller's mesh context governs
                return fn(*args, **kwargs)
            with ctx:
                return fn(*args, **kwargs)

        if hasattr(fn, "lower"):
            def lower(*args, **kwargs):
                with jax.sharding.set_mesh(mesh):
                    return fn.lower(*args, **kwargs)

            wrapped.lower = lower
        return wrapped

    jit_train_step = _under_mesh(jax.jit(
        train_step,
        in_shardings=(state_sharding, batch_spec, replicated),
        out_shardings=(state_sharding, replicated),
        donate_argnums=(0,),
    ))
    jit_eval_step = _under_mesh(jax.jit(
        eval_step,
        in_shardings=(state_sharding, batch_spec),
        out_shardings=replicated,
    ))

    logger.info(
        "accelerate: mesh=%s accum=%d rules=%s remat=%s"
        " grad_precision=%s",
        dict(zip(mesh.axis_names, mesh.devices.shape)),
        accum, strategy.rule_set, strategy.remat_policy or "none",
        grad_precision,
    )
    return AccelerateResult(
        train_step=jit_train_step,
        eval_step=jit_eval_step,
        init_fn=_under_mesh(sharded_init),
        mesh=mesh,
        state_sharding=state_sharding,
        batch_spec=batch_spec,
        strategy=strategy,
    )
