"""Dryrun profiling + strategy search.

Role parity: atorch's acceleration engine — ``dry_runner/dry_runner.py``
(timed profile steps), ``auto/engine/executor.py`` + ``sg_algo`` (candidate
generation and scoring). The TPU version scores candidates by compiling the
real train step (XLA cost analysis gives FLOPs/bytes for free) and timing a
few steps; the search space is the mesh-factorization catalog from
``mesh.candidate_plans``.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence

import jax

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.accelerate import AccelerateResult, accelerate
from dlrover_tpu.parallel.mesh import MeshPlan, candidate_plans
from dlrover_tpu.parallel.strategy import Strategy

logger = get_logger("parallel.tune")


@dataclass
class DryrunReport:
    strategy: Strategy
    compile_time_s: float = 0.0
    step_time_s: float = 0.0
    flops_per_step: float = 0.0
    peak_memory_bytes: int = 0
    error: str = ""

    @property
    def ok(self) -> bool:
        return not self.error

    @property
    def device_flops_per_s(self) -> float:
        if self.step_time_s <= 0:
            return 0.0
        return self.flops_per_step / self.step_time_s


def _process_local_slice(batch, process_count: int, process_index: int):
    """This process's contiguous row share of a GLOBAL example batch.

    Raises when the rows don't divide evenly: floor division would
    silently drop the trailing ``rows % process_count`` rows, and the
    assembled global batch would no longer match
    ``strategy.global_batch_size`` (the dryrun would then profile a
    different program than production runs).
    """
    rows = jax.tree.leaves(batch)[0].shape[0]
    if rows % process_count:
        raise ValueError(
            f"dryrun example batch has {rows} rows, not divisible by "
            f"process_count={process_count}: the per-process slice "
            f"would silently drop the trailing {rows % process_count} "
            f"row(s). Pad or trim the example batch to a multiple of "
            f"the process count."
        )
    share = rows // process_count
    return jax.tree.map(
        lambda x: x[share * process_index: share * (process_index + 1)],
        batch,
    )


def dryrun(result: AccelerateResult, example_batch, rng=None,
           warmup_steps: int = 1, profile_steps: int = 3,
           trace_dir: str = "") -> DryrunReport:
    """Compile + a few timed steps (``ATORCH_DRYRUN_*`` parity).

    ``trace_dir``: capture the timed steps as an xprof trace (open with
    tensorboard/xprof) — the per-op view when the aggregate numbers in
    the report aren't enough."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    report = DryrunReport(strategy=result.strategy)
    try:
        state = result.init_fn(rng)
        if jax.process_count() > 1:
            # shard_batch's multi-process contract takes PROCESS-LOCAL
            # rows; every engine node holds the same GLOBAL example, so
            # slice this process's share (otherwise the dryrun would
            # assemble — and time — a process_count-times larger batch)
            example_batch = _process_local_slice(
                example_batch, jax.process_count(), jax.process_index()
            )
        batch = result.shard_batch(example_batch)

        t0 = time.time()
        lowered = result.train_step.lower(state, batch, rng)
        compiled = lowered.compile()
        report.compile_time_s = time.time() - t0

        # the one peak-residency accounting (utils/prof)
        from dlrover_tpu.utils.prof import compiled_peak_bytes

        report.flops_per_step = float(
            compiled.cost_analysis().get("flops", 0.0))
        report.peak_memory_bytes = compiled_peak_bytes(compiled)

        for _ in range(warmup_steps):
            state, _metrics = compiled(state, batch, rng)
        jax.block_until_ready(state)
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            t0 = time.time()
            for _ in range(profile_steps):
                state, _metrics = compiled(state, batch, rng)
            jax.block_until_ready(state)
            report.step_time_s = (time.time() - t0) / max(1, profile_steps)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
    except Exception as e:  # candidate infeasible (OOM, bad factorization)
        report.error = f"{type(e).__name__}: {e}"
        logger.info("dryrun failed for %s: %s",
                    report.strategy.mesh, report.error[:200])
    return report


def search_strategy(
    init_fn: Callable,
    loss_fn: Callable,
    optimizer,
    example_batch,
    base_strategy: Optional[Strategy] = None,
    candidates: Optional[Sequence[MeshPlan]] = None,
    devices: Optional[Sequence] = None,
    max_candidates: int = 8,
    profile_steps: int = 3,
    model_spec=None,
) -> tuple:
    """Try candidate meshes; return (best_strategy, all_reports).

    The reference's engine distributes ANALYSE/TUNE/DRYRUN tasks over
    ranks; here every candidate compiles against the same devices, so the
    loop is local and the winning strategy is broadcast via the master's
    ParallelConfig push instead.

    ``model_spec`` (a ``planner.ModelSpec``): when given, the analytic
    planner orders the candidates before the budget truncation, so the
    measured search spends its compiles on the cost model's best guesses
    instead of dropping candidates in enumeration order.
    """
    base = base_strategy or Strategy()
    n_devices = len(devices) if devices is not None else jax.device_count()
    plans = list(candidates) if candidates is not None else candidate_plans(
        n_devices
    )
    if model_spec is not None and len(plans) > 1:
        from dlrover_tpu.parallel import planner

        scored = [
            # resolve -1 (infer) axes first: estimate() would clamp
            # them to 1 and misprice the plan
            planner.estimate(p.resolve(n_devices), model_spec,
                             remat_policy=base.remat_policy)
            for p in plans
        ]
        # predicted-feasible first (fastest first), predicted-OOM last —
        # kept in the pool so a wrong memory model only demotes, never
        # eliminates
        scored.sort(key=lambda s: (not s.fits, s.step_time_s))
        plans = [s.plan for s in scored]
    if len(plans) > max_candidates:
        logger.info(
            "search: truncating %d candidates to %d (dropped: %s)",
            len(plans), max_candidates,
            [p.axis_sizes() for p in plans[max_candidates:]],
        )
        plans = plans[:max_candidates]
    reports: List[DryrunReport] = []
    for plan in plans:
        strategy = dataclasses.replace(base, mesh=plan)
        try:
            result = accelerate(
                init_fn, loss_fn, optimizer, example_batch,
                strategy=strategy, devices=devices,
            )
        except Exception as e:
            reports.append(DryrunReport(strategy=strategy,
                                        error=f"{type(e).__name__}: {e}"))
            continue
        reports.append(
            dryrun(result, example_batch, profile_steps=profile_steps)
        )
    viable = [r for r in reports if r.ok and r.step_time_s > 0]
    if not viable:
        raise RuntimeError(
            "no viable strategy found; errors: "
            + "; ".join(r.error[:100] for r in reports)
        )
    best = min(viable, key=lambda r: r.step_time_s)
    logger.info(
        "search: best mesh %s at %.4fs/step over %d candidates",
        best.strategy.mesh, best.step_time_s, len(reports),
    )
    return best.strategy, reports
