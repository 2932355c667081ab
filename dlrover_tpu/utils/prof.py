"""FLOPs / memory / latency profiling.

Role parity: ``atorch/atorch/utils/prof.py:41`` (``AProfiler`` — per-module
FLOPs/params/latency via forward hooks and hand-written per-op formulas,
``:486-692``) and ``auto/dry_runner/dry_runner.py:12-144`` (timed dryrun
steps feeding the strategy search).

TPU-first: no hooks and no hand-written formulas — XLA already knows. A
jitted function's ``compiled.cost_analysis()`` carries exact FLOPs and
bytes-accessed for the whole fused program, and ``memory_analysis()`` the
real HBM footprint after layout/fusion. The dry runner times the compiled
step on device, which is what the auto-tune search actually optimizes.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from dlrover_tpu.common.log import get_logger

logger = get_logger("utils.prof")


def count_params(tree: Any) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def param_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def compiled_peak_bytes(compiled) -> int:
    """Per-device HBM residency of a compiled program from
    ``memory_analysis()``: arguments (the sharded state + batch) plus
    transient temps plus outputs, minus donated (aliased) bytes so
    donation isn't double-counted — the same accounting the AOT
    fit-proof applies. 0 when the backend has no memory analysis."""
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - backend-dependent API
        logger.debug("memory_analysis unavailable", exc_info=True)
        return 0
    if mem is None:
        return 0
    return int(
        getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "temp_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0)
    )


def derived_mfu(flops_per_step: float, step_time_s: float,
                peak_flops_per_s: float) -> float:
    """THE model-FLOPs-utilization formula: (FLOPs per step / step
    seconds) over hardware peak. ``ProfileResult.mfu``, the runtime
    attribution gauges (``telemetry.attribution``) and the bench all
    price MFU through this one function, so the one-shot profile and
    the live gauge can never drift apart. FLOPs and peak must share a
    basis (both per device, or both whole-mesh)."""
    if peak_flops_per_s <= 0 or step_time_s <= 0:
        return 0.0
    return flops_per_step / (step_time_s * peak_flops_per_s)


@dataclass
class CostReport:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    peak_memory_bytes: int = 0
    # arithmetic intensity = flops / bytes: low values ⇒ HBM-bound on TPU.
    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / self.bytes_accessed if self.bytes_accessed else 0.0


def analyze_cost(fn: Callable, *args, **kwargs) -> CostReport:
    """Compile ``fn`` for the given args and read XLA's cost model."""
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    cost = compiled.cost_analysis()
    report = CostReport(
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
    )
    report.peak_memory_bytes = compiled_peak_bytes(compiled)
    return report


@dataclass
class ProfileResult:
    steps_per_sec: float
    step_time_ms: float
    flops_per_step: float
    achieved_flops_per_sec: float
    param_count: int
    peak_memory_bytes: int

    def mfu(self, peak_flops_per_sec: float) -> float:
        """Model FLOPs utilization against a hardware peak (the shared
        ``derived_mfu`` formula — same one the live attribution gauges
        use)."""
        return derived_mfu(self.flops_per_step,
                           1.0 / max(self.steps_per_sec, 1e-12),
                           peak_flops_per_sec)


class DryRunner:
    """Timed execution of a compiled train step (reference: dry_runner).

    Env knobs mirror the reference's
    ``ATORCH_DRYRUN_WARMUP_STEP``/``PROFILE_STEP``
    (``auto/accelerate.py:150-152``):
    ``DLROVER_TPU_DRYRUN_WARMUP`` / ``DLROVER_TPU_DRYRUN_STEPS``.
    """

    def __init__(self, warmup: Optional[int] = None, steps: Optional[int] = None):
        import os

        self.warmup = warmup if warmup is not None else int(
            os.environ.get("DLROVER_TPU_DRYRUN_WARMUP", "2")
        )
        self.steps = steps if steps is not None else int(
            os.environ.get("DLROVER_TPU_DRYRUN_STEPS", "5")
        )

    def profile(
        self,
        train_step: Callable,
        state: Any,
        batch: Any,
        rng: Optional[jax.Array] = None,
    ) -> ProfileResult:
        """Run warmup + timed steps; returns throughput + cost facts.

        ``train_step`` must be (state, batch, rng) -> (state, metrics) and
        already sharded/jitted (i.e. ``AccelerateResult.train_step``).
        """
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        cost = analyze_cost(train_step, state, batch, rng)

        for _ in range(max(self.warmup, 1)):
            state, _ = train_step(state, batch, rng)
        jax.block_until_ready(state)

        t0 = time.perf_counter()
        for _ in range(max(self.steps, 1)):
            state, metrics = train_step(state, batch, rng)
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0

        n = max(self.steps, 1)
        sps = n / elapsed
        result = ProfileResult(
            steps_per_sec=sps,
            step_time_ms=1000.0 * elapsed / n,
            flops_per_step=cost.flops,
            achieved_flops_per_sec=cost.flops * sps,
            param_count=count_params(state.params)
            if hasattr(state, "params") else count_params(state),
            peak_memory_bytes=cost.peak_memory_bytes,
        )
        logger.info(
            "dryrun: %.2f steps/s (%.1f ms/step), %.3g flops/step, "
            "%d params",
            result.steps_per_sec, result.step_time_ms,
            result.flops_per_step, result.param_count,
        )
        return result


class AProfiler:
    """Model-level profile summary (reference: AProfiler).

    Where the reference walks modules with hooks, here the unit of
    reporting is the pytree path: per-subtree parameter counts plus the
    whole-program XLA cost — per-op FLOPs formulas are obsolete under
    fusion, so they are intentionally not reproduced.
    """

    def __init__(self, params: Any):
        self._params = params

    def params_by_subtree(self, depth: int = 1) -> Dict[str, int]:
        out: Dict[str, int] = {}
        flat = jax.tree_util.tree_flatten_with_path(self._params)[0]
        for path, leaf in flat:
            key = "/".join(
                str(getattr(p, "key", getattr(p, "idx", p)))
                for p in path[:depth]
            )
            out[key] = out.get(key, 0) + leaf.size
        return out

    def summary(
        self, loss_fn: Optional[Callable] = None, batch: Any = None,
        rng: Optional[jax.Array] = None,
    ) -> Dict[str, Any]:
        info: Dict[str, Any] = {
            "param_count": count_params(self._params),
            "param_bytes": param_bytes(self._params),
            "subtrees": self.params_by_subtree(),
        }
        if loss_fn is not None and batch is not None:
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            cost = analyze_cost(loss_fn, self._params, batch, rng)
            info["forward_flops"] = cost.flops
            info["bytes_accessed"] = cost.bytes_accessed
            info["arithmetic_intensity"] = cost.arithmetic_intensity
        return info
