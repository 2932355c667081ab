"""Calibrate the planner's cost model against measured runtime series.

``parallel.planner.estimate`` prices a mesh from first principles
(datasheet FLOPs, link bandwidths, a bench-fitted efficiency). That is
the right prior before anything has run — but the running job KNOWS its
real step time: the per-node series the diagnosis plane collects
(``master/monitor/node_series.py``) carries windowed step-time,
dispatch and host-sync percentiles. This module fits per-term
correction factors (predicted vs observed) so the optimizer's candidate
pricing is anchored to reality while keeping the analytic model's
RELATIVE structure (how cost scales with mesh shape and dispatch
mode) — the part measurement alone cannot provide.

Three factor families (``TermCorrections``):

  dispatch  measured per-step host dispatch time over the model's
            ``HOST_DISPATCH_OVERHEAD_S`` constant. The cleanest
            attribution: the executor's dispatch histogram times
            exactly this term, once per step.
  compute   measured device-bound per-step time over the predicted
            compute seconds. Observable only when the job is NOT
            dispatch-bound (otherwise the device time hides under the
            floor and the previous factor is kept).
  comm      collective seconds scale; defaults to tracking the compute
            factor (the two are not separable from step time alone —
            a future HLO-profile feed can split them).

Recombination uses the SAME formula as ``estimate`` itself
(``planner.combine_step_time``), so a calibrated prediction for the
*current* config reproduces the measured p50 by construction — the
property the acceptance test pins.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, Optional

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.planner import (
    COMM_BREAKDOWN_KEYS,
    HOST_DISPATCH_OVERHEAD_S,
    DeviceSpec,
    ModelSpec,
    PlanScore,
    combine_step_time,
    estimate,
)

logger = get_logger("master.optimizer.calibration")

# factors are clamped into this band: a single garbage window (clock
# hiccup, empty histogram) must not blow the model up by 10^6
_FACTOR_MIN = 0.02
_FACTOR_MAX = 1e4

# the measured step p50 must exceed the dispatch share by this margin
# before the residual is trusted as a DEVICE time observation
_DEVICE_VISIBLE_MARGIN = 1.25

# analytic-memory fit headroom (the planner's own 0.8: allocator
# fragmentation, collective buffers, hoisted gathers)
_FIT_HEADROOM = 0.8


class MemoryInfeasibleError(ValueError):
    """A candidate plan's predicted peak HBM exceeds the device budget:
    it must be REJECTED BEFORE PRICING (a cheap-looking plan the
    devices cannot hold would win the ranking and then OOM the apply).
    Carries the evidence the decision trail records."""

    def __init__(self, mesh, memory_bytes: float, budget_bytes: float):
        super().__init__(
            f"plan {mesh} memory-infeasible: predicted peak "
            f"{memory_bytes / 1e9:.2f} GB > budget "
            f"{budget_bytes / 1e9:.2f} GB"
        )
        self.mesh = mesh
        self.memory_bytes = float(memory_bytes)
        self.budget_bytes = float(budget_bytes)


@dataclass
class TermCorrections:
    """Multiplicative predicted->observed factors per cost-term family
    (1.0 = the analytic model was right)."""

    compute: float = 1.0
    comm: float = 1.0
    dispatch: float = 1.0
    samples: int = 0
    updated_ts: float = 0.0

    def to_dict(self) -> Dict[str, float]:
        return {
            "compute": round(self.compute, 4),
            "comm": round(self.comm, 4),
            "dispatch": round(self.dispatch, 4),
            "samples": self.samples,
            "updated_ts": self.updated_ts,
        }


def _clamp(x: float) -> float:
    return min(max(float(x), _FACTOR_MIN), _FACTOR_MAX)


def calibrated_step_time(
    score: PlanScore,
    corrections: TermCorrections,
    overlapped: bool = True,
) -> float:
    """Re-price one ``estimate`` result under the fitted corrections,
    through the planner's own combining formula."""
    bd = score.breakdown
    compute_s = bd.get("compute_s", 0.0) * corrections.compute
    comm_s = sum(bd.get(k, 0.0) for k in COMM_BREAKDOWN_KEYS)
    comm_s *= corrections.comm
    dispatch_s = HOST_DISPATCH_OVERHEAD_S * corrections.dispatch
    return combine_step_time(compute_s, comm_s, dispatch_s,
                             overlapped=overlapped)


@dataclass
class CostCalibrator:
    """Fits ``TermCorrections`` from measured (step p50, dispatch p50)
    points for the CURRENT config, one observation at a time (EMA over
    windows, so one noisy window cannot whipsaw the model)."""

    model: ModelSpec
    device: DeviceSpec = field(default_factory=DeviceSpec)
    remat_policy: str = ""
    # per-device HBM budget (bytes) for the memory-feasibility gate;
    # 0 = the device spec's capacity under the planner's fit headroom
    hbm_budget_bytes: float = 0.0
    ema: float = 0.5  # weight of the NEWEST observation
    corrections: TermCorrections = field(default_factory=TermCorrections)
    # factor families that have absorbed at least one real observation:
    # the FIRST observation of a family is adopted outright (blending
    # it with the 1.0 prior would halve a true 10x correction right
    # when the first replan decision is being made), later ones EMA in.
    # Keyed per family — a dispatch-only first pass must not make the
    # compute family think it has been observed.
    _seen: set = field(default_factory=set)

    def base_estimate(self, mesh) -> PlanScore:
        return estimate(
            mesh, self.model, self.device,
            remat_policy=self.remat_policy,
        )

    def observe(
        self,
        mesh,
        measured_step_p50: Optional[float],
        measured_dispatch_p50: Optional[float] = None,
        now: Optional[float] = None,
    ) -> TermCorrections:
        """One calibration pass against the running config's window.

        ``measured_dispatch_p50`` is what the executor's dispatch
        histogram observes; ``measured_step_p50`` is the node-series
        step histogram's."""
        if measured_step_p50 is None and measured_dispatch_p50 is None:
            return self.corrections
        base = self.base_estimate(mesh)
        cur = self.corrections

        def blend(family: str, old: float, new: float) -> float:
            if family not in self._seen:
                self._seen.add(family)
                return _clamp(new)
            return _clamp(old * (1.0 - self.ema) + new * self.ema)

        dispatch_per_step = None
        if measured_dispatch_p50 is not None and measured_dispatch_p50 > 0:
            cur.dispatch = blend(
                "dispatch", cur.dispatch,
                measured_dispatch_p50 / HOST_DISPATCH_OVERHEAD_S,
            )
            dispatch_per_step = measured_dispatch_p50
        if measured_step_p50 is not None and measured_step_p50 > 0:
            bd = base.breakdown
            pred_device = combine_step_time(
                bd.get("compute_s", 0.0),
                sum(bd.get(key, 0.0) for key in COMM_BREAKDOWN_KEYS),
                dispatch_s=0.0,
            )
            if dispatch_per_step is None:
                dispatch_per_step = (
                    HOST_DISPATCH_OVERHEAD_S * cur.dispatch)
            if (
                pred_device > 0
                and measured_step_p50
                > _DEVICE_VISIBLE_MARGIN * dispatch_per_step
            ):
                # device-visible regime: the step time IS the device
                # time (dispatch hides under the overlap floor)
                factor = measured_step_p50 / pred_device
                cur.compute = blend("compute", cur.compute, factor)
                # comm is not separable from step time alone; keep it
                # tracking the compute scale so mesh-relative structure
                # from the analytic model survives
                cur.comm = cur.compute
            elif dispatch_per_step and measured_dispatch_p50 is None:
                # dispatch-bound and no direct dispatch measurement:
                # the step p50 IS the per-step dispatch cost
                cur.dispatch = blend(
                    "dispatch", cur.dispatch,
                    measured_step_p50 / HOST_DISPATCH_OVERHEAD_S,
                )
        cur.samples += 1
        cur.updated_ts = float(now if now is not None else time.time())
        logger.info(
            "calibration pass %d: compute=%.3g comm=%.3g dispatch=%.3g "
            "(measured step p50=%s dispatch p50=%s)",
            cur.samples, cur.compute, cur.comm, cur.dispatch,
            measured_step_p50, measured_dispatch_p50,
        )
        return cur

    def price(self, mesh, train_window: int = 1,
              moe_dispatch: str = "",
              dispatch_chunks: int = 0,
              moe_precision: str = "",
              fsdp_precision: str = "",
              require_fit: bool = True) -> float:
        """Calibrated predicted per-step seconds for one candidate.

        ``require_fit`` (the candidate-enumeration default) rejects
        plans ``estimate`` judges infeasible BEFORE pricing: an
        unbuildable sharding (``step_s=inf``) raises ``ValueError``; a
        memory overflow — predicted peak HBM above ``hbm_budget_bytes``
        (or the device capacity under the planner's 0.8 fit headroom)
        — raises ``MemoryInfeasibleError`` carrying the evidence, so
        the optimizer can record a ``PLAN_REJECTED`` memory reason in
        the decision trail. The corrections rescale the breakdown
        TERMS, which stay finite even for plans the planner refused,
        and a cheap-looking infeasible mesh must never win the
        candidate ranking. Pass ``require_fit=False`` only for the
        CURRENT config, which is observably running regardless of what
        the analytic memory model thinks of it."""
        import dataclasses as _dc

        model = self.model
        if moe_dispatch and moe_dispatch != model.moe_dispatch:
            model = _dc.replace(model, moe_dispatch=moe_dispatch)
        if (dispatch_chunks
                and dispatch_chunks != model.moe_dispatch_chunks):
            # the chunk knob reshapes only the EXPOSED share of the
            # dispatch comm (overlap_exposed_comm); bytes are invariant
            model = _dc.replace(model,
                                moe_dispatch_chunks=int(dispatch_chunks))
        if moe_precision and moe_precision != model.moe_precision:
            # the precision knob reshapes the BYTES (the fp8 wire's
            # values + scale side-band, ModelSpec.moe_wire_bytes_per_elem)
            # — the dual of the chunk knob, priced through the same
            # estimate
            model = _dc.replace(model, moe_precision=moe_precision)
        if fsdp_precision and fsdp_precision != model.fsdp_precision:
            # the dense-wire knob reshapes the fsdp GATHER bytes
            # (ModelSpec.fsdp_wire_bytes_per_elem; the grad
            # reduce-scatter leg stays at the param dtype)
            model = _dc.replace(model, fsdp_precision=fsdp_precision)
        base = estimate(
            mesh, model, self.device, remat_policy=self.remat_policy,
        )
        if require_fit:
            if base.step_time_s == float("inf"):
                raise ValueError(
                    f"plan {mesh} unbuildable (fits={base.fits}, "
                    f"step_s={base.step_time_s})"
                )
            # an explicit operator budget GOVERNS (it already encodes
            # whatever headroom the operator wants); otherwise the
            # planner's own fit judgement (capacity x 0.8) applies
            if self.hbm_budget_bytes:
                budget = self.hbm_budget_bytes
                over = base.memory_bytes > budget
            else:
                budget = self.device.hbm_bytes * _FIT_HEADROOM
                over = not base.fits
            if over:
                raise MemoryInfeasibleError(
                    mesh, base.memory_bytes, budget)
        return calibrated_step_time(
            base, self.corrections, overlapped=train_window > 0,
        )
