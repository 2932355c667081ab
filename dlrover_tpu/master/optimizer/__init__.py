"""Runtime optimization loop: telemetry → planner → live reshard.

The fourth DLRover pillar (automatic resource optimization, PAPER.md
§pillars) closed as a master-side control loop:

  * ``calibration``        — fit the analytic planner's cost terms to
    the MEASURED per-node runtime series (predicted-vs-observed
    correction factors per term), so candidate pricing reflects the
    job actually running, not the datasheet.
  * ``runtime_optimizer``  — consume the node series and diagnosis
    verdicts, enumerate and price candidate configs (mesh shape,
    ``train_window``, MoE dispatch) through the
    calibrated cost model, and publish winning plans to workers —
    applied WITHOUT a restart through the live-reshard/retune path.

The remote case fronts ``brain/`` (``optimize_mode="cluster"``) for
cross-job initial plans; this loop owns the within-job re-planning.
"""

from dlrover_tpu.master.optimizer.calibration import (  # noqa: F401
    CostCalibrator,
    TermCorrections,
    calibrated_step_time,
)
from dlrover_tpu.master.optimizer.runtime_optimizer import (  # noqa: F401
    CandidateScore,
    Decision,
    RunningConfig,
    RuntimeOptimizer,
    decision_trail_from_events,
)
