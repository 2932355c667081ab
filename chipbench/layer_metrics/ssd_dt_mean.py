"""The mean step size ``dt`` of the Mamba-2 layers' recurrence: the
mean over the steps of the profiling window of the step's own mean over
Mamba layers, tokens and heads (event ``profile_window.step_counters.
ssd_dt_mean / steps``; the loss function's aux carries it,
``StepCounter.SSD_DT_MEAN``). ``softplus(0)`` = 0.693 where ``dt_bias``
is left out at small weights, and a few hundredths where the published
parametrisation ran at the assumed initialisation (a step log-uniform
in [1e-3, 1e-1]): the timed steps ran it. A program without such layers
gives nothing to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("ssd_dt_mean")
    return None if total is None else total / window["steps"]
