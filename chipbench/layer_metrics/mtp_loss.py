"""The multi-token-prediction module's loss before its weight, the mean
over the steps of the profiling window (event ``profile_window.
step_counters.mtp_loss / steps``; the loss function's aux counts it,
``StepCounter.MTP_LOSS``). There, and near the main loss, where the
timed steps ran the second objective; a program without a prediction
module gives nothing to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("mtp_loss")
    return None if total is None else total / window["steps"]
