"""Grouped differential attention's lambda, the mean over tokens,
signal heads, layers and the steps of the profiling window (event
``profile_window.step_counters.diff_lambda_mean / steps``; the loss
function's aux counts it, ``StepCounter.DIFF_LAMBDA_MEAN``). Near 0.5
at random weights (a sigmoid of a projection around 0): the share of a
group's noise head that is taken from each of its signal heads. A
program without noise heads gives nothing to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("diff_lambda_mean")
    return None if total is None else total / window["steps"]
