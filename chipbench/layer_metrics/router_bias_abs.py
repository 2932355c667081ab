"""The mean ``|bias|`` of the router's selection bias over experts and
expert layers after a step's update, the mean over the steps of the
profiling window (event ``profile_window.step_counters.router_bias_abs
/ steps``; the compiled step computes it where it moves the bias,
``StepCounter.ROUTER_BIAS_ABS``). It starts at 0 and grows by at most
the rate (``load_balance_coeff`` 1e-4) a step, so over the window it
says that the update runs and how long the job has run; 0 is a bias
that does not move. A program that keeps no such buffer gives nothing
to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("router_bias_abs")
    return None if total is None else total / window["steps"]
