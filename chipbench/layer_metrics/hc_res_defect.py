"""What the Sinkhorn iterations of the hyper-connections left: the mean
over the steps of the profiling window of the step's own mean, over
tokens and sublayers, of the largest ``|row or column sum - 1|`` of
``H_res`` (event ``profile_window.step_counters.hc_res_defect /
steps``; the loss function's aux counts it, ``StepCounter.
HC_RES_DEFECT``). Near 0 where the timed steps ran the doubly
stochastic mapping; a program without streams gives nothing to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("hc_res_defect")
    return None if total is None else total / window["steps"]
