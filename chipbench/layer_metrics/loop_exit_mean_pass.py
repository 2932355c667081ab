"""The expected exit pass of a looped model, ``sum_t t p_t``: the mean
over the steps of the profiling window of the step's own mean over the
unmasked tokens (event ``profile_window.step_counters.
loop_exit_mean_pass / steps``; the loss function's aux carries it,
``StepCounter.LOOP_EXIT_MEAN_PASS``). Between 1 and the number of
passes (4): where the head's weights lie among the passes, so which
passes' cross entropies the stack is trained on. A program without such
a loop gives nothing to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("loop_exit_mean_pass")
    return None if total is None else total / window["steps"]
