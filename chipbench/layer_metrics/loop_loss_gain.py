"""What a looped model's later passes gain, nats a token: the first
pass's cross entropy less the last pass's, each before its weight, the
mean over the steps of the profiling window (event ``profile_window.
step_counters``: ``(loop_loss_first - loop_loss_last) / steps``; the
loss function's aux carries both, ``StepCounter.LOOP_LOSS_FIRST`` and
``LOOP_LOSS_LAST``). About 0 at random weights and in a run's first
steps; a loop that has learned to refine reads above 0. A program
without such a loop gives nothing to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    counters = window.get("step_counters") or {}
    first, last = (counters.get("loop_loss_first"),
                   counters.get("loop_loss_last"))
    if first is None or last is None:
        return None
    return (first - last) / window["steps"]
