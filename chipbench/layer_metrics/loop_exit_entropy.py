"""The entropy of a looped model's exit distribution over its passes,
nats: the mean over the steps of the profiling window of the step's own
mean over the unmasked tokens (event ``profile_window.step_counters.
loop_exit_entropy / steps``; the loss function's aux carries it,
``StepCounter.LOOP_EXIT_ENTROPY``). Between 0 (every token exits after
one and the same pass) and ``ln T`` (1.386 at four passes: the uniform
prior the objective's entropy term pulls towards). A program without
such a loop gives nothing to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("loop_exit_entropy")
    return None if total is None else total / window["steps"]
