"""The mean log decay ``g`` of the Kimi-delta-attention layers'
recurrence: the mean over the steps of the profiling window of the
step's own mean over KDA layers, tokens, heads and key channels (event
``profile_window.step_counters.kda_log_decay_mean / steps``; the loss
function's aux carries it, ``StepCounter.KDA_LOG_DECAY_MEAN``). A
witness: inside ``(-5, 0)`` says the bounded per-channel gate ran
(``kda_lower_bound * sigmoid``), a few hundredths under 0 at the
assumed initialisation. A program without such layers gives nothing to
read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("kda_log_decay_mean")
    return None if total is None else total / window["steps"]
