"""The fullest held expert's rows over the mean of the held experts',
summed over the expert layers and the steps of the profiling window
(event ``profile_window.step_counters``: ``moe_rows_max`` over
``moe_rows_held / experts held``). 1 where the router spreads its
tokens evenly over the experts held here."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    counters = window.get("step_counters") or {}
    if not counters.get("moe_rows_held"):
        return None
    held = ctx["model"]["n_routed_experts"]
    return counters["moe_rows_max"] / (counters["moe_rows_held"] / held)
