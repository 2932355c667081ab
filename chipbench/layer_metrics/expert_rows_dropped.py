"""Assignments to held experts that fell past the static row bound of
the expert layer, over the steps of the profiling window (event
``profile_window.step_counters.moe_rows_dropped``). 0 in a run that is
``correct``: the family's job (``families/mla_moe/job.py``) makes the
loss of a step that dropped a row NaN."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    return (window.get("step_counters") or {}).get("moe_rows_dropped")
