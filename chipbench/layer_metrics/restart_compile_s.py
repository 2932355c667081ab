"""Seconds the restarted worker spent tracing, lowering and in XLA up to
its first trained step (``compile_first_step.compile``: ``trace_seconds
+ lower_seconds + backend_seconds``, the attribution pass's own among
them)."""


def read(ctx):
    run = ctx["run"]
    if not run.get("profile_window") or not ctx["resume"]:
        return None  # only the run that measured prints a setup_s
    pid = run["worker"]["pid"]
    ledger = next((e.get("compile") or {} for e in run["events"]
                   if e.get("kind") == "compile_first_step"
                   and e.get("pid") == pid), {})
    if "backend_seconds" not in ledger:
        return None
    return (ledger["trace_seconds"] + ledger["lower_seconds"]
            + ledger["backend_seconds"])
