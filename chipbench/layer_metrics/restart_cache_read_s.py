"""Seconds the restarted worker spent reading executables from the
persistent compile cache up to its first trained step
(``compile_first_step.compile.cache_read_seconds``)."""


def read(ctx):
    run = ctx["run"]
    if not run.get("profile_window") or not ctx["resume"]:
        return None  # only the run that measured prints a setup_s
    pid = run["worker"]["pid"]
    ledger = next((e.get("compile") or {} for e in run["events"]
                   if e.get("kind") == "compile_first_step"
                   and e.get("pid") == pid), {})
    return ledger.get("cache_read_seconds")
