"""Seconds the hooks' ``begin`` took in the job's first worker
(``train_start.hooks_begin_seconds`` of restart round 0): here the
reference check, which a job of the system's users does not run."""


def read(ctx):
    run = ctx["run"]
    if not run.get("profile_window"):
        return None  # only the run that measured prints a setup_s
    pid = next((r["pid"] for r in run["rounds"][0]
                if r.get("event") == "worker"), None)
    event = next((e for e in run["events"]
                  if e.get("kind") == "train_start"
                  and e.get("pid") == pid), None)
    if event is None:
        return None
    return event.get("hooks_begin_seconds")
