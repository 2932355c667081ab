"""Seconds the job's first worker took to initialise its state
(``trainer_ready.state_seconds`` of restart round 0: a fresh init, i.e.
its programs' tracing, lowering, cache reads and dispatch)."""


def read(ctx):
    run = ctx["run"]
    if not run.get("profile_window"):
        return None  # only the run that measured prints a setup_s
    pid = next((r["pid"] for r in run["rounds"][0]
                if r.get("event") == "worker"), None)
    event = next((e for e in run["events"]
                  if e.get("kind") == "trainer_ready"
                  and e.get("pid") == pid), None)
    if event is None:
        return None
    return event["state_seconds"]
