"""Seconds from the start of the job's first worker process
(``worker_boot.process_start_ts``, restart round 0) to its first
trained step (``compile_first_step.ts``): a job's start as its own
events tell it, reference check and all."""


def read(ctx):
    run = ctx["run"]
    if not run.get("profile_window"):
        return None  # only the run that measured prints a setup_s
    pid = next((r["pid"] for r in run["rounds"][0]
                if r.get("event") == "worker"), None)
    mine = [e for e in run["events"] if e.get("pid") == pid]
    boot = next((e for e in mine if e.get("kind") == "worker_boot"), None)
    step = next((e for e in mine
                 if e.get("kind") == "compile_first_step"), None)
    if boot is None or step is None:
        return None
    return step["ts"] - boot["process_start_ts"]
