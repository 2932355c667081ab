"""Seconds of the restarted worker's first step that went to the
attribution pass (``compile_first_step.capture_seconds``: its AOT
lowering and compile of the step, before the first dispatch)."""


def read(ctx):
    run = ctx["run"]
    if not run.get("profile_window") or not ctx["resume"]:
        return None  # only the run that measured prints a setup_s
    pid = run["worker"]["pid"]
    event = next((e for e in run["events"]
                  if e.get("kind") == "compile_first_step"
                  and e.get("pid") == pid), {})
    return event.get("capture_seconds")
