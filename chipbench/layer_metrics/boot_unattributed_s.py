"""Seconds of the restarted worker's set-up that no phase names: from
the start of its process to its first trained step
(``compile_first_step.ts - worker_boot.process_start_ts``) less its
phases (import, distributed and backend, script, checkpoint manager,
build, state, the hooks' begin, the first step), each from the event
that measured it. What ``unattributed`` is for idle gaps."""

PHASES = (("worker_boot", "import_seconds"),
          ("worker_boot", "distributed_seconds"),
          ("worker_boot", "backend_seconds"),
          ("trainer_ready", "script_seconds"),
          ("trainer_ready", "ckpt_manager_seconds"),
          ("trainer_ready", "build_seconds"),
          ("trainer_ready", "state_seconds"),
          ("train_start", "hooks_begin_seconds"),
          ("compile_first_step", "seconds"))


def read(ctx):
    run = ctx["run"]
    if not run.get("profile_window") or not ctx["resume"]:
        return None  # only the run that measured prints a setup_s
    pid = run["worker"]["pid"]
    first = {}
    for e in run["events"]:
        if e.get("pid") == pid:
            first.setdefault(e.get("kind"), e)
    if any(kind not in first or first[kind].get(field) is None
           for kind, field in PHASES):
        return None  # a program without the set-up timeline
    total = (first["compile_first_step"]["ts"]
             - first["worker_boot"]["process_start_ts"])
    return total - sum(first[kind][field] for kind, field in PHASES)
