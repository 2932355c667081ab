"""Gigabytes a second at which the restarted worker restored its state
(``ckpt_restore.bytes / restore_seconds / 1e9``: the state's arrays over
what span ``ckpt_restore`` covers)."""


def read(ctx):
    run = ctx["run"]
    if not run.get("profile_window") or not ctx["resume"]:
        return None  # only the run that measured prints a setup_s
    pid = run["worker"]["pid"]
    event = next((e for e in run["events"]
                  if e.get("kind") == "ckpt_restore"
                  and e.get("pid") == pid), {})
    if not event.get("bytes") or not event.get("restore_seconds"):
        return None
    return event["bytes"] / event["restore_seconds"] / 1e9
