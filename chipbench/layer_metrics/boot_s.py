"""Seconds the restarted worker took from the start of its script to
the start of training, less the restore: imports, TPU init, building
and placing the program."""


def read(ctx):
    resume = ctx["resume"]
    if not resume or not resume["start"]:
        return None
    return resume["start"]["boot_seconds"] - (resume["restore_s"] or 0.0)
