"""Seconds from the restarted worker's start of training to its first
completed step: trace, compile-cache reads (or compiles) and the step."""


def read(ctx):
    resume = ctx["resume"]
    if not resume or not resume["steps"]:
        return None
    return resume["steps"][0]["seconds"]
