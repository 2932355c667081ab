"""Seconds from the agent's ``worker_failed`` event to the start of the
restarted worker's script (failure report, new rendezvous round, fork,
interpreter start)."""


def read(ctx):
    resume = ctx["resume"]
    if not resume or not resume["worker"] or resume["t_failed"] is None:
        return None
    return resume["worker"]["t_boot"] - resume["t_failed"]
