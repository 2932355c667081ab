"""Seconds from the SIGKILL of the worker to the agent's
``worker_failed`` event (the agent polls its workers; the kernel first
has to tear the process down)."""


def read(ctx):
    resume = ctx["resume"]
    if not resume or resume["t_failed"] is None:
        return None
    return resume["t_failed"] - ctx["run"]["t_kill"]
