"""Seconds from the SIGKILL of the worker to the first step the
restarted worker completed, on one clock: what a failure costs in chip
time. The kill falls in set-up, so all of it is part of ``setup_s``.
One sample a run, and its parts vary independently (the agent's poll,
the kernel tearing the killed process down, TPU init): its parts are
the metrics beside it."""


def read(ctx):
    return ctx["resume"]["resume_s"] if ctx["resume"] else None
