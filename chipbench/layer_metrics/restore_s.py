"""Seconds the restarted worker spent restoring the committed step
(``ckpt_restore.restore_seconds``)."""


def read(ctx):
    return ctx["resume"]["restore_s"] if ctx["resume"] else None
