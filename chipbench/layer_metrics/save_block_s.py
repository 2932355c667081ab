"""Median seconds the train loop was blocked staging a save from the
device to the host (``ckpt_save.stage_seconds``), over the window's
saves."""

import statistics


def read(ctx):
    saves = ctx["window"]["saves"]
    if not saves:
        return None
    return statistics.median(e["stage_seconds"] for e in saves)
