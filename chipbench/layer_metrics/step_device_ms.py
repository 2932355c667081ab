"""Milliseconds a step in which an operation ran on the chip (the union
of the leaf operations' intervals), mean over the traced steps and
the chips."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    return trace["step_device_ms"]
