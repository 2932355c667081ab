"""Milliseconds a step the chip waits for the host between two runs of
the step program: from the end of one run to the start of the next,
mean over the traced steps and the chips."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    return trace["host_gap_ms"]
