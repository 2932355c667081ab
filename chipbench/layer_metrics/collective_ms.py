"""Milliseconds a step the chip spent in all-gather, reduce-scatter,
all-reduce, all-to-all and collective-permute operations."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    return trace["collective_ms"]
