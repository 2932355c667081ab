"""The part of collective_ms during which no other operation ran on that
chip."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    return trace["collective_exposed_ms"]
