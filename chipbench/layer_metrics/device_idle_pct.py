"""The share of the traced window in which no operation ran on the
chip: 1 - busy over the window, mean over the chips."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
