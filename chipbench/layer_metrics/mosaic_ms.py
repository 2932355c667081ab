"""Milliseconds a step the chip spent in Mosaic custom calls. In the
dense cells these are the flash kernels: forward, its remat replay,
dKV and dQ, every layer."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    return trace["mosaic_ms"]
