"""Milliseconds a step the chip spent in the windowed flash kernels
(the Mosaic calls whose instructions are named ``flash_win_fwd``,
``flash_win_dkv`` and ``flash_win_dq``: every window-attention layer's
two forward calls, their remat replay and the backward). A program
without such instructions gives nothing to read."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "flash_win_" in name)
    return 1e3 * seconds / trace["steps"] if seconds else None
