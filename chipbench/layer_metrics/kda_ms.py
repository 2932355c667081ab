"""Milliseconds a step the chip spent in Kimi delta attention's chain
kernels (the Mosaic calls whose names contain ``kda_``: every KDA
layer's ``kda_fwd``, its replays and ``kda_bwd``,
``dlrover_tpu/ops/kda.py``). The chunk-local preparation around them is
XLA's and is not counted here (scope ``kda_chunk``). A program without
such instructions gives nothing to read."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "kda_" in name)
    return 1e3 * seconds / trace["steps"] if seconds else None
