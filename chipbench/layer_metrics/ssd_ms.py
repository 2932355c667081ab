"""Milliseconds a step the chip spent in the state-space dual's kernels
(the Mosaic calls whose names contain ``ssd_``: every Mamba-2 layer's
``ssd_fwd``, its remat replay and ``ssd_bwd``,
``dlrover_tpu/ops/ssd.py``). What XLA does around them (the cumulative
sums, the row forms, the partial sums' addition) is not counted here
(``ssd_xla_ms``). A program without such instructions gives nothing to
read."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "ssd_" in name)
    return 1e3 * seconds / trace["steps"] if seconds else None
