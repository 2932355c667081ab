"""Milliseconds a step the chip spent in the selective-scan kernels
(the Mosaic calls whose instructions are named ``ssm_scan_fwd`` and
``ssm_scan_bwd``: every Mamba layer's forward, its remat replay and
its backward). A program without such instructions gives nothing to
read."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "ssm_scan_" in name)
    return 1e3 * seconds / trace["steps"] if seconds else None
