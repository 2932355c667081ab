"""Milliseconds a step the chip spent in the latent flash kernels (the
Mosaic calls whose instructions are named ``flash_mla_fwd``,
``flash_mla_dkv`` and ``flash_mla_dq``: every layer's forward, its
remat replay and the backward). A program without such instructions
gives nothing to read."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "flash_mla_" in name)
    return 1e3 * seconds / trace["steps"] if seconds else None
