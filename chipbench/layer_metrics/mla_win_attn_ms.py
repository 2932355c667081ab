"""Milliseconds a step the chip spent in the windowed latent flash
kernels (the Mosaic calls whose instructions are named
``flash_mla_win_fwd``, ``flash_mla_win_dkv`` and ``flash_mla_win_dq``:
every window layer's forward, its remat replay and the backward, on the
band's tiles alone). ``mla_attn_ms`` holds them too, beside the full
layers' kernels. A program without such instructions gives nothing to
read."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "flash_mla_win_" in name)
    return 1e3 * seconds / trace["steps"] if seconds else None
