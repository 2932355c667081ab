"""Milliseconds a step the chip spent in the plain causal flash kernels
(the Mosaic calls whose instructions are named ``flash_fwd``,
``flash_dkv`` and ``flash_dq``: every full-attention layer's forward,
its remat replay and the backward). The windowed kernels
(``flash_win_*``) and the latent ones (``flash_mla_*``) have readers of
their own and are not counted here. A program without such
instructions gives nothing to read."""

KERNELS = ("flash_fwd", "flash_dkv", "flash_dq")


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    # by the instruction's name before its number: mosaic:flash_fwd.12
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:")
                  and name[len("mosaic:"):].split(".")[0] in KERNELS)
    return 1e3 * seconds / trace["steps"] if seconds else None
