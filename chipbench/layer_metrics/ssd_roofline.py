"""The least time one chip could take for the state-space recurrence of
a step (the family's ``ssd_flops_per_step`` and ``ssd_bytes_per_step``
of its share of the batch, at the published peaks) over the time in the
``ssd_*`` kernels (``ssd_ms``). The work is what the model asks,
whatever implements it: the state's update and its read, ``2 P N``
FLOPs each a token and head forward and twice that backward; x, dt, B,
C, y and their gradients read or written once. What the chunked kernels
execute beyond it (the chunk's own products, the remat replay, the
float32 states each chunk starts from, the partial sums of ``dB`` and
``dC``) lowers the share. The bytes bind (``roofline`` says which)."""


def read(ctx):
    trace, flops = ctx["trace"], ctx["flops"]
    if (not trace or not trace["devices"]
            or not hasattr(flops, "ssd_flops_per_step")):
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "ssd_" in name)
    if not seconds:
        return None
    chips = ctx["device"]["count"]
    least, _ = ctx["arithmetic"].roofline(
        flops.ssd_flops_per_step(ctx["model"]) / chips,
        flops.ssd_bytes_per_step(ctx["model"]) / chips,
        ctx["device"]["kind"])
    return 100.0 * least / (seconds / trace["steps"])
