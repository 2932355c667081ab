"""The least time one chip could take for the delta rule under a
diagonal decay of a step (the family's ``kda_flops_per_step`` and
``kda_bytes_per_step`` of its share of the batch, at the published
peaks) over the time in the ``kda_*`` kernels (``kda_ms``). The work is
what the model asks, whatever implements it: the recurrence's three
products of ``dk x dv`` a token and head forward and twice that
backward; q, k, v, o, the float32 gate a key channel, beta and their
gradients read or written once. What the chunked kernels execute beyond
it (the chunk's own products, the replays of the layer's and the head
groups' checkpoints, the float32 states each chunk starts from) lowers
the share. The bytes bind (``roofline`` says which)."""


def read(ctx):
    trace, flops = ctx["trace"], ctx["flops"]
    if (not trace or not trace["devices"]
            or not hasattr(flops, "kda_flops_per_step")):
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "kda_" in name)
    if not seconds:
        return None
    chips = ctx["device"]["count"]
    least, _ = ctx["arithmetic"].roofline(
        flops.kda_flops_per_step(ctx["model"]) / chips,
        flops.kda_bytes_per_step(ctx["model"]) / chips,
        ctx["device"]["kind"])
    return 100.0 * least / (seconds / trace["steps"])
