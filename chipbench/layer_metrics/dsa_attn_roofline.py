"""The least time one chip could take for a step's attention over the
SELECTED pairs (the family's ``dsa_attn_flops_per_step`` and
``dsa_attn_bytes_per_step`` of its share of the batch, at the published
peaks) over the time in the ``dsa_attn_*`` kernels (``dsa_attn_ms``).
The work is the selected pairs' alone, whatever implements it: a pair
once forward and 2.5 times that backward, ``sum_t min(t + 1, topk)``
pairs a row and layer, q, k, v, o and their gradients once; pairs
computed and masked out, tiles on the diagonal computed whole, the
remat replay and a K/V block read once a query head lower the share.
The FLOPs bind (``roofline`` says which)."""

PREFIX = "dsa_attn_"


def read(ctx):
    trace, flops = ctx["trace"], ctx["flops"]
    if (not trace or not trace["devices"]
            or not hasattr(flops, "dsa_attn_flops_per_step")):
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:" + PREFIX))
    if not seconds:
        return None
    chips = ctx["device"]["count"]
    least, _ = ctx["arithmetic"].roofline(
        flops.dsa_attn_flops_per_step(ctx["model"]) / chips,
        flops.dsa_attn_bytes_per_step(ctx["model"]) / chips,
        ctx["device"]["kind"])
    return 100.0 * least / (seconds / trace["steps"])
