"""The least time one chip could take for the full (causal,
unwindowed) attention of a step (the family's ``causal_flops_per_step``
and ``causal_bytes_per_step`` of its share of the batch, at the
published peaks) over the time in the ``flash_fwd``, ``flash_dkv`` and
``flash_dq`` kernels (``full_attn_ms``). The work is the causal half's
alone: a visible (query, key) pair once forward and twice backward;
blocks on the diagonal computed whole and masked, recomputed scores,
the remat replay and a K/V block read once a query head lower the
share. The FLOPs bind (``roofline`` says which)."""

KERNELS = ("flash_fwd", "flash_dkv", "flash_dq")


def read(ctx):
    trace, flops = ctx["trace"], ctx["flops"]
    if (not trace or not trace["devices"]
            or not hasattr(flops, "causal_flops_per_step")):
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:")
                  and name[len("mosaic:"):].split(".")[0] in KERNELS)
    if not seconds:
        return None
    chips = ctx["device"]["count"]
    least, _ = ctx["arithmetic"].roofline(
        flops.causal_flops_per_step(ctx["model"]) / chips,
        flops.causal_bytes_per_step(ctx["model"]) / chips,
        ctx["device"]["kind"])
    return 100.0 * least / (seconds / trace["steps"])
