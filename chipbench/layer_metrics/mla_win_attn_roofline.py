"""The least time one chip could take for the window layers' latent
attention of a step (the family's ``mla_win_flops_per_step`` and
``mla_win_bytes_per_step`` of its share of the batch, at the published
peaks) over the time in the ``flash_mla_win_*`` kernels. The work is
the band's alone at scores of 128 + 64 and values of 128: a visible
(query, key) pair once forward and twice backward a query head, a key
and value head read once a group. Tiles of the band computed whole and
masked (two tiles of 128 squared a q block for 128 visible keys a
query), recomputed scores and the remat replay lower the share; at a
band this narrow the bytes bind (``roofline`` says which)."""


def read(ctx):
    trace, flops = ctx["trace"], ctx["flops"]
    if (not trace or not trace["devices"]
            or not hasattr(flops, "mla_win_flops_per_step")):
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "flash_mla_win_" in name)
    if not seconds:
        return None
    chips = ctx["device"]["count"]
    least, _ = ctx["arithmetic"].roofline(
        flops.mla_win_flops_per_step(ctx["model"]) / chips,
        flops.mla_win_bytes_per_step(ctx["model"]) / chips,
        ctx["device"]["kind"])
    return 100.0 * least / (seconds / trace["steps"])
