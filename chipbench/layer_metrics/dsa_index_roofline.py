"""The least time one chip could take for a step's indexer (the
family's ``dsa_index_flops_per_step`` and ``dsa_index_bytes_per_step``:
every causal pair scored once forward by 16 heads of 64, the scores'
backward over the selected pairs, and the main attention's scores once
more over the selected pairs for the head-mean probabilities the
indexer's loss is against) over the time in the ``dsa_index_*`` kernels
(``dsa_index_ms``). The bisection that finds each query's threshold is
no FLOP of the model and lowers the share, as do a selection made
again in the remat replay and tiles scored whole past the diagonal."""

PREFIX = "dsa_index_"


def read(ctx):
    trace, flops = ctx["trace"], ctx["flops"]
    if (not trace or not trace["devices"]
            or not hasattr(flops, "dsa_index_flops_per_step")):
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:" + PREFIX))
    if not seconds:
        return None
    chips = ctx["device"]["count"]
    least, _ = ctx["arithmetic"].roofline(
        flops.dsa_index_flops_per_step(ctx["model"]) / chips,
        flops.dsa_index_bytes_per_step(ctx["model"]) / chips,
        ctx["device"]["kind"])
    return 100.0 * least / (seconds / trace["steps"])
