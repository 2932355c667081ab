"""The least time one chip could take for the attention work of a step
(the family's ``kernel_flops_per_step`` and ``kernel_bytes_per_step``
of its share of the batch, at the published peaks) over ``mosaic_ms``.
The work is what the model asks of its kernels, six half-square
matmuls a head; what the kernels execute beyond it (recomputed scores,
a replayed forward) takes time and lowers the share. At these shapes
the FLOPs bind, not the bytes (``roofline`` says which)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"] or not trace["mosaic_ms"]:
        return None
    flops, model = ctx["flops"], ctx["model"]
    chips = ctx["device"]["count"]
    least, _ = ctx["arithmetic"].roofline(
        flops.kernel_flops_per_step(model) / chips,
        flops.kernel_bytes_per_step(model) / chips, ctx["device"]["kind"])
    return 100.0 * least / (trace["mosaic_ms"] / 1e3)
