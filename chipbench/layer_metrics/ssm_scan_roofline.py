"""The least time one chip could take for the selective scans of a step
(the family's ``scan_flops_per_step`` and ``scan_bytes_per_step`` of
its share of the batch, at the published peaks) over the time in the
``ssm_scan_*`` kernels. The work is what the model asks: one pass over
the states forward and the adjoint backward, u, dt and y read and
written once; what the kernels execute beyond it (the remat replay,
the backward's own replay of each chunk, float32 rows) lowers the
share. The bytes bind, not the FLOPs (``roofline`` says which)."""


def read(ctx):
    trace, flops = ctx["trace"], ctx["flops"]
    if (not trace or not trace["devices"]
            or not hasattr(flops, "scan_flops_per_step")):
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "ssm_scan_" in name)
    if not seconds:
        return None
    chips = ctx["device"]["count"]
    least, _ = ctx["arithmetic"].roofline(
        flops.scan_flops_per_step(ctx["model"]) / chips,
        flops.scan_bytes_per_step(ctx["model"]) / chips,
        ctx["device"]["kind"])
    return 100.0 * least / (seconds / trace["steps"])
