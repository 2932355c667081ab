"""The least time one chip could take for the routed experts' matmuls
of a step (the family's ``gmm_flops`` and ``gmm_bytes`` of the rows the
program counted: three matmuls of hidden x expert width a row of a held
expert, forward, dx and dW; every held expert's matrices read and their
gradients written once a pass) over the time in the ``gmm*`` kernels.
The rows are the run's own: event ``profile_window.step_counters.
moe_rows_held / steps``. Row tiles padded to 128, weights read again by
every row tile and the remat replay lower the share."""


def read(ctx):
    trace, flops = ctx["trace"], ctx["flops"]
    if (not trace or not trace["devices"]
            or not hasattr(flops, "gmm_flops")):
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "gmm" in name)
    window = ctx["run"].get("profile_window") or {}
    counters = window.get("step_counters") or {}
    if not seconds or not counters.get("moe_rows_held"):
        return None
    rows = (counters["moe_rows_held"] / window["steps"]
            / ctx["device"]["count"])
    least, _ = ctx["arithmetic"].roofline(
        flops.gmm_flops(ctx["model"], rows),
        flops.gmm_bytes(ctx["model"], rows), ctx["device"]["kind"])
    return 100.0 * least / (seconds / trace["steps"])
