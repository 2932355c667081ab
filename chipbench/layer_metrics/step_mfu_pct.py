"""Model FLOPs of a step (the family's ``model_flops_per_step``: forward
and backward, recompute not counted) over the step's device-busy time,
as a share of chips x the published bf16 peak."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    return ctx["arithmetic"].mfu_pct(
        ctx["flops"].model_flops_per_step(ctx["model"]),
        trace["step_device_ms"] / 1e3,
        ctx["device"]["count"], ctx["device"]["kind"])
