"""Milliseconds a step the chip spent in the gated delta rule's chain
kernels (the Mosaic calls whose names contain ``gdn_``: every
linear-attention layer's ``gdn_fwd``, its remat replay and ``gdn_bwd``,
``dlrover_tpu/ops/gated_delta.py``). The chunk-local preparation around
them is XLA's and is not counted here (scope ``gdn_chunk``). A program
without such instructions gives nothing to read."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "gdn_" in name)
    return 1e3 * seconds / trace["steps"] if seconds else None
