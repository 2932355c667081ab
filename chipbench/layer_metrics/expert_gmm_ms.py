"""Milliseconds a step the chip spent in the routed experts' grouped
matmuls (the Mosaic calls whose instructions are named ``gmm``,
``gmm_dx`` and ``gmm_dw``: gate, up and down of every expert layer
forward, their remat replay, and the backward's dx and dW). A program
without such instructions gives nothing to read."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith("mosaic:") and "gmm" in name)
    return 1e3 * seconds / trace["steps"] if seconds else None
