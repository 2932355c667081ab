"""Milliseconds a step the chip spent in what XLA runs of Kimi delta
attention's rule around its kernels: the instructions whose innermost
scope (event ``step_scopes.instructions``) is ``kda_chunk``
(``ops/kda.py``: the chunk-local preparation under the diagonal decay,
its sub-chunk products, the triangular inverse, and their backward)."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["innermost_ms"](ctx, ("kda_chunk",))
