"""Milliseconds a step the chip spent in what XLA runs of the gated
delta rule around its kernels: the instructions whose innermost scope
(event ``step_scopes.instructions``) is ``gdn_chunk``
(``ops/gated_delta.py``: the chunk-local preparation and its
backward)."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["innermost_ms"](ctx, ("gdn_chunk",))
