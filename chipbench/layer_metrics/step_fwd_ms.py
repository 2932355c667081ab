"""Milliseconds a step the chip spent in instructions of the phase
``forward`` (event ``step_scopes.instructions``, the keys that begin
``forward|``: ``parallel/accelerate.py``'s scope around the loss
function's first pass), kernels included."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["phase_ms"](ctx, "forward")
