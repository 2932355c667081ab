"""Milliseconds a step the chip spent in instructions of the phase
``backward`` (event ``step_scopes.instructions``, the keys that begin
``backward|``: ``parallel/accelerate.py``'s scope around the
transposed pass, the replay inside it not counted), kernels
included."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["phase_ms"](ctx, "backward")
