"""Milliseconds a step the chip spent in instructions of the phase
``replay`` (event ``step_scopes.instructions``, the keys that begin
``replay|``: an ``op_name`` with JAX's ``rematted_computation`` in it):
what a checkpoint runs again in the backward pass, kernels included."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["phase_ms"](ctx, "replay")
