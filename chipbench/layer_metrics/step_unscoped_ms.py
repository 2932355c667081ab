"""Milliseconds a step the chip spent in instructions that no name
covers: those the event ``step_scopes`` leaves out, those of the phase
``none`` (no ``op_name``, or one outside the step's three halves), and
those of ``forward``, ``replay`` or ``backward`` with no scope (the
layer scans' slicing of the stacked parameters, residual adds, norms
outside a scope)."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["total_ms"](
        ctx, lambda phase, path, kernel: phase in (
            scope_time["UNNAMED"], "none")
        or (phase != "optimizer" and not path))
