"""Milliseconds a step the chip spent in the hyper-connections: the
instructions with ``hc_map`` or ``hc_mix`` anywhere in their scope path
(event ``step_scopes.instructions``; ``ops/hyper_connections.py``),
the four ``hc_enter_*`` / ``hc_leave_*`` kernels and XLA's work between
them."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["under_ms"](ctx, ("hc_map", "hc_mix"))
