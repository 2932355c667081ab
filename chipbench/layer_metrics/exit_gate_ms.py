"""Milliseconds a step the chip spent in a looped model's exit gate:
the instructions with the scope ``exit_gate`` anywhere in their path
(event ``step_scopes.instructions``): the gate's projection of every
pass's normed state, the exit distribution over the passes, its
entropy, the means the counters carry, and their transposes. A program
without the scope gives nothing to read."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))

SCOPE = "exit_gate"


def read(ctx):
    found = scope_time["rows"](ctx)
    if found is None:
        return None
    under = [ms for ms, _, path, _ in found if SCOPE in path]
    return sum(under) if under else None
