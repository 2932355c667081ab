"""Milliseconds a step the chip spent in PolyNorm: the instructions
with ``polynorm`` anywhere in their scope path (event
``step_scopes.instructions``; ``models/mla_moe.py`` ``poly_norm``): the
three normalised powers of a gate row and their weighted sum, forward,
replay and backward, in the dense FFN, the shared expert and the held
experts' gate stage; memory-bound passes between MXU matmuls. A fusion
that holds the gate's multiply beside it counts whole. A program
without the scope gives nothing to read."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["under_ms"](ctx, ("polynorm",)) or None
