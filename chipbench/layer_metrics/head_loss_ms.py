"""Milliseconds a step the chip spent in the head and its loss: the
instructions under the scope ``head_loss`` (event
``step_scopes.instructions``; ``models/losses.py``
``chunked_lm_head_loss``), forward, its own checkpoint's replay and
backward; a prediction module's second pass too."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["under_ms"](ctx, ("head_loss",))
