"""Milliseconds a step the chip spent in the dense feed-forward
parts: the instructions whose innermost scope (event
``step_scopes.instructions``) is ``ffn``, ``moe_shared``,
``moe_router``, ``moe_groups`` or ``gated_norm``."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))

SCOPES = ("ffn", "moe_shared", "moe_router", "moe_groups", "gated_norm")


def read(ctx):
    return scope_time["innermost_ms"](ctx, SCOPES)
