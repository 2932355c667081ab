"""Milliseconds a step the chip spent in grouped differential
attention's subtraction: the instructions with ``attn_diff`` anywhere
in their scope path (event ``step_scopes.instructions``;
``models/mla_moe.py`` ``_differential``): lambda's projection and
sigmoid, a group's noise head times lambda taken from its four signal
heads, and their transposes in the backward; XLA's work between the
latent kernels and the output gate. A program without the scope gives
nothing to read."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["under_ms"](ctx, ("attn_diff",)) or None
