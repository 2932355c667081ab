"""Milliseconds a step the chip spent in what XLA runs of the routed
experts: the instructions whose innermost scope (event
``step_scopes.instructions``) is ``moe_experts``, the ``gmm`` kernels
left out (``expert_gmm_ms`` reads them): the rows' gather, the
activation, the combine's scatter-adds."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["innermost_ms"](ctx, ("moe_experts",), kernels=False)
