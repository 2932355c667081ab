"""Milliseconds a step the chip spent in what XLA runs of the Mamba-2
mixers: the instructions whose innermost scope (event
``step_scopes.instructions``) is ``ssd`` (``models/ssd_hybrid.py``:
``W_in``, the convolution and SiLU, ``dt``, the gated norm, ``W_out``)
or ``ssd_chunk`` (``ops/ssd.py``: ``dt A`` and its cumulative sums, the
row forms, the addition of the kernels' partial sums), the ``ssd_*``
kernels among them left out (``ssd_ms`` reads those)."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))


def read(ctx):
    return scope_time["innermost_ms"](ctx, ("ssd", "ssd_chunk"),
                                      kernels=False)
