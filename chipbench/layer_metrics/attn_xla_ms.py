"""Milliseconds a step the chip spent in what XLA runs of the token
mixers: the instructions whose innermost scope (event
``step_scopes.instructions``) is an attention's, a latent attention's,
a delta-rule mixer's, a state-space mixer's or a memory unit's, the
Mosaic kernels among them left out (they have readers of their own):
projections, rotary, norms, convolutions, gates, the indexer's
selection."""

import os
import runpy

scope_time = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "scope_time.py"))

MIXERS = ("attention", "attention_window", "attention_full",
          "attention_cross", "attn_full", "attn_window", "attn_sparse",
          "dsa_index", "mla", "attn_gate", "gdn", "ssm", "gmu")


def read(ctx):
    return scope_time["innermost_ms"](ctx, MIXERS, kernels=False)
