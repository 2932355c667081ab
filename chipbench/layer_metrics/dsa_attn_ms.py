"""Milliseconds a step the chip spent in the selected-attention kernels
(the Mosaic calls whose instructions are named ``dsa_attn_fwd``,
``dsa_attn_dkv``, ``dsa_attn_dq`` or ``dsa_attn_bwd``: every sparse
layer's forward, its remat replay and the backward; ``ops/
sparse_attention.py``). The indexer's kernels (``dsa_index_*``) have a
reader of their own. A program without such instructions gives nothing
to read."""

PREFIX = "dsa_attn_"


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    # by the instruction's name: mosaic:dsa_attn_fwd.12
    spent = sum(s for name, s in trace["device_ops"]
                if name.startswith("mosaic:" + PREFIX))
    return 1e3 * spent / trace["steps"] if spent else None
