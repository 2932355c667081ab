"""Milliseconds a step the chip spent in the indexer's kernels (the
Mosaic calls whose instructions are named ``dsa_index_*``: the scores
and the selection, ``dsa_index_select``, in every sparse layer's
forward and its remat replay, and the indexer's loss and its gradient,
``dsa_index_kl_fwd`` and ``dsa_index_kl_bwd``; ``ops/
sparse_attention.py``). A program without such instructions gives
nothing to read."""

PREFIX = "dsa_index_"


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    spent = sum(s for name, s in trace["device_ops"]
                if name.startswith("mosaic:" + PREFIX))
    return 1e3 * spent / trace["steps"] if spent else None
