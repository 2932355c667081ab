"""The share of the causal (query, key) pairs that the indexer selected,
over the sparse layers and the steps of the profiling window (event
``profile_window.step_counters``: ``dsa_pairs_selected /
dsa_pairs_causal``; the loss function's aux counts both,
``StepCounter.DSA_PAIRS_SELECTED``). At a row of 16,384 and ``topk``
2048 it is ``sum_t min(t + 1, 2048)`` over ``16384 * 16385 / 2``,
0.2344, or the selection is wrong: the timed steps attended to exactly
``topk`` keys a query. A program without such layers gives nothing to
read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    counters = window.get("step_counters") or {}
    selected, causal = (counters.get("dsa_pairs_selected"),
                        counters.get("dsa_pairs_causal"))
    return selected / causal if selected is not None and causal else None
