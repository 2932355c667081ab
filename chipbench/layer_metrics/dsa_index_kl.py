"""The indexer's loss, nats a query: the KL divergence from the
attention's head-mean probabilities to the softmax of the index scores
over the selected keys, the mean over the sparse layers and the steps
of the profiling window (event ``profile_window.step_counters.
dsa_index_kl / steps / layers``; the loss function's aux carries a
step's sum over the layers, ``StepCounter.DSA_INDEX_KL``). Finite, and
falling over a run, where the timed steps trained the indexer beside
the model. A program without such layers gives nothing to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("dsa_index_kl")
    if total is None:
        return None
    return total / window["steps"] / ctx["model"]["num_hidden_layers"]
