"""The share of the gated delta rule's updates whose transition ``I -
beta k k^T`` has a negative eigenvalue (``beta > 1``): the mean over
the steps of the profiling window of the step's own mean over linear
layers, tokens and heads (event ``profile_window.step_counters.
gdn_neg_eig / steps``; the loss function's aux counts it,
``StepCounter.GDN_NEG_EIG``). 0 exactly where ``beta`` is not doubled
(``linear_allow_neg_eigval`` not applied) and about a half at random
weights: the timed steps ran the published rule. A program without such
layers gives nothing to read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    total = (window.get("step_counters") or {}).get("gdn_neg_eig")
    return None if total is None else total / window["steps"]
