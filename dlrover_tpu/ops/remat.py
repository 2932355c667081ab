"""Rematerialization policies.

Role parity: ``atorch/auto/opt_lib/checkpoint_optimization.py`` (activation
checkpointing by module class) — on TPU this is ``jax.checkpoint`` with a
policy choosing what stays in HBM. The catalog maps the reference's
module-granular choices onto XLA-granular ones.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax

def remat_enabled(policy) -> bool:
    """Single source of truth for 'does this policy value mean remat':
    shared by ``apply_remat`` and the models' pipeline ``remat_stage``
    plumbing so the per-layer and stage-boundary layers cannot disagree
    (e.g. on a falsy ``None`` policy)."""
    return bool(policy) and policy != "none"


# policies that are another policy plus named values: "attn_saveable"
# is "full" and "dots_and_attn_saveable" is "dots_saveable", each plus
# the name ``attn_out`` that a model puts on its attention's result
# OUTSIDE the op's ``custom_vjp``. That saves a copy of the output
# (B*S*D a layer) and the product that reads it; it does NOT spare the
# flash kernel's forward, which is replayed for the op's own residuals
# (``PERF.md`` section 6, PR 50). What spares it is a name inside the
# op's forward rule and a checkpoint that keeps it:
# ``ops.flash_attention.KEPT_NAMES`` and ``sparse_attention.KEPT_NAMES``
# through ``apply_remat``'s ``keep``
_NAMED = {"attn_saveable": ("full", ("attn_out",)),
          "dots_and_attn_saveable": ("dots_saveable", ("attn_out",))}


def apply_remat(fn: Callable, policy: str = "dots_saveable",
                prevent_cse: bool = True,
                keep: Sequence[str] = ()) -> Callable:
    """Wrap a block function with a remat policy.

    ``policy`` is "none" (no remat), "full" (save nothing but what the
    wrapped function's ops declare through ``keep``), "attn_saveable"
    ("full" + the named Pallas attention outputs),
    "dots_and_attn_saveable" (dots + those), or any
    ``jax.checkpoint_policies`` attribute name — "dots_saveable" (keep MXU
    outputs, recompute elementwise — the usual TPU sweet spot),
    "nothing_saveable", "dots_with_no_batch_dims_saveable", ...

    ``keep`` names values (``jax.ad_checkpoint.checkpoint_name``) that the
    checkpoint saves in addition to whatever ``policy`` saves: an op whose
    ``custom_vjp`` forward rule names its results AND its residuals (the
    same values; a name put on a copy outside the op would leave the
    residual, the kernel's own result, to be replayed) is then not run
    again in the backward. Under "none" there is no checkpoint and
    ``keep`` does nothing; with no ``keep`` every policy is what it was.
    """
    if not remat_enabled(policy):
        return fn
    policies = jax.checkpoint_policies
    policy, named = _NAMED.get(policy, (policy, ()))
    policy_fn = None
    if policy != "full":
        policy_fn = getattr(policies, policy, None)
        if not callable(policy_fn):
            available = sorted(
                n for n in dir(policies) if not n.startswith("_")
            )
            raise ValueError(
                f"unknown remat policy {policy!r}; have 'none', 'full' or "
                f"one of {available}"
            )
    if named or keep:
        kept = policies.save_only_these_names(*named, *keep)
        policy_fn = kept if policy_fn is None else (
            policies.save_from_both_policies(policy_fn, kept))
    return jax.checkpoint(fn, policy=policy_fn, prevent_cse=prevent_cse)
