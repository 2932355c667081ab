"""The controls of the ``delta_hybrid`` family's reference check: the
precision below the configuration's, and each of the two kinds of
layer's mechanisms wrong in turn. Each is a change to the REFERENCE
alone (``chipbench/families/delta_hybrid/reference.py``: one of its
hooks swapped, or the dictionary it reads changed), so the program it
is compared with stays sound and the comparison has to come out not
``correct``.

The tests import ``CONTROLS`` and ``applied`` (a toy size, float32, on
the CPU). Run as a script it gives the harness's own verdict at a
configuration's timed sizes, which is how ``job.py``'s limits were read
on the chip::

    chiprun -- python tests/chipbench/delta_hybrid_controls.py \\
        --controls 3000004301,3000004302 --sound 3000004303,3000004304

For every seed it builds the job and its trainer as ``worker.py`` does
and calls ``worker.ReferenceCheck`` (the compiled ``eval_step`` against
``job.reference_loss``) once sound and, on the ``--controls`` seeds,
once under each control: one JSON line a check, with the worker's
``reference`` event (``abs_diff``, ``tolerance``, ``ok``) and the job's
``reference_hidden`` reading. Exit code 1 where a sound check is not
``ok`` or a control is.
"""

import argparse
import contextlib
import io
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import worker  # noqa: E402
from chipbench.families.delta_hybrid import reference  # noqa: E402


def _e4m3(a, b):
    """``reference.mm`` with operands of 4 significant bits, where the
    configuration's bf16 has 8: the nearest precision below it."""
    low = lambda t: t.astype(jnp.float8_e4m3fn).astype(  # noqa: E731
        jnp.float32)
    return jnp.matmul(low(a), low(b))


def _rotary(q, k, model):
    """Rotate-half rotary positions at theta 5e5 on a full layer's q
    and k [seq, heads, d], which the published layer does not have."""
    del model
    seq, _, d = q.shape
    inv_freq = 5e5 ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]

    def turn(x):
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    return turn(q), turn(k)


def _pre_norm(x, f, scale, eps):
    """The usual block in place of the family's: the sublayer reads the
    normalised ``x`` and its output is added as it is."""
    return x + f(reference.rms_norm(x, scale, eps))


# name -> (a hook of ``reference``, its replacement), or what to change
# in the configuration's dictionary, given that dictionary
CONTROLS = {
    "beta not doubled": lambda m: {"linear_allow_neg_eigval": False},
    "the decay dropped": ("decay", lambda g: jnp.ones_like(g)),
    "the erase dropped": ("target", lambda v_t, seen: v_t),
    "the convolutions left off": ("conv", lambda u, taps: u),
    "q and k not l2-normalised": ("unit", lambda u, eps: u),
    "the output gate dropped": ("out_gate", lambda o, gate: o),
    "rotary applied on the full layers": ("positions", _rotary),
    "the qk norms left off": ("qk_norm", lambda u, scale, eps: u),
    "pre-norm for the reordered norm": ("sublayer", _pre_norm),
    "e4m3 operands": ("mm", _e4m3),
}


@contextlib.contextmanager
def applied(model, control):
    """The reference under ``control``: ``model`` is the dictionary the
    reference reads (the one the job was built from: the program's
    config was made from it before, and does not change)."""
    change = CONTROLS[control]
    if isinstance(change, tuple):
        hook, replacement = change
        saved = getattr(reference, hook)
        setattr(reference, hook, replacement)
        try:
            yield
        finally:
            setattr(reference, hook, saved)
    else:
        new = change(model)
        saved = {key: model[key] for key in new}
        model.update(new)
        try:
            yield
        finally:
            model.update(saved)


def _check(check, state):
    """One ``ReferenceCheck``: what the worker and the job printed."""
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        check.begin(types.SimpleNamespace(state=state))
    events = {line["event"]: line for line in map(
        json.loads, said.getvalue().splitlines())}
    ref = events["reference"]
    return {"ok": ref["ok"], "abs_diff": abs(
                ref["system_loss"]
                - events["reference_hidden"]["reference_loss"]),
            "tolerance": ref["tolerance"],
            "system_loss": ref["system_loss"],
            # the reference's own, where the job gave the worker NaN
            "reference_loss": events["reference_hidden"]["reference_loss"],
            "median_token_error":
                events["reference_hidden"]["median_token_error"],
            "hidden_tolerance": events["reference_hidden"]["tolerance"],
            "seconds": ref["seconds"]}


def main(argv=None):
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    p = argparse.ArgumentParser()
    p.add_argument("--config", default=os.path.join(
        REPO, "chipbench", "configs", "olmo-hybrid-7b-d8-1chip.json"))
    p.add_argument("--controls", default="",
                   help="seeds checked sound and under every control")
    p.add_argument("--sound", default="", help="seeds checked sound alone")
    args = p.parse_args(argv)
    seeds = {int(s): True for s in args.controls.split(",") if s}
    seeds.update({int(s): False for s in args.sound.split(",")
                  if s and int(s) not in seeds})
    model = worker.load(args.config)
    job = worker.build_job(model)
    batch = model["assumed"]["batch"]
    trainer = ElasticTrainer(
        job.init_fn, job.loss_fn,
        worker.build_optimizer(model["assumed"]["optimizer"]),
        worker.batch_for(0, 0, job.vocab_size, batch, job.seq_len),
        strategy=job.strategy, devices=jax.devices()[:model["chips"]])
    wrong = 0
    for seed, controlled in seeds.items():
        trainer._rng = jax.random.PRNGKey(seed % 2 ** 32)  # as worker.py
        state = trainer.prepare()
        check = worker.ReferenceCheck(job, trainer, seed % 2 ** 32, batch)
        for control in [None] + (list(CONTROLS) if controlled else []):
            with applied(model, control) if control else (
                    contextlib.nullcontext()):
                line = _check(check, state)
            wrong += line["ok"] != (control is None)
            print(json.dumps({"seed": seed, "control": control or "sound",
                              "device": jax.devices()[0].device_kind,
                              **line}), flush=True)
        del state, check
    return int(wrong > 0)


if __name__ == "__main__":
    sys.exit(main())
