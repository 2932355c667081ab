"""The controls of the ``looped`` family's reference check: the
precision below the configuration's, and each of the loop's mechanisms
wrong in turn. Each is a change to the REFERENCE alone
(``chipbench/families/looped/reference.py``: one of its hooks swapped,
or the dictionary it reads changed), so the program it is compared with
stays sound and the comparison has to come out not ``correct``.

The tests import ``CONTROLS`` and ``applied`` (a toy size, float32, on
the CPU). Run as a script it gives the harness's own verdict at a
configuration's timed sizes, which is how ``job.py``'s limits were read
on the chip::

    chiprun -- python tests/chipbench/looped_controls.py \\
        --controls 3000006511,3000006512 --sound 3000006513,3000006514

For every seed it builds the job and its trainer as ``worker.py`` does
and calls ``worker.ReferenceCheck`` (the compiled ``eval_step`` against
``job.reference_loss``) once sound and, on the ``--controls`` seeds,
once under each control: one JSON line a check, with the worker's
``reference`` event (``abs_diff``, ``tolerance``, ``ok``) and the job's
``reference_hidden`` readings (the four ``L_t``, the mean exit
distribution, the last pass's normed states, the gate's and the head's
gradient). Exit code 1 where a sound check is not ``ok`` or a control
is.
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
from chipbench.families.looped import reference  # noqa: E402


def _e4m3(a, b):
    """``reference.mm`` with operands of 4 significant bits, where the
    configuration's bf16 has 8: the nearest precision below it."""
    low = lambda t: t.astype(jnp.float8_e4m3fn).astype(  # noqa: E731
        jnp.float32)
    return jnp.matmul(low(a), low(b))


def _pre_norm(x, f, in_scale, out_scale, eps):
    """A pre-norm sublayer: the sandwich without its output norm."""
    return x + f(reference.rms_norm(x, in_scale, eps))


def _last_gate_used(lam, sound=reference.exit_distribution):
    """``p_T = lambda_T prod_{j<T} (1 - lambda_j)``: the last pass
    gated as the others, the rest of the mass lost."""
    return sound(lam).at[-1].multiply(lam[-1])


# name -> (a hook of ``reference``, its replacement), or what to change
# in the configuration's dictionary, given that dictionary
CONTROLS = {
    "three passes for four": lambda m: {
        "total_ut_steps": m["total_ut_steps"] - 1},
    "the final norm left out between passes": (
        "next_input", lambda h, state: h),
    "the sandwich's output norms left out": ("sublayer", _pre_norm),
    "the last pass gated too": ("exit_distribution", _last_gate_used),
    "the entropy's sign turned": (
        "entropy", lambda p, entropy=reference.entropy: -entropy(p)),
    "rotary base 1e4": ("rope_theta", lambda model: 1e4),
    "the head's weights all 1": ("head_weights", jnp.ones_like),
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
    ref, found = events["reference"], events["reference_hidden"]
    return {"ok": ref["ok"],
            # against the reference's own, where the job gave the
            # worker NaN
            "abs_diff": abs(ref["system_loss"] - found["reference_loss"]),
            "tolerance": ref["tolerance"],
            "system_loss": ref["system_loss"],
            **{k: v for k, v in found.items() if k != "event"},
            "seconds": ref["seconds"]}


def main(argv=None):
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    p = argparse.ArgumentParser()
    p.add_argument("--config", default=os.path.join(
        REPO, "chipbench", "configs", "ouro-2.6b-d12-1chip.json"))
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
