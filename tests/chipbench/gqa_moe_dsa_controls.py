"""The controls of the ``gqa_moe_dsa`` family's reference check: the
precision below the configuration's, and each of the layer's mechanisms
wrong in turn. Each is a change to the REFERENCE alone
(``chipbench/families/gqa_moe_dsa/reference.py``: one of its hooks
swapped, or the dictionary it reads changed), so the program it is
compared with stays sound and the comparison has to come out not
``correct``, by at least one of ``job.py``'s limits.

The tests import ``CONTROLS`` and ``applied`` (a toy size, float32, on
the CPU). Run as a script it gives the harness's own verdict at a
configuration's timed sizes, which is how ``job.py``'s limits were read
on the chip::

    chiprun -- python tests/chipbench/gqa_moe_dsa_controls.py \\
        --controls 3000004801 --sound 3000004802,3000004803

For every seed it builds the job and its trainer as ``worker.py`` does
and calls ``worker.ReferenceCheck`` (the compiled ``eval_step`` against
``job.reference_loss``) once sound and, on the ``--controls`` seeds,
once under each control: one JSON line a check, with the worker's
``reference`` event (``abs_diff``, ``tolerance``, ``ok``) and the job's
``reference_hidden`` readings. Exit code 1 where a sound check is not
``ok`` or a control is.

``a bf16 softmax`` shows in float32 alone (``BF16_BLIND``): against a
bf16 program, which hands its probabilities to the PV product in bf16
itself, the reference's 2048 rounded probabilities a row average out
(``job.py`` has the readings), so a bf16 configuration's run leaves it
out. ``the rotary sections swapped`` cannot show on text, where the three
position rows are equal and every assignment of pairs to rows is plain
rotary: the harness's row is text, so on the chip it is left out
(``TEXT_BLIND``), and the CPU test gives both sides unequal rows. On
text the rotary is held by ``rotary at a tenth of theta``.
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

import jax.numpy as jnp  # noqa: E402

from chipbench.families.gqa_moe_dsa import reference  # noqa: E402


def _e4m3(a, b):
    """``reference.mm`` with operands of 4 significant bits, where the
    configuration's bf16 has 8: the nearest precision below it."""
    low = lambda t: t.astype(jnp.float8_e4m3fn).astype(  # noqa: E731
        jnp.float32)
    return jnp.matmul(low(a), low(b))


def _sa(**change):
    return lambda m: {"sa_config": dict(m["sa_config"], **change)}


# name -> (a hook of ``reference``, its replacement), or what to change
# in the configuration's dictionary, given that dictionary
CONTROLS = {
    "a bf16 softmax": ("softmax_dtype", lambda: jnp.bfloat16),
    "topk halved": lambda m: _sa(topk=m["sa_config"]["topk"] // 2)(m),
    "no selection at all": lambda m: _sa(
        topk=m["assumed"]["seq_len"])(m),
    "the indexer without its ReLU": ("index_act", lambda x: x),
    "the rotary sections swapped": lambda m: {"rope_scaling": dict(
        m["rope_scaling"],
        mrope_section=m["rope_scaling"]["mrope_section"][::-1])},
    "rotary at a tenth of theta": lambda m: {
        "rope_theta": m["rope_theta"] / 10},
    "the indexer's loss left out": lambda m: {"assumed": dict(
        m["assumed"], index_loss_weight=0.0)},
    "e4m3 operands": ("mm", _e4m3),
}
# what a row of text, and what a bf16 program, cannot show (see the
# module's docstring)
TEXT_BLIND = ("the rotary sections swapped",)
BF16_BLIND = ("a bf16 softmax",)


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
    ref, read = events["reference"], events["reference_hidden"]
    return {"ok": ref["ok"],
            # against the reference's own loss, where the job gave the
            # worker NaN
            "abs_diff": abs(ref["system_loss"] - read["reference_loss"]),
            "tolerance": ref["tolerance"],
            "system_loss": ref["system_loss"],
            **{k: v for k, v in read.items() if k != "event"},
            "seconds": ref["seconds"]}


def main(argv=None):
    import jax

    from chipbench import worker
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    p = argparse.ArgumentParser()
    p.add_argument("--config", default=os.path.join(
        REPO, "chipbench", "configs", "keye-vl-2.0-30b-a3b-ep4-1chip.json"))
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
        blind = TEXT_BLIND + (BF16_BLIND if model["assumed"].get(
            "compute_dtype", "bfloat16") == "bfloat16" else ())
        controls = [c for c in CONTROLS if c not in blind]
        for control in [None] + (controls if controlled else []):
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
