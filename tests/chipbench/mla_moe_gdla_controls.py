"""The controls of the ``mla_moe_gdla`` family's reference check: the
precision below the configuration's, a float32 piece in bf16, and each
of the layer's mechanisms wrong in turn. Each is a change to the
REFERENCE alone (``chipbench/families/mla_moe_gdla/reference.py``: one
of its hooks swapped, or the dictionary it reads changed), so the
program it is compared with stays sound and the comparison has to come
out not ``correct``.

The tests import ``CONTROLS`` and ``applied`` (a toy size, float32, on
the CPU). Run as a script it gives the harness's own verdict at a
configuration's timed sizes, which is how ``job.py``'s limits were read
on the chip::

    chiprun -- python tests/chipbench/mla_moe_gdla_controls.py \\
        --controls 3000005511 --sound 3000005512,3000005513

For every seed it builds the job and its trainer as ``worker.py`` does
and calls ``worker.ReferenceCheck`` (the compiled ``eval_step`` against
``job.reference_loss``) once sound and, on the ``--controls`` seeds,
once under each control: one JSON line a check, with the worker's
``reference`` event (``abs_diff``, ``tolerance``, ``ok``) and the job's
``reference_hidden`` readings. Exit code 1 where a sound check is not
``ok`` or a control is; a control named under ``--blind`` is run and
reported and does not count.
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

from chipbench.families.mla_moe_gdla import reference  # noqa: E402


def _e4m3(a, b):
    """``reference.mm`` with operands of 4 significant bits, where the
    configuration's bf16 has 8: the nearest precision below it."""
    low = lambda t: t.astype(jnp.float8_e4m3fn).astype(  # noqa: E731
        jnp.float32)
    return jnp.matmul(low(a), low(b))


def _bf16(x):
    """``reference.f32``: what the configuration states in float32
    (router scores, lambda, PolyNorm, the mappings, softmax's scores,
    the logits) rounded to bf16."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _window_left_out(model):
    return {"use_sliding_window": False}


# name -> (a hook of ``reference``, its replacement), or what to change
# in the configuration's dictionary, given that dictionary
CONTROLS = {
    "e4m3 operands": ("mm", _e4m3),
    "float32 pieces in bf16": ("f32", _bf16),
    "the window left out": _window_left_out,
    "the window doubled": lambda m: {
        "sliding_window": 2 * m["sliding_window"]},
    "every layer of a period shifted": lambda m: {"deployment": dict(
        m["deployment"],
        first_published_layer=m["deployment"]["first_published_layer"] + 1)},
    "PolyNorm without its output scale": lambda m: {
        "polynorm_output_scale": 1.0},
    "the routed scale left out": lambda m: {"route_scale": 1.0},
    "rotary at a tenth of theta": lambda m: {
        "rope_theta": m["rope_theta"] / 10},
    "one Sinkhorn iteration": lambda m: {"mhc_sinkhorn_iters": 1},
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
        REPO, "chipbench", "configs", "motif-3-beta-1chip.json"))
    p.add_argument("--controls", default="",
                   help="seeds checked sound and under every control")
    p.add_argument("--sound", default="", help="seeds checked sound alone")
    p.add_argument("--blind", default="",
                   help="controls, comma-separated, that do not count")
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
        blind = [c for c in args.blind.split(",") if c]
        for control in [None] + (list(CONTROLS) if controlled else []):
            with applied(model, control) if control else (
                    contextlib.nullcontext()):
                line = _check(check, state)
            wrong += (line["ok"] != (control is None)
                      and control not in blind)
            print(json.dumps({"seed": seed, "control": control or "sound",
                              "device": jax.devices()[0].device_kind,
                              **line}), flush=True)
        del state, check
    return int(wrong > 0)


if __name__ == "__main__":
    sys.exit(main())
