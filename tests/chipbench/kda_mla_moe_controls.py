"""The controls of the ``kda_mla_moe`` family's reference check: the
precision below the configuration's, and each published mechanism of
the two mixers and of the router wrong in turn. Each is a change to the
REFERENCE alone (``chipbench/families/kda_mla_moe/reference.py``: one
of its hooks swapped, or the dictionary it reads changed), so the
program it is compared with stays sound and the comparison has to come
out not ``correct``.

The tests import ``CONTROLS`` and ``applied`` (a toy size, float32, on
the CPU). Run as a script it gives the harness's own verdict at a
configuration's timed sizes, which is how ``job.py``'s limits were read
on the chip::

    chiprun -- python tests/chipbench/kda_mla_moe_controls.py \\
        --controls 3000006201,3000006202 --sound 3000006203,3000006204

For every seed it builds the job and its trainer as ``worker.py`` does
and calls ``worker.ReferenceCheck`` (the compiled ``eval_step`` against
``job.reference_loss``) once sound and, on the ``--controls`` seeds,
once under each control: one JSON line a check, with the worker's
``reference`` event (``abs_diff``, ``tolerance``, ``ok``) and the job's
``reference_hidden`` readings (the final hidden states, the last KDA
layer's mixer alone and the MLA layer's mixer alone, each on what the
reference's mixer read). Exit code 1 where a sound check is not ``ok``
or a control is.
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
from chipbench.families.kda_mla_moe import reference  # noqa: E402


def _e4m3(a, b):
    """``reference.mm`` with operands of 4 significant bits, where the
    configuration's bf16 has 8: the nearest precision below it."""
    low = lambda t: t.astype(jnp.float8_e4m3fn).astype(  # noqa: E731
        jnp.float32)
    return jnp.matmul(low(a), low(b))


def _bf16(state):
    """A float32 array at bf16's 8 significant bits, by an operation the
    compiler keeps (it drops a pair of casts where it may keep more
    precision than asked: PR 57's finding on the chip)."""
    return jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)


def _scalar_decay(g):
    """The scalar rule: the log decay's mean over a head's channels in
    place of the vector."""
    return jnp.exp(jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape))


def _unbounded(raw, a_log, bound):
    """The gate without its bound (Gated DeltaNet's and the first
    KDA's): ``-exp(A_log) softplus(raw)``."""
    del bound
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(raw)


# name -> (a hook of ``reference``, its replacement), or what to change
# in the configuration's dictionary, given that dictionary
CONTROLS = {
    "the decay's mean over a head's channels": ("decay", _scalar_decay),
    "the gate's bound left out": ("log_decay", _unbounded),
    "beta without its sigmoid": ("beta_of", lambda logits: logits),
    "the KDA output gate left out": ("out_gate", lambda o, logits: o),
    "the KDA head norm left out": ("head_norm", lambda o, scale, eps: o),
    "the convolution left out": ("conv", lambda u, taps: u),
    "a bf16 carried state": ("carried", _bf16),
    "the head-wise gate left out": ("head_gate", lambda a, logits: a),
    "the QK norms left out": ("qk_norm", lambda x, scale, eps: x),
    "the group limit left out": lambda m: {"n_group": 1, "topk_group": 1},
    "the routed scaling factor at 1": lambda m: {
        "routed_scaling_factor": 1.0},
    "the shared expert left out": ("shared_expert",
                                   lambda z, w: jnp.zeros_like(z)),
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
    ref, hidden = events["reference"], events["reference_hidden"]
    return {"ok": ref["ok"],
            "abs_diff": abs(ref["system_loss"] - hidden["reference_loss"]),
            "tolerance": ref["tolerance"],
            "system_loss": ref["system_loss"],
            # the reference's own, where the job gave the worker NaN
            **{key: hidden[key] for key in (
                "reference_loss", "median_token_error", "kda_token_error",
                "kda_tolerance", "mla_token_error", "mla_tolerance")},
            "hidden_tolerance": hidden["tolerance"],
            "seconds": ref["seconds"]}


def main(argv=None):
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    p = argparse.ArgumentParser()
    p.add_argument("--config", default=os.path.join(
        REPO, "chipbench", "configs", "ling-3.0-flash-1chip.json"))
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
