"""The controls of the ``ssd_hybrid`` family's reference check: the
precision below the configuration's, and each of the two kinds of
layer's mechanisms wrong in turn. Each is a change to the REFERENCE
alone (``chipbench/families/ssd_hybrid/reference.py``: one of its hooks
swapped, or the dictionary it reads changed), so the program it is
compared with stays sound and the comparison has to come out not
``correct``.

The tests import ``CONTROLS`` and ``applied`` (a toy size, float32, on
the CPU). Run as a script it gives the harness's own verdict at a
configuration's timed sizes, which is how ``job.py``'s limits were read
on the chip::

    chiprun -- python tests/chipbench/ssd_hybrid_controls.py \\
        --controls 3000005701,3000005702 --sound 3000005703,3000005704

For every seed it builds the job and its trainer as ``worker.py`` does
and calls ``worker.ReferenceCheck`` (the compiled ``eval_step`` against
``job.reference_loss``) once sound and, on the ``--controls`` seeds,
once under each control: one JSON line a check, with the worker's
``reference`` event (``abs_diff``, ``tolerance``, ``ok``) and the job's
``reference_hidden`` readings (the final hidden states, and the first
attention layer's mixer alone: at the initial weights a query averages
its keys almost evenly, so the scale and rotary move the final hidden
states by less than bf16's rounding does and only that second number
sees them). Exit code 1 where a sound check is not ``ok`` or a control
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
from chipbench.families.ssd_hybrid import reference  # noqa: E402


def _e4m3(a, b):
    """``reference.mm`` with operands of 4 significant bits, where the
    configuration's bf16 has 8: the nearest precision below it."""
    low = lambda t: t.astype(jnp.float8_e4m3fn).astype(  # noqa: E731
        jnp.float32)
    return jnp.matmul(low(a), low(b))


def _bf16(state):
    """A float32 array at bf16's 8 significant bits, by an operation the
    compiler keeps (it drops a pair of casts where it may keep more
    precision than asked: on the chip such a pair changed nothing)."""
    return jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)


def _rotary(q, k, model):
    """Rotate-half rotary positions at the published and unused
    ``rope_theta`` on an attention layer's q and k [seq, heads, d]."""
    seq, _, d = q.shape
    inv_freq = model["rope_theta"] ** (
        -2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]

    def turn(x):
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    return turn(q), turn(k)


def _norm_before_gate(y, z, scale, eps):
    """Mamba-2's other order (``norm_before_gate``): the norm, then the
    gate."""
    return reference.rms_norm(y, scale, eps) * jax.nn.silu(z)


def _root_scale(model):
    """The usual ``1 / sqrt(head_dim)`` (1/8 at a head of 64) where the
    family publishes the scale itself."""
    return model["assumed"]["head_dim"] ** -0.5


# name -> (a hook of ``reference``, its replacement), or what to change
# in the configuration's dictionary, given that dictionary
CONTROLS = {
    "a bf16 carried state": ("carried", _bf16),
    "the norm before the gate": ("gated_norm", _norm_before_gate),
    "a scale of 1 / sqrt(head_dim)": ("attention_scale", _root_scale),
    "rotary applied on the attention layers": ("positions", _rotary),
    "the residual multiplier at 1": lambda m: {"residual_multiplier": 1.0},
    "the embedding multiplier at 1": lambda m: {"embedding_multiplier": 1.0},
    "D_skip left out": ("skip", lambda y, x, d_skip: y),
    "dt_bias left out": ("step_size", lambda dt_raw, dt_bias:
                         jax.nn.softplus(dt_raw)),
    "the convolution's bias left out": (
        "conv", lambda u, taps, bias, conv=reference.conv: conv(
            u, taps, jnp.zeros_like(bias))),
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
            "attention_token_error":
                events["reference_hidden"]["attention_token_error"],
            "attention_tolerance":
                events["reference_hidden"]["attention_tolerance"],
            "seconds": ref["seconds"]}


def main(argv=None):
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    p = argparse.ArgumentParser()
    p.add_argument("--config", default=os.path.join(
        REPO, "chipbench", "configs",
        "granite-4.0-h-micro-d20-1chip.json"))
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
