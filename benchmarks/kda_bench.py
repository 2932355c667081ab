"""One KDA layer's rule alone, on the chip: ``ops.kda`` at
Ling-3.0-flash's shape (two rows of 8192 tokens, 32 heads, keys and
values of 128, bf16) against the recurrence token by token at
``highest``, its gradients against the float32 chunked form, and its
time a call over the chunk and the heads a program,
which is the sweep behind ``chain_tiles``; the forward pass's one
kernel (``kda_forward``) beside the preparation and the chain it takes
the place of; the backward's two kernels (``kda_backward``) beside the
two steps' backward a head group at a time that they take the place of,
and their five gradients against that one's at the timed shape.

Run on the TPU host, from the repo root:
``PYTHONPATH=. python benchmarks/kda_bench.py [--rows 2] [--heads 32]``.
Prints one JSON line a measurement and appends them to
``chiprun_out/kda_bench.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import kda as kd

SEQ, DK, DV = 8192, 128, 128
NAMES = "q k v g beta".split()
BOUND = -5.0
STEPS = 10


def operands(seed, rows, heads, dtype):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)

    def unit(key):
        u = jax.random.normal(key, (rows, SEQ, heads, DK))
        return u / jnp.linalg.norm(u, axis=-1, keepdims=True)

    q, key = unit(k[0]) / math.sqrt(DK), unit(k[1])
    v = jax.nn.silu(jax.random.normal(k[2], (rows, SEQ, heads, DV)))
    # as the model's initialisation gives them: a rate in (1, 16) a
    # head, a step log-uniform in [1e-3, 1e-1] a channel through the
    # inverse softplus, a projection of unit variance
    rate = jax.random.uniform(k[3], (heads, 1), minval=1.0, maxval=16.0)
    step = jnp.exp(jax.random.uniform(k[4], (heads, DK))
                   * math.log(100.0) + math.log(1e-3))
    raw = jax.random.normal(k[5], (rows, SEQ, heads, DK)) + (
        step + jnp.log(-jnp.expm1(-step)))
    g = BOUND * jax.nn.sigmoid(rate * raw)
    beta = jax.nn.sigmoid(jax.random.normal(k[6], (rows, SEQ, heads)))
    return (q.astype(dtype), key.astype(dtype), v.astype(dtype), g, beta)


def timed(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)  # compile and warm
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / STEPS


def say(out, **line):
    line["device"] = jax.devices()[0].device_kind
    print(json.dumps(line), flush=True)
    out.write(json.dumps(line) + "\n")
    out.flush()


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def accuracy(out, heads):
    """The op (bf16, kernels) against the recurrence token by token at
    ``highest`` on the same bf16-rounded operands, and its gradients
    against the float32 chain as a scan over chunks (which the CPU
    tests hold to the recurrence; the recurrence's own backward would
    keep 8192 states a head). One row: the float32 side is large."""
    args = operands(0, 1, heads, jnp.bfloat16)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, SEQ, heads, DV))
    want, want_state = jax.jit(kd.kda_reference)(*args)
    got, state = jax.jit(kd.kda)(*args)
    rule, rule_state = jax.jit(kd.kda_forward)(*args)

    def loss(fn, cast):
        return lambda *a: (fn(*(t.astype(cast) for t in a[:3]), *a[3:])[0]
                           .astype(jnp.float32) * weight).sum()

    # a float32 product on the chip multiplies in bf16 unless told
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(jax.grad(loss(
            lambda *a: kd.kda(*a, use_kernels=False), jnp.float32),
            argnums=range(5)))(*args)
    ours = jax.jit(jax.grad(loss(kd.kda, jnp.bfloat16),
                            argnums=range(5)))(*args)
    rule_grads = jax.jit(kd.kda_backward)(*args, weight.astype(jnp.bfloat16))
    say(out, what="accuracy", heads=heads,
        forward_rel_err=rel(got, want), state_rel_err=rel(state, want_state),
        rule_forward_rel_err=rel(rule, want),
        rule_state_rel_err=rel(rule_state, want_state),
        rule_to_two_step_rel_err=rel(rule, got),
        grad_rel_err=dict(zip(NAMES, map(rel, ours, plain))),
        rule_grad_rel_err=dict(zip(NAMES, map(rel, rule_grads, plain))))


def _chunked(t, chunk):  # [B, S, H, ...] -> [B, H, N, C, ...]
    b, s, h = t.shape[:3]
    return jnp.moveaxis(t, 2, 1).reshape((b, h, s // chunk, chunk)
                                         + t.shape[3:])


def _prepared(q, k, v, g, beta, chunk):
    """The chain's operands as ``kda`` hands them over."""
    b, _, h, _ = q.shape
    qg, kdn, w, ubar, p, decay = kd._prepare(
        _chunked(q, chunk), _chunked(k, chunk), _chunked(v, chunk),
        _chunked(g, chunk), _chunked(beta, chunk))
    return (qg, kdn, w, ubar, p, decay[..., None, :],
            jnp.zeros((b, h, DV, DK), jnp.float32))


def rule_forward_ms(args, hb):
    """``kda_forward`` alone, ms a call: its operands handed over as
    the layer's projections leave them, [B, S, H x columns] (a
    parameter in the 4-D form costs a copy into the kernel's tiling
    that the model's fused producers do not pay)."""
    shapes = [t.shape for t in args]
    flat = [t.reshape(t.shape[:2] + (-1,)) for t in args]

    def fn(*flat):
        return kd.kda_forward(*(t.reshape(s) for t, s in zip(flat, shapes)),
                              heads_per_program=hb)[0]

    return timed(jax.jit(fn), *flat)


def two_steps_backward(args, do, groups):
    """The backward this PR's kernels take the place of (PR 62's, and
    PR 63's): the derivative of the two steps (``kda``: the
    preparation in XLA, ``kda_fwd`` for the chunks' start states,
    ``kda_bwd``, XLA's transpose of the preparation) a group of heads
    at a time."""
    h = args[0].shape[2]

    def split(t):
        return jnp.moveaxis(t.reshape(
            t.shape[:2] + (groups, h // groups) + t.shape[3:]), 2, 0)

    def one(xs):
        *a, d = xs
        return jax.vjp(lambda *a: kd.kda(*a)[0], *a)[1](d)

    grads = jax.lax.map(one, tuple(split(t) for t in (*args, do)))
    return tuple(jnp.moveaxis(t, 0, 2).reshape(like.shape)
                 for t, like in zip(grads, args))


def sweep(out, rows, heads):
    """One head group's worth of heads: the preparation, the chain by
    the heads a program, and the op whole."""
    args = operands(1, rows, heads, jnp.bfloat16)
    weight = jax.random.normal(jax.random.PRNGKey(9), (rows, SEQ, heads, DV),
                               jnp.bfloat16)
    for chunk in (64, 128):
        prep = jax.jit(lambda *a, c=chunk: _prepared(*a, chunk=c))
        ops = prep(*args)
        both = jax.jit(jax.grad(lambda *a, c=chunk: sum(
            (t.astype(jnp.float32) ** 2).sum()
            for t in _prepared(*a, chunk=c)[:6]), argnums=range(5)))
        say(out, what="prepare", rows=rows, heads=heads, chunk=chunk,
            forward_ms=timed(prep, *args),
            forward_backward_ms=timed(both, *args))
        if chunk == kd.chain_tiles(SEQ, heads)[0]:
            for hb in [d for d in (2, 4, 8) if heads % d == 0]:
                say(out, what="rule", rows=rows, heads=heads, chunk=chunk,
                    heads_per_program=hb,
                    forward_ms=rule_forward_ms(args, hb))
        for hb in [d for d in (2, 4, 8) if heads % d == 0]:
            try:
                fwd = jax.jit(lambda *o, hb=hb: kd._chain(*o, hb, False)[0])
                grad = jax.jit(jax.grad(
                    lambda *o, hb=hb: (kd._chain(*o, hb, False)[0]
                                       * _chunked(weight, chunk)).sum()
                    .astype(jnp.float32), argnums=range(6)))
                line = dict(chain_forward_ms=timed(fwd, *ops),
                            chain_forward_backward_ms=timed(grad, *ops))
                whole = jax.jit(jax.grad(
                    lambda *a, hb=hb, c=chunk: (kd.kda(
                        *a, chunk=c, heads_per_program=hb)[0]
                        * weight).sum().astype(jnp.float32),
                    argnums=range(5)))
                line["op_forward_backward_ms"] = timed(whole, *args)
            except Exception as e:  # noqa: BLE001 - VMEM, say and go on
                line = {"refused": str(e)[:200]}
            say(out, what="chain", rows=rows, heads=heads, chunk=chunk,
                heads_per_program=hb, **line)


def backward(out, rows, heads):
    """The layer's backward: the two kernels by the heads a program
    (the states pass alone, the backward pass alone on its results, and
    ``kda_backward`` whole) beside the two steps' in sixteen head
    groups, and the five gradients of the one against the other's, the
    largest difference over the largest entry. Both sides are bf16
    with their own rounding points (the two steps round the chain's
    five gradients to bf16 on their way into XLA, the kernel keeps them
    float32), so they differ by a few bf16 roundings of a sum, as each
    does from the float32 form (``accuracy``): a wrong term reads 0.1
    to 1."""
    args = operands(2, rows, heads, jnp.bfloat16)
    do = jax.random.normal(jax.random.PRNGKey(9), (rows, SEQ, heads, DV),
                           jnp.bfloat16)
    groups = max(heads // 2, 1)  # of two heads, as PR 62's sweep chose
    old = jax.jit(lambda *a: two_steps_backward(a[:5], a[5], groups))
    want = old(*args, do)
    say(out, what="two_steps_backward", rows=rows, heads=heads,
        head_groups=groups, backward_ms=timed(old, *args, do))
    flat = [t.reshape(t.shape[:2] + (-1,)) for t in (*args, do)]
    s0 = jnp.zeros((rows, heads, DV, DK), jnp.float32)
    chunk = kd.chain_tiles(SEQ, heads)[0]
    interpret = kd._resolve_interpret(None)
    for hb in [d for d in (2, 4, 8) if heads % d == 0]:
        try:
            states = jax.jit(lambda *a, hb=hb: kd._rule_starts(
                *a, chunk, hb, interpret))
            kept = states(*flat[1:5], s0)
            line = dict(states_ms=timed(states, *flat[1:5], s0))
            line["backward_pass_ms"] = timed(jax.jit(
                lambda *a, hb=hb: kd._rule_backward(*a, chunk, hb, interpret)),
                *flat, *kept)
            del kept
            new = jax.jit(lambda *a, hb=hb: kd.kda_backward(
                *a, heads_per_program=hb))
            line["backward_ms"] = timed(new, *args, do)
            line["grad_rel_err_to_two_steps"] = dict(zip(
                NAMES, map(rel, new(*args, do), want)))
        except Exception as e:  # noqa: BLE001 - VMEM, say and go on
            line = {"refused": str(e)[:200]}
        say(out, what="rule_backward", rows=rows, heads=heads,
            heads_per_program=hb, **line)


def rule(out, rows, heads):
    """The forward pass's kernel on the layer's heads, by the heads a
    program."""
    args = operands(2, rows, heads, jnp.bfloat16)
    for hb in (2, 4, 8):
        say(out, what="rule", rows=rows, heads=heads, heads_per_program=hb,
            forward_ms=rule_forward_ms(args, hb))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=2)
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--sweep_heads", type=int, default=8,
                   help="the heads of the tile sweep (one head group's)")
    p.add_argument("--skip", default="",
                   help="accuracy,sweep,rule,backward")
    args = p.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("a time comes only from the chip")
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "kda_bench.jsonl"), "a") as out:
        if "accuracy" not in args.skip:
            accuracy(out, args.sweep_heads)
        if "sweep" not in args.skip:
            sweep(out, args.rows, args.sweep_heads)
        if "rule" not in args.skip:
            rule(out, args.rows, args.heads)
        if "backward" not in args.skip:
            backward(out, args.rows, args.heads)


if __name__ == "__main__":
    main()
