"""One linear-attention layer's gated delta rule alone, on the chip:
``ops.gated_delta`` at Olmo-Hybrid's shape (one row of 8192 tokens, 30
heads, keys of 96, values of 192, bf16) against the recurrence token by
token at ``highest``, and its time a call over the chunk and the heads a
program, which is the sweep behind ``chain_tiles``: the whole rule's
three kernels (rows ``rule_forward``, ``rule_starts``,
``rule_backward``: a kernel alone on operands a head and chunk; row
``op``: what a layer calls, forward and backward with XLA's transposes
around it) beside the two steps (rows ``prepare`` and ``chain``).

Run on the TPU host, from the repo root:
``PYTHONPATH=. python benchmarks/gdn_bench.py [--heads 30]
[--skip sweep]``. Prints one JSON line a measurement and appends them
to ``chiprun_out/gdn_bench.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import gated_delta as gd

SEQ, DK, DV = 8192, 96, 192
STEPS = 10


def operands(seed, heads, dtype):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(key):
        u = jax.random.normal(key, (1, SEQ, heads, DK))
        return u / jnp.linalg.norm(u, axis=-1, keepdims=True)

    q, key = unit(k[0]) / math.sqrt(DK), unit(k[1])
    v = jax.nn.silu(jax.random.normal(k[2], (1, SEQ, heads, DV)))
    # as the model's initialisation gives them: a rate in (0, 16), a
    # step log-uniform in [1e-3, 1e-1]; beta = 2 sigmoid(normal)
    rate = jax.random.uniform(k[3], (heads,), minval=1e-4, maxval=16.0)
    step = jnp.exp(jax.random.uniform(k[4], (1, SEQ, heads))
                   * math.log(100.0) + math.log(1e-3))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(k[5], (1, SEQ, heads)))
    return (q.astype(dtype), key.astype(dtype), v.astype(dtype),
            -rate * step, beta)


def timed(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)  # compile and warm
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / STEPS


def device_ms(fn, *args, kernel=None):
    """ms a call on the device, from a trace of ``STEPS`` calls
    (``chipbench.trace_reduce``'s reading of the ``XLA Ops`` line): the
    union of every instruction's time or, given ``kernel``, the time in
    the Mosaic calls of that name alone. A kernel in a jit of its own
    stands between copies to and from the layouts the jit's arguments
    and results have (``gdn_rule_bwd`` 5.2 ms with them and 2.3 as the
    step's program runs it; my chip runs, PR 66), so a kernel's own row
    names it."""
    from jax.profiler import ProfileData

    from chipbench import trace_reduce as tr

    jax.block_until_ready(fn(*args))  # compile and warm
    with tempfile.TemporaryDirectory() as where:
        with jax.profiler.trace(where):
            for _ in range(STEPS):
                out = fn(*args)
            jax.block_until_ready(out)
        planes = tr.read_trace(ProfileData.from_file(tr.find_xplane(where)))
    busy = [(start, end) for plane, lines in planes.items()
            if plane.startswith("/device:TPU")
            for text, start, end in lines.get(tr.OPS_LINE, [])
            if kernel is None or kernel in tr.op_name(text)]
    return 1e3 * tr.total(tr.union(busy)) / STEPS


def say(out, **line):
    line["device"] = jax.devices()[0].device_kind
    print(json.dumps(line), flush=True)
    out.write(json.dumps(line) + "\n")
    out.flush()


def accuracy(out, heads):
    """The op (bf16, kernels) against the recurrence token by token at
    ``highest`` on the same bf16-rounded operands, and its gradients
    against the float32 chain as a scan over chunks (which the CPU
    tests hold to the recurrence; the recurrence's own backward would
    keep 8192 states a head)."""
    args = operands(0, heads, jnp.bfloat16)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, SEQ, heads, DV))
    want, want_state = jax.jit(gd.gated_delta_rule_reference)(*args)
    got, state = jax.jit(gd.gated_delta_rule)(*args)

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.abs(a - b).max() / jnp.abs(b).max())

    def loss(fn, cast):
        return lambda *a: (fn(*(t.astype(cast) for t in a[:3]), *a[3:])[0]
                           .astype(jnp.float32) * weight).sum()

    # a float32 product on the chip multiplies in bf16 unless told
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(jax.grad(loss(
            lambda *a: gd.gated_delta_rule(*a, use_kernels=False),
            jnp.float32), argnums=range(5)))(*args)
    ours = jax.jit(jax.grad(loss(gd.gated_delta_rule, jnp.bfloat16),
                            argnums=range(5)))(*args)
    rule, rule_state = jax.jit(gd.gdn_forward)(*args)
    rule_grads = jax.jit(gd.gdn_backward)(*args, weight.astype(jnp.bfloat16))
    names = "q k v g beta".split()
    say(out, what="accuracy", heads=heads,
        forward_rel_err=rel(got, want), state_rel_err=rel(state, want_state),
        grad_rel_err=dict(zip(names, map(rel, ours, plain))),
        rule_forward_rel_err=rel(rule, want),
        rule_state_rel_err=rel(rule_state, want_state),
        rule_to_two_step_rel_err=rel(rule, got),
        rule_grad_rel_err=dict(zip(names, map(rel, rule_grads, plain))))


def sweep(out, heads):
    args = operands(1, heads, jnp.bfloat16)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, SEQ, heads, DV),
                               jnp.bfloat16)
    for chunk in (64, 128):
        prep = jax.jit(lambda *a, c=chunk: _prepared(*a, chunk=c))
        ops = prep(*args)
        say(out, what="prepare", heads=heads, chunk=chunk,
            forward_ms=timed(prep, *args))
        for hb in [d for d in range(1, heads + 1) if heads % d == 0]:
            try:
                fwd = jax.jit(lambda *o, hb=hb: gd._chain(*o, hb, False)[0])
                both = jax.jit(jax.grad(
                    lambda *o, hb=hb: (gd._chain(*o, hb, False)[0]
                                       * _chunked(weight, chunk)).sum()
                    .astype(jnp.float32), argnums=range(6)))
                line = dict(chain_forward_ms=timed(fwd, *ops),
                            chain_forward_backward_ms=timed(both, *ops))
                whole = jax.jit(jax.grad(
                    lambda *a, hb=hb, c=chunk: (gd.gated_delta_rule(
                        *a, chunk=c, heads_per_program=hb)[0]
                        * weight).sum().astype(jnp.float32),
                    argnums=range(5)))
                line["op_forward_backward_ms"] = timed(whole, *args)
            except Exception as e:  # noqa: BLE001 - VMEM, say and go on
                line = {"refused": str(e)[:200]}
            say(out, what="chain", heads=heads, chunk=chunk,
                heads_per_program=hb, **line)


def rule(out, heads):
    """The three kernels alone (the Mosaic call's own time) over the
    heads a program, then the op a layer calls
    (``gated_delta_rule_grouped``: the transposes to a head and chunk
    and back are XLA's) forward and backward, beside the two steps' at
    ``chain_tiles``'s tiles; ms a call on the device."""
    args = operands(1, heads, jnp.bfloat16)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, SEQ, heads, DV),
                               jnp.bfloat16)
    chunk = gd.chain_tiles(SEQ, heads)[0]
    q, k, v, do = (_chunked(t, chunk) for t in (*args[:3], weight))
    g, beta = args[3:]
    h0 = jnp.zeros((1, heads, DK, DV), jnp.float32)
    for hb in [d for d in range(1, heads + 1) if heads % d == 0]:
        def starts(*a, hb=hb):
            return gd._rule_starts(*a, hb, False)

        for what, kernel, fn, operands_ in (
                ("rule_forward", "gdn_rule_fwd",
                 lambda *a, hb=hb: gd._rule_forward(*a, hb, False),
                 (q, k, v, g, beta, h0)),
                ("rule_starts", "gdn_rule_starts", starts, (k, v, g, beta)),
                ("rule_backward", "gdn_rule_bwd",
                 lambda *a, hb=hb, starts=starts: gd._rule_backward(
                     *a, *starts(*a[1:5]), hb, False),
                 (q, k, v, g, beta, do))):
            try:
                line = {"ms": device_ms(jax.jit(fn), *operands_,
                                        kernel=kernel)}
            except Exception as e:  # noqa: BLE001 - VMEM, say and go on
                line = {"refused": str(e)[:200]}
            say(out, what=what, heads=heads, chunk=chunk,
                heads_per_program=hb, **line)

    def both(fn):  # a loss that reads the output: the forward runs
        return jax.jit(jax.grad(
            lambda *a: (fn(*a).astype(jnp.float32) ** 2 * weight).sum(),
            argnums=range(5)))

    say(out, what="op", heads=heads, chunk=chunk,
        rule_forward_ms=device_ms(jax.jit(gd.gated_delta_rule_grouped), *args),
        rule_forward_backward_ms=device_ms(
            both(gd.gated_delta_rule_grouped), *args),
        two_steps_forward_ms=device_ms(
            jax.jit(lambda *a: gd.gated_delta_rule(*a)[0]), *args),
        two_steps_forward_backward_ms=device_ms(
            both(lambda *a: gd.gated_delta_rule(*a)[0]), *args))


def _chunked(t, chunk):  # [B, S, H, d] -> [B, H, N, C, d]
    b, s, h, d = t.shape
    return jnp.moveaxis(t, 2, 1).reshape(b, h, s // chunk, chunk, d)


def _prepared(q, k, v, g, beta, chunk):
    """The chain's operands as ``gated_delta_rule`` hands them over."""
    b, s, h, _ = q.shape
    n = s // chunk
    small = lambda t: jnp.moveaxis(t, 2, 1).reshape(b, h, n, chunk)  # noqa
    qg, kd, w, ubar, p, decay = gd._prepare(
        _chunked(q, chunk), _chunked(k, chunk), _chunked(v, chunk),
        small(g), small(beta))
    row = jnp.broadcast_to(decay[..., None, None], (b, h, n, 1, DV))
    return qg, kd, w, ubar, p, row, jnp.zeros((b, h, DK, DV), jnp.float32)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--heads", default="30")
    p.add_argument("--skip", default="", help="accuracy, rule, sweep")
    args = p.parse_args()
    skip = args.skip.split(",")
    if jax.default_backend() != "tpu":
        raise SystemExit("a time comes only from the chip")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gdn_bench.jsonl", "a") as out:
        for heads in map(int, args.heads.split(",")):
            for part in (accuracy, rule, sweep):
                if part.__name__ not in skip:
                    part(out, heads)


if __name__ == "__main__":
    main()
