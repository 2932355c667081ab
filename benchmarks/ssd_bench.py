"""One Mamba-2 layer's state-space dual alone, on the chip: ``ops.ssd``
at Granite-4.0-H-Micro's shape (one row of 8192 tokens, 64 heads of 64
on one shared 128-wide state, chunks of 256, bf16) against the
recurrence token by token at ``highest``, its gradients against the
float32 chunked form, and its time a call over the heads a program,
which is the sweep behind ``ssd.HEADS_PER_PROGRAM``; beside it what XLA
makes of the same chunked form, and the plain flash kernels at the
attention layers' shape (32 query heads on 8 KV heads of 64, a scale of
1/64) against ``ops.attention_ref``.

Run on the TPU host, from the repo root:
``PYTHONPATH=. python benchmarks/ssd_bench.py``. Prints one JSON line a
measurement and appends them to ``chiprun_out/ssd_bench.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import ssd
from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.flash_attention import flash_attention

SEQ, HEADS, P, N = 8192, 64, 64, 128
STEPS = 10
F32 = jnp.float32


def operands(seed, dtype):
    """As the model's initialisation gives them: ``x``, ``B`` and ``C``
    out of a SiLU, a rate uniform in [1, 16] a head, a step log-uniform
    in [1e-3, 1e-1]."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.nn.silu(jax.random.normal(k[0], (1, SEQ, HEADS, P)))
    b = jax.nn.silu(jax.random.normal(k[1], (1, SEQ, 1, N)))
    c = jax.nn.silu(jax.random.normal(k[2], (1, SEQ, 1, N)))
    dt = jnp.exp(jax.random.uniform(k[3], (1, SEQ, HEADS))
                 * math.log(100.0) + math.log(1e-3))
    a = -jax.random.uniform(k[4], (HEADS,), minval=1.0, maxval=16.0)
    d = jnp.ones((HEADS,), F32)
    return x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d


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
    a, b = a.astype(F32), b.astype(F32)
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def accuracy(out):
    """The op (bf16, kernels) against the recurrence token by token at
    ``highest`` on the same bf16-rounded operands, and its gradients
    against the float32 chunked form (which the CPU tests hold to the
    recurrence; the recurrence's own backward would keep 8192 states a
    head)."""
    args = operands(0, jnp.bfloat16)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, SEQ, HEADS, P))
    want = jax.jit(ssd.ssd_reference)(*args)
    got = jax.jit(ssd.ssd)(*args)

    def loss(fn, cast):
        def run(x, dt, a, b, c, d):
            return (fn(x.astype(cast), dt, a, b.astype(cast),
                       c.astype(cast), d).astype(F32) * weight).sum()
        return run

    # a float32 product on the chip multiplies in bf16 unless told
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(jax.grad(loss(ssd.ssd_chunked, F32),
                                 argnums=range(6)))(*args)
    ours = jax.jit(jax.grad(loss(ssd.ssd, jnp.bfloat16),
                            argnums=range(6)))(*args)
    say(out, what="accuracy", forward_rel_err=rel(got, want),
        grad_rel_err={n: rel(a, b) for n, a, b in zip(
            "x dt A B C D".split(), ours, plain)})


def sweep(out, heads_per_program):
    args = operands(1, jnp.bfloat16)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, SEQ, HEADS, P),
                               jnp.bfloat16)

    def both(fn):
        return jax.jit(jax.grad(
            lambda *a: (fn(*a) * weight).sum().astype(F32),
            argnums=range(6)))

    chosen = ssd.HEADS_PER_PROGRAM
    for hb in heads_per_program:
        ssd.HEADS_PER_PROGRAM = hb  # read where the op is traced
        try:
            # a jit of its own a value: the op is traced under it anew
            line = dict(
                forward_ms=timed(jax.jit(lambda *a: ssd.ssd(*a)), *args),
                forward_backward_ms=timed(both(lambda *a: ssd.ssd(*a)),
                                          *args))
        except Exception as e:  # noqa: BLE001 - VMEM, say and go on
            line = {"refused": str(e)[:200]}
        say(out, what="kernels", heads_per_program=hb, **line)
    ssd.HEADS_PER_PROGRAM = chosen
    say(out, what="xla_chunked",
        forward_ms=timed(jax.jit(ssd.ssd_chunked), *args),
        forward_backward_ms=timed(both(ssd.ssd_chunked), *args))


def attention(out):
    """The plain flash kernels at a head of 64 with four query heads a
    KV head and an explicit scale of 1/64: against the dense reference
    on a row of 2048 (its scores fit there), timed on one of 8192."""
    scale = 1.0 / 64

    def kernels(q, k, v):
        return flash_attention(q, k, v, True, scale)

    def plain(q, k, v):
        return mha_reference(q.astype(F32), k.astype(F32), v.astype(F32),
                             causal=True, scale=scale)

    def both(fn, weight):
        return jax.jit(jax.grad(
            lambda *a: (fn(*a).astype(F32) * weight).sum(),
            argnums=range(3)))

    def drawn(seq):
        k = jax.random.split(jax.random.PRNGKey(2), 4)
        return [jax.random.normal(key, (1, heads, seq, 64), jnp.bfloat16)
                for key, heads in zip(k, (32, 8, 8, 32))]

    *qkv, weight = drawn(2048)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(plain)(*qkv)
        want_grads = both(plain, weight)(*qkv)
    line = dict(
        forward_rel_err=rel(jax.jit(kernels)(*qkv), want),
        grad_rel_err={n: rel(a, b) for n, a, b in zip(
            "q k v".split(), both(kernels, weight)(*qkv), want_grads)})
    *qkv, weight = drawn(SEQ)
    say(out, what="flash_head64_scale", **line,
        forward_ms=timed(jax.jit(kernels), *qkv),
        forward_backward_ms=timed(both(kernels, weight), *qkv))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--heads_per_program", default="8,16,32")
    args = p.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("a time comes only from the chip")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssd_bench.jsonl", "a") as out:
        accuracy(out)
        sweep(out, list(map(int, args.heads_per_program.split(","))))
        attention(out)


if __name__ == "__main__":
    main()
