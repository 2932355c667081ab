"""Head-to-head: the in-tree Pallas flash attention vs the stock jax
TPU kernel (``jax.experimental.pallas.ops.tpu.flash_attention``).

Substantiates docs/parallelism.md's kernel claim with a measured number
at the bench shapes. Forward+backward (grad wrt q, k, v), causal, bf16.

Run on the TPU host, from the repo root:
``PYTHONPATH=. python benchmarks/flash_bench.py``
Prints one JSON line per shape.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SHAPES = [
    # (batch, heads, kv_heads, seq, head_dim)  — the two bench configs
    (16, 20, 20, 1024, 128),
    (8, 20, 20, 2048, 128),
    (1, 16, 16, 16384, 128),  # long-context preset shape
]
STEPS = 10


def _inputs(b, h, hkv, s, d, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, hkv, s, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, hkv, s, d), jnp.bfloat16)
    return q, k, v


def _time_fwd_bwd(fn, q, k, v):
    def scalar(q, k, v):
        # one program: fwd + bwd, reduced to ONE scalar so the sync
        # (device_get of a value that depends on everything) keeps the
        # transfer out of the measurement
        loss, grads = jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        return loss + sum(
            jnp.sum(jnp.abs(g).astype(jnp.float32)) for g in grads
        )

    step = jax.jit(scalar)
    jax.device_get(step(q, k, v))  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(STEPS):
        out = step(q, k, v)
    jax.device_get(out)  # device queue is FIFO: waits for all steps
    return (time.perf_counter() - t0) / STEPS


def main() -> int:
    from jax.experimental.pallas.ops.tpu import flash_attention as stock

    from dlrover_tpu.ops.flash_attention import flash_attention

    for b, h, hkv, s, d in SHAPES:
        q, k, v = _inputs(b, h, hkv, s, d)
        block_q = min(1024, s)
        ours_t = _time_fwd_bwd(
            lambda q, k, v: flash_attention(
                q, k, v, True, block_q=block_q, block_k=min(1024, s)
            ),
            q, k, v,
        )
        scale = 1.0 / (d ** 0.5)
        # fairness: the stock kernel gets BOTH its library defaults and
        # the same 1024-tile configuration ours runs; best-of wins
        bs = min(1024, s)
        tuned = stock.BlockSizes(
            block_q=bs, block_k_major=bs, block_k=bs, block_b=1,
            block_q_major_dkv=bs, block_k_major_dkv=bs, block_k_dkv=bs,
            block_q_dkv=bs, block_k_major_dq=bs, block_k_dq=bs,
            block_q_dq=bs,
        )
        stock_times = {}
        for name, sizes in (("default", None), ("tuned1024", tuned)):
            try:
                stock_times[name] = _time_fwd_bwd(
                    lambda q, k, v: stock.flash_attention(
                        q, k, v, causal=True, sm_scale=scale,
                        block_sizes=sizes,
                    ),
                    q, k, v,
                )
            except Exception as e:  # noqa: BLE001 — config infeasible
                stock_times[name] = float("inf")
                print(f"# stock {name} failed: {e}"[:160])
        stock_best = min(stock_times, key=stock_times.get)
        stock_t = stock_times[stock_best]
        stock_ok = stock_t != float("inf")
        print(json.dumps({
            "metric": "flash_attention_vs_stock",
            "shape": f"b{b}h{h}s{s}d{d}",
            "ours_ms": round(ours_t * 1e3, 2),
            # null, not Infinity: the line must stay valid JSON even
            # when every stock config fails on this shape
            "stock_ms": round(stock_t * 1e3, 2) if stock_ok else None,
            "stock_best_config": stock_best if stock_ok else None,
            "speedup": round(stock_t / ours_t, 3) if stock_ok else None,
        }))

    if os.environ.get("FLASH_BENCH_MASKS", "1") == "1":
        _mask_variants()
    return 0


def _mask_variants():
    """Fused masking vs materialized bias: the segmented (packed) and
    prefix-LM kernels against the XLA reference with an additive S x S
    bias — the memory/time cost the fused masks exist to remove."""
    from dlrover_tpu.ops.flash_attention import (
        flash_attention_prefix,
        flash_attention_segmented,
        segmented_attention,
    )

    for b, h, hkv, s, d in SHAPES:
        q, k, v = _inputs(b, h, hkv, s, d)
        bq, bk = min(1024, s), min(1024, s)

        # packed: 4 documents per row, uneven boundaries
        seg_np = np.sort(
            np.random.RandomState(1).randint(0, 4, (b, s)), axis=1
        ).astype(np.int32)
        seg = jnp.asarray(seg_np)
        seg_t = _time_fwd_bwd(
            lambda q, k, v: flash_attention_segmented(
                q, k, v, seg, True, block_q=bq, block_k=bk),
            q, k, v,
        )
        try:
            # the PRODUCTION bias dispatch (use_flash=False), not a
            # hand-rolled replica — this is exactly what the fused
            # kernel replaces; everything (incl. the S x S bias its
            # trace materializes) stays inside the try, since that
            # allocation is the thing expected to blow up at long S
            bias_t = _time_fwd_bwd(
                lambda q, k, v: segmented_attention(
                    q, k, v, seg, use_flash=False),
                q, k, v,
            )
        except Exception as e:  # noqa: BLE001 — S x S bias can OOM
            bias_t = None
            print(f"# bias path failed (expected at long S): {e}"[:160])
        print(json.dumps({
            "metric": "segmented_fused_vs_bias",
            "shape": f"b{b}h{h}s{s}d{d}",
            "fused_ms": round(seg_t * 1e3, 2),
            "bias_ms": round(bias_t * 1e3, 2) if bias_t else None,
            "speedup": round(bias_t / seg_t, 3) if bias_t else None,
        }))

        # prefix-LM: prompt = S/4
        prefix = jnp.full((b,), s // 4, jnp.int32)
        pre_t = _time_fwd_bwd(
            lambda q, k, v: flash_attention_prefix(
                q, k, v, prefix, block_q=bq, block_k=bk),
            q, k, v,
        )
        print(json.dumps({
            "metric": "prefix_fused",
            "shape": f"b{b}h{h}s{s}d{d}",
            "fused_ms": round(pre_t * 1e3, 2),
        }))


if __name__ == "__main__":
    sys.exit(main())
