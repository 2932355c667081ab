"""Ops: flash attention (Pallas, interpret mode on CPU), ring attention
over a seq mesh axis, MoE routing/dispatch, remat policies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.flash_attention import (
    KEPT_NAMES,
    flash_attention,
    flash_attention_lse,
)
from dlrover_tpu.ops.moe import (
    MoEConfig,
    init_moe_params,
    moe_ffn,
    router_dispatch,
)
from dlrover_tpu.ops.remat import apply_remat
from dlrover_tpu.ops.ring_attention import ring_attention
from dlrover_tpu.parallel.mesh import MeshPlan


def _qkv(b=2, h=2, s=256, d=64, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        jax.random.normal(k, (b, h, s, d), dtype) for k in keys
    )


class TestFlashAttention:
    def test_matches_reference_causal(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_matches_reference_non_causal(self):
        q, k, v = _qkv(s=128)
        out = flash_attention(q, k, v, causal=False)
        ref = mha_reference(q, k, v, causal=False)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gradients_match_reference(self):
        q, k, v = _qkv(b=1, h=1, s=128)
        gf = jax.grad(lambda *a: flash_attention(*a).sum(), argnums=(0, 1, 2))(
            q, k, v
        )
        gr = jax.grad(
            lambda *a: mha_reference(*a, causal=True).sum(), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_indivisible_seq_falls_back_to_fitting_blocks(self):
        q, k, v = _qkv(s=192)  # 192 % 128 != 0: blocks auto-shrink to 96
        out = flash_attention(q, k, v, True, None, 128, 128)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_rejects_tpu_illegal_tiling(self):
        # 1000's best divisor under 512 is 500 (not a multiple of 8):
        # explicit error instead of a Mosaic lowering failure later
        q, k, v = _qkv(b=1, h=1, s=1000, d=64)
        with pytest.raises(ValueError, match="multiple of 8"):
            flash_attention(q, k, v, True, None, 512, 512)

    def test_multi_block_grid_forward_and_grad(self):
        # explicit small blocks force a 4x4 grid so the scratch-carry
        # accumulation, re-init boundaries, and causal block-skip paths
        # in both backward kernels are exercised
        q, k, v = _qkv(b=1, h=2, s=256, d=64)

        def f(*a):
            return flash_attention(*a, True, None, 64, 64).sum()

        def r(*a):
            return mha_reference(*a, causal=True).sum()

        np.testing.assert_allclose(
            flash_attention(q, k, v, True, None, 64, 64),
            mha_reference(q, k, v, causal=True), atol=2e-5, rtol=2e-5,
        )
        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_bf16_inputs(self):
        q, k, v = _qkv(s=128, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v)
        ref = mha_reference(q, k, v)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32),
            atol=3e-2, rtol=3e-2,
        )

    def test_gqa_matches_reference(self):
        # 4 query heads sharing 2 kv heads, no repeat materialized
        q, _, _ = _qkv(b=2, h=4, s=128, d=32)
        _, k, v = _qkv(b=2, h=2, s=128, d=32, seed=1)
        for causal in (True, False):
            out = flash_attention(q, k, v, causal)
            ref = mha_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_asymmetric_bwd_tiles_match_reference(self):
        """block_q_bwd/block_k_bwd tile the backward independently of
        the forward (the long-context VMEM lever): gradients must be
        identical for any legal tiling."""
        q, _, _ = _qkv(b=1, h=4, s=256, d=32)
        _, k, v = _qkv(b=1, h=2, s=256, d=32, seed=3)

        def f(*a):
            return flash_attention(
                *a, True, None, 128, 128, None, 64, 32
            ).sum()

        def r(*a):
            return mha_reference(*a, causal=True).sum()

        # forward unaffected by bwd tiles
        out = flash_attention(q, k, v, True, None, 128, 128, None, 64, 32)
        np.testing.assert_allclose(
            out, mha_reference(q, k, v, causal=True),
            atol=2e-5, rtol=2e-5,
        )
        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_gqa_gradients_match_reference(self):
        # dk/dv must sum over the query-head group (the 5D dKV grid)
        q, _, _ = _qkv(b=1, h=4, s=128, d=32)
        _, k, v = _qkv(b=1, h=2, s=128, d=32, seed=3)

        def f(*a):
            return flash_attention(*a, True, None, 64, 64).sum()

        def r(*a):
            return mha_reference(*a, causal=True).sum()

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        assert gf[1].shape == k.shape and gf[2].shape == v.shape
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_lse_matches_reference_and_is_differentiable(self):
        q, k, v = _qkv(b=1, h=2, s=128, d=32)
        scale = 1.0 / (32 ** 0.5)
        _, lse = flash_attention_lse(q, k, v, True)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        mask = jnp.tril(jnp.ones((128, 128), bool))
        logits = jnp.where(mask, logits, -jnp.inf)
        ref_lse = jax.scipy.special.logsumexp(logits, axis=-1)
        np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)

        # gradient THROUGH the lse output (the ring merge path)
        def f(q, k, v):
            out, lse = flash_attention_lse(q, k, v, True)
            return (out * jnp.exp(lse)[..., None]).sum()

        def r(q, k, v):
            out = mha_reference(q, k, v, causal=True)
            lg = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            lg = jnp.where(mask, lg, -jnp.inf)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            return (out * jnp.exp(lse)[..., None]).sum()

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)

    @pytest.mark.parametrize("op", ["flash_attention", "flash_attention_lse"])
    def test_a_checkpoint_that_keeps_the_names_has_one_forward_kernel(
            self, op):
        """A checkpoint given ``KEPT_NAMES`` holds ``out`` and ``lse`` as
        residuals and its gradient program runs ``flash_fwd`` once; one
        given nothing (every caller but ``models/gqa_moe.py``) holds its
        arguments alone, runs the kernel again in its replay, and gives
        the same bits."""
        from jax._src.ad_checkpoint import saved_residuals

        q, k, v = _qkv(b=1, h=2, s=128, d=32)

        def f(q, k, v):
            if op == "flash_attention":
                return jnp.sin(flash_attention(q, k, v, True)).sum()
            out, lse = flash_attention_lse(q, k, v, True)
            return (jnp.sin(out) * lse[..., None]).sum()

        got = {}
        for keep, forwards, kept in (((), 2, []), (KEPT_NAMES, 1, [
                (1, 2, 128, 32), (1, 2, 128)])):
            g = apply_remat(f, "full", keep=keep)
            assert [value.shape for value, why in saved_residuals(g, q, k, v)
                    if why.startswith(("output of", "named"))] == kept
            grad = jax.grad(g, argnums=(0, 1, 2))
            text = str(jax.make_jaxpr(grad)(q, k, v))
            assert text.count("name=flash_fwd") == forwards
            assert (text.count("name=flash_dkv"),
                    text.count("name=flash_dq")) == (1, 1)
            got[keep] = grad(q, k, v)
        for a, b, c in zip(got[()], got[KEPT_NAMES],
                           jax.grad(f, argnums=(0, 1, 2))(q, k, v)):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)


def _segment_bias(segment_ids):
    """[B, S] -> additive bias [B, 1, S, S] for the reference path."""
    same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
    return jnp.where(same, 0.0, jnp.finfo(jnp.float32).min)


class TestFlashAttentionSegmented:
    """Packed-sequence masking fused into the Pallas tiles."""

    def _packed(self, b=2, s=128):
        q, k, v = _qkv(b=b, s=s)
        # uneven document boundaries per row
        seg = np.zeros((b, s), np.int32)
        seg[0, int(s * 0.3):] = 1
        if b > 1:
            seg[1, int(s * 0.2):int(s * 0.8)] = 1
            seg[1, int(s * 0.8):] = 2
        return q, k, v, jnp.asarray(seg)

    def test_matches_reference_causal(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_segmented

        q, k, v, seg = self._packed()
        out = flash_attention_segmented(q, k, v, seg, causal=True)
        ref = mha_reference(q, k, v, causal=True, bias=_segment_bias(seg))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_matches_reference_non_causal(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_segmented

        q, k, v, seg = self._packed()
        out = flash_attention_segmented(q, k, v, seg, causal=False)
        ref = mha_reference(q, k, v, causal=False, bias=_segment_bias(seg))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_small_blocks_fully_masked_tiles_no_nan(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_segmented

        # block_k 8 with a 32-token leading segment: queries of segment 1
        # visit 4 fully-masked k tiles first — the running-max clamp must
        # keep the accumulator finite
        q, k, v = _qkv(b=1, s=64)
        seg = jnp.asarray(
            np.concatenate([np.zeros(32, np.int32), np.ones(32, np.int32)])
        )[None, :]
        out = flash_attention_segmented(q, k, v, seg, causal=True,
                                        block_q=8, block_k=8)
        assert np.isfinite(np.asarray(out)).all()
        ref = mha_reference(q, k, v, causal=True, bias=_segment_bias(seg))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gradients_match_reference(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_segmented

        q, k, v, seg = self._packed(b=1, s=64)

        def f_flash(q, k, v):
            return flash_attention_segmented(q, k, v, seg).sum()

        def f_ref(q, k, v):
            return mha_reference(
                q, k, v, causal=True, bias=_segment_bias(seg)
            ).sum()

        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_gqa_segmented(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_segmented

        keys = jax.random.split(jax.random.PRNGKey(3), 3)
        b, s, d = 2, 64, 32
        q = jax.random.normal(keys[0], (b, 4, s, d))
        k = jax.random.normal(keys[1], (b, 2, s, d))
        v = jax.random.normal(keys[2], (b, 2, s, d))
        seg = jnp.asarray(np.repeat([[0, 1]], s // 2, axis=1
                                    ).reshape(1, s).repeat(b, 0))
        seg = jnp.sort(seg, axis=1)  # contiguous halves
        out = flash_attention_segmented(q, k, v, seg, causal=True)
        ref = mha_reference(q, k, v, causal=True, bias=_segment_bias(seg))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_packed_equals_separate_documents(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_segmented

        # the semantic contract: packing two docs into one row computes
        # EXACTLY what two padded rows would
        q, k, v = _qkv(b=1, s=128)
        seg = jnp.asarray(
            np.concatenate([np.zeros(48, np.int32),
                            np.ones(80, np.int32)]))[None, :]
        packed = flash_attention_segmented(q, k, v, seg, causal=True)
        doc0 = flash_attention(q[:, :, :48], k[:, :, :48], v[:, :, :48],
                               causal=True)
        doc1 = flash_attention(q[:, :, 48:], k[:, :, 48:], v[:, :, 48:],
                               causal=True)
        np.testing.assert_allclose(packed[:, :, :48], doc0,
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(packed[:, :, 48:], doc1,
                                   atol=2e-5, rtol=2e-5)


class TestFlashAttentionPrefix:
    """Prefix-LM (GLM) masking fused into the Pallas tiles."""

    def _ref(self, q, k, v, prefix):
        s = q.shape[2]
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        allowed = jnp.logical_or(j <= i,
                                 j[None] < prefix[:, None, None])
        bias = jnp.where(allowed, 0.0, jnp.finfo(jnp.float32).min)
        return mha_reference(q, k, v, causal=False, bias=bias[:, None])

    def test_matches_reference(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_prefix

        q, k, v = _qkv(b=2, s=128)
        prefix = jnp.asarray([40, 0])  # one prefix row, one pure-causal
        out = flash_attention_prefix(q, k, v, prefix)
        ref = self._ref(q, k, v, prefix)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_small_blocks_no_nan(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_prefix

        # early q rows visit prefix-needed blocks fully beyond both
        # their diagonal and the prefix — the clamp must hold
        q, k, v = _qkv(b=1, s=64)
        prefix = jnp.asarray([24])
        out = flash_attention_prefix(q, k, v, prefix, block_q=8,
                                     block_k=8)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(out, self._ref(q, k, v, prefix),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_reference(self):
        from dlrover_tpu.ops.flash_attention import flash_attention_prefix

        q, k, v = _qkv(b=1, s=64)
        prefix = jnp.asarray([20])
        gf = jax.grad(
            lambda *a: flash_attention_prefix(*a, prefix).sum(),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(
            lambda *a: self._ref(*a, prefix).sum(), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_glm_flash_matches_bias_path(self):
        from dlrover_tpu.models import glm

        cfg_flash = glm.glm_tiny(use_flash=True, flash_interpret=True)
        cfg_bias = glm.glm_tiny(use_flash=False)
        params = glm.init(jax.random.PRNGKey(0), cfg_flash)
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 32)))
        prefix = jnp.asarray([10, 0])
        out_f = glm.apply(params, ids, cfg_flash, prefix_len=prefix)
        out_b = glm.apply(params, ids, cfg_bias, prefix_len=prefix)
        np.testing.assert_allclose(out_f, out_b, atol=3e-5, rtol=3e-5)


class TestRingAttentionPacked:
    """Packed documents under sequence parallelism: segment ids rotate
    with the KV shards; documents may span ring shards."""

    def _case(self, b=2, s=128):
        q, k, v = _qkv(b=b, s=s, h=2, d=32)
        seg = np.zeros((b, s), np.int32)
        # boundaries deliberately NOT aligned to the 4-way seq shards
        seg[0, int(s * 0.4):] = 1
        if b > 1:
            seg[1, int(s * 0.16):int(s * 0.7)] = 1
            seg[1, int(s * 0.7):] = 2
        return q, k, v, jnp.asarray(seg)

    def test_matches_reference_over_seq_axis(self):
        mesh = MeshPlan(data=2, seq=4).build()
        q, k, v, seg = self._case()
        out = ring_attention(q, k, v, mesh, causal=True, head_axis=None,
                             segment_ids=seg)
        ref = mha_reference(q, k, v, causal=True, bias=_segment_bias(seg))
        np.testing.assert_allclose(
            jax.device_get(out), jax.device_get(ref), atol=2e-5, rtol=2e-5
        )

    def test_non_causal(self):
        mesh = MeshPlan(data=2, seq=4).build()
        q, k, v, seg = self._case()
        out = ring_attention(q, k, v, mesh, causal=False, head_axis=None,
                             segment_ids=seg)
        ref = mha_reference(q, k, v, causal=False,
                            bias=_segment_bias(seg))
        np.testing.assert_allclose(
            jax.device_get(out), jax.device_get(ref), atol=2e-5, rtol=2e-5
        )

    # budget triage (PR 16): packed-ring bwd stays pinned tier-1 by
    # test_pallas_kernel_inside_packed_ring and the model-level
    # packed-segments parities; the standalone grad check rides slow
    @pytest.mark.slow
    def test_differentiable(self):
        mesh = MeshPlan(data=2, seq=4).build()
        q, k, v, seg = self._case(b=2, s=64)

        def f_ring(q, k, v):
            return ring_attention(q, k, v, mesh, causal=True,
                                  head_axis=None,
                                  segment_ids=seg).sum()

        def f_ref(q, k, v):
            return mha_reference(q, k, v, causal=True,
                                 bias=_segment_bias(seg)).sum()

        gr = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(
                jax.device_get(a), jax.device_get(b),
                atol=5e-5, rtol=5e-5)

    def test_gqa_packed_ring_matches_reference(self):
        # GQA (2 kv heads under 4 q heads) composing with segments and
        # the ring: only the kv heads + ids rotate, masking stays exact
        mesh = MeshPlan(data=2, seq=4).build()
        keys = jax.random.split(jax.random.PRNGKey(5), 3)
        b, s, d = 2, 128, 32
        q = jax.random.normal(keys[0], (b, 4, s, d))
        k = jax.random.normal(keys[1], (b, 2, s, d))
        v = jax.random.normal(keys[2], (b, 2, s, d))
        seg = jnp.asarray(np.sort(
            np.random.RandomState(2).randint(0, 3, (b, s)), axis=1))
        out = ring_attention(q, k, v, mesh, causal=True, head_axis=None,
                             segment_ids=seg)
        ref = mha_reference(q, k, v, causal=True, bias=_segment_bias(seg))
        np.testing.assert_allclose(
            jax.device_get(out), jax.device_get(ref), atol=2e-5, rtol=2e-5
        )

    def test_pallas_kernel_inside_packed_ring(self):
        # the TPU path: each ring step runs the segmented PAIR kernel
        # (independent q-side/kv-side ids; interpret mode here)
        mesh = MeshPlan(seq=2).build()
        q, k, v, seg = self._case(b=1, s=128)
        out = ring_attention(q, k, v, mesh, causal=True, head_axis=None,
                             batch_axes=None, impl="pallas_interpret",
                             block_q=64, block_k=64, segment_ids=seg)
        ref = mha_reference(q, k, v, causal=True, bias=_segment_bias(seg))
        np.testing.assert_allclose(
            jax.device_get(out), jax.device_get(ref), atol=2e-5, rtol=2e-5
        )

    def test_llama_seq_parallel_packed_matches_dense(self):
        """The whole model: packed llama under a (data x seq) mesh equals
        the dense packed path."""
        from dlrover_tpu.models import llama

        mesh = MeshPlan(data=2, seq=4).build()
        cfg_ring = llama.llama_tiny(remat_policy="none", seq_axis="seq",
                                    mesh=mesh)
        cfg_dense = llama.llama_tiny(remat_policy="none")
        params = llama.init(jax.random.PRNGKey(0), cfg_ring)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg_ring.vocab_size, (2, 64)))
        seg = jnp.asarray(
            np.sort(rng.randint(0, 3, (2, 64)), axis=1))
        out_ring, _ = llama.apply(params, ids, cfg_ring,
                                  segment_ids=seg)
        out_dense, _ = llama.apply(params, ids, cfg_dense,
                                   segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out_ring),
                                   np.asarray(out_dense),
                                   atol=3e-5, rtol=3e-5)


class TestRingAttention:
    def test_matches_reference_over_seq_axis(self):
        mesh = MeshPlan(data=2, seq=4).build()
        q, k, v = _qkv(b=2, h=2, s=128, d=32)
        out = ring_attention(q, k, v, mesh, causal=True, head_axis=None)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            jax.device_get(out), jax.device_get(ref), atol=2e-5, rtol=2e-5
        )

    def test_non_causal(self):
        mesh = MeshPlan(seq=8).build()
        q, k, v = _qkv(b=1, h=2, s=64, d=32)
        out = ring_attention(q, k, v, mesh, causal=False, head_axis=None,
                             batch_axes=None)
        ref = mha_reference(q, k, v, causal=False)
        np.testing.assert_allclose(
            jax.device_get(out), jax.device_get(ref), atol=2e-5, rtol=2e-5
        )

    # budget triage (PR 16): ring grads stay pinned tier-1 by
    # test_gqa_ring_gradients_match_reference and
    # test_ring_bwd_tiles_reach_the_kernel; this one rides slow
    @pytest.mark.slow
    def test_differentiable(self):
        mesh = MeshPlan(seq=4).build()
        q, k, v = _qkv(b=1, h=1, s=64, d=32)

        def loss(q, k, v):
            return ring_attention(q, k, v, mesh, head_axis=None,
                                  batch_axes=None).sum()

        def ref_loss(q, k, v):
            return mha_reference(q, k, v, causal=True).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(
                jax.device_get(a), jax.device_get(b), atol=5e-5, rtol=5e-5
            )

    def test_gqa_ring_gradients_match_reference(self):
        # the training path: grad flows through the lse merge, the
        # lax.cond skip, the ppermute rotation, and the GQA group map
        mesh = MeshPlan(seq=4).build()
        q, _, _ = _qkv(b=1, h=4, s=128, d=32)
        _, k, v = _qkv(b=1, h=2, s=128, d=32, seed=9)
        w = jax.random.normal(jax.random.PRNGKey(13), (1, 4, 128, 32))

        def loss(q, k, v):
            out = ring_attention(q, k, v, mesh, causal=True,
                                 head_axis=None, batch_axes=None)
            return (out * w).sum()

        def ref_loss(q, k, v):
            return (mha_reference(q, k, v, causal=True) * w).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        assert g[1].shape == k.shape  # kv grads at kv head count
        for a, b in zip(g, gr):
            np.testing.assert_allclose(
                jax.device_get(a), jax.device_get(b), atol=5e-5, rtol=5e-5
            )

    def test_xla_attend_pads_indivisible_kv_len(self):
        from dlrover_tpu.ops.ring_attention import _xla_attend_lse

        # s_k=509 is prime: the fallback must pad, not degrade to bk=1
        q, _, _ = _qkv(b=1, h=2, s=64, d=32)
        _, k, v = _qkv(b=1, h=2, s=509, d=32, seed=15)
        out, lse = _xla_attend_lse(q, k, v, causal=False,
                                   scale=1.0 / (32 ** 0.5), block_k=128)
        ref = mha_reference(q, k, v, causal=False)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa_ring_matches_reference_and_rotates_only_kv_heads(self):
        mesh = MeshPlan(seq=4).build()
        q, _, _ = _qkv(b=1, h=4, s=128, d=32)
        _, k, v = _qkv(b=1, h=2, s=128, d=32, seed=5)
        out = ring_attention(q, k, v, mesh, causal=True, head_axis=None,
                             batch_axes=None)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            jax.device_get(out), jax.device_get(ref), atol=2e-5, rtol=2e-5
        )
        # structural ICI check: every ppermute operand carries the KV
        # head count (2), not the query head count (4) — ring bytes are
        # kv/h of the MHA equivalent
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: ring_attention(
                q, k, v, mesh, causal=True, head_axis=None,
                batch_axes=None,
            )
        )(q, k, v)
        perm_shapes = []

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "ppermute":
                    perm_shapes.extend(x.aval.shape for x in eqn.invars)
                for sub in eqn.params.values():
                    subs = sub if isinstance(sub, (list, tuple)) else [sub]
                    for s in subs:
                        while hasattr(s, "jaxpr"):  # ClosedJaxpr
                            s = s.jaxpr
                        if hasattr(s, "eqns"):
                            walk(s)

        walk(jaxpr.jaxpr)
        assert perm_shapes, "ring must rotate via ppermute"
        for shape in perm_shapes:
            assert shape[1] == 2, f"rotated {shape}, expected kv heads=2"

    def test_indivisible_kv_heads_warns_and_stays_correct(self):
        """Round-2 verdict #9: the kv-repeat fallback must not be a
        silent bandwidth cliff — it logs the repeat factor (the planner
        prices the same factor via ring_kv_repeat) and stays exact."""
        import logging

        from dlrover_tpu.common.log import get_logger

        records = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        handler = _Capture(level=logging.WARNING)
        target = get_logger("ops.ring_attention")
        target.addHandler(handler)
        try:
            mesh = MeshPlan(seq=2, tensor=4).build()
            # 8 query heads, 2 kv heads: 2 % 4 != 0 -> repeat x2
            q, _, _ = _qkv(b=1, h=8, s=64, d=32)
            _, k, v = _qkv(b=1, h=2, s=64, d=32, seed=5)
            out = ring_attention(q, k, v, mesh, causal=True,
                                 head_axis="tensor", batch_axes=None)
        finally:
            target.removeHandler(handler)
        assert any("repeating kv" in m for m in records), records
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            jax.device_get(out), jax.device_get(ref), atol=2e-5, rtol=2e-5
        )
        # the runtime's minimal repeat equals what the planner prices
        from dlrover_tpu.parallel.planner import ring_kv_repeat

        assert ring_kv_repeat(2, 8, 4) == 2

    def test_pallas_kernel_inside_ring(self):
        # the TPU path: each ring step invokes the flash kernel
        # (interpret mode here); parity against the dense reference
        mesh = MeshPlan(seq=2).build()
        q, _, _ = _qkv(b=1, h=2, s=128, d=32)
        _, k, v = _qkv(b=1, h=1, s=128, d=32, seed=7)
        out = ring_attention(q, k, v, mesh, causal=True, head_axis=None,
                             batch_axes=None, impl="pallas_interpret",
                             block_q=64, block_k=64)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            jax.device_get(out), jax.device_get(ref), atol=2e-5, rtol=2e-5
        )

    # budget triage (PR 16): the model-level GLM gate
    # test_prefix_lm_seq_parallel_ring_matches_dense stays tier-1;
    # the op-level decomposition check rides slow
    @pytest.mark.slow
    def test_prefix_lm_ring_matches_dense_reference(self):
        """GLM's prefix-LM mask decomposed over the ring: past shards
        fully visible, diagonal runs the locally-shifted prefix
        kernel, future shards contribute only prompt columns. Prefixes
        deliberately straddle shard boundaries. Both impls, plus
        gradients through the Pallas path."""
        mesh = MeshPlan(seq=4).build()
        q, k, v = _qkv(b=2, h=2, s=128, d=32)
        prefix = jnp.asarray([37, 100], jnp.int32)  # shard size is 32

        i = jnp.arange(128)
        allowed = (i[None, :] <= i[:, None])[None] | (
            i[None, None, :] < prefix[:, None, None])
        bias = jnp.where(allowed, 0.0,
                         jnp.finfo(jnp.float32).min)[:, None]
        ref = mha_reference(q, k, v, causal=False, bias=bias)

        for impl in ("xla", "pallas_interpret"):
            out = ring_attention(
                q, k, v, mesh, causal=True, head_axis=None,
                batch_axes=None, impl=impl, block_q=32, block_k=32,
                prefix_len=prefix,
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5
            )

        def f_ring(q, k, v):
            return ring_attention(
                q, k, v, mesh, causal=True, head_axis=None,
                batch_axes=None, impl="pallas_interpret", block_q=32,
                block_k=32, prefix_len=prefix,
            ).sum()

        def f_ref(q, k, v):
            return mha_reference(q, k, v, causal=False,
                                 bias=bias).sum()

        gr = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gd):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_prefix_ring_rejects_packed_and_noncausal(self):
        from dlrover_tpu.ops.ring_attention import ring_attention_local

        from jax import shard_map

        mesh = MeshPlan(seq=2).build()
        q, k, v = _qkv(b=1, h=2, s=64, d=32)
        prefix = jnp.asarray([10], jnp.int32)
        seg = jnp.zeros((1, 64), jnp.int32)
        with pytest.raises(ValueError, match="mutually exclusive"):
            ring_attention(q, k, v, mesh, causal=True, head_axis=None,
                           batch_axes=None, prefix_len=prefix,
                           segment_ids=seg)
        with pytest.raises(ValueError, match="causal"):
            jax.jit(
                lambda q, k, v: shard_map(
                    lambda ql, kl, vl: ring_attention_local(
                        ql, kl, vl, causal=False, prefix_len=prefix,
                        impl="xla",
                    ),
                    mesh=mesh,
                    in_specs=(jax.sharding.PartitionSpec(
                        None, None, "seq", None),) * 3,
                    out_specs=jax.sharding.PartitionSpec(
                        None, None, "seq", None),
                )(q, k, v)
            )(q, k, v)

    def test_ring_bwd_tiles_reach_the_kernel(self):
        """block_q_bwd/block_k_bwd plumb through the ring (the
        long-context path the knob documents): gradients with
        asymmetric backward tiles equal the XLA-ring gradients."""
        mesh = MeshPlan(seq=2).build()
        q, _, _ = _qkv(b=1, h=2, s=128, d=32)
        _, k, v = _qkv(b=1, h=1, s=128, d=32, seed=7)

        def f(q, k, v):
            return ring_attention(
                q, k, v, mesh, causal=True, head_axis=None,
                batch_axes=None, impl="pallas_interpret",
                block_q=64, block_k=64, block_q_bwd=32, block_k_bwd=32,
            ).sum()

        def r(q, k, v):
            return ring_attention(
                q, k, v, mesh, causal=True, head_axis=None,
                batch_axes=None, impl="xla",
            ).sum()

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


@pytest.mark.slow
class TestRingAttentionLongContext:
    def test_16k_tokens_on_8_device_mesh(self):
        """16k-token causal ring on the 8-device CPU mesh.

        Full dense parity would need a 16k x 16k tile (the very thing
        the ring avoids), so correctness uses the causal prefix
        property: rows < 2048 attend only to keys < 2048, so they must
        equal plain attention on the first shard.
        """
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        mesh = MeshPlan(seq=8).build()
        s, d = 16384, 64
        q, _, _ = _qkv(b=1, h=2, s=s, d=d, dtype=jnp.bfloat16)
        _, k, v = _qkv(b=1, h=1, s=s, d=d, dtype=jnp.bfloat16, seed=11)

        fn = jax.jit(
            lambda q, k, v: ring_attention(
                q, k, v, mesh, causal=True, head_axis=None,
                batch_axes=None,
            )
        )
        out = jax.device_get(fn(q, k, v))
        assert out.shape == (1, 2, s, d)
        assert np.isfinite(out.astype(np.float32)).all()

        prefix = 2048  # = S_local: exactly the first shard
        ref = mha_reference(
            q[:, :, :prefix], k[:, :, :prefix], v[:, :, :prefix],
            causal=True,
        )
        np.testing.assert_allclose(
            out[:, :, :prefix].astype(np.float32),
            jax.device_get(ref).astype(np.float32),
            atol=3e-2, rtol=3e-2,
        )


class TestMoE:
    def test_router_dispatch_respects_capacity(self):
        rng = jax.random.PRNGKey(0)
        logits = jax.random.normal(rng, (16, 4))
        dispatch, combine, aux = router_dispatch(logits, capacity=2)
        # per-expert token counts never exceed capacity
        per_expert = dispatch.sum(axis=(0, 2))
        assert (per_expert <= 2 * 1.0 + 1e-6).all()
        # each slot holds at most one token
        per_slot = dispatch.sum(axis=0)
        assert (per_slot <= 1.0 + 1e-6).all()
        assert float(aux) > 0

    def test_top2_routing(self):
        logits = jax.random.normal(jax.random.PRNGKey(1), (32, 4))
        dispatch, combine, aux = router_dispatch(logits, capacity=16, top_k=2)
        # most tokens dispatched twice at generous capacity
        sends = dispatch.sum(axis=(1, 2))
        assert float(sends.mean()) > 1.5

    def test_moe_ffn_forward_and_grad(self):
        cfg = MoEConfig(num_experts=4, capacity_factor=2.0)
        params = init_moe_params(jax.random.PRNGKey(0), d_model=16, d_ff=32,
                                 num_experts=4)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        out, aux, metrics = moe_ffn(params, x, cfg)
        assert out.shape == x.shape
        assert metrics["expert_load"].shape == (4,)

        def loss(params):
            o, a, _ = moe_ffn(params, x, cfg)
            return (o ** 2).mean() + 0.01 * a

        grads = jax.grad(loss)(params)
        gnorm = jnp.sqrt(sum(
            (g ** 2).sum() for g in jax.tree.leaves(grads)
        ))
        assert float(gnorm) > 0

    @pytest.mark.parametrize("top_k", [1, 2])
    @pytest.mark.parametrize("capacity_factor", [0.5, 1.25])
    def test_gather_matches_einsum_reference(self, top_k, capacity_factor):
        """The fast slot-gather dispatch is numerically the einsum
        oracle — including under capacity overflow (dropped tokens) and
        top-2 round-by-round queue filling."""
        e = 4
        params = init_moe_params(jax.random.PRNGKey(2), d_model=16,
                                 d_ff=32, num_experts=e)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 16))
        outs, auxs, grads = {}, {}, {}
        for dispatch in ("einsum", "gather"):
            cfg = MoEConfig(num_experts=e, capacity_factor=capacity_factor,
                            top_k=top_k, dispatch=dispatch)

            def loss(p):
                o, a, _ = moe_ffn(p, x, cfg)
                return (o ** 2).mean() + 0.01 * a

            outs[dispatch], auxs[dispatch], _ = moe_ffn(params, x, cfg)
            grads[dispatch] = jax.grad(loss)(params)
        np.testing.assert_allclose(outs["gather"], outs["einsum"],
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(auxs["gather"], auxs["einsum"],
                                   atol=1e-6, rtol=1e-6)
        for ga, ge in zip(jax.tree.leaves(grads["gather"]),
                          jax.tree.leaves(grads["einsum"])):
            np.testing.assert_allclose(ga, ge, atol=1e-5, rtol=1e-4)

    def test_skewed_tokens_load_metrics(self):
        """Under a skewed routing distribution, top-2 + tight capacity
        must report the overflow: dropped_frac > 0 and expert_load
        concentrated on the hot expert (switch_gating.py:24-195 parity:
        capacity-overflow accounting surfaced, not silently dropped)."""
        e, t = 4, 64
        params = init_moe_params(jax.random.PRNGKey(4), d_model=16,
                                 d_ff=32, num_experts=e)
        # bias the router so ~all tokens prefer experts 0 then 1
        params["router"]["kernel"] = params["router"]["kernel"] * 0.0 + \
            jnp.array([[8.0, 4.0, 0.0, -4.0]] * 16)
        # positive features: every token's logit ordering follows the
        # biased router columns (a negative feature-sum would flip it)
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (1, t, 16)))
        cfg = MoEConfig(num_experts=e, capacity_factor=1.0, top_k=2)
        out, aux, metrics = moe_ffn(params, x, cfg)
        load = np.asarray(metrics["expert_load"])
        # every token's round-0 pick is expert 0, round-1 pick expert 1
        assert load[0] == pytest.approx(0.5, abs=1e-6)
        assert load[1] == pytest.approx(0.5, abs=1e-6)
        # gshard capacity = t*k*1.0/e = 32 slots/expert; 2*64
        # assignments all want experts 0/1 but only 64 slots exist
        # there -> 50% dropped
        assert float(metrics["dropped_frac"]) == pytest.approx(0.5,
                                                               abs=1e-6)
        # the aux loss sees the imbalance: >> 1 (balanced value is 1.0)
        assert float(aux) > 1.5

    def test_dropped_tokens_get_zero_combine(self):
        # capacity 1 with all tokens preferring expert 0: overflow dropped
        logits = jnp.tile(jnp.array([[10.0, 0.0]]), (8, 1))
        dispatch, combine, _ = router_dispatch(logits, capacity=1)
        assert float(dispatch[:, 0, :].sum()) == 1.0
        assert float(combine.sum(axis=(1, 2))[1:].max()) == 0.0


class TestRemat:
    def test_policies_apply(self):
        def f(x):
            return jnp.sin(x @ x).sum()

        for policy in ["full", "dots_saveable", "nothing_saveable", "none",
                       "dots_and_attn_saveable", "attn_saveable"]:
            g = jax.grad(apply_remat(f, policy))(jnp.eye(8))
            assert g.shape == (8, 8)

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError):
            apply_remat(lambda x: x, "bogus")(jnp.ones(1))

    @pytest.mark.parametrize("policy, keep, sines, saved", [
        # "full" saves nothing but what the function's ops declare
        ("full", (), 2, 0), ("full", ("kept",), 1, 1),
        ("full", ("other",), 2, 0),
        # no checkpoint: nothing is replayed, ``keep`` does nothing
        ("none", (), 1, None), ("none", ("kept",), 1, None),
        # a named policy saves its own and the names
        ("dots_saveable", (), 2, 1), ("dots_saveable", ("kept",), 1, 2),
        ("attn_saveable", ("kept",), 1, 1),
    ])
    def test_keep_saves_the_named_values_beside_the_policys(
            self, policy, keep, sines, saved):
        """``sin`` runs again in the replay unless its named result is
        kept; its input is the product, which ``dots_saveable`` saves
        and "full" recomputes."""
        from jax._src.ad_checkpoint import saved_residuals
        from jax.ad_checkpoint import checkpoint_name

        def f(x):
            return jnp.tanh(checkpoint_name(jnp.sin(x @ x), "kept")).sum()

        x = jnp.eye(8) * 0.5
        g = apply_remat(f, policy, keep=keep)
        assert str(jax.make_jaxpr(jax.grad(g))(x)).count("= sin ") == sines
        np.testing.assert_array_equal(jax.grad(g)(x), jax.grad(f)(x))
        if saved is not None:
            assert sum(why.startswith("output of")
                       for _, why in saved_residuals(g, x)) == saved


class TestGroupedMatmul:
    """ops.grouped_matmul: the dropless-MoE Pallas kernel (interpret
    mode on CPU; Mosaic lowering proven hermetically in test_aot)."""

    def _setup(self, tiles_per, d=16, f=48, bt=8):
        rng = np.random.RandomState(0)
        tp = sum(tiles_per) * bt
        x = jnp.asarray(rng.randn(tp, d), jnp.float32)
        w = jnp.asarray(rng.randn(len(tiles_per), d, f) * 0.1, jnp.float32)
        tile_expert = jnp.asarray(
            sum([[e] * n for e, n in enumerate(tiles_per)], []), jnp.int32
        )
        row_e = np.repeat(np.asarray(tile_expert), bt)
        return x, w, tile_expert, row_e, bt

    def test_forward_matches_per_row_reference(self):
        from dlrover_tpu.ops.grouped_matmul import grouped_matmul

        x, w, te, row_e, bt = self._setup([2, 1, 3])
        y = grouped_matmul(x, w, te, bt, 16)
        ref = np.stack([
            np.asarray(x)[i] @ np.asarray(w)[row_e[i]]
            for i in range(x.shape[0])
        ])
        np.testing.assert_allclose(np.asarray(y), ref, atol=1e-4)

    @pytest.mark.parametrize("vmem_budget", [None, 1200])
    def test_grads_match_reference(self, vmem_budget, monkeypatch):
        from dlrover_tpu.ops import grouped_matmul as gm
        from dlrover_tpu.ops.grouped_matmul import grouped_matmul

        if vmem_budget:
            # a budget this small makes every kernel shrink its tile
            # and the dw kernel tile D as well as F (bd=8 of 16, bf=1):
            # the choice the chip's 16 MiB forces at 11008 -> 4096
            monkeypatch.setattr(gm, "_VMEM_BUDGET_BYTES", vmem_budget)
            assert gm._fit_block(
                16, 16, lambda b: 64 * (b + 1) + 12 * b) == 8
        x, w, te, row_e, bt = self._setup([1, 2, 1])

        def loss(x, w):
            return (grouped_matmul(x, w, te, bt, 16) ** 2).sum()

        def ref_loss(x, w):
            y = jnp.stack([x[i] @ w[int(row_e[i])]
                           for i in range(x.shape[0])])
            return (y ** 2).sum()

        gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
        rgx, rgw = jax.grad(ref_loss, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rgx),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rgw),
                                   rtol=1e-3, atol=1e-3)

    def test_block_f_that_does_not_divide_is_repicked(self):
        from dlrover_tpu.ops.grouped_matmul import grouped_matmul

        # f=48 with block_f=32: picker falls back to a divisor
        x, w, te, row_e, bt = self._setup([1, 1], f=48)
        y = grouped_matmul(x, w, te, bt, 32)
        ref = np.stack([
            np.asarray(x)[i] @ np.asarray(w)[row_e[i]]
            for i in range(x.shape[0])
        ])
        np.testing.assert_allclose(np.asarray(y), ref, atol=1e-4)


class TestMoEGroupedDispatch:
    """The DROPLESS "grouped" dispatch: megablocks-style expert compute
    with no capacity and no dropped tokens."""

    def _params_x(self, d=32, f=64, e=4, b=2, s=64):
        rng = np.random.RandomState(0)
        params = init_moe_params(jax.random.PRNGKey(0), d, f, e)
        x = jnp.asarray(rng.randn(b, s, d), jnp.float32)
        return params, x, e

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_matches_no_drop_einsum_oracle(self, top_k):
        params, x, e = self._params_x()
        # an einsum config with capacity == T serves every token too
        cfg_oracle = MoEConfig(num_experts=e, top_k=top_k,
                               capacity_factor=float(e),
                               eval_capacity_factor=float(e),
                               dispatch="einsum")
        cfg_grouped = MoEConfig(num_experts=e, top_k=top_k,
                                dispatch="grouped")
        out_o, aux_o, _ = moe_ffn(params, x, cfg_oracle, train=False)
        out_g, aux_g, m = moe_ffn(params, x, cfg_grouped, train=False)
        np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_o),
                                   rtol=1e-4, atol=1e-4)
        assert float(aux_g) == pytest.approx(float(aux_o))
        assert float(m["dropped_frac"]) == 0.0

    def test_dropless_under_skew(self):
        """Tokens that overflow a tight capacity are DROPPED by the
        capacity paths but served by the grouped path."""
        params, x, e = self._params_x()
        params["router"]["kernel"] = (
            params["router"]["kernel"].at[:, 0].add(10.0)
        )
        cfg_tight = MoEConfig(num_experts=e, capacity_factor=1.0,
                              dispatch="gather")
        cfg_grouped = MoEConfig(num_experts=e, dispatch="grouped")
        out_t, _, m_t = moe_ffn(params, x, cfg_tight, train=True)
        out_g, _, m_g = moe_ffn(params, x, cfg_grouped, train=True)
        assert float(m_t["dropped_frac"]) > 0.1
        assert float(m_g["dropped_frac"]) == 0.0
        assert not np.allclose(np.asarray(out_t), np.asarray(out_g),
                               atol=1e-5)

    def test_grads_flow_through_router_and_experts(self):
        params, x, e = self._params_x()
        cfg = MoEConfig(num_experts=e, top_k=2, dispatch="grouped")

        def loss(p):
            out, aux, _ = moe_ffn(p, x, cfg, train=False)
            return (out ** 2).sum() + aux

        g = jax.grad(loss)(params)
        for leaf in jax.tree.leaves(g):
            assert np.isfinite(np.asarray(leaf)).all()
        assert float(jnp.abs(g["router"]["kernel"]).sum()) > 0
        assert float(jnp.abs(g["experts"]["up"]["kernel"]).sum()) > 0

    def test_llama_grouped_moe_trains(self):
        """moe_dispatch="grouped" flows through the model config into a
        full train step (dropless expert FFN inside the decoder)."""
        import optax

        from dlrover_tpu.models import llama
        from dlrover_tpu.parallel.accelerate import accelerate

        cfg = llama.llama_tiny(num_experts=4, moe_dispatch="grouped")

        batch = {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size
            ),
            "labels": jax.random.randint(
                jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
            ),
        }
        result = accelerate(
            llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
            optax.adam(1e-2), batch,
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        sharded = result.shard_batch(batch)
        losses = []
        for i in range(3):
            state, metrics = result.train_step(
                state, sharded, jax.random.PRNGKey(i)
            )
            losses.append(float(metrics["loss"]))
            assert float(metrics["moe_dropped_frac"]) == 0.0
        assert losses[-1] < losses[0]

    def test_zero_token_expert_gets_zero_grad(self):
        """An expert with NO routed tokens still owns one (sentinel)
        tile, so its dw block is INITIALIZED to zero by the kernel —
        an unvisited output block would be garbage on real TPU."""
        params, x, e = self._params_x()
        # an all-zero router ties every token's logits; argmax breaks
        # ties to expert 0, so experts 1..e-1 get ZERO tokens
        params["router"]["kernel"] = jnp.zeros_like(
            params["router"]["kernel"]
        )
        cfg = MoEConfig(num_experts=e, dispatch="grouped")

        def loss(p):
            out, aux, _ = moe_ffn(p, x, cfg, train=False)
            return (out ** 2).sum()

        g = jax.grad(loss)(params)
        up = np.asarray(g["experts"]["up"]["kernel"])
        down = np.asarray(g["experts"]["down"]["kernel"])
        assert np.abs(up[0]).sum() > 0  # the busy expert learns
        for i in range(1, e):
            assert np.abs(up[i]).sum() == 0.0, i
            assert np.abs(down[i]).sum() == 0.0, i

    def test_unknown_dispatch_raises(self):
        params, x, e = self._params_x()
        with pytest.raises(ValueError, match="unknown MoE dispatch"):
            moe_ffn(params, x, MoEConfig(num_experts=e,
                                         dispatch="groupd"))


class TestGroupedMatmulContract:
    """The debug-mode tile_expert contract checks: violations are
    SILENT garbage on real TPU (interpret mode zero-fills), so concrete
    calls validate loudly (``grouped_matmul._check_tile_expert``)."""

    def _xw(self, tiles, d=16, f=32, bt=8, e=3):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(len(tiles) * bt, d), jnp.float32)
        w = jnp.asarray(rng.randn(e, d, f) * 0.1, jnp.float32)
        return x, w, jnp.asarray(tiles, jnp.int32), bt

    def test_missing_expert_raises(self):
        from dlrover_tpu.ops.grouped_matmul import grouped_matmul

        x, w, te, bt = self._xw([0, 0, 2])  # expert 1 owns no tile
        with pytest.raises(ValueError, match="absent from"):
            grouped_matmul(x, w, te, bt, 16)

    def test_decreasing_tile_expert_raises(self):
        from dlrover_tpu.ops.grouped_matmul import grouped_matmul

        x, w, te, bt = self._xw([0, 2, 1])  # expert 1 revisited later
        with pytest.raises(ValueError, match="NON-DECREASING"):
            grouped_matmul(x, w, te, bt, 16)

    def test_valid_concrete_call_unaffected(self):
        from dlrover_tpu.ops.grouped_matmul import grouped_matmul

        x, w, te, bt = self._xw([0, 1, 2])
        y = grouped_matmul(x, w, te, bt, 16)
        assert y.shape == (x.shape[0], w.shape[2])

    def test_traced_tile_expert_skips_check(self):
        """The jitted production path (tile_expert is a tracer) must
        stay check-free — the moe dispatchers construct valid maps by
        construction."""
        from dlrover_tpu.ops.grouped_matmul import grouped_matmul

        x, w, te, bt = self._xw([0, 1, 2])

        @jax.jit
        def f(x, w, te):
            return grouped_matmul(x, w, te, bt, 16)

        assert f(x, w, te).shape == (x.shape[0], w.shape[2])


class TestMoEGroupedEP:
    """The DROPLESS expert-parallel "grouped_ep" dispatch: shard_map +
    two all_to_alls around the grouped Pallas kernel, experts sharded
    over an explicit 8-device "expert" submesh (the CPU-mesh rendering
    of the reference's expert process groups, moe_layer.py:87)."""

    E = 8

    def _mesh(self):
        from jax.sharding import Mesh

        devs = jax.devices()
        assert len(devs) >= 8, "conftest forces an 8-device CPU backend"
        return Mesh(np.array(devs[:8]), ("expert",))

    def _params_x(self, d=32, f=64, b=4, s=16, seed=0):
        rng = np.random.RandomState(seed)
        params = init_moe_params(jax.random.PRNGKey(0), d, f, self.E)
        x = jnp.asarray(rng.randn(b, s, d), jnp.float32)
        return params, x

    def _cfgs(self, top_k=1):
        mesh = self._mesh()
        oracle = MoEConfig(num_experts=self.E, top_k=top_k,
                           capacity_factor=float(self.E),
                           eval_capacity_factor=float(self.E),
                           dispatch="einsum")
        ep = MoEConfig(num_experts=self.E, top_k=top_k,
                       dispatch="grouped_ep", ep_axes=("expert",),
                       mesh=mesh)
        return oracle, ep

    # PR 13 triage: the top_k=1 parametrization is a strict subset of
    # the top_k=2 regime (fewer routing paths) and rides slow; the
    # exact-oracle contract stays tier-1 at top_k=2 here and fwd+bwd
    # in test_grads_match_oracle
    @pytest.mark.parametrize("top_k", [
        pytest.param(1, marks=pytest.mark.slow), 2])
    def test_matches_no_drop_einsum_oracle(self, top_k):
        params, x = self._params_x()
        cfg_o, cfg_ep = self._cfgs(top_k)
        out_o, aux_o, _ = moe_ffn(params, x, cfg_o, train=False)
        out_g, aux_g, m = moe_ffn(params, x, cfg_ep, train=False)
        np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_o),
                                   rtol=1e-4, atol=1e-4)
        # pmean'd routing fractions reproduce the GLOBAL aux exactly
        assert float(aux_g) == pytest.approx(float(aux_o), rel=1e-5)
        assert float(m["dropped_frac"]) == 0.0
        assert m["expert_load"].shape == (self.E,)

    # budget triage (PR 16): the grouped_ep bwd stays pinned tier-1 by
    # test_fp8_matches_qdq_oracle_bitwise_fwd_bwd (bitwise fwd+bwd),
    # the fwd einsum oracle [top_k=2], skewed dropless routing and
    # test_llama_grouped_ep_trains; the heaviest bf16 grads-vs-einsum
    # oracle rides the slow tier with its top_k=1 sibling
    @pytest.mark.slow
    def test_grads_match_oracle(self):
        """The custom VJP composes with the all_to_alls: d(params) and
        d(x) equal the einsum oracle's (top_k=2, the stricter case —
        cross-round queue fill rides the exchanged ranks)."""
        params, x = self._params_x()
        cfg_o, cfg_ep = self._cfgs(top_k=2)

        def loss(p, x, cfg):
            o, a, _ = moe_ffn(p, x, cfg, train=False)
            return (o.astype(jnp.float32) ** 2).sum() + a

        g_o = jax.grad(loss, argnums=(0, 1))(params, x, cfg_o)
        g_e = jax.grad(loss, argnums=(0, 1))(params, x, cfg_ep)
        for lo, le in zip(jax.tree.leaves(g_o), jax.tree.leaves(g_e)):
            np.testing.assert_allclose(np.asarray(le), np.asarray(lo),
                                       rtol=1e-3, atol=1e-4)

    def test_skewed_routing_crosses_shards_dropless(self):
        """Every token routed to ONE expert (one shard owns all the
        compute): the all-to-all carries all rows there and back, and
        nothing is dropped — the capacity paths would drop 7/8 of the
        assignments at factor 1."""
        params, x = self._params_x()
        # positive tokens + a large positive bias column force EVERY
        # argmax to expert 3 (a random-sign x would flip the bias term
        # for negative-sum rows)
        x = jnp.abs(x)
        params["router"]["kernel"] = (
            params["router"]["kernel"].at[:, 3].add(50.0)
        )
        cfg_o, cfg_ep = self._cfgs()
        out_o, _, _ = moe_ffn(params, x, cfg_o, train=False)
        out_g, _, m = moe_ffn(params, x, cfg_ep, train=False)
        np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_o),
                                   rtol=1e-4, atol=1e-4)
        assert float(m["dropped_frac"]) == 0.0
        load = np.asarray(m["expert_load"])
        assert load[3] == pytest.approx(1.0)

    def test_zero_recompiles_across_steps(self):
        """Static shapes survive the count exchange: one compile serves
        arbitrary routing patterns (the elasticity/throughput contract —
        a routing-dependent shape would recompile every step). Also
        pins the explicit ``kernel_interpret=True`` CPU-mesh contract
        riding through the shard_map."""
        params, x0 = self._params_x()
        cfg_ep = MoEConfig(num_experts=self.E, top_k=2,
                           dispatch="grouped_ep", ep_axes=("expert",),
                           mesh=self._mesh(), kernel_interpret=True)

        @jax.jit
        def step(p, x):
            o, a, m = moe_ffn(p, x, cfg_ep, train=False)
            return o.sum() + a, m["dropped_frac"]

        rs = np.random.RandomState(7)
        for i in range(4):
            x = jnp.asarray(rs.randn(*x0.shape), jnp.float32)
            if i == 3:  # adversarial: skew all tokens onto one expert
                p = dict(params)
                p["router"]["kernel"] = (
                    params["router"]["kernel"].at[:, 0].add(50.0)
                )
                step(p, x)
            else:
                step(params, x)
        assert step._cache_size() == 1

    def test_missing_axis_raises(self):
        params, x = self._params_x()
        mesh = self._mesh()
        cfg = MoEConfig(num_experts=self.E, dispatch="grouped_ep",
                        ep_axes=("nonexistent",), mesh=mesh)
        with pytest.raises(ValueError, match="lacks expert submesh"):
            moe_ffn(params, x, cfg, train=False)

    def test_indivisible_experts_raise(self):
        d, f = 16, 32
        params = init_moe_params(jax.random.PRNGKey(0), d, f, 6)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 16, d),
                        jnp.float32)
        cfg = MoEConfig(num_experts=6, dispatch="grouped_ep",
                        ep_axes=("expert",), mesh=self._mesh())
        with pytest.raises(ValueError, match="not divisible"):
            moe_ffn(params, x, cfg, train=False)

    def test_no_mesh_degrades_to_per_shard_grouped(self):
        """No usable expert submesh (no mesh context at all): the same
        dropless math runs per shard — the elastic-shrink contract."""
        params, x = self._params_x()
        cfg_ep = MoEConfig(num_experts=self.E, top_k=2,
                           dispatch="grouped_ep")
        cfg_g = MoEConfig(num_experts=self.E, top_k=2,
                          dispatch="grouped")
        out_e, aux_e, m = moe_ffn(params, x, cfg_ep, train=False)
        out_g, aux_g, _ = moe_ffn(params, x, cfg_g, train=False)
        np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_g))
        assert float(aux_e) == pytest.approx(float(aux_g))
        assert float(m["dropped_frac"]) == 0.0

    def test_llama_grouped_ep_trains(self):
        """moe_dispatch="grouped_ep" + rule_set="moe_ep" flow through
        accelerate into a full train step on the (data x fsdp) expert
        submesh: loss falls, droplessness holds, and the ambient-mesh
        resolution (no mesh frozen into the config) keeps it
        elastic-safe."""
        import optax

        from dlrover_tpu.models import llama
        from dlrover_tpu.parallel.accelerate import accelerate
        from dlrover_tpu.parallel.strategy import Strategy

        cfg = llama.llama_tiny(num_experts=8,
                               moe_dispatch="grouped_ep")
        batch = {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size
            ),
            "labels": jax.random.randint(
                jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
            ),
        }
        strategy = Strategy(mesh=MeshPlan(data=2, fsdp=4),
                            rule_set="moe_ep")
        result = accelerate(
            llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
            optax.adam(1e-2), batch, strategy=strategy,
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        sharded = result.shard_batch(batch)
        losses = []
        for i in range(3):
            state, metrics = result.train_step(
                state, sharded, jax.random.PRNGKey(i)
            )
            losses.append(float(metrics["loss"]))
            assert float(metrics["moe_dropped_frac"]) == 0.0
        assert losses[-1] < losses[0]
