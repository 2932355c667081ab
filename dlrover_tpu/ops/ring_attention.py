"""Ring attention: sequence-parallel flash attention over a mesh axis.

Role parity: ``atorch/atorch/modules/distributed_transformer/
distributed_attention.py:21-130`` (DistributedSoftmax + micro-chunk
allgather with compute/comm overlap on two CUDA streams). The TPU-native
formulation inverts the data movement: K/V shards rotate around the "seq"
mesh axis with ``lax.ppermute`` (one ICI hop per step — the natural TPU
torus pattern) while Q stays resident, and per-step outputs are merged
*online* via their logsumexp, so no [S, S] tile and no second pass over
the sequence ever exist. XLA overlaps the ppermute with the block
attention compute, which is the dual-stream overlap of the reference.

Each ring step runs the in-tree Pallas flash kernel
(``ops.flash_attention.flash_attention_lse``) on the visiting K/V shard:
the [Bq, Bk] logits tile exists only in VMEM inside the kernel, and the
kernel returns ``(out, lse)`` which the ring merges exactly:

  lse' = logaddexp(lse, lse_i)
  o'   = o * exp(lse - lse') + o_i * exp(lse_i - lse')

Causality is resolved at *block* granularity, for free: the local shard
attends with the standard causal kernel; a visiting shard is either
entirely in the past (attend with no mask) or entirely in the future
(skip — ``lax.cond`` keeps the carry). GQA rotates only the KV heads
(``[B, H_kv, S_local, D]``), so ring ICI bytes are ``kv/h`` of the MHA
equivalent and the kernel indexes the shared KV head per query group.

Memory per chip: O(S_local * D). Sequence length scales linearly with
the "seq" axis size.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dlrover_tpu.ops.flash_attention import flash_attention_lse
from dlrover_tpu.ops.ring import ring_shift

NEG_INF = float(jnp.finfo(jnp.float32).min)


def ambient_ring_mesh(axis_name: str = "seq"):
    """The ambient mesh (``jax.sharding.set_mesh`` — what ``accelerate``
    establishes while tracing) when it carries a non-trivial
    ``axis_name`` axis that is NOT already manual; else None.

    This is what lets a model config say just ``seq_axis="seq"`` with
    ``mesh=None`` and stay ELASTIC-SAFE: a mesh frozen into the config
    at startup would survive ``on_world_change``'s re-accelerate and
    make the ring shard_map reference departed devices, while the
    ambient mesh is rebuilt with each accelerate. A manual (already
    inside shard_map) seq axis returns None so the caller falls back to
    ``ring_attention_local`` — the body form — instead of illegally
    nesting shard_maps (``shard_compat.ambient_mesh_with_axes``)."""
    from dlrover_tpu.ops.shard_compat import ambient_mesh_with_axes

    return ambient_mesh_with_axes((axis_name,))


def impl_from_flags(use_flash: bool, flash_interpret) -> Optional[str]:
    """Map a model config's flash knobs onto the ring impl selector —
    THE one mapping every family shares: use_flash=False -> blockwise
    XLA; flash_interpret=True -> interpreted Pallas; flash_interpret=
    False -> FORCE Mosaic (the AOT contract: tracing on a CPU host for
    a TPU topology, where a backend sniff would silently pick the XLA
    attend whose autodiff backward stacks O(S^2) probability tiles
    across the ring scan); None -> auto (Mosaic on TPU, the blockwise
    XLA attend elsewhere)."""
    if not use_flash:
        return "xla"
    if flash_interpret:
        return "pallas_interpret"
    if flash_interpret is False:
        return "pallas"
    return None


def _xla_attend_lse(q, k, v, *, causal: bool, scale: float,
                    block_k: int = 512, seg_q=None, seg_k=None,
                    prefix=None):
    """Blockwise-XLA attention returning ``(out_f32, lse_f32)``.

    The non-TPU counterpart of the Pallas kernel: a ``lax.scan`` over
    K/V chunks carrying (acc, m, l), so peak memory is O(S_q * block_k)
    per head — linear in the sequence, like the kernel, which keeps the
    CPU-mesh long-context tests honest. GQA-aware (k/v may carry fewer
    heads). ``prefix`` [B]: keys with column < prefix are visible to
    EVERY query (OR-ed with the causal mask when ``causal`` — the
    prefix-LM rule; with causal=False it is the pure column-bound mask
    the prefix ring uses on wholly-future shards).
    """
    if seg_q is not None and seg_k is None:
        # self-attention shape: one id array serves both sides — never
        # fall through to the dummy carry, which would silently mask
        # every nonzero-segment token against everything
        seg_k = seg_q
    b, h, s_q, d = q.shape
    hkv, s_k = k.shape[1], k.shape[2]
    g = h // hkv
    bk = min(block_k, s_k)
    pad = (-s_k) % bk
    if pad:  # pad K/V with masked keys instead of shrinking the block
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if seg_k is not None:
            # sentinel no real query segment carries
            seg_k = jnp.pad(seg_k, ((0, 0), (0, pad)),
                            constant_values=-2)
    nk = (s_k + pad) // bk

    qf = q.reshape(b, hkv, g, s_q, d).astype(jnp.float32)
    kb = jnp.moveaxis(k.reshape(b, hkv, nk, bk, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, hkv, nk, bk, d), 2, 0)
    sb = (jnp.moveaxis(seg_k.reshape(b, nk, bk), 1, 0)
          if seg_k is not None else jnp.zeros((nk, b, 1), jnp.int32))

    def step(carry, inp):
        acc, m, l = carry
        kj, vj, sj, j = inp
        s = jnp.einsum(
            "bkgqd,bkcd->bkgqc", qf, kj.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * scale
        cols = lax.broadcasted_iota(jnp.int32, s.shape, 4) + j * bk
        if causal or prefix is not None:
            rows = lax.broadcasted_iota(jnp.int32, s.shape, 3)
            allowed = (rows >= cols) if causal else jnp.zeros(
                s.shape, bool)
            if prefix is not None:
                allowed = jnp.logical_or(
                    allowed,
                    cols < prefix[:, None, None, None, None],
                )
            s = jnp.where(allowed, s, NEG_INF)
        if pad:
            s = jnp.where(cols < s_k, s, NEG_INF)
        if seg_q is not None:
            # packed documents: mask cross-segment pairs
            same = (seg_q[:, None, None, :, None]
                    == sj[:, None, None, None, :])
            s = jnp.where(same, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where((s <= NEG_INF / 2)[..., :], 0.0, p)
        alpha = jnp.where(
            m <= NEG_INF / 2, 0.0, jnp.exp(m - m_safe)
        )
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqc,bkcd->bkgqd", p, vj.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, l_new), None

    # derive init from qf so the carry varies over any shard_map manual
    # axes exactly like the step outputs do
    init = (
        qf * 0.0,
        qf[..., 0] * 0.0 + NEG_INF,
        qf[..., 0] * 0.0,
    )
    (acc, m, l), _ = lax.scan(
        step, init, (kb, vb, sb, jnp.arange(nk, dtype=jnp.int32))
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).reshape(b, h, s_q, d)
    lse = jnp.where(
        l == 0.0, NEG_INF, jnp.maximum(m, NEG_INF / 2) + jnp.log(l_safe)
    ).reshape(b, h, s_q)
    return out, lse


def _attend_lse(q, k, v, *, causal, scale, impl, block_q, block_k,
                seg_q=None, seg_k=None, block_q_bwd=0, block_k_bwd=0,
                prefix=None):
    """One (local-q x visiting-kv) shard attention -> (out f32, lse f32).

    ``prefix`` [B] (shard-local): with ``causal`` it is the prefix-LM
    rule (visible iff j <= i OR j < prefix — the ring's DIAGONAL
    shard); with causal=False it is the pure column bound (visible iff
    j < prefix — a wholly-FUTURE shard whose prompt columns are
    bidirectionally visible)."""
    if impl == "xla":
        return _xla_attend_lse(q, k, v, causal=causal, scale=scale,
                               block_k=block_k, seg_q=seg_q,
                               seg_k=seg_k, prefix=prefix)
    # "pallas" must pin interpret=False: under AOT the host backend is
    # CPU and the _resolve sniff would lower the interpreter emulation
    # into a TPU executable
    interp = True if impl == "pallas_interpret" else False
    if prefix is not None:
        if causal:
            from dlrover_tpu.ops.flash_attention import (
                flash_attention_prefix_lse,
            )

            out, lse = flash_attention_prefix_lse(
                q, k, v, prefix, scale, block_q, block_k, interp,
                block_q_bwd, block_k_bwd,
            )
            return out.astype(jnp.float32), lse
        # column-bound-only mask, no new kernel: the pair-segmented
        # kernel with q-side ids all 0 and k-side ids 0 iff visible
        from dlrover_tpu.ops.flash_attention import (
            flash_attention_segmented_pair_lse,
        )

        cols = jnp.arange(k.shape[2], dtype=jnp.int32)
        seg_kp = (cols[None, :] >= prefix[:, None]).astype(jnp.int32)
        seg_q0 = jnp.zeros((q.shape[0], q.shape[2]), jnp.int32)
        out, lse = flash_attention_segmented_pair_lse(
            q, k, v, seg_q0, seg_kp, False, scale, block_q, block_k,
            interp, block_q_bwd, block_k_bwd,
        )
        return out.astype(jnp.float32), lse
    if seg_q is not None:
        # ring steps attend local q against a VISITING kv shard: the two
        # sides carry independent segment arrays
        from dlrover_tpu.ops.flash_attention import (
            flash_attention_segmented_pair_lse,
        )

        out, lse = flash_attention_segmented_pair_lse(
            q, k, v, seg_q, seg_k, causal, scale, block_q, block_k,
            interp, block_q_bwd, block_k_bwd,
        )
        return out.astype(jnp.float32), lse
    out, lse = flash_attention_lse(
        q, k, v, causal, scale, block_q, block_k,
        interp, block_q_bwd, block_k_bwd,
    )
    return out.astype(jnp.float32), lse


def ring_attention_local(
    q: jax.Array,  # local shard [B, H, S_local, D]
    k: jax.Array,  # [B, H_kv, S_local, D]
    v: jax.Array,
    axis_name: str = "seq",
    causal: bool = True,
    scale: Optional[float] = None,
    impl: Optional[str] = None,  # pallas | pallas_interpret | xla
    block_q: int = 512,
    block_k: int = 1024,
    segment_ids: Optional[jax.Array] = None,  # local [B, S_local]
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
    prefix_len: Optional[jax.Array] = None,  # [B] GLOBAL prefix length
) -> jax.Array:
    """The per-device body; call inside shard_map over ``axis_name``.

    Sequence layout is contiguous: device i owns global positions
    [i * S_local, (i+1) * S_local). With ``segment_ids``, packed
    documents may SPAN ring shards: the id arrays rotate with the KV
    shards (negligible ICI bytes next to KV) and every step masks
    cross-segment pairs.

    ``prefix_len`` (GLM's prefix-LM rule — visible iff j <= i OR
    j < prefix) decomposes over the ring exactly: a wholly-PAST
    visiting shard is fully visible (unchanged), the DIAGONAL shard
    runs the prefix kernel with the locally-shifted prefix, and a
    wholly-FUTURE shard contributes only its prompt columns
    (column-bound mask) — so unlike the causal ring, future shards are
    attended, not skipped. Requires ``causal=True`` and no
    ``segment_ids``.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    attend = functools.partial(
        _attend_lse, scale=scale, impl=impl,
        block_q=block_q, block_k=block_k,
        block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
    )
    seg = segment_ids
    merge = _merge_lse

    if prefix_len is not None:
        if not causal or seg is not None:
            raise ValueError(
                "prefix_len needs causal=True and no segment_ids "
                "(prefix-LM is a causal-family mask; packed prefix "
                "rows use the dense segmented path)"
            )
        return _ring_prefix(q, k, v, attend, prefix_len, axis_name,
                            n, my)

    # step 0: the local block — the only one needing an intra-block
    # causal mask, which the flash kernel applies at tile granularity
    o, lse = attend(q, k, v, causal=causal, seg_q=seg, seg_k=seg)

    def attend_merge(o, lse, ck, cv, cs):
        o_i, lse_i = attend(
            q, ck, cv, causal=False, seg_q=seg,
            seg_k=cs if seg is not None else None,
        )
        return merge(o, lse, o_i, lse_i)

    def step(carry, _):
        o, lse, cur_k, cur_v, cur_s, owner = carry
        # rotate kv to the next neighbor (single ICI hop, the shared
        # ops.ring step), then attend; n-1 rotations total — the last
        # visiting shard is not re-sent. Only the H_kv heads travel:
        # GQA pays kv/h of the MHA bytes.
        cur_k = ring_shift(cur_k, axis_name, n)
        cur_v = ring_shift(cur_v, axis_name, n)
        if seg is not None:
            cur_s = ring_shift(cur_s, axis_name, n)
        owner = jnp.asarray((owner - 1) % n, jnp.int32)
        if causal:
            # visiting shard is wholly past (attend, unmasked) or wholly
            # future (skip — keep the carry); never straddles the
            # diagonal because the layout is contiguous
            o, lse = lax.cond(
                owner < my,
                attend_merge,
                lambda o, lse, ck, cv, cs: (o, lse),
                o, lse, cur_k, cur_v, cur_s,
            )
        else:
            o, lse = attend_merge(o, lse, cur_k, cur_v, cur_s)
        return (o, lse, cur_k, cur_v, cur_s, owner), None

    init_seg = seg if seg is not None else jnp.zeros(
        (q.shape[0], 1), jnp.int32)
    (o, lse, _, _, _, _), _ = lax.scan(
        step, (o, lse, k, v, init_seg, jnp.asarray(my, jnp.int32)), None,
        length=n - 1,
    )
    return o.astype(q.dtype)


def _merge_lse(o, lse, o_i, lse_i):
    """The online-softmax merge — the numerical heart of the ring,
    shared by the causal and prefix bodies so their numerics can never
    fork. A fully-masked contribution (lse_i == -inf / NEG_INF) merges
    as an exact no-op."""
    lse_new = jnp.logaddexp(lse, lse_i)
    o_new = (
        o * jnp.exp(lse - lse_new)[..., None]
        + o_i * jnp.exp(lse_i - lse_new)[..., None]
    )
    return o_new, lse_new


def _ring_prefix(q, k, v, attend, prefix_len, axis_name, n, my):
    """The prefix-LM ring body (see ``ring_attention_local``)."""
    s_local = q.shape[2]
    p = prefix_len.astype(jnp.int32)

    # diagonal: causal OR locally-shifted prefix, fused in the kernel
    p_loc = jnp.clip(p - my * s_local, 0, s_local)
    o, lse = attend(q, k, v, causal=True, prefix=p_loc)

    def step(carry, _):
        o, lse, cur_k, cur_v, owner = carry
        cur_k = ring_shift(cur_k, axis_name, n)
        cur_v = ring_shift(cur_v, axis_name, n)
        owner = jnp.asarray((owner - 1) % n, jnp.int32)
        # p_vis: how many of the visiting shard's columns are prompt
        p_vis = jnp.clip(p - owner * s_local, 0, s_local)

        def past(o, lse, ck, cv):
            o_i, lse_i = attend(q, ck, cv, causal=False)
            return _merge_lse(o, lse, o_i, lse_i)

        def future(o, lse, ck, cv):
            # only the prompt columns are visible
            o_i, lse_i = attend(q, ck, cv, causal=False, prefix=p_vis)
            return _merge_lse(o, lse, o_i, lse_i)

        def visible(o, lse, ck, cv):
            return lax.cond(owner < my, past, future, o, lse, ck, cv)

        # a future shard wholly past the prompt (p_vis == 0 for every
        # batch row) contributes nothing — skip the kernel entirely,
        # like the causal ring skips future shards. The typical
        # long-context prefix batch (short prompt, long generation)
        # makes MOST ring steps skippable on most devices.
        o, lse = lax.cond(
            jnp.logical_or(owner < my, jnp.any(p_vis > 0)),
            visible,
            lambda o, lse, ck, cv: (o, lse),
            o, lse, cur_k, cur_v,
        )
        return (o, lse, cur_k, cur_v, owner), None

    (o, lse, _, _, _), _ = lax.scan(
        step, (o, lse, k, v, jnp.asarray(my, jnp.int32)), None,
        length=n - 1,
    )
    return o.astype(q.dtype)


def ring_attention(
    q: jax.Array,  # global [B, H, S, D], S sharded on `axis_name`
    k: jax.Array,  # global [B, H_kv, S, D]
    v: jax.Array,
    mesh,
    axis_name: str = "seq",
    causal: bool = True,
    scale: Optional[float] = None,
    batch_axes=("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    impl: Optional[str] = None,
    block_q: int = 512,
    block_k: int = 1024,
    segment_ids: Optional[jax.Array] = None,  # global [B, S]
    block_q_bwd: int = 0,
    block_k_bwd: int = 0,
    prefix_len: Optional[jax.Array] = None,  # [B] global prefix length
) -> jax.Array:
    """shard_map wrapper: global arrays in, global arrays out.

    Composes with the surrounding GSPMD program: batch stays sharded on the
    data axes, heads on the tensor axis, sequence on the ring axis.
    ``segment_ids`` (packed documents, which may span ring shards) shard
    on (batch, seq) and rotate with the KV shards. ``prefix_len`` [B]
    (GLM prefix-LM) shards on batch only; see ``ring_attention_local``
    for the ring decomposition of the prefix mask.
    """
    if head_axis is not None:
        # GQA kv heads must still divide the head mesh axis; when they
        # don't (e.g. 8 kv heads over tensor=16), repeat minimally so
        # the spec is legal — still cheaper than the full h/kv repeat.
        # axis_sizes, not devices.shape: the mesh may be the ABSTRACT
        # ambient mesh (jax.sharding.get_abstract_mesh), which carries
        # sizes but no concrete device array
        tensor_size = dict(zip(mesh.axis_names, mesh.axis_sizes)).get(
            head_axis, 1
        )
        kv_heads, heads = k.shape[1], q.shape[1]
        if kv_heads % tensor_size:
            from dlrover_tpu.ops.flash_attention import minimal_kv_repeat

            rep = minimal_kv_repeat(kv_heads, heads, tensor_size)
            # No hidden bandwidth cliff (round-2 verdict #9): this costs
            # rep x the ring's ICI bytes, and the planner's seq-comm term
            # prices exactly this factor (planner.ring_kv_repeat).
            from dlrover_tpu.common.log import get_logger

            get_logger("ops.ring_attention").warning(
                "kv_heads=%d does not divide %s=%d: repeating kv x%d — "
                "ring ICI bytes grow %dx (planner prices this; prefer a "
                "tensor size dividing kv_heads)",
                kv_heads, head_axis, tensor_size, rep, rep,
            )
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
    spec = P(batch_axes, head_axis, axis_name, None)
    body = functools.partial(
        ring_attention_local, axis_name=axis_name, causal=causal,
        scale=scale, impl=impl, block_q=block_q, block_k=block_k,
        block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
    )
    if prefix_len is not None:
        if segment_ids is not None:
            raise ValueError(
                "prefix_len and segment_ids are mutually exclusive in "
                "the ring (packed prefix rows use the dense path)"
            )
        pl_spec = P(batch_axes)

        def prefix_body(ql, kl, vl, pl_):
            return body(ql, kl, vl, prefix_len=pl_)

        fn = jax.shard_map(
            prefix_body, mesh=mesh,
            in_specs=(spec, spec, spec, pl_spec), out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v, prefix_len.astype(jnp.int32))
    if segment_ids is not None:
        seg_spec = P(batch_axes, axis_name)

        def seg_body(ql, kl, vl, sl):
            return body(ql, kl, vl, segment_ids=sl)

        fn = jax.shard_map(
            seg_body, mesh=mesh,
            in_specs=(spec, spec, spec, seg_spec), out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v, segment_ids.astype(jnp.int32))
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
