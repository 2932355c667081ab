"""Shared loss functions for the model family."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dlrover_tpu.telemetry.names import DeviceScope

IGNORE_INDEX = -100  # HF convention: masked label positions


def masked_lm_loss(logits: jax.Array, labels: jax.Array,
                   z_loss_weight: float = 0.0) -> jax.Array:
    """Causal-LM cross entropy with ``IGNORE_INDEX`` masking and optional
    z-loss regularization on the logsumexp."""
    mask = (labels != IGNORE_INDEX).astype(jnp.float32)
    labels_safe = jnp.where(labels == IGNORE_INDEX, 0, labels)
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logprobs, labels_safe[..., None], axis=-1
    )[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    if z_loss_weight > 0.0:
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        loss = loss + z_loss_weight * ((z ** 2) * mask).sum() / denom
    return loss


@jax.named_scope(DeviceScope.HEAD_LOSS)
def chunked_lm_head_loss(
    hidden: jax.Array,  # [B, S, D] final hidden states (compute dtype)
    kernel: jax.Array,  # [D, V] lm head
    labels: jax.Array,  # [B, S]
    chunk_size: int = 512,
    z_loss_weight: float = 0.0,
) -> jax.Array:
    """Fused lm-head + cross entropy over sequence chunks.

    The full [B, S, V] f32 logits tensor (1 GB at B=4, S=2048, V=32k)
    never materializes: each chunk's logits live only inside its scan
    step, and ``jax.checkpoint`` recomputes them in the backward pass —
    peak extra memory is O(B * chunk * V). All of it, the chunks'
    replay too, is under the scope ``head_loss`` in a device trace.
    """
    b, s, d = hidden.shape
    if s % chunk_size:
        # keep the memory bound: largest divisor of S <= requested,
        # never a silent collapse to the full sequence
        chunk_size = min(chunk_size, s)
        while s % chunk_size:
            chunk_size -= 1
    n_chunks = s // chunk_size
    x_c = hidden.reshape(b, n_chunks, chunk_size, d).transpose(1, 0, 2, 3)
    l_c = labels.reshape(b, n_chunks, chunk_size).transpose(1, 0, 2)
    kernel_c = kernel.astype(hidden.dtype)

    @jax.checkpoint
    def chunk_fn(carry, xc_lc):
        nll_sum, mask_sum, z_sum = carry
        xc, lc = xc_lc
        logits = (xc @ kernel_c).astype(jnp.float32)  # [B, C, V]
        mask = (lc != IGNORE_INDEX).astype(jnp.float32)
        safe = jnp.where(lc == IGNORE_INDEX, 0, lc)
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logprobs, safe[..., None], axis=-1)[..., 0]
        nll_sum = nll_sum + (nll * mask).sum()
        mask_sum = mask_sum + mask.sum()
        if z_loss_weight > 0.0:
            z = jax.scipy.special.logsumexp(logits, axis=-1)
            z_sum = z_sum + ((z ** 2) * mask).sum()
        return (nll_sum, mask_sum, z_sum), None

    (nll_sum, mask_sum, z_sum), _ = jax.lax.scan(
        chunk_fn,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
         jnp.zeros((), jnp.float32)),
        (x_c, l_c),
    )
    denom = jnp.maximum(mask_sum, 1.0)
    loss = nll_sum / denom
    if z_loss_weight > 0.0:
        loss = loss + z_loss_weight * z_sum / denom
    return loss


def lm_head_loss(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                 head_chunk: int = 0) -> jax.Array:
    """The head and the causal-LM cross entropy of the final ``hidden``
    [B, S, D]; ``head`` is [D, V], a tied model's the table's transpose
    (its gradient is then the head's and the gather's, summed by
    autodiff). With ``head_chunk`` the two are fused over sequence
    chunks (``chunked_lm_head_loss``); with 0 the whole float32 logits
    are made."""
    if head_chunk > 0:
        return chunked_lm_head_loss(hidden, head, labels,
                                    chunk_size=head_chunk)
    logits = (hidden @ head.astype(hidden.dtype)).astype(jnp.float32)
    return masked_lm_loss(logits, labels)
