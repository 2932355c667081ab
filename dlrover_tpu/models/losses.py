"""Shared loss functions for the model family."""

from __future__ import annotations

import functools

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


def _sequence_chunks(hidden, labels, chunk_size):
    """``hidden`` [B, S, D] and ``labels`` [B, S] cut along the sequence,
    the chunks leading ([n, B, C, D] and [n, B, C]), by
    ``chunked_lm_head_loss``'s rule: the largest divisor of S not over
    ``chunk_size``."""
    b, s, d = hidden.shape
    chunk_size = min(chunk_size, s)
    while s % chunk_size:
        chunk_size -= 1
    n_chunks = s // chunk_size
    return (hidden.reshape(b, n_chunks, chunk_size, d).transpose(1, 0, 2, 3),
            labels.reshape(b, n_chunks, chunk_size).transpose(1, 0, 2))


def _unmasked(labels):
    """What the summed loss terms are divided by: the labels that count,
    1 where none does."""
    return jnp.maximum(
        (labels != IGNORE_INDEX).sum().astype(jnp.float32), 1.0)


def _token_terms(xc, lc, kernel_c):
    """One chunk's float32 log-probabilities [.., C, V], its labels with
    the masked ones at 0, its mask [.., C] and its loss terms a token
    [.., C], the masked ones not yet taken out:
    ``chunked_lm_head_loss``'s chunk body without the z-loss. A
    label's term is picked from the product's own result, which the
    float32 logits widen exactly, and shifted as ``log_softmax`` shifts
    its row (the same value, an operation at a time to the bit): picked
    from the log-probabilities, these would be written out in float32
    a chunk for the one read a token."""
    product = xc @ kernel_c
    logits = product.astype(jnp.float32)
    mask = (lc != IGNORE_INDEX).astype(jnp.float32)
    safe = jnp.where(lc == IGNORE_INDEX, 0, lc)
    top = logits.max(axis=-1, keepdims=True)
    shifted = logits - top
    lse = jnp.log(jnp.exp(shifted).sum(axis=-1, keepdims=True))
    picked = jnp.take_along_axis(product, safe[..., None], axis=-1)
    nll = -((picked.astype(jnp.float32) - top) - lse)[..., 0]
    return shifted - lse, safe, mask, nll


def _chunk_terms(xc, lc, kernel_c):
    """``_token_terms`` with the chunk's loss terms summed over its
    unmasked tokens."""
    logprobs, safe, mask, nll = _token_terms(xc, lc, kernel_c)
    return logprobs, safe, mask, (nll * mask).sum()


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
@jax.named_scope(DeviceScope.HEAD_LOSS)
def one_pass_lm_head_loss(
    hidden: jax.Array,  # [B, S, D] final hidden states (compute dtype)
    kernel: jax.Array,  # [D, V] lm head
    labels: jax.Array,  # [B, S]
    chunk_size: int,
) -> jax.Array:
    """``chunked_lm_head_loss`` (no z-loss) with the gradients made in
    the pass that makes the loss: the loss's cotangent is a scalar, so
    where a gradient is asked the forward rule forms each chunk's ``dx``
    and its share of ``dW`` from the logits it already holds, keeps the
    two ([B, S, D] and [D, V]; nothing of the logits) and the backward
    rule scales them. The head's product runs three times a step where
    the checkpointed scan's runs four, and the float32 softmax once
    where it runs twice; all of it is under the scope ``head_loss``.
    With no gradient asked this is the value alone. Beside
    ``chunked_lm_head_loss``, kept apart by which function a module
    already calls and not by a switch, only until ``models/llama.py``
    may change its program too (``ROADMAP.md`` S2(c), S12); then that
    one goes."""
    x_c, l_c = _sequence_chunks(hidden, labels, chunk_size)
    kernel_c = kernel.astype(hidden.dtype)

    def chunk_fn(nll_sum, xc_lc):
        return nll_sum + _chunk_terms(*xc_lc, kernel_c)[-1], None

    nll_sum, _ = jax.lax.scan(chunk_fn, jnp.zeros((), jnp.float32),
                              (x_c, l_c))
    return nll_sum / _unmasked(labels)


@jax.named_scope(DeviceScope.HEAD_LOSS)
def _one_pass_fwd(hidden, kernel, labels, chunk_size):
    x_c, l_c = _sequence_chunks(hidden, labels, chunk_size)
    kernel_c = kernel.astype(hidden.dtype)
    denom = _unmasked(labels)

    def chunk_fn(carry, xc_lc):
        nll_sum, dw = carry
        xc, lc = xc_lc
        logprobs, safe, mask, nll = _chunk_terms(xc, lc, kernel_c)
        # the logits' cotangent, rounded where the checkpointed scan's
        # backward rounds it (the transpose of ``.astype(float32)``)
        dlogits = ((jnp.exp(logprobs)
                    - jax.nn.one_hot(safe, logprobs.shape[-1],
                                     dtype=jnp.float32))
                   * (mask / denom)[..., None]).astype(xc.dtype)
        # both products read the two from memory, as the checkpointed
        # scan's backward does: left to itself the v5e's compiler makes
        # the softmax again inside each product's input and slices the
        # chunk there (phi4flash, PR 61: 63.7 and 55.2 ms a step where
        # these take 45.5 and 44.8)
        xc, dlogits = jax.lax.optimization_barrier((xc, dlogits))
        # summed chunk by chunk in the head's compute dtype, as the
        # transposed scan sums its constant's cotangent
        dw = dw + jnp.einsum("bcd,bcv->dv", xc, dlogits)
        return (nll_sum + nll, dw), dlogits @ kernel_c.T

    (nll_sum, dw), dx_c = jax.lax.scan(
        chunk_fn, (jnp.zeros((), jnp.float32), jnp.zeros_like(kernel_c)),
        (x_c, l_c))
    dx = dx_c.transpose(1, 0, 2, 3).reshape(hidden.shape)
    return nll_sum / denom, (dx, dw.astype(kernel.dtype))


@jax.named_scope(DeviceScope.HEAD_LOSS)
def _one_pass_bwd(chunk_size, kept, g):
    # rounded at the scale 1 / denom and then multiplied: one ulp of the
    # compute dtype from folding ``g`` in first, and none where g is 1
    return tuple((g * a).astype(a.dtype) for a in kept) + (None,)


one_pass_lm_head_loss.defvjp(_one_pass_fwd, _one_pass_bwd)


def _pass_chunks(hidden, labels, weights, chunk_size):
    """``hidden`` [T, B, S, D], ``labels`` [B, S] and ``weights``
    [T, B, S] as chunks of ``chunk_size`` tokens of one row of one pass,
    pass by pass ([N, C, D], [N, C] and [N, C], N = T x B x S / C).
    Refuses a row that is no whole number of chunks: a chunk would
    straddle two rows, and a smaller one would be chosen in silence."""
    t, b, s, d = hidden.shape
    if weights.shape != (t, b, s) or labels.shape != (b, s):
        raise ValueError(
            f"hidden {hidden.shape} takes weights [{t}, {b}, {s}] and "
            f"labels [{b}, {s}]: got {weights.shape} and {labels.shape}")
    if chunk_size <= 0 or s % chunk_size:
        raise ValueError(f"a row of {s} tokens is no whole number of "
                         f"chunks of {chunk_size}")
    n = t * b * s // chunk_size
    return (hidden.reshape(n, chunk_size, d),
            jnp.broadcast_to(labels, (t, b, s)).reshape(n, chunk_size),
            weights.astype(jnp.float32).reshape(n, chunk_size))


def _pass_sums(by_chunk, passes):
    """[N] sums a chunk -> [T] sums a pass (the chunks lie pass by
    pass)."""
    return by_chunk.reshape(passes, -1).sum(axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
@jax.named_scope(DeviceScope.HEAD_LOSS)
def _weighted_head(hidden, kernel, labels, weights, chunk_size):
    x_c, l_c, w_c = _pass_chunks(hidden, labels, weights, chunk_size)
    kernel_c = kernel.astype(hidden.dtype)

    def chunk_fn(total, xc_lc_wc):
        xc, lc, wc = xc_lc_wc
        _, _, mask, nll = _token_terms(xc, lc, kernel_c)
        nll = nll * mask
        return total + (wc * nll).sum(), nll.sum()

    total, nll_c = jax.lax.scan(chunk_fn, jnp.zeros((), jnp.float32),
                                (x_c, l_c, w_c))
    return total / _unmasked(labels), _pass_sums(nll_c, hidden.shape[0])


@jax.named_scope(DeviceScope.HEAD_LOSS)
def _weighted_head_fwd(hidden, kernel, labels, weights, chunk_size):
    x_c, l_c, w_c = _pass_chunks(hidden, labels, weights, chunk_size)
    kernel_c = kernel.astype(hidden.dtype)
    denom = _unmasked(labels)

    def chunk_fn(carry, xc_lc_wc):
        total, dw = carry
        xc, lc, wc = xc_lc_wc
        logprobs, safe, mask, nll = _token_terms(xc, lc, kernel_c)
        nll = nll * mask
        # the logits' cotangent under the token's weight, rounded where
        # autodiff rounds it (the transpose of ``.astype(float32)``)
        dlogits = ((jnp.exp(logprobs)
                    - jax.nn.one_hot(safe, logprobs.shape[-1],
                                     dtype=jnp.float32))
                   * (mask * wc / denom)[..., None]).astype(xc.dtype)
        # both products read the two from memory
        # (``_one_pass_fwd``: left alone the v5e's compiler makes the
        # softmax again inside each product's input)
        xc, dlogits = jax.lax.optimization_barrier((xc, dlogits))
        # ONE gradient of the kernel, summed over every chunk of every
        # pass in the head's compute dtype
        dw = dw + jnp.einsum("cd,cv->dv", xc, dlogits)
        return ((total + (wc * nll).sum(), dw),
                (dlogits @ kernel_c.T, nll / denom, nll.sum()))

    (total, dw), (dx_c, dweights_c, nll_c) = jax.lax.scan(
        chunk_fn, (jnp.zeros((), jnp.float32), jnp.zeros_like(kernel_c)),
        (x_c, l_c, w_c))
    kept = (dx_c.reshape(hidden.shape), dw.astype(kernel.dtype),
            dweights_c.reshape(weights.shape).astype(weights.dtype))
    return (total / denom, _pass_sums(nll_c, hidden.shape[0])), kept


@jax.named_scope(DeviceScope.HEAD_LOSS)
def _weighted_head_bwd(chunk_size, kept, g):
    g, _ = g  # the sums a pass are counters: ``weighted_lm_head_loss``
    dx, dw, dweights = ((g * a).astype(a.dtype) for a in kept)
    return dx, dw, None, dweights


_weighted_head.defvjp(_weighted_head_fwd, _weighted_head_bwd)


def weighted_lm_head_loss(
    hidden: jax.Array,  # [T, B, S, D] a state a pass (compute dtype)
    kernel: jax.Array,  # [D, V] the one lm head
    labels: jax.Array,  # [B, S], the same for every pass
    weights: jax.Array,  # [T, B, S] float32, carrying gradient
    chunk_size: int,
):
    """The head and the cross entropy of ``T`` states of the same rows
    against ONE kernel, a token's term of pass ``t`` under
    ``weights[t]`` (a model whose stack runs ``T`` times a step and
    exits after any of them, ``models/looped.py``): (``sum_t sum w_t *
    nll_t / unmasked labels``, the ``T`` unweighted sums ``sum nll_t``,
    counters with no gradient). As in ``one_pass_lm_head_loss`` the
    gradients are made in the pass that makes the value, a chunk of one
    row of one pass at a time: each chunk's ``dx`` from its logits'
    cotangent under the token's weight, ONE ``dW`` summed over all
    ``T x B x S / chunk_size`` chunks, and the weights' own cotangent
    ``nll / unmasked`` in float32; the three are kept ([T, B, S, D],
    [D, V], [T, B, S]; nothing of the logits) and the backward rule
    scales them. The product runs three times a pass. A row that is no
    whole number of chunks is refused."""
    loss, sums = _weighted_head(hidden, kernel, labels, weights, chunk_size)
    return loss, jax.lax.stop_gradient(sums)


def lm_head_loss(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                 head_chunk: int = 0) -> jax.Array:
    """The head and the causal-LM cross entropy of the final ``hidden``
    [B, S, D]; ``head`` is [D, V], a tied model's the table's transpose
    (its gradient is then the head's and the gather's, summed by
    autodiff). With ``head_chunk`` the two are fused over sequence
    chunks and the gradients made in the same pass
    (``one_pass_lm_head_loss``); with 0 the whole float32 logits are
    made."""
    if head_chunk > 0:
        return one_pass_lm_head_loss(hidden, head, labels, head_chunk)
    logits = (hidden @ head.astype(hidden.dtype)).astype(jnp.float32)
    return masked_lm_loss(logits, labels)
