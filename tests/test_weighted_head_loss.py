"""``losses.weighted_lm_head_loss``: the head and the cross entropy of
``T`` states of the same rows against one kernel under per-token
weights that carry gradient, the gradients made in the forward rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.losses import (
    IGNORE_INDEX,
    one_pass_lm_head_loss,
    weighted_lm_head_loss,
)

T, B, S, D, V = 3, 2, 32, 16, 64


def _inputs(dtype=jnp.float32, masked=True):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    hidden = jax.random.normal(k[0], (T, B, S, D), dtype)
    kernel = (jax.random.normal(k[1], (D, V)) / 4).astype(dtype)
    labels = jax.random.randint(k[2], (B, S), 0, V)
    if masked:
        labels = labels.at[0, :5].set(IGNORE_INDEX).at[1, 20:].set(
            IGNORE_INDEX)
    weights = jax.nn.softmax(jax.random.normal(k[3], (T, B, S)), axis=0)
    return hidden, kernel, labels, weights


def _whole(hidden, kernel, labels, weights):
    """The same value from the whole float32 logits, for autodiff."""
    logits = (hidden @ kernel.astype(hidden.dtype)).astype(jnp.float32)
    mask = (labels != IGNORE_INDEX).astype(jnp.float32)
    safe = jnp.where(labels == IGNORE_INDEX, 0, labels)
    nll = -jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1),
        jnp.broadcast_to(safe, weights.shape)[..., None], axis=-1)[..., 0]
    nll = nll * mask
    return ((weights * nll).sum() / jnp.maximum(mask.sum(), 1.0),
            nll.sum(axis=(1, 2)))


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_value_and_every_gradient_against_whole_logits(chunk):
    hidden, kernel, labels, weights = _inputs()
    loss, sums = weighted_lm_head_loss(hidden, kernel, labels, weights,
                                       chunk)
    want, want_sums = _whole(hidden, kernel, labels, weights)
    assert abs(float(loss) - float(want)) < 1e-6
    np.testing.assert_allclose(sums, want_sums, rtol=1e-6)

    # under a cotangent that is not 1, so that the backward rule's
    # scaling shows
    def scaled(f):
        return jax.grad(lambda h, k, w: 3.0 * f(h, k, labels, w)[0],
                        argnums=(0, 1, 2))(hidden, kernel, weights)

    for got, ref in zip(
            scaled(lambda *a: weighted_lm_head_loss(*a, chunk)),
            scaled(_whole)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        assert float(jnp.abs(got - ref).max()) < 1e-6 * max(
            1.0, float(jnp.abs(ref).max()))
    # the weights' cotangent is the token's own loss over the count,
    # and 0 where the label is masked
    dweights = jax.grad(lambda w: weighted_lm_head_loss(
        hidden, kernel, labels, w, chunk)[0])(weights)
    assert float(jnp.abs(dweights[:, 0, :5]).max()) == 0.0
    assert float(dweights[:, 0, 5:].min()) > 0.0


def test_the_sums_a_pass_are_counters_without_a_gradient():
    hidden, kernel, labels, weights = _inputs()
    grads = jax.grad(lambda h, k, w: weighted_lm_head_loss(
        h, k, labels, w, 16)[1].sum(), argnums=(0, 1, 2))(
            hidden, kernel, weights)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)


@pytest.mark.parametrize("dtype, band", [(jnp.float32, 1e-6),
                                         (jnp.bfloat16, 2 ** -6)])
def test_weights_of_one_and_one_pass_are_the_one_pass_head(dtype, band):
    """In float32 to the order of its sums; in bf16 to a few roundings
    of a logit (the one-pass head multiplies a chunk of both rows at
    once, this one a row at a time, and a bf16 product's last bit
    follows the order of its sum)."""
    hidden, kernel, labels, _ = _inputs(dtype)
    ones = jnp.ones((1, B, S), jnp.float32)

    def weighted(h, k):
        return weighted_lm_head_loss(h[None], k, labels, ones, 16)[0]

    def plain(h, k):
        return one_pass_lm_head_loss(h, k, labels, 16)

    got = jax.value_and_grad(weighted, argnums=(0, 1))(hidden[0], kernel)
    want = jax.value_and_grad(plain, argnums=(0, 1))(hidden[0], kernel)
    assert abs(float(got[0]) - float(want[0])) < band * float(want[0])
    for a, b in zip(got[1], want[1]):
        assert a.dtype == b.dtype == dtype
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(a - b).max()) <= band * float(jnp.abs(b).max())


def test_every_label_masked_gives_zero_not_nan():
    hidden, kernel, labels, weights = _inputs()
    none = jnp.full_like(labels, IGNORE_INDEX)
    loss, sums = weighted_lm_head_loss(hidden, kernel, none, weights, 16)
    assert float(loss) == 0.0 and float(jnp.abs(sums).max()) == 0.0
    grads = jax.grad(lambda h: weighted_lm_head_loss(
        h, kernel, none, weights, 16)[0])(hidden)
    assert float(jnp.abs(grads).max()) == 0.0


@pytest.mark.parametrize("chunk", [0, 5, 24, 64])
def test_a_row_that_is_no_whole_number_of_chunks_is_refused(chunk):
    hidden, kernel, labels, weights = _inputs()
    with pytest.raises(ValueError, match="whole number of chunks"):
        weighted_lm_head_loss(hidden, kernel, labels, weights, chunk)


def test_shapes_that_do_not_belong_together_are_refused():
    hidden, kernel, labels, weights = _inputs()
    with pytest.raises(ValueError, match="takes weights"):
        weighted_lm_head_loss(hidden, kernel, labels, weights[:2], 16)
    with pytest.raises(ValueError, match="takes weights"):
        weighted_lm_head_loss(hidden, kernel, labels[:1], weights, 16)


def test_nothing_of_the_logits_is_kept_for_the_backward():
    """What the forward rule keeps: ``dx``, ``dW`` and the weights'
    cotangent, and no array with the vocabulary's width but ``dW``."""
    hidden, kernel, labels, weights = _inputs()
    _, vjp = jax.vjp(lambda h, k, w: weighted_lm_head_loss(
        h, k, labels, w, 8)[0], hidden, kernel, weights)
    kept = sorted(tuple(a.shape) for a in jax.tree.leaves(vjp)
                  if hasattr(a, "shape") and a.ndim)
    assert kept == sorted([(T, B, S, D), (D, V), (T, B, S)])
