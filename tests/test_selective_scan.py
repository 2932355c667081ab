"""``ops/selective_scan.py``: the Pallas kernels (in the interpreter:
this box has no TPU) against the recurrence token by token."""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.ops.selective_scan import (
    _fit_channels,
    selective_scan,
    selective_scan_reference,
)

ARGS = ("u", "dt", "a", "b", "c", "d")


def inputs(batch, seq, channels, states, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (batch, seq, channels)),
            jax.nn.softplus(jax.random.normal(k[1], (batch, seq, channels))
                            - 2.0),
            -jnp.exp(jax.random.normal(k[2], (channels, states)) * 0.5),
            jax.random.normal(k[3], (batch, seq, states)),
            jax.random.normal(k[4], (batch, seq, states)),
            jax.random.normal(k[5], (channels,)))


# (batch, seq, channels, chunk, block_c): a row of several chunks (the
# state crosses chunk boundaries), a row that is no multiple of the
# chunk (padded steps must leave the state alone), several channel
# blocks (dB and dC are summed over them), channels that 128 does not
# divide (one block of all of them), and a row shorter than a chunk
CASES = {
    "chunks-4": (2, 32, 128, 8, 128),
    "ragged-row": (1, 27, 128, 8, 128),
    "channel-blocks-2": (1, 24, 256, 8, 128),
    "odd-channels": (2, 16, 96, 8, 640),
    "short-row": (1, 5, 128, 16, 128),
}


@pytest.fixture(scope="module", params=list(CASES), ids=list(CASES))
def case(request):
    batch, seq, channels, chunk, block_c = CASES[request.param]
    args = inputs(batch, seq, channels, 16)
    weight = jax.random.normal(jax.random.PRNGKey(9),
                               (batch, seq, channels))

    def kernel(*a):
        return selective_scan(*a, chunk=chunk, block_c=block_c,
                              interpret=True)

    grads = [jax.grad(lambda *a, f=f: (f(*a) * weight).sum(),
                      argnums=range(6))(*args)
             for f in (kernel, selective_scan_reference)]
    return args, kernel, grads


def test_values_match_the_token_by_token_scan(case):
    args, kernel, _ = case
    want = selective_scan_reference(*args)
    got = kernel(*args)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("arg", range(6), ids=ARGS)
def test_gradients_match_the_token_by_token_scan(case, arg):
    _, _, (got, want) = case
    scale = float(jnp.abs(want[arg]).max())
    assert float(jnp.abs(got[arg] - want[arg]).max()) < 1e-4 * scale


def test_state_is_carried_across_a_chunk_boundary():
    """The second half of a row depends on the first through the state
    alone: with the state dropped at the boundary the values differ."""
    args = inputs(1, 16, 128, 16, seed=3)
    whole = selective_scan(*args, chunk=8, block_c=128, interpret=True)
    u, dt, a, b, c, d = args
    alone = selective_scan(u[:, 8:], dt[:, 8:], a, b[:, 8:], c[:, 8:], d,
                           chunk=8, block_c=128, interpret=True)
    assert float(jnp.abs(whole[:, 8:] - alone).max()) > 1e-2
    want = selective_scan_reference(*args)
    assert float(jnp.abs(whole - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


def test_low_precision_inputs_are_computed_in_float32():
    args = inputs(1, 16, 128, 16, seed=4)
    low = tuple(t.astype(jnp.bfloat16) for t in args)
    got = selective_scan(*low, chunk=8, interpret=True)
    want = selective_scan_reference(*low)
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("requested,channels,want", [
    (640, 5120, 640), (1024, 5120, 1024), (700, 5120, 640),
    (64, 5120, 128), (640, 96, 96), (640, 384, 384)])
def test_channel_blocks_are_lane_multiples_that_divide(requested, channels,
                                                       want):
    assert _fit_channels(requested, channels) == want


def test_a_chunk_that_is_no_multiple_of_8_is_refused():
    with pytest.raises(ValueError, match="multiple of 8"):
        selective_scan(*inputs(1, 8, 128, 16), chunk=12, interpret=True)
