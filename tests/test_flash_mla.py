"""The latent (MLA) flash kernels of ``ops/flash_attention.py`` in the
interpreter against a dense float32 attention, outputs and gradients,
and the entry points that were there beside them: the same calls,
names and grids as before."""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_mla,
    flash_attention_mla_auto,
    flash_attention_window,
)

BATCH, HEADS, SEQ, NOPE, ROPE, VALUE = 2, 4, 256, 32, 16, 32
SCALE = 0.21


def operands(seed=0, heads=HEADS):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = lambda h, d: (BATCH, h, SEQ, d)  # noqa: E731
    return (jax.random.normal(k[0], shape(heads, NOPE)),
            jax.random.normal(k[1], shape(heads, ROPE)),
            jax.random.normal(k[2], shape(heads, NOPE)),
            jax.random.normal(k[3], shape(1, ROPE)),  # one head for all
            jax.random.normal(k[4], shape(heads, VALUE)),
            jax.random.normal(k[5], shape(heads, VALUE)))  # the weight


def dense(q_nope, q_rope, k_nope, k_rope, v):
    with jax.default_matmul_precision("highest"):
        scores = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope)
                  + jnp.einsum("bhqd,bkd->bhqk", q_rope, k_rope[:, 0]))
        visible = jnp.tril(jnp.ones((SEQ, SEQ), bool))
        probs = jax.nn.softmax(
            jnp.where(visible, scores * SCALE, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# q blocks narrower than k blocks, wider, equal, and the whole row
BLOCKS = [(64, 128), (128, 64), (64, 64), (256, 256)]


@pytest.fixture(scope="module", params=BLOCKS,
                ids=[f"q{q}-k{k}" for q, k in BLOCKS])
def latent(request):
    block_q, block_k = request.param
    *args, weight = operands()
    kernel = lambda *a: flash_attention_mla(  # noqa: E731
        *a, SCALE, block_q, block_k, True)
    loss = lambda f: (lambda *a: (f(*a) * weight).sum())  # noqa: E731
    every = tuple(range(5))
    return ((kernel(*args), dense(*args)),
            jax.grad(loss(kernel), every)(*args),
            jax.grad(loss(dense), every)(*args))


def test_latent_forward_matches_dense_attention(latent):
    (got, want), _, _ = latent
    assert got.shape == (BATCH, HEADS, SEQ, VALUE)
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("arg,name", enumerate(
    ["q_nope", "q_rope", "k_nope", "k_rope", "v"]))
def test_latent_backward_matches_dense_attention(latent, arg, name):
    """dq_nope and dq_rope come from ``flash_mla_dq``; dk_nope, dv and
    the shared rotary key's gradient, summed over every head, from
    ``flash_mla_dkv``."""
    _, got, want = latent
    assert got[arg].shape == want[arg].shape, name
    assert float(jnp.abs(got[arg] - want[arg]).max()) < 1e-4, name


def test_the_three_latent_calls_their_names_and_grids():
    *args, weight = operands()
    f = lambda *a: (flash_attention_mla(  # noqa: E731
        *a, SCALE, 64, 128, True) * weight).sum()
    text = str(jax.make_jaxpr(jax.grad(f, tuple(range(5))))(*args))
    for name in ("flash_mla_fwd", "flash_mla_dkv", "flash_mla_dq"):
        assert f"name={name}" in text, name
    # forward and dQ: (batch, head, q block, k block); dKV: (batch,
    # k block, head, q block), the heads swept inside a k block
    assert text.count(f"grid=({BATCH}, {HEADS}, 4, 2)") == 2
    assert text.count(f"grid=({BATCH}, 2, {HEADS}, 4)") == 1


def test_a_rotary_key_head_a_query_head_is_refused():
    q_nope, q_rope, k_nope, _, v, _ = operands()
    with pytest.raises(ValueError, match="one rotary key head for all"):
        flash_attention_mla(q_nope, q_rope, k_nope, q_rope, v, SCALE,
                            64, 64, True)


def test_default_scale_is_of_the_whole_head():
    *args, _ = operands()
    got = flash_attention_mla(*args, None, 64, 64, True)
    want = flash_attention_mla(*args, (NOPE + ROPE) ** -0.5, 64, 64, True)
    assert (got == want).all()


def test_without_a_mesh_auto_is_the_plain_call():
    *args, _ = operands()
    assert (flash_attention_mla_auto(*args, SCALE, 64, 64, True)
            == flash_attention_mla(*args, SCALE, 64, 64, True)).all()


def test_under_a_mesh_the_heads_shard_and_the_shared_key_does_not():
    """Two head shards on ``tensor``: outputs and gradients as on one
    device, the shared key's gradient summed over the shards."""
    from jax.sharding import Mesh
    import numpy as np

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    *args, weight = operands()
    loss = lambda f: (lambda *a: (f(*a) * weight).sum())  # noqa: E731
    plain = lambda *a: flash_attention_mla(  # noqa: E731
        *a, SCALE, 64, 64, True)
    routed = lambda *a: flash_attention_mla_auto(  # noqa: E731
        *a, SCALE, 64, 64, True)
    want = jax.grad(loss(plain), (0, 3))(*args)
    mesh = Mesh(np.asarray(devices[:2]).reshape(1, 1, 2),
                ("data", "fsdp", "tensor"))
    with jax.sharding.set_mesh(mesh):
        got_out = jax.jit(routed)(*args)
        got = jax.jit(jax.grad(loss(routed), (0, 3)))(*args)
    assert float(jnp.abs(got_out - plain(*args)).max()) < 1e-5
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 1e-4


# -- what was there stays as it was -------------------------------------------


def test_no_rotary_part_is_todays_causal_kernel_bitwise():
    """With a rotary part of zeros the latent kernels compute what
    ``flash_attention`` computes, bitwise, output and gradients: the
    old entry point has not moved against the new."""
    q_nope, q_rope, k_nope, k_rope, v, weight = operands()
    zeros_q, zeros_k = jnp.zeros_like(q_rope), jnp.zeros_like(k_rope)
    old = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, True, SCALE, 64, 128, True)
    new = lambda q, k, v: flash_attention_mla(  # noqa: E731
        q, zeros_q, k, zeros_k, v, SCALE, 64, 128, True)
    assert (old(q_nope, k_nope, v) == new(q_nope, k_nope, v)).all()
    for a, b in zip(
            jax.grad(lambda *x: (old(*x) * weight).sum(), (0, 1, 2))(
                q_nope, k_nope, v),
            jax.grad(lambda *x: (new(*x) * weight).sum(), (0, 1, 2))(
                q_nope, k_nope, v)):
        assert (a == b).all()


@pytest.mark.parametrize("entry,names,grids", [
    (lambda q, k, v: flash_attention(q, k, v, True, None, 64, 128, True),
     ["flash_fwd", "flash_dkv", "flash_dq"],
     [(2, 4, 4, 2), (2, 2, 2, 2, 4), (2, 4, 4, 2)]),
    (lambda q, k, v: flash_attention_window(q, k, v, 64, None, 64, True),
     ["flash_win_fwd", "flash_win_dkv", "flash_win_dq"],
     [(2, 4, 4, 2), (2, 2, 4, 2, 2), (2, 4, 4, 2)]),
], ids=["causal", "window"])
def test_the_entry_points_that_were_there_keep_names_and_grids(
        entry, names, grids):
    """The calls the benchmark's three other configurations make: the
    same kernels by name, the same grids (GQA, two query heads a key
    head, 256 tokens in blocks of 64 and 128)."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (2, 4, SEQ, 32))
    kk = jax.random.normal(k[1], (2, 2, SEQ, 32))
    v = jax.random.normal(k[2], (2, 2, SEQ, 32))
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: entry(*a).sum(), (0, 1, 2)))(q, kk, v))
    assert "flash_mla" not in text
    for name, grid in zip(names, grids):
        assert f"name={name}" in text, name
        assert f"grid={grid}" in text, (name, grid)
