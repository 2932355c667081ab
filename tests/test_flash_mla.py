"""The latent (MLA) flash kernels of ``ops/flash_attention.py`` in the
interpreter against a dense float32 attention, outputs and gradients,
the backward as one kernel and as the two that rows too long for the
one take, and the entry points that were there beside them: the same
calls, names and grids as before."""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.ops import flash_attention as flash
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
# the backward as ``flash_mla_bwd``, and as ``flash_mla_dkv`` and
# ``flash_mla_dq``, the path of rows over the one kernel's VMEM budget
PATHS = ["one-kernel", "two-kernels"]
EVERY = tuple(range(5))


def weighted(f, weight):
    return lambda *a: (f(*a) * weight).sum()


def latent_grads(path, block_q, block_k, args, weight):
    """Gradients by all five operands through the named backward path:
    the choice is made from the shape, so the two kernels are reached at
    these small rows by a budget of nothing."""
    kernel = lambda *a: flash_attention_mla(  # noqa: E731
        *a, SCALE, block_q, block_k, True)
    with pytest.MonkeyPatch.context() as patch:
        if path == "two-kernels":
            patch.setattr(flash, "_MLA_ROW_STATE_BUDGET_BYTES", 0)
        return jax.grad(weighted(kernel, weight), EVERY)(*args)


@pytest.fixture(scope="module", params=[
    pytest.param((path, blocks), id=f"{path}-q{blocks[0]}-k{blocks[1]}")
    for path in PATHS for blocks in BLOCKS])
def latent(request):
    path, (block_q, block_k) = request.param
    *args, weight = operands()
    out = flash_attention_mla(*args, SCALE, block_q, block_k, True)
    return ((out, dense(*args)),
            latent_grads(path, block_q, block_k, args, weight),
            jax.grad(weighted(dense, weight), EVERY)(*args))


def test_latent_forward_matches_dense_attention(latent):
    (got, want), _, _ = latent
    assert got.shape == (BATCH, HEADS, SEQ, VALUE)
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("arg,name", enumerate(
    ["q_nope", "q_rope", "k_nope", "k_rope", "v"]))
def test_latent_backward_matches_dense_attention(latent, arg, name):
    """All five from ``flash_mla_bwd``, or dq_nope and dq_rope from
    ``flash_mla_dq`` and dk_nope, dv and the shared rotary key's
    gradient, summed over every head, from ``flash_mla_dkv``."""
    _, got, want = latent
    assert got[arg].shape == want[arg].shape, name
    assert float(jnp.abs(got[arg] - want[arg]).max()) < 1e-4, name


@pytest.mark.parametrize("blocks", BLOCKS,
                         ids=[f"q{q}-k{k}" for q, k in BLOCKS])
def test_one_kernel_is_the_two_bitwise_at_equal_tiles(blocks):
    """dK and dV sum over the q blocks and dQ over the k blocks in the
    order the two kernels sum them: not one bit differs."""
    *args, weight = operands(seed=1)
    one = latent_grads("one-kernel", *blocks, args, weight)
    two = latent_grads("two-kernels", *blocks, args, weight)
    for name, a, b in zip("dqn dqr dkn dkr dv".split(), one, two):
        assert (a == b).all(), name


@pytest.mark.parametrize("heads", [1, 3])
def test_the_shared_keys_gradient_is_the_sum_over_every_head(heads):
    """An odd number of heads through the one kernel, whose whole-row
    accumulator of the rotary key's gradient lives from a batch row's
    first head to its last; and a single head, first and last at once."""
    *args, weight = operands(seed=2, heads=heads)
    got = latent_grads("one-kernel", 64, 128, args, weight)
    want = jax.grad(weighted(dense, weight), EVERY)(*args)
    assert got[3].shape == (BATCH, 1, SEQ, ROPE)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 1e-4


def _calls_of_the_gradient(batch, heads, seq):
    """The jaxpr of the gradient by all five operands at abstract
    float32 operands: nothing runs, so the rows may be as long as a
    cell's."""
    of = lambda h, d: jax.ShapeDtypeStruct(  # noqa: E731
        (batch, h, seq, d), jnp.float32)
    f = lambda *a: flash_attention_mla(  # noqa: E731
        *a, SCALE, 64, 128, True).astype(jnp.float32).sum()
    return str(jax.make_jaxpr(jax.grad(f, EVERY))(
        of(heads, NOPE), of(heads, ROPE), of(heads, NOPE), of(1, ROPE),
        of(heads, VALUE)))


def test_the_two_latent_calls_their_names_and_grids():
    text = _calls_of_the_gradient(BATCH, HEADS, SEQ)
    for name in ("flash_mla_fwd", "flash_mla_bwd"):
        assert f"name={name}" in text, name
    assert "flash_mla_dkv" not in text and "flash_mla_dq" not in text
    # forward: (batch, head, q block, k block); backward: (batch, head,
    # k block, q block), a head's k blocks swept inside it and the q
    # blocks inside a k block
    assert text.count(f"grid=({BATCH}, {HEADS}, 4, 2)") == 1
    assert text.count(f"grid=({BATCH}, {HEADS}, 2, 4)") == 1


def test_rows_over_the_budget_take_the_two_kernels():
    """Float32 rows of 8192: 36 MiB of whole-row state for the 32 the
    one kernel may hold, so dKV and dQ run, each with a block."""
    text = _calls_of_the_gradient(1, 2, 8192)
    for name in ("flash_mla_fwd", "flash_mla_dkv", "flash_mla_dq"):
        assert f"name={name}" in text, name
    assert "flash_mla_bwd" not in text
    # forward and dQ: (batch, head, q block, k block); dKV: (batch,
    # k block, head, q block), the heads swept inside a k block
    assert text.count("grid=(1, 2, 128, 64)") == 2
    assert text.count("grid=(1, 64, 2, 128)") == 1


@pytest.mark.parametrize("seq,itemsize,one_kernel", [
    (4096, 2, True),  # xing4-1chip.steady's rows
    (8192, 2, True),  # axk1-1chip.steady's
    (16384, 2, False),
    (8192, 4, False),
])
def test_the_backward_is_chosen_from_the_rows_state(seq, itemsize,
                                                    one_kernel):
    """128 + 64 lanes-padded: float32 accumulators of the query's and
    the shared key's gradients and their double-buffered outputs."""
    state = flash._mla_row_state_bytes(seq, 128, 64, itemsize)
    assert state == (4 + 2 * itemsize) * seq * (128 + 2 * 128)
    assert (state <= flash._MLA_ROW_STATE_BUDGET_BYTES) == one_kernel
    assert (2 * flash._MLA_ROW_STATE_BUDGET_BYTES
            <= flash._MLA_VMEM_LIMIT_BYTES)


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
     ["flash_win_fwd", "flash_win_bwd"],
     # the window fills a tile of 64: the forward's squares of 64, the
     # backward's of 32 (``window_tiles``), in one kernel since PR 44
     [(2, 4, 4, 2), (2, 2, 2, 8, 3)]),
], ids=["causal", "window"])
def test_the_entry_points_that_were_there_keep_names_and_grids(
        entry, names, grids):
    """The calls the benchmark's other configurations make: the same
    kernels by name, the plain kernels on the same grids (GQA, two
    query heads a key head, 256 tokens in blocks of 64 and 128), the
    window kernels on the band's tiles."""
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


def test_a_checkpoint_that_keeps_the_names_has_one_forward_kernel():
    """Around ``flash_attention_mla`` a checkpoint given ``KEPT_NAMES``
    holds ``out`` and ``lse4`` as residuals and its gradient program
    runs ``flash_mla_fwd`` once; one given nothing holds its arguments
    alone, runs the kernel again in its replay, and gives the same
    bits."""
    from hlo_checks import kept_names_spare_the_forward

    *args, weight = operands(5)

    def f(*a):
        return (jnp.sin(flash_attention_mla(*a, SCALE, 64, 128, True))
                * weight).sum()

    text = kept_names_spare_the_forward(
        f, args, flash.KEPT_NAMES, "flash_mla_fwd",
        [(BATCH, HEADS, SEQ, VALUE), (BATCH, HEADS, 1, SEQ)])
    assert text.count("name=flash_mla_bwd") == 1
