"""``models/sambay.py``: the layer plan, the sizes, what the cross
decoder shares, the kernel path against the XLA path, and the rule set
on virtual devices."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import sambay
from dlrover_tpu.ops.flash_attention import band_walk, window_tiles
from dlrover_tpu.parallel.accelerate import accelerate
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.sharding_rules import (
    _flatten_with_paths,
    sambay_rules,
)
from dlrover_tpu.parallel.strategy import RULE_SETS, Strategy
from dlrover_tpu.telemetry.names import StepCounter

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)


def batch_of(config, rows=2, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed),
                             (rows, config.max_seq_len + 1), 0,
                             config.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


@pytest.mark.parametrize("depth,counts", [
    (32, (9, 8, 1, 7, 7)), (16, (5, 4, 1, 3, 3)), (12, (4, 3, 1, 2, 2)),
    (8, (3, 2, 1, 1, 1))])
def test_layer_plan(depth, counts):
    plan = sambay.layer_plan(depth)
    assert len(plan) == depth
    assert tuple(plan.count(k) for k in sambay.KINDS) == counts
    half = depth // 2
    # even slots hold state-space layers and memory units, odd slots
    # attention; the boundary pair sits at L/2 and L/2 + 1
    assert set(plan[0:half:2]) == {"ssm"}
    assert set(plan[1:half:2]) == {"attention_window"}
    assert plan[half:half + 2] == ["ssm", "attention_full"]
    assert set(plan[half + 2::2]) == {"gmu"}
    assert set(plan[half + 3::2]) == {"attention_cross"}
    assert sambay.layer_kinds(sambay.SambaYConfig(num_layers=depth)) == dict(
        zip(sambay.KINDS, counts))


@pytest.mark.parametrize("depth", [4, 6, 10, 30])
def test_a_depth_the_plan_cannot_have_is_refused(depth):
    with pytest.raises(ValueError, match="multiple of 4"):
        sambay.layer_plan(depth)


def test_param_count_at_the_published_sizes():
    """3.8B as published: 9 x 119.9M + 9 x 98.3M + 7 x 104.9M + 7 x
    91.8M + a tied table of 512.2M (ISSUE 29's reading)."""
    count = sambay.param_count(sambay.SambaYConfig())
    assert abs(count - 3.85e9) < 0.01 * 3.85e9, count
    assert sambay.param_count(sambay.SambaYConfig(num_layers=12)) \
        == 1_778_306_304


def test_initialisation_gives_a_stable_recurrence():
    c = sambay.sambay_tiny(**F32)
    ssm = jax.jit(sambay.make_init_fn(c))(
        jax.random.PRNGKey(0))["self_layers"]["ssm"]
    assert np.allclose(np.exp(ssm["a_log"][0, 0]), np.arange(1, 17))
    assert (ssm["d_skip"] == 1).all()
    step = jax.nn.softplus(ssm["dt_proj"]["bias"])
    assert float(step.min()) >= c.dt_min * 0.999
    assert float(step.max()) <= c.dt_max * 1.001


@pytest.fixture(scope="module")
def tiny():
    c = sambay.sambay_tiny(num_layers=12, **F32)
    params = jax.jit(sambay.make_init_fn(c))(jax.random.PRNGKey(0))
    return c, params, batch_of(c)


@pytest.fixture(scope="module")
def tiny_grad(tiny):
    c, _, batch = tiny
    return jax.jit(jax.grad(
        lambda p: sambay.make_loss_fn(c)(p, batch, None)[0]))


def test_fused_head_equals_the_plain_head_near_uniform(tiny):
    c, params, batch = tiny
    plain, _ = jax.jit(sambay.make_loss_fn(c))(params, batch, None)
    fused, _ = jax.jit(sambay.make_loss_fn(c, head_chunk=16))(
        params, batch, None)
    assert abs(float(plain) - float(fused)) < 1e-5
    assert abs(float(plain) - np.log(c.vocab_size)) < 0.5


def test_kernel_path_equals_the_xla_path():
    """Selective scan, windowed and full flash (interpreted) against
    the XLA references, loss and every gradient."""
    c = sambay.sambay_tiny(**F32)
    params = jax.jit(sambay.make_init_fn(c))(jax.random.PRNGKey(0))
    batch = batch_of(c, 1)
    kernels = dataclasses.replace(
        c, use_kernels=True, kernel_interpret=True, window_block=8,
        flash_block_q=16, flash_block_k=16)
    want = jax.jit(jax.value_and_grad(
        lambda p: sambay.make_loss_fn(c)(p, batch, None)[0]))(params)
    got = jax.jit(jax.value_and_grad(
        lambda p: sambay.make_loss_fn(kernels)(p, batch, None)[0]))(params)
    assert abs(float(got[0]) - float(want[0])) < 1e-5
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), got[1], want[1])))
    assert worst < 1e-5, worst


@pytest.mark.parametrize("window,block,by_hand", [
    # squares of 8 over 32 tokens: a band of two, both edges
    (8, 8, (7, 0)),
    # a window of the whole row: the causal half, four on the diagonal
    (32, 8, (10, 6)),
    # the forward's tiles of 8 x 16 under a window short of 16
    (8, 16, None),
])
def test_the_loss_counts_the_bands_tiles(window, block, by_hand):
    """``attn_band_tiles`` and ``attn_band_tiles_unmasked`` in the
    aux: what ``band_walk`` says of the forward's tiles, two calls of
    half the heads a row and window layer; XLA's dense attention
    visits none and counts nothing."""
    c = sambay.sambay_tiny(sliding_window=window, window_block=block,
                           use_kernels=True, kernel_interpret=True,
                           flash_block_q=16, flash_block_k=16, **F32)
    params = jax.jit(sambay.make_init_fn(c))(jax.random.PRNGKey(0))
    batch = batch_of(c)
    _, aux = sambay.make_loss_fn(c)(params, batch, None)
    walk = band_walk(c.max_seq_len, window,
                     *window_tiles(c.max_seq_len, window, block)[0])
    calls = 2 * c.num_heads * sambay.layer_kinds(c)["attention_window"]
    assert calls == 2 * 4 * 2
    assert aux == {
        StepCounter.ATTN_BAND_TILES: calls * walk.tiles,
        StepCounter.ATTN_BAND_TILES_UNMASKED: calls * walk.unmasked}
    if by_hand:
        assert (walk.tiles, walk.unmasked) == by_hand
    dense = dataclasses.replace(c, use_kernels=False)
    assert sambay.make_loss_fn(dense)(params, batch, None)[1] == {}


def test_a_window_layer_runs_the_tiles_the_rule_chose():
    """The two window kernels by name in the program, on the grids
    ``band_walk`` gives for ``window_tiles``' answer: the forward at
    8 x 16 (the window does not fill a tile of 16), the one backward
    kernel in squares of 8 (a row of this length is under its budget:
    no dKV and dQ pair); a call holds one head of every query pair."""
    c = sambay.sambay_tiny(window_block=16, use_kernels=True,
                           kernel_interpret=True, flash_block_q=16,
                           flash_block_k=16, **F32)
    params = jax.jit(sambay.make_init_fn(c))(jax.random.PRNGKey(0))
    batch = batch_of(c, rows=1)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: sambay.make_loss_fn(c)(p, batch, None)[0]))(params))
    seq, window = c.max_seq_len, c.sliding_window
    fwd, bwd = window_tiles(seq, window, 16)
    assert (fwd, bwd) == ((8, 16), (8, 8))
    pairs, kv_pairs = c.num_heads // 2, c.num_kv_heads // 2
    forward, backward = band_walk(seq, window, *fwd), band_walk(
        seq, window, *bwd)
    for name, grid in (
            ("flash_win_fwd", (1, pairs, seq // 8, forward.k_steps)),
            ("flash_win_bwd", (1, kv_pairs, pairs // kv_pairs, seq // 8,
                               backward.q_steps))):
        assert f"name={name}" in text, name
        assert f"grid={grid}" in text, (name, grid)
    assert "flash_win_dkv" not in text and "flash_win_dq" not in text


def _silenced(params, keep_period, what):
    """Parameters under which the boundary pair's keys, values and
    memory reach the loss through cross period ``keep_period`` alone
    (None: through nothing): every other reader's output projection is
    zero, the boundary layers' own among them."""
    params = jax.tree.map(lambda a: a, params)
    mixer, proj = {"kv": ("attn", "o_proj"), "m": ("gmu", "out_proj")}[what]
    own = params["boundary"]["attn" if what == "kv" else "ssm"][
        "o_proj" if what == "kv" else "out_proj"]
    own["kernel"] = jnp.zeros_like(own["kernel"])
    cross = params["cross_layers"][mixer][proj]
    keep = jnp.arange(cross["kernel"].shape[0]) == (
        -1 if keep_period is None else keep_period)
    cross["kernel"] = cross["kernel"] * keep[:, None, None]
    return params


@pytest.mark.parametrize("what,leaf", [
    ("kv", ("attn", "k_proj", "kernel")),
    ("kv", ("attn", "v_proj", "kernel")),
    ("m", ("ssm", "x_proj", "kernel"))], ids=["keys", "values", "memory"])
@pytest.mark.parametrize("period", [0, 1, None])
def test_gradients_reach_what_is_shared_from_every_cross_period(
        tiny, tiny_grad, what, leaf, period):
    c, params, _ = tiny
    assert c.cross_periods == 2
    grads = tiny_grad(_silenced(params, period, what))["boundary"]
    for key in leaf:
        grads = grads[key]
    if period is None:
        assert float(jnp.abs(grads).max()) == 0.0
    else:
        assert float(jnp.abs(grads).max()) > 1e-6


def test_rule_set_is_registered_and_names_every_leaf():
    assert RULE_SETS["sambay"] is sambay_rules
    with pytest.raises(ValueError, match="sambay"):
        Strategy(rule_set="no-such-rules").rules()
    shapes = jax.eval_shape(sambay.make_init_fn(
        sambay.SambaYConfig(num_layers=12)), jax.random.PRNGKey(0))
    sizes = {"data": 1, "fsdp": 2, "tensor": 2}
    rules = sambay_rules()
    for path, leaf in _flatten_with_paths(shapes):
        spec = rules.spec_for(path, leaf.shape, sizes)
        stacked = path.split("/")[0] in ("self_layers", "cross_layers")
        if stacked:  # never fsdp (or anything) on the stacked axis
            assert spec[0] is None, (path, spec)
        if leaf.size > 1e6:  # every kernel is split both ways
            assert "fsdp" in spec and "tensor" in spec or path.endswith(
                ("x_proj/kernel", "dt_proj/kernel")), (path, spec)
        if "ssm/" in path and leaf.shape[-1] == 5120 and len(
                leaf.shape) - stacked <= 2 and "in_proj" not in path:
            assert spec[-1] == "tensor", (path, spec)  # the channel axis


def test_trains_sharded_on_virtual_devices():
    """fsdp x tensor on the CPU's virtual devices under the ``sambay``
    rules: the loss falls, and a kernel lands where its rule puts it."""
    c = sambay.sambay_tiny(**F32)
    batch = batch_of(c, rows=4)
    result = accelerate(
        sambay.make_init_fn(c), sambay.make_loss_fn(c, head_chunk=16),
        optax.adam(3e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                          rule_set="sambay"))
    state = result.init_fn(jax.random.PRNGKey(0))
    spec = state.params["self_layers"]["ssm"]["in_proj"][
        "kernel"].sharding.spec
    assert tuple(spec) == (None, "fsdp", None, "tensor")
    sharded = result.shard_batch(batch)
    losses = []
    for i in range(8):
        state, metrics = result.train_step(state, sharded,
                                           jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] * 0.9


def test_init_fn_carries_the_layer_kinds():
    init_fn = sambay.make_init_fn(sambay.sambay_tiny())
    assert init_fn.layer_kinds == {"ssm": 3, "attention_window": 2,
                                   "attention_full": 1, "gmu": 1,
                                   "attention_cross": 1}


def test_the_example_reuses_the_llama_examples_step_lines():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples"))
    import train_llama
    import train_sambay

    assert train_sambay.StepLines is train_llama.StepLines
    assert train_sambay.synthetic_batches is train_llama.synthetic_batches
