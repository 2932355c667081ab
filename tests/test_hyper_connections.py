"""``ops/hyper_connections.py`` against a loop in NumPy, token by
token, through the flat ``[B, S, n * C]`` form the ops take and return;
the ``hc_enter`` / ``hc_leave`` kernels in the Pallas interpreter
against those functions, value and every gradient, the rule that
picks between them, and each kernel body traced once a process
whatever the call sites;
``hc_mult`` 1 giving the plain-residual program unchanged; a checkpoint
of the ``[B, S, n, C]`` tree restoring into this one; the
new leaves (hyper-connections, the router's selection bias, the
prediction module) under ``mla_moe_rules`` on virtual devices."""

import functools
import importlib.util
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import mla_moe
from dlrover_tpu.ops import hyper_connections as hc
from dlrover_tpu.parallel.accelerate import accelerate
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.sharding_rules import (
    _flatten_with_paths,
    mla_moe_rules,
)
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.telemetry.names import StepCounter

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)


def _loop(x, p, y, iters, clamp, eps):
    """The equations of the module's docstring, one token at a time."""
    b, s, n, c = x.shape
    phi = np.asarray(p["phi"]["kernel"], np.float64)
    scale = np.asarray(p["norm"]["scale"], np.float64)
    alpha = np.asarray(p["alpha"], np.float64)
    bias = np.asarray(p["bias"], np.float64)
    x_in = np.zeros((b, s, c))
    out = np.zeros((b, s, n, c))
    defect = []
    for i in range(b):
        for t in range(s):
            streams = np.asarray(x[i, t], np.float64)
            v = streams.reshape(-1)
            u = v / np.sqrt(np.mean(v * v) + eps) * scale
            z = u @ phi
            pre = 1 / (1 + np.exp(-(alpha[0] * z[:n] + bias[:n])))
            post = 2 / (1 + np.exp(-(alpha[1] * z[n:2 * n] + bias[n:2 * n])))
            m = np.exp(np.clip(alpha[2] * z[2 * n:] + bias[2 * n:],
                               *clamp)).reshape(n, n)
            for _ in range(iters):
                m = m / m.sum(axis=0, keepdims=True)
                m = m / m.sum(axis=1, keepdims=True)
            defect.append(max(np.abs(m.sum(1) - 1).max(),
                              np.abs(m.sum(0) - 1).max()))
            x_in[i, t] = pre @ streams
            out[i, t] = m @ streams + post[:, None] * np.asarray(
                y[i, t], np.float64)[None, :]
    return x_in, out, np.mean(defect)


@pytest.mark.parametrize("iters", [1, 20])
def test_mappings_and_mixes_against_a_loop_in_numpy(iters):
    n, width = 4, 32
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    p = hc.init(key[0], (), n, width, jnp.float32)
    p["norm"]["scale"] = 1 + 0.1 * jax.random.normal(key[3], (n * width,))
    x = jax.random.normal(key[1], (2, 5, n, width))
    y = jax.random.normal(key[2], (2, 5, width))
    clamp = (-3.0, 3.0)  # narrow enough to bind for some entries
    # the ops' form: stream j is [..., j * C:(j + 1) * C], the order of
    # ``norm/scale`` and of ``phi``'s rows
    flat = x.reshape(2, 5, n * width)
    pre, post, res = hc.mappings(flat, p, n, iters, clamp, 1e-6)
    assert pre.shape == post.shape == (n, 2, 5) and res.shape == (n, n, 2, 5)
    want_in, want_out, want_defect = _loop(x, p, y, iters, clamp, 1e-6)
    assert np.allclose(hc.mix_in(flat, pre), want_in, atol=2e-5)
    got_out = hc.mix_out(flat, y, post, res)
    assert got_out.shape == flat.shape
    assert np.allclose(got_out.reshape(x.shape), want_out, atol=2e-5)
    assert float(hc.res_defect(res)) == pytest.approx(want_defect, abs=1e-5)
    if iters == 20:
        assert want_defect < 1e-3
        # doubly stochastic: the streams' sum is carried through
        kept = hc.mix_out(flat, jnp.zeros_like(y), post, res)
        assert np.allclose(hc.leave(kept, n), x.sum(axis=2), atol=1e-3)
    else:
        assert want_defect > 0.02


def test_the_pieces_keep_their_arguments_alone_for_the_backward(capsys):
    """Each piece is a checkpoint of its own: the gradients are those
    of the plain functions, and a backward pass saves no float32 copy
    of bf16 streams."""
    n, width = 4, 128
    key = jax.random.split(jax.random.PRNGKey(1), 3)
    p = hc.init(key[0], (), n, width, jnp.float32)
    x = jax.random.normal(key[1], (1, 8, n * width))
    y = jax.random.normal(key[2], (1, 8, width))

    def loss(x, y, p, fns):
        mappings, mix_in, mix_out = fns
        pre, post, res = mappings(x, p, n, 20, (-30.0, 30.0), 1e-6)
        return (mix_out(x, y + mix_in(x, pre), post, res) ** 2).sum()

    plain = (hc.mappings.__wrapped__.__wrapped__, hc.mix_in.__wrapped__
             .__wrapped__, hc.mix_out.__wrapped__.__wrapped__)
    got = jax.grad(loss, (0, 1, 2))(x, y, p, (hc.mappings, hc.mix_in,
                                              hc.mix_out))
    want = jax.grad(loss, (0, 1, 2))(x, y, p, plain)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.allclose(a, b, rtol=1e-4, atol=1e-5)
    xb = x.astype(jnp.bfloat16)
    from jax.ad_checkpoint import print_saved_residuals

    print_saved_residuals(
        lambda x: loss(x, y.astype(jnp.bfloat16), p,
                       (hc.mappings, hc.mix_in, hc.mix_out)), xb)
    saved = [line.split(" ")[0] for line in
             capsys.readouterr().out.splitlines()]
    assert "bf16[1,8,512]" in saved
    assert not [a for a in saved if a.startswith("f32[1,8,")], saved


def _connected(x, y0, p, iters, kernels):
    """A sublayer ``y = tanh(x_in) + y0`` under the hyper-connection:
    the streams out, weighted, and the defect, as one loss."""
    out, _, defect, took = hc.connect(
        x, p, lambda u: (jnp.tanh(u) + y0, None), 4, iters, (-3.0, 3.0),
        1e-6, kernels, True)
    assert took == int(kernels)
    weights = jnp.linspace(-1.0, 1.0, out.size).reshape(out.shape)
    return (out * weights).sum() + 3.0 * defect, out


@functools.lru_cache(maxsize=None)
def _both_paths(iters):
    """(value, out, gradients by name) of ``_connected`` through the
    ``jax.numpy`` functions and through the kernels, lane-aligned toy
    shape: two rows of 128 tokens, four streams of 128."""
    n, width = 4, 128
    key = jax.random.split(jax.random.PRNGKey(2), 5)
    p = hc.init(key[0], (), n, width, jnp.float32)
    p["norm"]["scale"] = 1 + 0.1 * jax.random.normal(key[3], (n * width,))
    x = jax.random.normal(key[1], (2, 128, n * width))
    y0 = jax.random.normal(key[2], (2, 128, width))
    assert hc.token_tile(x.shape, x.dtype, n) == 128
    found = []
    for kernels in (False, True):
        (value, out), (dx, dy, dp) = jax.value_and_grad(
            _connected, (0, 1, 2), has_aux=True)(x, y0, p, iters, kernels)
        found.append({"forward": jnp.append(out.ravel(), value), "X": dx,
                      "y": dy, "norm/scale": dp["norm"]["scale"],
                      "phi": dp["phi"]["kernel"], "alpha": dp["alpha"],
                      "bias": dp["bias"]})
    return found


@pytest.mark.parametrize("what", ["forward", "X", "y", "norm/scale", "phi",
                                  "alpha", "bias"])
@pytest.mark.parametrize("iters", [1, 20])
def test_the_kernels_against_the_jax_numpy_functions(iters, what):
    """``hc_enter`` and ``hc_leave`` in the interpreter, forward and
    backward, give what ``mappings``, ``mix_in`` and ``mix_out`` and
    their autodiff give, to the NumPy loop's tolerance (of the
    largest entry: the gradients are sums over 256 tokens)."""
    plain, kernels = (found[what] for found in _both_paths(iters))
    assert plain.shape == kernels.shape and float(jnp.abs(plain).max()) > 0
    assert np.allclose(kernels, plain, rtol=0, atol=2e-5 * max(
        1.0, float(jnp.abs(plain).max())))


def test_the_kernels_keep_their_arguments_alone_for_the_backward(capsys):
    """On the kernel path too a backward pass keeps the bf16 streams
    as they came and nothing of their size in float32: beside the
    arguments, a token's 24 projections and 20 mappings."""
    n, width = 4, 256
    key = jax.random.split(jax.random.PRNGKey(1), 3)
    p = hc.init(key[0], (), n, width, jnp.float32)
    x = jax.random.normal(key[1], (1, 128, n * width)).astype(jnp.bfloat16)
    y0 = jax.random.normal(key[2], (1, 128, width)).astype(jnp.bfloat16)
    assert hc.token_tile(x.shape, x.dtype, n) == 128
    from jax.ad_checkpoint import print_saved_residuals

    print_saved_residuals(lambda x: hc.connect(
        x, p, lambda u: (jnp.tanh(u) + y0, None), n, 20, (-30.0, 30.0),
        1e-6, True, True)[0].sum(), x)
    saved = [line.split(" ")[0] for line in
             capsys.readouterr().out.splitlines()]
    assert "bf16[1,128,1024]" in saved
    wide = [a for a in saved if a.startswith("f32[1,128,")
            and int(a[len("f32[1,128,"):-1]) >= width]
    assert not wide, saved


def test_a_process_traces_each_kernel_body_once(monkeypatch):
    """Every call of a kernel at one shape goes through one shared
    jitted callable: a stack of two scans of two-sublayer layers under
    full remat, with its gradient, is twelve call sites of each forward
    kernel and four of each backward one, and calls each body once; a
    second ``jax.jit`` of the same stack at the same shapes calls none
    (the trace is the process's), and another shape calls each again."""
    n, width, layers = 4, 128, 2
    bodies = ("_hc_enter_fwd_kernel", "_hc_enter_bwd_kernel",
              "_hc_leave_fwd_kernel", "_hc_leave_bwd_kernel")
    calls = dict.fromkeys(bodies, 0)

    def counted(name, body):
        @functools.wraps(body)
        def kernel(*refs, **static):
            calls[name] += 1
            return body(*refs, **static)
        return kernel

    # new function objects, so new shared callables: what earlier tests
    # of this process traced does not count here
    for name in bodies:
        monkeypatch.setattr(hc, name, counted(name, getattr(hc, name)))

    @jax.checkpoint
    def layer(x, p):
        for sub in ("attn", "ffn"):
            x, _, _, took = hc.connect(
                x, p[sub], lambda u: (jnp.tanh(u), None), n, 2,
                (-3.0, 3.0), 1e-6, True, True)
            assert took == 1
        return x, None

    def stack(params, x):
        x = hc.enter(x, n)
        for scan in params:
            x, _ = jax.lax.scan(layer, x, scan)
        return hc.leave(x, n).sum()

    def lowered(seq):
        keys = jax.random.split(jax.random.PRNGKey(3), 4)
        params = [{sub: hc.init(keys[2 * i + j], (layers,), n, width,
                                jnp.float32)
                   for j, sub in enumerate(("attn", "ffn"))}
                  for i in range(2)]
        x = jax.ShapeDtypeStruct((1, seq, width), jnp.float32)
        # under a mesh, as ``accelerate`` traces every program (with
        # none, JAX linearizes a scan under an empty abstract mesh where
        # the trace before it had no mesh at all: another trace context,
        # and the forward bodies' second trace)
        with jax.sharding.set_mesh(jax.sharding.Mesh(
                jax.devices()[:1], ("data",))):
            return jax.jit(jax.grad(stack)).lower(params, x).as_text()

    text = lowered(128)
    assert calls == dict.fromkeys(bodies, 1), calls
    # the sites call functions of the module (a handful a kernel: one
    # a set of outputs in use), where each had its own copy of the body
    for name in ("hc_enter_fwd", "hc_enter_bwd", "hc_leave_fwd",
                 "hc_leave_bwd"):
        sites = text.count(f"call @{name}")
        assert sites > text.count(f"func.func private @{name}") > 0, name
    lowered(128)
    assert calls == dict.fromkeys(bodies, 1), calls
    lowered(256)
    assert calls == dict.fromkeys(bodies, 2), calls


@pytest.mark.parametrize("hidden, seq, budget, tile", [
    (128, 128, None, 128), (96, 128, None, 0), (128, 96, None, 0),
    (128, 128, 1 << 20, 0)],
    ids=["whole-lanes", "C-96", "a-row-of-96", "tile-over-the-budget"])
def test_the_path_follows_the_shapes(hidden, seq, budget, tile, monkeypatch):
    """Streams of whole lanes in rows of whole tiles take the kernels
    where a tile's blocks fit the budget written beside them; anything
    else takes the
    ``jax.numpy`` functions; ``hc_kernel_passes`` counts the sublayers
    that took the kernels: two a layer, the prediction module's too."""
    if budget is not None:
        monkeypatch.setattr(hc, "_TILE_STATE_BUDGET_BYTES", budget)
    assert hc.token_tile((2, seq, 4 * hidden), jnp.float32, 4) == tile
    config = mla_moe.mla_moe_tiny(
        hidden_size=hidden, experts_held=tuple(range(8)), hc_mult=4,
        mtp_layers=1, num_layers=2, use_kernels=True, flash_block_q=32,
        flash_block_k=32, max_seq_len=seq, rope_original_max=seq, **F32)
    params = mla_moe.init(jax.random.PRNGKey(0), config)
    ids = np.random.default_rng(0).integers(0, 256, (2, seq + 1)).astype(
        np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    loss_fn = mla_moe.make_loss_fn(config, head_chunk=16)
    text = str(jax.make_jaxpr(loss_fn)(params, batch, None))
    for name in ("hc_enter_fwd", "hc_leave_fwd"):
        assert (name in text) == bool(tile), name
    loss, aux = loss_fn(params, batch, None)
    assert np.isfinite(float(loss))
    assert float(aux[StepCounter.HC_KERNEL_PASSES]) == (6 if tile else 0)
    # a model that runs no kernel at all runs none here
    import dataclasses

    _, aux = mla_moe.make_loss_fn(dataclasses.replace(
        config, use_kernels=False), head_chunk=16)(params, batch, None)
    assert float(aux[StepCounter.HC_KERNEL_PASSES]) == 0


def _lowered(config):
    loss_fn = mla_moe.make_loss_fn(config, head_chunk=16)
    params = jax.eval_shape(mla_moe.make_init_fn(config),
                            jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    return jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, None), has_aux=True)).lower(
        params, {"input_ids": ids, "labels": ids}).as_text()


def test_one_stream_is_the_plain_residual_program():
    """``hc_mult`` 1, no selection bias and no prediction module, said
    outright, lower to the very text of a configuration that says
    nothing (A.X-K1's): the plain residual, not a one-stream mapping;
    and the text has none of the new scopes' work in it."""
    held = tuple(range(8))
    plain = _lowered(mla_moe.mla_moe_tiny(experts_held=held, **F32))
    said = _lowered(mla_moe.mla_moe_tiny(
        experts_held=held, hc_mult=1, router_bias=False, mtp_layers=0,
        hc_sinkhorn_iters=3, mtp_loss_weight=0.9, **F32))
    assert said == plain
    streams = _lowered(mla_moe.mla_moe_tiny(
        experts_held=held, hc_mult=4, router_bias=True, mtp_layers=1, **F32))
    assert streams != plain
    # the streams are one flat residual [B, S, 4 * 64], no stream axis
    assert "<2x32x256x" in streams and "<2x32x256x" not in plain
    assert "x4x64x" not in streams
    params = mla_moe.init(jax.random.PRNGKey(0),
                          mla_moe.mla_moe_tiny(experts_held=held))
    names = {jax.tree_util.keystr(path)
             for path, _ in jax.tree_util.tree_leaves_with_path(params)}
    assert not any("hc_" in n or "mtp" in n or "bias" in n for n in names)


PARENT_CKPT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "testdata", "hc_parent_ckpt")


@pytest.mark.parametrize("iters", [1, 20])
def test_a_checkpoint_of_the_stream_axis_tree_restores(iters, tmp_path):
    """``testdata/hc_parent_ckpt`` was written by the tree whose scans
    carried ``[B, S, n, C]`` (its ``written_by.py`` says how): this
    tree's parameters have those leaves at those shapes, the checkpoint
    restores into them, and the hidden states, the loss and the two
    counters are what that tree gave, so stream j's ``norm/scale`` and
    ``phi`` rows are still ``j * C`` to ``(j + 1) * C``."""
    from dlrover_tpu.checkpoint.manager import ElasticCheckpointManager

    spec = importlib.util.spec_from_file_location(
        "written_by", os.path.join(PARENT_CKPT, "written_by.py"))
    written_by = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(written_by)
    config = mla_moe.mla_moe_tiny(hc_sinkhorn_iters=iters, **written_by.TOY)
    shutil.copytree(os.path.join(PARENT_CKPT, "ckpt"), tmp_path / "ckpt")
    mgr = ElasticCheckpointManager(str(tmp_path / "ckpt"), async_save=False,
                                   staging_dir="")
    abstract = jax.eval_shape(mla_moe.make_init_fn(config),
                              jax.random.PRNGKey(0))
    out = mgr.restore(abstract)
    mgr.close()
    assert out is not None and out["step"] == 1
    params = out["state"]
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), abstract)
    gave = np.load(os.path.join(PARENT_CKPT, "hidden.npz"))
    batch = {"input_ids": gave["ids"][:, :-1], "labels": gave["ids"][:, 1:]}
    hidden = mla_moe.apply_all_hidden(params, batch["input_ids"],
                                      batch["labels"], config)
    assert np.allclose(hidden, gave[f"iters{iters}"], atol=2e-5)
    loss, aux = mla_moe.make_loss_fn(config, head_chunk=16)(params, batch,
                                                            None)
    assert np.allclose(
        [loss, aux[StepCounter.HC_RES_DEFECT], aux[StepCounter.MTP_LOSS]],
        gave[f"iters{iters}_loss"], rtol=1e-5, atol=1e-6)


def test_the_new_leaves_under_the_mla_moe_rules():
    """``fsdp=2 x tensor=2`` on the CPU's virtual devices: ``phi``
    shards its long axis over ``fsdp``; gates, biases, norm scales and
    the selection bias are whole; the prediction module's stack takes
    the layers' rules and its projection a column's; and the step
    trains under the mesh, the bias left as it was."""
    config = mla_moe.mla_moe_tiny(
        experts_held=tuple(range(8)), hc_mult=4, router_bias=True,
        mtp_layers=1, balance_loss_weight=0.0, **F32)
    shapes = jax.eval_shape(mla_moe.make_init_fn(config),
                            jax.random.PRNGKey(0))
    sizes = {"data": 1, "fsdp": 2, "tensor": 2}
    rules = mla_moe_rules()
    spec = {path: rules.spec_for(path, leaf.shape, sizes)
            for path, leaf in _flatten_with_paths(shapes)}
    for stack in ("moe_layers", "dense_layers", "mtp/layer"):
        for sub in ("hc_attn", "hc_ffn"):
            assert tuple(spec[f"{stack}/{sub}/phi/kernel"]) == (
                None, "fsdp", None)
            for leaf in ("alpha", "bias", "norm/scale"):
                assert not any(spec[f"{stack}/{sub}/{leaf}"]), (stack, leaf)
    for stack in ("moe_layers", "mtp/layer"):
        assert not any(spec[f"{stack}/moe/router/bias"])
        assert not any(spec[f"{stack}/moe/router/kernel"])
        assert tuple(spec[f"{stack}/attn/q_b_proj/kernel"]) == (
            None, "fsdp", "tensor")
        assert tuple(spec[f"{stack}/moe/experts/down/kernel"]) == (
            None, None, None, "fsdp")
    assert tuple(spec["mtp/eh_proj/kernel"]) == (None, "fsdp", "tensor")
    for leaf in ("h_norm", "e_norm", "norm"):
        assert not any(spec[f"mtp/{leaf}/scale"])

    ids = np.random.default_rng(0).integers(0, 256, (4, 65)).astype(np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    result = accelerate(
        mla_moe.make_init_fn(config),
        mla_moe.make_loss_fn(config, head_chunk=16), optax.adam(3e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                          rule_set="mla_moe", remat_policy=""))
    state = result.init_fn(jax.random.PRNGKey(0))
    layer = state.params["mtp"]["layer"]
    assert tuple(layer["hc_ffn"]["phi"]["kernel"].sharding.spec) == (
        None, "fsdp", None)
    bias = np.asarray(state.params["moe_layers"]["moe"]["router"]["bias"])
    sharded = result.shard_batch(batch)
    losses = []
    for i in range(6):
        state, metrics = result.train_step(state, sharded,
                                           jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert float(metrics[StepCounter.HC_RES_DEFECT]) < 1e-2
    assert 0 < float(metrics[StepCounter.MTP_LOSS]) < 7
    # no gradient reaches the selection bias, so the optimizer leaves it
    assert np.array_equal(bias, np.asarray(
        state.params["moe_layers"]["moe"]["router"]["bias"]))
