"""``chipbench/families/sambay/``: the plain reference (float32
``jax.numpy``, the recurrence token by token, dense masked softmax)
against ``models/sambay.py``, the code the cell runs, at a toy size on
the CPU: the loss and every gradient; the faults the comparison has to
catch; and ``flops.py`` by hand.

Both sides compute in float32 here (the toy states float32 parameters
and compute and the XLA paths), so they differ only by the order of
float32 sums: losses near 5.5 agree to 1e-5 and gradient leaves to 1e-4
of their largest entry plus 1e-7 (the lambda vectors' gradients are
sums of cancelling terms and read 7e-5; bf16, 4e-3 a rounding, would
miss by orders). On the chip the same comparison runs
in every first worker round at the published widths, against bf16
compute, with the tolerance ``job.py`` gives.
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import worker  # noqa: E402
from chipbench.families.sambay import flops, reference  # noqa: E402
from chipbench.families.sambay.job import (  # noqa: E402
    REFERENCE_TOL,
    reference_layers,
)
from dlrover_tpu.models import sambay  # noqa: E402

LOSS_TOL = 1e-5
GRAD_RTOL, GRAD_ATOL = 1e-4, 1e-7


def toy():
    with open(os.path.join(HERE, "tiny_sambay.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def job():
    model = toy()
    built = worker.build_job(model)
    # scales are ones and biases zeros at init: perturb them so that a
    # reference that dropped one would show
    params = jax.jit(lambda key: jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                              a.shape, a.dtype),
        built.init_fn(key)))(jax.random.PRNGKey(3))
    batch = worker.batch_for(11, 0, built.vocab_size, 1, built.seq_len)
    return model, built, params, batch


def config_of(model):
    return sambay.SambaYConfig(num_layers=model["num_hidden_layers"])


def reference_loss(model, params, batch, layers=None):
    """The reference on the program's parameter tree, differentiable in
    it (``reference.loss`` runs it layer by layer)."""
    if layers is None:
        layers = reference_layers(params, config_of(model))
    return reference.loss(
        model, batch["input_ids"][0], batch["labels"][0],
        params["embed_tokens"]["embedding"], layers, params["norm"])


def test_loss_matches_the_program(job):
    model, built, params, batch = job
    system = float(jax.jit(built.loss_fn)(params, batch, None)[0])
    ref = float(reference_loss(model, params, batch))
    assert abs(system - ref) < LOSS_TOL, (system, ref)
    assert LOSS_TOL < built.reference_tol == REFERENCE_TOL["float32"]
    # the job's own form, as the worker calls it
    assert abs(built.reference_loss(params, batch["input_ids"][0],
                                    batch["labels"][0]) - ref) < 1e-6


def test_every_gradient_matches_the_program(job):
    model, built, params, batch = job
    want = jax.jit(jax.grad(
        lambda p: reference_loss(model, p, batch)))(params)
    got = jax.jit(jax.grad(
        lambda p: built.loss_fn(p, batch, None)[0]))(params)
    flat_want = jax.tree_util.tree_flatten_with_path(want)[0]
    flat_got = jax.tree.leaves(got)
    assert len(flat_want) == len(flat_got) > 60
    for (path, w), g in zip(flat_want, flat_got):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path  # every parameter is in the mathematics
        assert float(jnp.abs(g - w).max()) < GRAD_RTOL * scale + GRAD_ATOL, (
            path, scale)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "chipbench", "families", "sambay",
                           "reference.py")) as f:
        text = f.read()
    assert "dlrover_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


# -- what the comparison has to catch ---------------------------------------
# Each fault is put into the reference's side (the program is the code
# under test and has no switch for them) and must move the loss by more
# than the float32 tolerance here and than the chip's bf16 tolerance.


def fault_dropped_layer(model, params, batch):
    """The second window-attention layer's mixer and MLP silenced."""
    layers = list(reference_layers(params, config_of(model)))
    quiet = jax.tree.map(jnp.zeros_like, layers[3])
    quiet["mix_norm"], quiet["mlp_norm"] = (layers[3]["mix_norm"],
                                            layers[3]["mlp_norm"])
    layers[3] = quiet
    return reference_loss(model, params, batch, layers)


def fault_window_edge(model, params, batch):
    """Key j visible where t - window <= j: one key too many."""
    return reference_loss(
        dict(model, sliding_window=model["sliding_window"] + 1), params,
        batch)


def fault_dropped_lambda(model, params, batch, monkeypatch):
    """a_1 - lam0 * a_2: the learned part of lambda left out."""
    monkeypatch.setattr(reference, "lam_of", lambda w, lam0: lam0)
    return reference_loss(model, params, batch)


def fault_eight_bit(model, params, batch, monkeypatch):
    """Every matrix product's operands rounded to 8-bit floating point
    (e4m3), the nearest precision below the bf16 the cell states."""
    def rounded(a, b):
        low = jnp.float8_e4m3fn
        return (a.astype(low).astype(jnp.float32)
                @ b.astype(low).astype(jnp.float32))

    monkeypatch.setattr(reference, "mm", rounded)
    return reference_loss(model, params, batch)


@pytest.mark.parametrize("fault", ["dropped_layer", "window_edge",
                                   "dropped_lambda", "eight_bit"])
def test_a_fault_fails_the_comparison(job, fault, monkeypatch):
    model, built, params, batch = job
    system = float(jax.jit(built.loss_fn)(params, batch, None)[0])
    args = (model, params, batch)
    if fault in ("dropped_lambda", "eight_bit"):
        args += (monkeypatch,)
    faulty = float(globals()["fault_" + fault](*args))
    assert abs(system - faulty) > REFERENCE_TOL["bfloat16"], (
        fault, system, faulty)


# -- flops.py by hand -------------------------------------------------------


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "phi-4-mini-flash-1chip.json")) as f:
        return json.load(f)


def test_flops_imports_no_jax():
    spec = importlib.util.spec_from_file_location(
        "sambay_flops_alone", flops.__file__)
    with open(flops.__file__) as f:
        assert "import jax" not in f.read()
    assert spec is not None


@pytest.mark.parametrize("depth,want", [(12, 1_778_306_304),
                                        (16, 2_193_157_632),
                                        (32, 3_852_562_944)])
def test_param_count_is_the_programs(depth, want):
    model = dict(cell_model(), num_hidden_layers=depth)
    assert flops.param_count(model) == want
    assert sambay.param_count(
        worker.build_job(model).init_fn.keywords["config"]) == want


def test_layer_counts_are_the_plans():
    for depth in (8, 12, 16, 32):
        assert flops.layer_counts({"num_hidden_layers": depth}) \
            == sambay.layer_kinds(sambay.SambaYConfig(num_layers=depth))


def test_matmul_parameters_by_hand():
    """By kind at the published widths (ISSUE 29's table): Mamba
    119.9M, attention 98.3M, gated memory 104.9M, cross 91.8M a layer
    with its MLP of 78.6M; the head 512.2M."""
    d, f, di = 2560, 10240, 5120
    mlp = 3 * d * f
    ssm = 2 * d * di + di * (160 + 32) + 160 * di + di * d
    attn = d * 2560 + 2 * d * 1280 + 2560 * d
    cross, gmu = 2 * d * 2560, 2 * d * di
    # the table's figures count the elementwise parameters too
    for matmul, table in ((ssm, 119.9), (attn, 98.3), (gmu, 104.9),
                          (cross, 91.8)):
        assert abs((matmul + mlp) / 1e6 - table) < 0.15
    want = (4 * (ssm + mlp) + 4 * (attn + mlp) + 2 * (gmu + mlp)
            + 2 * (cross + mlp) + d * 200064)
    assert flops.matmul_params(cell_model()) == want


def test_kernel_work_by_hand():
    model = cell_model()
    seq, window, tokens = 8192, 512, 8192
    assert flops.tokens_per_step(model) == tokens
    # visible pairs a row: the band, and the causal half
    band = window * (window + 1) // 2 + (seq - window) * window
    half = seq * (seq + 1) // 2
    # a pair: QK^T 2 x 64 and PV 2 x 128 FLOPs a query head forward,
    # 20 query heads a call, 2 calls a layer, backward twice forward
    per_pair = (2 * 64 + 2 * 128) * 20 * 2 * 3
    assert flops.window_flops_per_step(model) == 3 * per_pair * band
    assert flops.causal_flops_per_step(model) == 3 * per_pair * half
    # the band is 1/8.26 of the half square at 8192 tokens
    assert 8.2 < half / band < 8.3
    # a call forward: q 20 x 64, k 10 x 64, v 10 x 128, o 20 x 128
    # elements a token in bf16; backward reads those and do, writes
    # dq, dk, dv
    fwd = (20 * 64 + 10 * 64 + 10 * 128 + 20 * 128) * 2 * tokens
    bwd = fwd + 20 * 128 * 2 * tokens + (20 * 64 + 10 * 64 + 10 * 128) \
        * 2 * tokens
    assert flops.window_bytes_per_step(model) == 3 * 2 * (fwd + bwd)
    assert flops.scan_flops_per_step(model) == 4 * 21 * tokens * 5120 * 16
    assert flops.scan_bytes_per_step(model) == 4 * 22 * tokens * 5120
    assert flops.kernel_flops_per_step(model) == (
        flops.window_flops_per_step(model)
        + flops.causal_flops_per_step(model)
        + flops.scan_flops_per_step(model))
    assert flops.model_flops_per_step(model) == (
        6 * flops.matmul_params(model) * tokens
        + flops.kernel_flops_per_step(model))
    # 9.3e13 a step at depth 12: nine tenths of it the matmuls
    assert 9.2e13 < flops.model_flops_per_step(model) < 9.4e13


def test_the_new_readers_read_a_reduced_trace():
    """The four readers on a made-up reduced trace, and on one without
    their instructions (the parent's program): nothing, not an error."""
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import arithmetic

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, "chipbench", "layer_metrics",
                               name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    model = cell_model()
    trace = {"devices": {"/device:TPU:0": {}}, "steps": 4, "device_ops": [
        ["fusion.1", 2.0], ["mosaic:ssm_scan_bwd.11", 0.24],
        ["mosaic:ssm_scan_fwd.17", 0.08], ["mosaic:ssm_scan_fwd.18", 0.08],
        ["mosaic:flash_win_fwd.30", 0.02], ["mosaic:flash_win_dkv.20", 0.04],
        ["mosaic:flash_win_dq.20", 0.02], ["mosaic:flash_fwd.2", 0.5],
        ["ssm_scan_lookalike_fusion", 9.0]]}
    ctx = {"trace": trace, "model": model, "flops": flops,
           "arithmetic": arithmetic,
           "device": {"count": 1, "kind": "TPU v5 lite"}}
    assert reader("ssm_scan_ms")(ctx) == pytest.approx(100.0)
    assert reader("window_attn_ms")(ctx) == pytest.approx(20.0)
    least_scan = flops.scan_bytes_per_step(model) / 819e9
    assert reader("ssm_scan_roofline")(ctx) == pytest.approx(
        100 * least_scan / 0.1)
    least_window = flops.window_flops_per_step(model) / 197e12
    assert reader("window_attn_roofline")(ctx) == pytest.approx(
        100 * least_window / 0.02)
    bare = dict(ctx, trace=dict(trace, device_ops=[["fusion.1", 2.0]]))
    for name in ("ssm_scan_ms", "ssm_scan_roofline", "window_attn_ms",
                 "window_attn_roofline"):
        assert reader(name)(bare) is None
        assert reader(name)(dict(ctx, trace=None)) is None
