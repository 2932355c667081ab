"""The ``keye-vl-2.0-30b-a3b-ep4-1chip`` configuration's whole train
step and forward-only step, asked of the v5e's own compiler with no
chip attached (see ``test_tpu_compile.py``).
"""

import os
import re

import jax
import numpy as np
import pytest
from hlo_checks import (
    _peak_bytes,
    _resident_bytes,
    compile_once,
    compile_step,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_keye_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``keye-vl-2.0-30b-a3b-ep4-1chip`` configuration
    through its own job builder: the whole train step (the sparse
    layers in one scan, each its own checkpoint: indexer, selection,
    selected attention, the indexer's loss, 32 held SwiGLU experts
    routed from the post-attention norm) and the forward-only step of
    the reference check compile for one v5e chip at one row of 16,384,
    with the four sparse kernels and the grouped matmuls in them; the
    selected attention's forward and the indexer's loss's one kernel
    once a layer (its checkpoint keeps the one's output and logsumexp
    and the other's three gradients) and the selection twice (its
    mask is replayed); what the compiler allocates at the step's peak
    under the 15.0 GB ISSUE 48 allows of the chip's 15.75
    (``hlo_checks._peak_bytes``; ``_resident_bytes``, the estimate
    that counts the kept stack twice, is printed beside it; ``PERF.md``
    section 4 has the size of each depth tried;
    ``KEYE_COMPILE_DEPTH`` tries another)."""
    import functools
    import json

    from chipbench import worker
    from dlrover_tpu.models import gqa_moe
    from dlrover_tpu.parallel.accelerate import accelerate

    with open(os.path.join(REPO, "chipbench", "configs",
                           "keye-vl-2.0-30b-a3b-ep4-1chip.json")) as fh:
        model = json.load(fh)
    committed = model["num_hidden_layers"]
    model["num_hidden_layers"] = int(
        os.environ.get("KEYE_COMPILE_DEPTH", committed))
    monkeypatch.setattr(gqa_moe, "GqaMoeConfig", functools.partial(
        gqa_moe.GqaMoeConfig, kernel_interpret=False))
    job = worker.build_job(model)
    if model["num_hidden_layers"] == committed:
        assert (job.param_count, job.seq_len, job.layers) == (
            1_534_758_912, 16384, 8)
    batch = model["assumed"]["batch"]
    example = {"input_ids": np.zeros((batch, job.seq_len), np.int32),
               "labels": np.zeros((batch, job.seq_len), np.int32)}
    result = accelerate(
        job.init_fn, job.loss_fn,
        worker.build_optimizer(model["assumed"]["optimizer"]), example,
        strategy=job.strategy, devices=v5e[:1],
    )
    state = jax.eval_shape(result.init_fn, jax.random.PRNGKey(0))
    compile_once(result.eval_step.lower(state, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), example)))
    compiled = compile_step(result, example)
    text = compiled.as_text()
    for name in ("dsa_index_select", "dsa_attn_fwd", "dsa_attn_bwd",
                 "dsa_index_kl",
                 "gmm", "gmm_dx", "gmm_dw"):
        assert f"%{name}." in text, name
    # the layer's replay leaves the kept forward out and runs the
    # selection again
    assert [len(re.findall(rf"%{name}\.\d+ = ", text)) for name in (
        "dsa_attn_fwd", "dsa_index_select")] == [1, 2]
    # the indexer's loss, value and gradient, is one kernel in the
    # forward pass: the layer's checkpoint keeps its three gradients
    assert len(re.findall(r"%dsa_index_kl\.\d+ = ", text)) == 1
    assert "dsa_index_kl_fwd" not in text and "dsa_index_kl_bwd" not in text
    # a row of 16,384 at widths of 128 fits the one backward kernel
    # (as instructions: the module's table of stack frames may name a
    # function of the same stem that an earlier test of this process
    # traced)
    for name in ("dsa_attn_dkv", "dsa_attn_dq", "flash_fwd"):
        assert f"%{name}" not in text, name
    for scope in ("/attn_sparse/", "/dsa_index/", "/moe_router/",
                  "/moe_experts/"):
        assert scope in text, scope
    # no float [rows, rows] score matrix: the selection alone is that
    # large, as bytes
    assert "f32[1,16384,16384]" not in text and (
        "bf16[1,16384,16384]" not in text)
    peak = _peak_bytes(compiled)
    print(f"keye train_step depth {model['num_hidden_layers']}: "
          f"{peak / 1e9:.2f} GB allocated at the peak, "
          f"{_resident_bytes(compiled) / 1e9:.2f} GB estimated")
    assert peak < 15.0e9, f"{peak / 1e9:.2f} GB"


def test_the_peak_is_under_the_estimate_and_is_never_zero():
    """``_peak_bytes`` on the CPU, of a scan whose layers' checkpoints
    keep a named value: under ``_resident_bytes``' sum, and refused
    where the compiler gives none. (Plumbing alone: the CPU backend's
    field leaves the temporaries out, and its estimate counts a carried
    stack once; the v5e's readings are the test above.)"""
    import types

    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    from dlrover_tpu.ops.remat import apply_remat

    def layer(x, w):
        return jnp.tanh(checkpoint_name(jnp.sin(x @ w), "kept")), None

    def loss(w, x):
        x, _ = jax.lax.scan(apply_remat(layer, "full", keep=("kept",)), x, w)
        return x.sum()

    compiled = jax.jit(jax.grad(loss)).lower(
        jnp.ones((8, 64, 64)), jnp.ones((128, 64))).compile()
    assert 0 < _peak_bytes(compiled) < _resident_bytes(compiled)
    silent = types.SimpleNamespace(memory_analysis=lambda: (
        types.SimpleNamespace(peak_memory_in_bytes=0)))
    with pytest.raises(AssertionError, match="peak_memory_in_bytes"):
        _peak_bytes(silent)
