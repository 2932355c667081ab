"""The ``smallthinker-21b-a3b-ep4-1chip`` configuration's whole train
step and forward-only step, asked of the v5e's own compiler with no
chip attached (see ``test_tpu_compile.py``).
"""

import os

import jax
import numpy as np
from hlo_checks import _resident_bytes, compile_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smallthinker_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``smallthinker-21b-a3b-ep4-1chip`` configuration
    through its own job builder: the whole train step (three periods of
    one full and three window layers in one scan, the router ahead of
    each attention, 16 held ReGLU experts with a row buffer of every
    assignment) and the forward-only step of the reference check
    compile for one v5e chip at one row of 16,384, with both kinds of
    flash kernel and the grouped matmuls in them, under the 15.0 GB
    ISSUE 41 allows of the chip's 15.75: 14.57 at depth 12 (depth 16
    16.30 at half the row buffer; 18.21 at depth 12 while the period's
    layers shared one stack ``[periods, 4, ...]`` and the scan kept a
    copy of every layer's slice for the backward)."""
    import functools
    import json

    from chipbench import worker
    from dlrover_tpu.models import gqa_moe
    from dlrover_tpu.parallel.accelerate import accelerate

    with open(os.path.join(REPO, "chipbench", "configs",
                           "smallthinker-21b-a3b-ep4-1chip.json")) as fh:
        model = json.load(fh)
    monkeypatch.setattr(gqa_moe, "GqaMoeConfig", functools.partial(
        gqa_moe.GqaMoeConfig, kernel_interpret=False))
    job = worker.build_job(model)
    assert (job.param_count, job.seq_len, job.layers) == (
        1_580_628_480, 16384, 12)
    batch = model["assumed"]["batch"]
    example = {"input_ids": np.zeros((batch, job.seq_len), np.int32),
               "labels": np.zeros((batch, job.seq_len), np.int32)}
    result = accelerate(
        job.init_fn, job.loss_fn,
        worker.build_optimizer(model["assumed"]["optimizer"]), example,
        strategy=job.strategy, devices=v5e[:1],
    )
    state = jax.eval_shape(result.init_fn, jax.random.PRNGKey(0))
    result.eval_step.lower(state, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), example)).compile()
    compiled = compile_step(result, example)
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_dkv", "flash_dq", "flash_win_fwd",
                 "flash_win_bwd", "gmm", "gmm_dx", "gmm_dw"):
        assert f"%{name}." in text, name
    assert "flash_win_dkv" not in text and "flash_win_dq" not in text
    for scope in ("/attn_full/", "/attn_window/", "/moe_router/",
                  "/moe_experts/"):
        assert scope in text, scope
    # no [rows, rows] score matrix of a head, and no stack of every
    # layer's parameters beside the state's own
    assert "16384,16384]" not in text
    resident = _resident_bytes(compiled)
    print(f"smallthinker train_step: {resident / 1e9:.2f} GB")
    assert resident < 15.0e9, f"{resident / 1e9:.2f} GB"
