"""The ``smallthinker-21b-a3b-ep4-1chip`` configuration's whole train
step and forward-only step, asked of the v5e's own compiler with no
chip attached (see ``test_tpu_compile.py``).
"""

import os
import re

import jax
import numpy as np
from hlo_checks import (
    _peak_bytes,
    _resident_bytes,
    compile_once,
    compile_step,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smallthinker_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``smallthinker-21b-a3b-ep4-1chip`` configuration
    through its own job builder: the whole train step (three periods of
    one full and three window layers in one scan, the router ahead of
    each attention, 16 held ReGLU experts with a row buffer of every
    assignment) and the forward-only step of the reference check
    compile for one v5e chip at one row of 16,384, with both kinds of
    flash kernel and the grouped matmuls in them; each layer's forward
    kernel once a period (its checkpoint keeps the kernel's output and
    logsumexp: PR 56); what the compiler allocates at the step's peak
    under the 15.0 GB ISSUE 41 allows of the chip's 15.75
    (``hlo_checks._peak_bytes``: 12.02 at depth 12 with the twelve
    layers' 1.43 GB of outputs kept, 10.69 with nothing kept;
    ``_resident_bytes``, the estimate that counts a stack the scan
    carries twice, is printed beside it: 17.10 and 14.57; depth 16
    16.30 by the estimate at half the row buffer; 18.21 at depth 12
    while the period's layers shared one stack ``[periods, 4, ...]``
    and the scan kept a copy of every layer's slice for the
    backward)."""
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
    compile_once(result.eval_step.lower(state, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), example)))
    compiled = compile_step(result, example)
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_dkv", "flash_dq", "flash_win_fwd",
                 "flash_win_bwd", "gmm", "gmm_dx", "gmm_dw"):
        assert f"%{name}." in text, name
    assert "flash_win_dkv" not in text and "flash_win_dq" not in text
    # the scan's one period: a full and three window layers, and none of
    # their forward kernels again in the replay (two and six in the
    # parent's step, whose checkpoints kept nothing: deviceless
    # compile of 125fe7a, PR 56)
    assert [len(re.findall(rf"%{name}\.\d+ = ", text)) for name in (
        "flash_fwd", "flash_win_fwd")] == [1, 3]
    for scope in ("/attn_full/", "/attn_window/", "/moe_router/",
                  "/moe_experts/"):
        assert scope in text, scope
    # no [rows, rows] score matrix of a head, and no stack of every
    # layer's parameters beside the state's own
    assert "16384,16384]" not in text
    peak = _peak_bytes(compiled)
    print(f"smallthinker train_step: {peak / 1e9:.2f} GB allocated at the "
          f"peak, {_resident_bytes(compiled) / 1e9:.2f} GB estimated")
    assert peak < 15.0e9, f"{peak / 1e9:.2f} GB"
