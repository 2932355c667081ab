"""The ``phi-4-mini-flash-1chip`` configuration's whole train step,
asked of the v5e's own compiler with no chip attached (see
``test_tpu_compile.py``, which keeps its kernels' own compiles).
"""

import os

import numpy as np
from hlo_checks import _resident_bytes, compile_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phi4flash_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``phi-4-mini-flash-1chip`` configuration through
    its own job builder: the whole train step (state-space layers, the
    window layers' two kernels, full and cross attention, the tied head)
    compiles for one v5e chip at one row of 8192 under the 15.0 GB
    ISSUE 29 allowed of the chip's 15.75 (12.82 at depth 12)."""
    import functools
    import json

    from chipbench import worker
    from dlrover_tpu.models import sambay
    from dlrover_tpu.parallel.accelerate import accelerate

    with open(os.path.join(REPO, "chipbench", "configs",
                           "phi-4-mini-flash-1chip.json")) as fh:
        model = json.load(fh)
    monkeypatch.setattr(sambay, "SambaYConfig", functools.partial(
        sambay.SambaYConfig, kernel_interpret=False))
    job = worker.build_job(model)
    assert (job.param_count, job.seq_len, job.layers) == (
        1_778_306_304, 8192, 12)
    batch = model["assumed"]["batch"]
    example = {"input_ids": np.zeros((batch, job.seq_len), np.int32),
               "labels": np.zeros((batch, job.seq_len), np.int32)}
    result = accelerate(
        job.init_fn, job.loss_fn,
        worker.build_optimizer(model["assumed"]["optimizer"]), example,
        strategy=job.strategy, devices=v5e[:1],
    )
    compiled = compile_step(result, example)
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_dkv", "flash_dq", "flash_win_fwd",
                 "flash_win_bwd", "ssm_scan_fwd", "ssm_scan_bwd"):
        assert f"{name}." in text, name
    assert "flash_win_dkv" not in text and "flash_win_dq" not in text
    assert "8192,8192]" not in text
    resident = _resident_bytes(compiled)
    print(f"phi4flash train_step: {resident / 1e9:.2f} GB")
    assert resident < 15.0e9, f"{resident / 1e9:.2f} GB"
