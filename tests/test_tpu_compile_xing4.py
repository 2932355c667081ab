"""The ``xing4.0-29b-a4b-ep4-1chip`` configuration's whole train step
and forward-only step, asked of the v5e's own compiler with no chip
attached (see ``test_tpu_compile.py``). A file of its own: this is the
longest compile of the suite.
"""

import os
import re

import jax
import numpy as np
from hlo_checks import (
    _peak_bytes,
    _resident_bytes,
    compile_once,
    lower_step,
    moves_of,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_xing4_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``xing4.0-29b-a4b-ep4-1chip`` configuration
    through its own job builder: the whole train step (four streams
    through the layer scans, the prediction module and its head pass)
    and the forward-only step of the reference check compile for one
    v5e chip with the latent flash and grouped-matmul kernels in them,
    the forward kernel once a scan (a layer's and the module's
    checkpoint keeps its output and logsumexp: PR 58); what the
    compiler allocates at the step's peak under the 15.0 GB ISSUE 36
    allows of the chip's 15.75 (``hlo_checks._peak_bytes``: 11.04
    with the eight calls' 0.55 GB kept, 10.57 with nothing kept;
    ``_resident_bytes``, the estimate that counts a stack the scan
    carries twice, is printed beside it: 15.56 and 14.60; by the
    estimate 14.40 at 2 + 5 layers when the streams became one flat
    residual (14.81 with a stream axis), then 2 + 4: 12.99; 2 + 6:
    16.60, and 17.90 before a hyper-connection's pieces kept their
    arguments alone for the backward). And the carry ``[B, S, 4 *
    3584]`` stays where it is: no ``copy`` under the
    hyper-connections' scopes moves it to another layout (with a
    stream axis 32 did, in the forward, the replay and the backward:
    XLA put that axis outermost and materialised the flat view the norm
    and the projection read)."""
    import functools
    import json

    from chipbench import worker
    from dlrover_tpu.models import mla_moe
    from dlrover_tpu.parallel.accelerate import accelerate

    with open(os.path.join(REPO, "chipbench", "configs",
                           "xing4.0-29b-a4b-ep4-1chip.json")) as fh:
        model = json.load(fh)
    monkeypatch.setattr(mla_moe, "MlaMoeConfig", functools.partial(
        mla_moe.MlaMoeConfig, kernel_interpret=False))
    job = worker.build_job(model)
    assert (job.param_count, job.seq_len, job.layers) == (
        1_816_249_136, 4096, 7)
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
    # a kernel body is lowered once a call site, at every boot whatever
    # the compile cache holds; the hyper-connections' call sites (two a
    # sublayer, in three scans, forward, replay and backward) share one
    # callable a kernel and shape, so the module holds each body once or
    # twice: 71 calls, 65 without the streams' kernels, 99 with a body
    # a site (ISSUE 46)
    lowered = lower_step(result, example)
    assert lowered.as_text().count("tpu_custom_call") <= 80
    compiled = compile_once(lowered)
    text = compiled.as_text()
    for name in ("flash_mla_fwd", "flash_mla_bwd", "gmm", "gmm_dx",
                 "gmm_dw"):
        assert f"%{name}." in text, name
    assert "flash_mla_dkv" not in text and "flash_mla_dq" not in text
    # the dense layers' scan, the expert layers' and the prediction
    # module's: none's forward kernel again in its replay (six in the
    # parent's step, whose checkpoints kept nothing: deviceless compile
    # of b53da53, PR 58)
    assert len(re.findall(r"%flash_mla_fwd\.\d+ = ", text)) == 3
    for scope in ("/hc_map/", "/hc_mix/", "jvp(mtp)"):
        assert scope in text, scope
    # the hyper-connections' passes over the carry are Mosaic calls
    # under the scopes of the work they took over (ISSUE 45), which the
    # shared callables open themselves
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name, scope in (("hc_enter_fwd", "/hc_map/"),
                        ("hc_enter_bwd", "/hc_map/"),
                        ("hc_leave_fwd", "/hc_mix/"),
                        ("hc_leave_bwd", "/hc_mix/")):
        assert [line for line in calls if f"%{name}." in line
                and scope in line], name
    # the streams ride the scans flat and row-major: no stream axis to
    # pad or to move outermost
    width = 4 * model["hidden_size"]
    assert f"bf16[5,{batch},4096,{width}]{{3,2,1,0:" in text
    assert ",4096,4,3584]" not in text
    moves = moves_of(text, batch * 4096 * width)
    in_hc = [m for m in moves if "/hc_map/" in m.op_name
             or "/hc_mix/" in m.op_name]
    assert not [m for m in in_hc if m.relayout], in_hc
    # what is left under those names is each forward scan's own copy of
    # its carry (same layout: the carry is also kept for the backward);
    # in the whole step, the dense backward scan besides, which XLA
    # keeps tokens-minor: one relayout into it, one a layer of the kept
    # carry, one out (39 such instructions with a stream axis)
    assert len(in_hc) <= 2 and len(moves) <= 5, moves
    peak = _peak_bytes(compiled)
    print(f"xing4 train_step: {peak / 1e9:.2f} GB allocated at the peak, "
          f"{_resident_bytes(compiled) / 1e9:.2f} GB estimated, carry-sized "
          f"copies {[(m.name, m.relayout) for m in moves]}")
    assert peak < 15.0e9, f"{peak / 1e9:.2f} GB"
