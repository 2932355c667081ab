"""The ``granite-4.0-h-micro-d20-1chip`` configuration's whole train
step and forward-only step, asked of the v5e's own compiler with no
chip attached (see ``test_tpu_compile.py``).
"""

import functools
import json
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


def test_granite4h_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``granite-4.0-h-micro-d20-1chip`` configuration
    through its own job builder: the whole train step (two periods of
    nine Mamba-2 layers and one position-free attention layer in one
    scan, each layer its own checkpoint, the four multipliers, the head
    tied to the whole 100,352-row table) compiles for one v5e chip at
    one row of 8192 with the ``ssd_fwd`` and ``ssd_bwd`` kernels and
    the plain flash kernels in it, the decay matrix ``L`` nowhere
    outside a kernel (no ``[.., 256, 256]`` float32 array in the
    program), and what the compiler allocates at the step's peak at or
    under the 15.0 GB ISSUE 57 allowed (``hlo_checks._peak_bytes``;
    ``_resident_bytes`` is printed beside it; ``PERF.md`` section 4 has
    the reading of each rung tried; ``GRANITE4H_COMPILE_LAYERS`` tries
    another depth). The forward-only ``eval_step``, which the
    benchmark's reference check runs, compiles too."""
    from chipbench import worker
    from dlrover_tpu.models import ssd_hybrid
    from dlrover_tpu.parallel.accelerate import accelerate

    with open(os.path.join(REPO, "chipbench", "configs",
                           "granite-4.0-h-micro-d20-1chip.json")) as fh:
        model = json.load(fh)
    committed = model["num_hidden_layers"]
    depth = int(os.environ.get("GRANITE4H_COMPILE_LAYERS", committed))
    model["num_hidden_layers"] = depth
    monkeypatch.setattr(ssd_hybrid, "SsdHybridConfig", functools.partial(
        ssd_hybrid.SsdHybridConfig, kernel_interpret=False))
    job = worker.build_job(model)
    assert (job.seq_len, job.layers) == (8192, depth)
    if depth == committed:
        assert job.param_count == 1_698_459_520
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
    peak = _peak_bytes(compiled)
    print(f"granite4h train_step at {depth} layers "
          f"({job.param_count / 1e9:.3f} B parameters): "
          f"{peak / 1e9:.2f} GB allocated at the peak, "
          f"{_resident_bytes(compiled) / 1e9:.2f} GB estimated")
    if os.environ.get("GRANITE4H_COMPILE_TEXT"):
        with open(os.environ["GRANITE4H_COMPILE_TEXT"], "w") as fh:
            fh.write(text)
    for name in ("ssd_fwd", "ssd_bwd", "flash_fwd", "flash_dkv", "flash_dq"):
        assert f"%{name}." in text, name
    for scope in ("/ssd/", "/ssd_chunk/", "/attn_full/", "/ffn/",
                  "head_loss"):
        assert scope in text, scope
    # the decay matrix of a chunk stays in VMEM
    assert not re.search(r"f32\[[0-9,]*256,256\]", text)
    assert peak <= 15.0e9, f"{peak / 1e9:.2f} GB"
    # the reference check's program
    state = jax.eval_shape(result.init_fn, jax.random.PRNGKey(0))
    compile_once(result.eval_step.lower(state, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        example)))
