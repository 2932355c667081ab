"""The ``keye-vl-2.0-30b-a3b-ep4-1chip`` configuration's whole train
step and forward-only step, asked of the v5e's own compiler with no
chip attached (see ``test_tpu_compile.py``).
"""

import os

import jax
import numpy as np
from hlo_checks import _resident_bytes, compile_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_keye_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``keye-vl-2.0-30b-a3b-ep4-1chip`` configuration
    through its own job builder: the whole train step (the sparse
    layers in one scan, each its own checkpoint: indexer, selection,
    selected attention, the indexer's loss, 32 held SwiGLU experts
    routed from the post-attention norm) and the forward-only step of
    the reference check compile for one v5e chip at one row of 16,384,
    with the five sparse kernels and the grouped matmuls in them, under
    the 15.0 GB ISSUE 48 allows of the chip's 15.75 (``PERF.md``
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
    result.eval_step.lower(state, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), example)).compile()
    compiled = compile_step(result, example)
    text = compiled.as_text()
    for name in ("dsa_index_select", "dsa_attn_fwd", "dsa_attn_bwd",
                 "dsa_index_kl_fwd", "dsa_index_kl_bwd",
                 "gmm", "gmm_dx", "gmm_dw"):
        assert f"%{name}." in text, name
    # a row of 16,384 at widths of 128 fits the one backward kernel
    for name in ("dsa_attn_dkv", "dsa_attn_dq", "flash_fwd"):
        assert name not in text, name
    for scope in ("/attn_sparse/", "/dsa_index/", "/moe_router/",
                  "/moe_experts/"):
        assert scope in text, scope
    # no float [rows, rows] score matrix: the selection alone is that
    # large, as bytes
    assert "f32[1,16384,16384]" not in text and (
        "bf16[1,16384,16384]" not in text)
    resident = _resident_bytes(compiled)
    print(f"keye train_step depth {model['num_hidden_layers']}: "
          f"{resident / 1e9:.2f} GB")
    assert resident < 15.0e9, f"{resident / 1e9:.2f} GB"
