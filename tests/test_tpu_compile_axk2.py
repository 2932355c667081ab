"""The ``a.x-k2-ep32-1chip`` configuration's whole train step and
forward-only step, asked of the v5e's own compiler with no chip
attached (see ``test_tpu_compile.py``).
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


def test_axk2_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``a.x-k2-ep32-1chip`` configuration through its
    own job builder: the whole train step (a dense and four expert
    layers in two scans, each layer its own checkpoint: gated norms,
    latent projections, the 64-head indexer and its selection, the
    selected latent attention at a contraction of 192, its gate, the
    indexer's loss, a group-limited router, the shared and 8 held
    experts) and the forward-only step of the reference check compile
    for one v5e chip at one row of 8192, with the four sparse kernels
    and the grouped matmuls in them and no latent flash kernel; the
    selected attention's forward and the indexer's loss's one kernel
    once a scan (a layer's checkpoint keeps the one's output and
    logsumexp and the other's three gradients) and the selection twice;
    what the compiler allocates at the step's peak under 15.25 GB of the
    chip's 15.75: 15.11 GB with the indexer's loss's gradients kept
    (0.69 GB over the five layers; 14.61 before, under the 15.0 GB ISSUE
    51 allowed), and the cell ran at that on the chip (ISSUE 52,
    ``PERF.md`` section 6) (``hlo_checks._peak_bytes``;
    ``_resident_bytes`` is printed beside it; ``PERF.md`` section 4 has
    the reading at each number of heads tried; ``AXK2_COMPILE_HEADS``
    tries another)."""
    import functools
    import json

    from chipbench import worker
    from dlrover_tpu.models import mla_moe
    from dlrover_tpu.parallel.accelerate import accelerate

    with open(os.path.join(REPO, "chipbench", "configs",
                           "a.x-k2-ep32-1chip.json")) as fh:
        model = json.load(fh)
    committed = model["num_attention_heads"]
    heads = int(os.environ.get("AXK2_COMPILE_HEADS", committed))
    model["num_attention_heads"] = model["num_key_value_heads"] = heads
    monkeypatch.setattr(mla_moe, "MlaMoeConfig", functools.partial(
        mla_moe.MlaMoeConfig, kernel_interpret=False))
    job = worker.build_job(model)
    if heads == committed:
        assert (job.param_count, job.seq_len, job.layers) == (
            2_792_613_120, 8192, 5)
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
    # a layer's replay leaves the kept forward out and runs the
    # selection again, in the dense layers' scan and in the expert
    # layers' alike
    assert [len(re.findall(rf"%{name}\.\d+ = ", text)) for name in (
        "dsa_attn_fwd", "dsa_index_select")] == [2, 4]
    # the indexer's loss, value and gradient, is one kernel in each
    # scan's forward pass: a layer's checkpoint keeps its three gradients
    assert len(re.findall(r"%dsa_index_kl\.\d+ = ", text)) == 2
    assert "dsa_index_kl_fwd" not in text and "dsa_index_kl_bwd" not in text
    # a row of 8192 at scores of 192 and values of 128 fits the one
    # backward kernel, and no layer runs dense latent attention
    # (as instructions: the module's table of stack frames may name a
    # function of the same stem that an earlier test of this process
    # traced)
    for name in ("dsa_attn_dkv", "dsa_attn_dq", "flash_mla_"):
        assert f"%{name}" not in text, name
    for scope in ("/mla/", "/attn_sparse/", "/dsa_index/", "/attn_gate/",
                  "/gated_norm/", "/moe_router/", "/moe_groups/",
                  "/moe_experts/"):
        assert scope in text, scope
    # no float [rows, rows] score matrix: the selection alone is that
    # large, as bytes
    assert "f32[1,8192,8192]" not in text and (
        "bf16[1,8192,8192]" not in text)
    peak = _peak_bytes(compiled)
    print(f"axk2 train_step at {heads} heads: "
          f"{peak / 1e9:.2f} GB allocated at the peak, "
          f"{_resident_bytes(compiled) / 1e9:.2f} GB estimated")
    assert peak < 15.25e9, f"{peak / 1e9:.2f} GB"
