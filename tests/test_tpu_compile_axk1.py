"""The ``a.x-k1-ep24-1chip`` configuration asked of the v5e's own
compiler with no chip attached (see ``test_tpu_compile.py``): the held
experts' layer at every rung of its ladder, one expert layer in the
model's own nesting, and the whole train step with the forward-only
step of the reference check. A file of its own so that one pytest-xdist
worker does not carry every configuration's whole-step compile.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hlo_checks import (
    _kernel_names,
    _on,
    _peak_bytes,
    _resident_bytes,
    compile_once,
    compile_step,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _axk1_model():
    import json

    with open(os.path.join(REPO, "chipbench", "configs",
                           "a.x-k1-ep24-1chip.json")) as fh:
        return json.load(fh)


def test_held_experts_compile_at_every_rung_of_the_axk1_ladder(v5e):
    """The held experts' layer at the cell's shapes (8192 tokens of
    7168, 8 of 192 experts of 2048 held, top-8) with its ladder of row
    counts, 6,016 and 12,032: forward and the gradients by the tokens,
    the weights and the three kernels, the branches on the last group's
    end in the program and the grouped kernels under their names."""
    from dlrover_tpu.ops import moe

    tokens, d, f, experts, held, top_k = 8192, 7168, 2048, 192, 8, 8
    ladder = moe.held_row_ladder(tokens, top_k, experts, held, 4.0, 128)
    assert ladder == (6016, 12032)

    def loss(kernels, xt, top_w, top_i):
        out, stats = moe.held_expert_ffn(
            kernels, xt, top_i, top_w, tuple(range(held)), ladder, 128,
            False)
        return out.astype(jnp.float32).sum(), stats

    on = lambda shape, dtype: _on(v5e[0], shape, dtype)  # noqa: E731
    compiled = compile_once(jax.jit(jax.value_and_grad(
        loss, (0, 1, 2), has_aux=True)).lower(
        {name: {"kernel": on((held,) + shape, jnp.bfloat16)}
         for name, shape in (("gate", (d, f)), ("up", (d, f)),
                             ("down", (f, d)))},
        on((tokens, d), jnp.bfloat16), on((tokens, top_k), jnp.float32),
        on((tokens, top_k), jnp.int32)))
    text = compiled.as_text()
    assert " conditional(" in text
    for name in ("gmm", "gmm_dx", "gmm_dw"):
        assert any(name in k for k in _kernel_names(text)), name
    for rows in ladder:  # both rungs' gathers are in the program
        assert f"bf16[{rows},{d}]" in text, rows


@pytest.mark.parametrize("program", ["train", "eval"])
def test_one_axk1_expert_layer_compiles_with_the_branch_in_its_scan(
        v5e, program):
    """One expert layer of the cell's configuration at its widths, in
    the model's own nesting (the layer scan, full remat, the rung's
    branch inside): the gradient of the loss, and the forward alone,
    which once stopped the v5e's compiler where the step did not (a
    scatter inside that scan; PR 34)."""
    from chipbench.families.mla_moe import job
    from dlrover_tpu.models import mla_moe

    config = job.model_config(_axk1_model(), num_layers=1, first_k_dense=0,
                              kernel_interpret=False)
    loss_fn = mla_moe.make_loss_fn(config, head_chunk=1024)
    params = jax.tree.map(
        lambda a: _on(v5e[0], a.shape, a.dtype),
        jax.eval_shape(mla_moe.make_init_fn(config), jax.random.PRNGKey(0)))
    ids = _on(v5e[0], (1, config.max_seq_len), jnp.int32)
    batch = {"input_ids": ids, "labels": ids}
    run = (jax.value_and_grad(loss_fn, has_aux=True) if program == "train"
           else loss_fn)
    text = compile_once(jax.jit(lambda p, b: run(p, b, None)).lower(
        params, batch)).as_text()
    assert " conditional(" in text
    assert any("gmm" in k for k in _kernel_names(text))


def test_axk1_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``a.x-k1-ep24-1chip`` configuration through its
    own job builder: the whole train step compiles for one v5e chip
    with the latent flash and grouped-matmul kernels in it, the forward
    kernel once a scan (a layer's checkpoint keeps its output and
    logsumexp: PR 58); what the compiler allocates at the step's peak
    under the 15.0 GB that ISSUE 34 and 35 allow of the chip's 15.75
    (``hlo_checks._peak_bytes``: 12.00 with the five layers' 0.17 GB
    kept, 11.83 with nothing kept; ``_resident_bytes``, the estimate
    that counts a stack the scan carries twice, is printed beside it:
    15.28 and 14.98, which was 14.18 before the expert section existed
    at two row counts, and 16.82 with all 64 heads)."""
    import functools

    from chipbench import worker
    from dlrover_tpu.models import mla_moe
    from dlrover_tpu.parallel.accelerate import accelerate

    model = _axk1_model()
    # traced on the CPU, compiled for the chip: force the Mosaic kernels
    monkeypatch.setattr(mla_moe, "MlaMoeConfig", functools.partial(
        mla_moe.MlaMoeConfig, kernel_interpret=False))
    job = worker.build_job(model)
    assert (job.param_count, job.seq_len, job.layers) == (
        2_464_177_152, 8192, 5)
    batch = model["assumed"]["batch"]
    example = {"input_ids": np.zeros((batch, job.seq_len), np.int32),
               "labels": np.zeros((batch, job.seq_len), np.int32)}
    result = accelerate(
        job.init_fn, job.loss_fn,
        worker.build_optimizer(model["assumed"]["optimizer"]), example,
        strategy=job.strategy, devices=v5e[:1],
    )
    # the forward alone too, as the benchmark's reference check runs it
    # (a small table scattered together on the device inside the layer
    # scan once stopped the v5e's compiler there and only there)
    state = jax.eval_shape(result.init_fn, jax.random.PRNGKey(0))
    compile_once(result.eval_step.lower(state, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), example)))
    compiled = compile_step(result, example)
    text = compiled.as_text()
    for name in ("flash_mla_fwd", "flash_mla_bwd", "gmm", "gmm_dx",
                 "gmm_dw"):
        assert f"%{name}." in text, name
    assert "flash_mla_dkv" not in text and "flash_mla_dq" not in text
    # the dense layer's scan and the expert layers': neither's forward
    # kernel again in its replay (four in the parent's step, whose
    # checkpoints kept nothing: deviceless compile of b53da53, PR 58)
    assert len(re.findall(r"%flash_mla_fwd\.\d+ = ", text)) == 2
    peak = _peak_bytes(compiled)
    print(f"axk1 train_step: {peak / 1e9:.2f} GB allocated at the peak, "
          f"{_resident_bytes(compiled) / 1e9:.2f} GB estimated")
    assert peak < 15.0e9, f"{peak / 1e9:.2f} GB"
