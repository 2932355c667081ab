"""The ``olmo-hybrid-7b-d8-1chip`` configuration asked of the v5e's own
compiler with no chip attached (see ``test_tpu_compile.py``): the gated
delta rule's kernels at the cell's shape, and the whole train step with
the forward-only step of the reference check.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hlo_checks import _on, _resident_bytes, compile_once, compile_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("heads", [30, 10])
def test_gated_delta_compiles_at_olmohybrid_shape(v5e, heads):
    """One linear layer's rule at the cell's shape (one row of 8192
    tokens, keys of 96 and values of 192, bf16; all 30 heads, and the
    10 a head group holds), on the tiles ``chain_tiles`` picks: forward
    and backward lower to Mosaic kernels named ``gdn_fwd`` and
    ``gdn_bwd`` that fit their VMEM, the residual is the float32 state
    each chunk starts from, and no state a token exists."""
    from dlrover_tpu.ops.gated_delta import chain_tiles, gated_delta_rule

    seq, dk, dv = 8192, 96, 192
    chunk, group = chain_tiles(seq, heads)

    def loss(*args):
        return gated_delta_rule(*args, interpret=False)[0].astype(
            jnp.float32).sum()

    wide = lambda d: _on(v5e[0], (1, seq, heads, d), jnp.bfloat16)  # noqa
    narrow = _on(v5e[0], (1, seq, heads), jnp.float32)
    text = compile_once(jax.jit(jax.grad(loss, argnums=range(5))).lower(
        wide(dk), wide(dk), wide(dv), narrow, narrow)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert "gdn_fwd" in text and "gdn_bwd" in text
    assert f"f32[1,{heads},{seq // chunk},{dk},{dv}]" in text
    assert f"{seq},{heads},{dk},{dv}]" not in text
    assert heads % group == 0


def test_olmohybrid_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``olmo-hybrid-7b-d8-1chip`` configuration
    through its own job builder: the whole train step (two periods of
    three gated-delta-rule layers and one full layer in one scan, the
    rule's heads in three groups, each its own checkpoint) and the
    forward-only step of the reference check compile for one v5e chip
    at one row of 8192, with the ``gdn_*`` and the plain flash kernels
    in them, at the 15.0 GB ISSUE 43 allows of the chip's 15.75:
    14.995 with a quarter of the vocabulary (the whole vocabulary 16.15;
    18.61 while the triangular inverse kept every level of its doubling
    for the backward, 16.90 with the inverse's own gradient, 15.33 with
    the head groups, 15.06 before the convolution, SiLU and l2 norm
    became a checkpoint of their own)."""
    import functools
    import json

    from chipbench import worker
    from dlrover_tpu.models import delta_hybrid
    from dlrover_tpu.parallel.accelerate import accelerate

    with open(os.path.join(REPO, "chipbench", "configs",
                           "olmo-hybrid-7b-d8-1chip.json")) as fh:
        model = json.load(fh)
    monkeypatch.setattr(delta_hybrid, "DeltaHybridConfig", functools.partial(
        delta_hybrid.DeltaHybridConfig, kernel_interpret=False))
    job = worker.build_job(model)
    assert (job.param_count, job.seq_len, job.layers) == (
        1_857_720_552, 8192, 8)
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
    for name in ("gdn_fwd", "gdn_bwd", "flash_fwd", "flash_dkv", "flash_dq"):
        assert f"{name}." in text, name
    for scope in ("/gdn/", "/gdn_chunk/", "/attn_full/", "/ffn/"):
        assert scope in text, scope
    # no [rows, rows] score matrix of a head, no state a token
    assert "8192,8192]" not in text and "8192,30,96,192]" not in text
    resident = _resident_bytes(compiled)
    print(f"olmohybrid train_step: {resident / 1e9:.3f} GB")
    assert resident <= 15.0e9, f"{resident / 1e9:.3f} GB"
