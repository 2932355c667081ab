"""The main path's kernels and train step, asked of the v5e's own
compiler with no chip attached.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``v5e:2x2``: four ``TPU v5 lite`` devices;
``v5e:1x1`` is refused, so one chip is ``devices[0]``). It refuses what
the chip would refuse — a tile the lane rule rejects, more scoped VMEM
than a kernel may use, a program that does not fit 16 GB — which the
Pallas interpreter never shows. Nothing runs: these are compiles, not
chip runs, and say nothing about results or times.

The tracing host is the CPU, so code that asks ``jax.default_backend()``
would pick interpret mode; the tests steer it (``interpret=False``,
``flash_interpret=False``) and compile the jitted function itself.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hlo_checks import (
    V5E_HBM_BYTES,
    _entry_results,
    _kernel_names,
    _on,
    _resident_bytes,
    compile_once,
    compile_step,
    stack_gathers,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "examples"))


@pytest.mark.parametrize("segmented", [False, True],
                         ids=["plain", "segmented"])
def test_flash_fwd_bwd_compiles_at_7b_head_shape(v5e, segmented):
    """32 heads of 128 over 4096 tokens, bf16, the model's own tiles
    (512/1024): forward, dKV and dQ all lower to Mosaic and compile."""
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_segmented,
    )

    cfg = LlamaConfig()
    shape = (2, cfg.num_heads, cfg.max_seq_len, cfg.head_dim)
    bq, bk = cfg.flash_block_q, cfg.flash_block_k

    def attend(q, k, v, seg):
        if segmented:
            return flash_attention_segmented(
                q, k, v, seg, True, None, bq, bk, False)
        return flash_attention(q, k, v, True, None, bq, bk, False)

    def fwd_bwd(q, k, v, seg, do):
        out, vjp = jax.vjp(lambda q, k, v: attend(q, k, v, seg), q, k, v)
        return (out, *vjp(do))

    x = _on(v5e[0], shape, jnp.bfloat16)
    seg = _on(v5e[0], (shape[0], shape[2]), jnp.int32)
    compiled = jax.jit(fwd_bwd).lower(x, x, x, seg, x).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    # the kernels' instructions carry the names a trace shows them by
    kernels = _kernel_names(text)
    for name in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert any(name in k for k in kernels), (name, kernels)


@pytest.mark.parametrize("rows,d,f,experts", [
    (32768, 2048, 1024, 64),  # OLMoE-1B-7B: up/gate
    (32768, 1024, 2048, 64),  # OLMoE-1B-7B: down
    # the Llama-2-7B FFN as 8 experts: the kernels size their tiles
    # from the shapes (block_f is an upper bound)
    (8192, 4096, 11008, 8),
    (8192, 11008, 4096, 8),
])
def test_grouped_matmul_fwd_bwd_compiles(v5e, rows, d, f, experts):
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    block_t = 128  # ops.moe's row tile

    def loss(x, w, tile_expert):
        y = grouped_matmul(x, w, tile_expert, block_t, 512, False)
        return (y.astype(jnp.float32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        _on(v5e[0], (rows, d), jnp.bfloat16),
        _on(v5e[0], (experts, d, f), jnp.bfloat16),
        _on(v5e[0], (rows // block_t,), jnp.int32),
    ).compile()
    # forward is folded into dx/dw here: the two backward kernels
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_selective_scan_compiles_at_phi4flash_shape(v5e):
    """One Mamba layer's scan at the cell's shape (one row of 8192
    tokens, 5120 channels, 16 states, float32) with the model's own
    chunk and channel block: forward and backward lower to Mosaic, fit
    the kernels' VMEM, and no array of the whole state history
    (``[8192, 5120, 16]``, 2.7 GB) exists in either direction."""
    from dlrover_tpu.models.sambay import SambaYConfig
    from dlrover_tpu.ops.selective_scan import selective_scan

    cfg = SambaYConfig()
    seq, channels, states = cfg.max_seq_len, cfg.d_inner, cfg.d_state
    assert (seq, channels, states) == (8192, 5120, 16)

    def loss(*args):
        return selective_scan(*args, chunk=cfg.scan_chunk,
                              block_c=cfg.scan_block_c,
                              interpret=False).sum()

    f32 = jnp.float32
    text = jax.jit(jax.grad(loss, argnums=range(6))).lower(
        _on(v5e[0], (1, seq, channels), f32),
        _on(v5e[0], (1, seq, channels), f32),
        _on(v5e[0], (channels, states), f32),
        _on(v5e[0], (1, seq, states), f32),
        _on(v5e[0], (1, seq, states), f32),
        _on(v5e[0], (channels,), f32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    for history in ("8192,5120,16]", "8192,16,5120]"):
        assert history not in text
    # the residual is the state each chunk starts from, 1/chunk of it
    assert f"f32[1,{seq // cfg.scan_chunk},16,5120]" in text


@pytest.mark.parametrize("window", [512, None], ids=["window", "full"])
def test_flash_compiles_at_phi4flash_head_shape(v5e, window):
    """One call of the differential attention: 20 query and 10 key heads
    of 64 against 10 value heads of 128 over 8192 tokens, bf16, the
    model's own tiles, windowed (the band-limited grid) and full."""
    from dlrover_tpu.models.sambay import SambaYConfig
    from dlrover_tpu.ops.flash_attention import flash_attention_auto

    cfg = SambaYConfig()
    seq = cfg.max_seq_len

    def loss(q, k, v):
        return flash_attention_auto(
            q, k, v, causal=True,
            block_q=cfg.window_block if window else cfg.flash_block_q,
            block_k=cfg.flash_block_k, interpret=False,
            window=window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _on(v5e[0], (1, 20, seq, cfg.head_dim), jnp.bfloat16),
        _on(v5e[0], (1, 10, seq, cfg.head_dim), jnp.bfloat16),
        _on(v5e[0], (1, 10, seq, cfg.value_dim), jnp.bfloat16),
    ).compile().as_text()
    names = ("flash_win_fwd", "flash_win_bwd") if window \
        else ("flash_fwd", "flash_dkv", "flash_dq")
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert name in text
    assert ("flash_win" in text) == bool(window)
    assert f"{seq},{seq}]" not in text  # no score matrix


WINDOW_CELLS = {
    # 16,384 tokens under a window of 4096: squares of 1024, five a band
    "smallthinker": (28, 4, 128, 128, [(1, 28, 16, 5), (1, 4, 7, 16, 5)]),
    # 8192 under 512: the forward at 512 x 1024 (two k tiles a q block,
    # one of them skipped every other block), the backward in squares
    # of 512, two a band; a call is one head of each of the 20 pairs
    "phi4flash": (20, 10, 64, 128, [(1, 20, 16, 2), (1, 10, 2, 16, 2)]),
}


def _window_layer(v5e, cell):
    """(jitted gradient, operand shapes on the chip, the model's
    configuration) of one window layer's attention at a cell's shapes,
    its model's defaults."""
    from dlrover_tpu.models.gqa_moe import GqaMoeConfig
    from dlrover_tpu.models.sambay import SambaYConfig
    from dlrover_tpu.ops.flash_attention import flash_attention_auto

    heads, kv_heads, dim, value_dim, _ = WINDOW_CELLS[cell]
    cfg = GqaMoeConfig() if cell == "smallthinker" else SambaYConfig()
    seq = cfg.max_seq_len
    assert (cfg.num_heads if cell == "smallthinker"
            else cfg.num_heads // 2) == heads

    def loss(q, k, v):
        return flash_attention_auto(
            q, k, v, causal=True, block_q=cfg.window_block,
            block_k=cfg.flash_block_k, interpret=False,
            window=cfg.sliding_window).astype(jnp.float32).sum()

    shapes = (_on(v5e[0], (1, heads, seq, dim), jnp.bfloat16),
              _on(v5e[0], (1, kv_heads, seq, dim), jnp.bfloat16),
              _on(v5e[0], (1, kv_heads, seq, value_dim), jnp.bfloat16))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))), shapes, cfg


@pytest.mark.parametrize("cell", WINDOW_CELLS)
def test_a_window_layer_compiles_on_the_grids_the_rule_chose(v5e, cell):
    """One window layer's attention at each window cell's shapes: the
    two ``flash_win_*`` kernels by name in the program the v5e's
    compiler accepts, on the grids ``band_walk`` gives for the tiles
    ``window_tiles`` picks from row and window. The backward is one
    kernel whose whole-row accumulators (48 MiB of VMEM with their
    output rows at SmallThinker's shape, 24 at Phi-4-mini-flash's) the
    compiler grants: no dKV, no dQ, and no float32 gradient of a
    query's or a key's size between operations."""
    from dlrover_tpu.ops.flash_attention import band_walk, window_tiles

    heads, kv_heads, _, _, grids = WINDOW_CELLS[cell]
    grad, shapes, cfg = _window_layer(v5e, cell)
    seq, window = cfg.max_seq_len, cfg.sliding_window
    fwd, bwd = window_tiles(seq, window, cfg.window_block)
    forward, backward = band_walk(seq, window, *fwd), band_walk(
        seq, window, *bwd)
    assert grids == [
        (1, heads, seq // fwd[0], forward.k_steps),
        (1, kv_heads, heads // kv_heads, seq // bwd[1], backward.q_steps)]
    traced = str(jax.make_jaxpr(grad)(*shapes))
    compiled = grad.lower(*shapes).compile().as_text()
    for name, grid in zip(("flash_win_fwd", "flash_win_bwd"), grids):
        assert f"name={name}" in traced and f"grid={grid}" in traced, name
        assert name in compiled, name
    assert "flash_win_dkv" not in compiled and "flash_win_dq" not in compiled
    assert compiled.count("tpu_custom_call") == 2
    assert f"{seq},{seq}]" not in compiled  # no score matrix
    assert not [r for r in _entry_results(compiled)
                if r.startswith("f32[1,") and f",{seq}," in r]


@pytest.mark.parametrize("cell", WINDOW_CELLS)
def test_a_row_over_the_budget_compiles_as_the_two_kernels(
        v5e, cell, monkeypatch):
    """The same layers with no room for the one kernel's rows: the dKV
    and the dQ kernel, which stay in the file for longer rows, still
    compile at these shapes, on the grids they had."""
    from dlrover_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_WIN_ROW_STATE_BUDGET_BYTES", 0)
    heads, kv_heads, _, _, grids = WINDOW_CELLS[cell]
    grad, shapes, cfg = _window_layer(v5e, cell)
    seq, window = cfg.max_seq_len, cfg.sliding_window
    bq, bk = fa.window_tiles(seq, window, cfg.window_block)[1]
    walk = fa.band_walk(seq, window, bq, bk)
    traced = str(jax.make_jaxpr(grad)(*shapes))
    compiled = grad.lower(*shapes).compile().as_text()
    for name, grid in (
            ("flash_win_fwd", grids[0]),
            ("flash_win_dkv", (1, kv_heads, seq // bk, heads // kv_heads,
                               walk.q_steps)),
            ("flash_win_dq", (1, heads, seq // bq, walk.k_steps))):
        assert f"name={name}" in traced and f"grid={grid}" in traced, name
        assert name in compiled, name
    assert "flash_win_bwd" not in compiled
    assert compiled.count("tpu_custom_call") == 3


def test_smoke_train_step_fits_one_v5e(v5e):
    """The whole ``accelerate`` train step of chip_smoke.py's
    configuration (its MODEL_ARGS through the worker's own build_job)
    compiles for one v5e chip with the Mosaic kernels in it and under
    the chip's memory. This is where the smoke's size was settled."""
    import chip_smoke
    import train_llama

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import accelerate
    from dlrover_tpu.parallel.mesh import MeshPlan

    batch = chip_smoke.ONE_CHIP_BATCH
    args = train_llama.build_parser().parse_args(
        [*chip_smoke.MODEL_ARGS, "--batch", str(batch)])
    config, strategy, _, optimizer, _ = train_llama.build_job(
        args, MeshPlan(data=1, fsdp=1))
    assert (config.hidden_size, config.intermediate_size,
            config.num_heads, config.head_dim, config.vocab_size,
            config.max_seq_len) == (4096, 11008, 32, 128, 32000, 4096)
    assert llama.param_count(config) == 1_881_214_976
    # traced on the CPU, compiled for the chip: force the Mosaic kernel
    config = dataclasses.replace(config, flash_interpret=False)
    example = {
        "input_ids": np.zeros((batch, config.max_seq_len), np.int32),
        "labels": np.zeros((batch, config.max_seq_len), np.int32),
    }
    result = accelerate(
        llama.make_init_fn(config),
        llama.make_loss_fn(config, head_chunk=args.head_chunk),
        optimizer, example, strategy=strategy, devices=v5e[:1],
    )
    compiled = compile_step(result, example)
    assert compiled.as_text().count("tpu_custom_call") >= 3
    resident = _resident_bytes(compiled)
    assert resident < V5E_HBM_BYTES, f"{resident / 1e9:.2f} GB"


def test_fsdp4_step_gathers_one_layer_at_a_time(v5e, monkeypatch):
    """The benchmark's four-chip configuration (Mistral-7B widths, depth
    20, ``MeshPlan(data=1, fsdp=4)``) through its own job builder,
    compiled for the 2x2: no all-gather yields a whole stack
    ``[20, ...]`` (before ISSUE 27 the layer axis sat on ``fsdp`` and
    every layer of the scan gathered ``bf16[20,4096,14336]``), the
    per-layer gathers are there, and the step takes less memory than
    the 12.54 GB it took then."""
    import functools
    import json

    from chipbench import worker
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import accelerate

    with open(os.path.join(REPO, "chipbench", "configs",
                           "mistral-7b-v0.3-d20-fsdp4.json")) as fh:
        model = json.load(fh)
    assert model["layout"] == {"data": 1, "fsdp": 4}
    layers, batch = model["num_hidden_layers"], model["assumed"]["batch"]
    # traced on the CPU, compiled for the chip: force the Mosaic kernel
    monkeypatch.setattr(llama, "LlamaConfig", functools.partial(
        llama.LlamaConfig, flash_interpret=False))
    job = worker.build_job(model)
    example = {"input_ids": np.zeros((batch, job.seq_len), np.int32),
               "labels": np.zeros((batch, job.seq_len), np.int32)}
    result = accelerate(
        job.init_fn, job.loss_fn,
        worker.build_optimizer(model["assumed"]["optimizer"]), example,
        strategy=job.strategy, devices=v5e,
    )
    compiled = compile_step(result, example)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert stack_gathers(text, layers) == []
    hidden, ffn = model["hidden_size"], model["intermediate_size"]
    assert f"bf16[{hidden},{ffn}]" in text  # one layer's gate or up
    resident = _resident_bytes(compiled)
    assert resident < 12.54e9, f"{resident / 1e9:.2f} GB"


@pytest.mark.parametrize("batch,heads,seq,backward", [
    (1, 16, 8192, ["flash_mla_bwd"]),
    (2, 32, 4096, ["flash_mla_bwd"]),
    (1, 4, 16384, ["flash_mla_dkv", "flash_mla_dq"]),
], ids=["axk1", "xing4", "rows-over-the-budget"])
def test_latent_flash_compiles_at_the_cells_head_shapes(v5e, batch, heads,
                                                        seq, backward):
    """Heads of 128 + 64 against one shared rotary key head and values
    of 128, bf16, the model's own tiles (512/1024), at the rows of the
    two cells that run them: forward and the one-kernel backward lower
    to Mosaic under their names, dKV and dQ are not there, no score
    matrix exists, and the query's float32 gradient stays in VMEM: no
    float32 array of its size in the program. Rows of 16384, whose
    accumulators the one kernel may not hold, lower as dKV and dQ."""
    from dlrover_tpu.ops.flash_attention import flash_attention_mla

    def fwd_bwd(qn, qr, kn, kr, v, do):
        out, vjp = jax.vjp(lambda *a: flash_attention_mla(
            *a, None, 512, 1024, False), qn, qr, kn, kr, v)
        return (out, *vjp(do))

    on = lambda h, d: _on(  # noqa: E731
        v5e[0], (batch, h, seq, d), jnp.bfloat16)
    compiled = jax.jit(fwd_bwd).lower(
        on(heads, 128), on(heads, 64), on(heads, 128), on(1, 64),
        on(heads, 128), on(heads, 128)).compile()
    text = compiled.as_text()
    kernels = _kernel_names(text)
    assert len(kernels) == 1 + len(backward), kernels
    for name in ["flash_mla_fwd", *backward]:
        assert any(name in k for k in kernels), (name, kernels)
    assert f"{seq},{seq}]" not in text
    assert not [r for r in _entry_results(text)
                if r.startswith(f"f32[{batch},{heads},{seq},")]


@pytest.mark.parametrize("d,f", [(7168, 2048), (2048, 7168)],
                         ids=["gate-up", "down"])
def test_grouped_matmul_compiles_at_the_axk1_expert_shape(v5e, d, f):
    """The held experts' row buffer (4 x 2,731 rows and a tile an
    expert: 12,032) against 8 experts of 7168 x 2048: forward, dx
    (the weights read as they lie, no transposed copy) and dW."""
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    rows, experts, block_t = 12032, 8, 128

    def loss(x, w, tile_expert, num_tiles):
        return grouped_matmul(
            x, w, tile_expert, block_t, interpret=False,
            num_tiles=num_tiles).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(
        _on(v5e[0], (rows, d), jnp.bfloat16),
        _on(v5e[0], (experts, d, f), jnp.bfloat16),
        _on(v5e[0], (rows // block_t,), jnp.int32),
        _on(v5e[0], (1,), jnp.int32)).compile()
    text = compiled.as_text()
    kernels = _kernel_names(text)
    for name in ("gmm_dx", "gmm_dw"):
        assert any(name in k for k in kernels), (name, kernels)
    assert f"bf16[{experts},{f},{d}]" not in text  # no transposed weights


@pytest.mark.parametrize("second", ["the-same", "another-module",
                                    "another-flag", "a-file-cut-short"])
def test_a_module_compiled_before_is_read_back(tmp_path, monkeypatch,
                                               second):
    """``compile_once`` on the CPU's compiler (plumbing alone): the very
    same module for the same compiler is not compiled again and reads
    the text and the memory analysis the first compile gave; another
    module, another flag and a kept file that was cut short compile."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
    compiles = []

    def lowered(scale):
        found = jax.jit(lambda x: jnp.sin(x) * scale).lower(jnp.ones((8,)))
        compile_ = found.compile
        found.compile = lambda: compiles.append(scale) or compile_()
        return found

    first = compile_once(lowered(2.0))
    kept = list((tmp_path / "tpu_compiles").iterdir())
    assert compiles == [2.0] and len(kept) == 1
    assert "sine" in first.as_text()
    assert first.memory_analysis().argument_size_in_bytes == 32
    if second == "another-flag":
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_tpu_anything=1")
    if second == "a-file-cut-short":
        kept[0].write_bytes(kept[0].read_bytes()[:40])
    scale = 3.0 if second == "another-module" else 2.0
    again = compile_once(lowered(scale))
    assert compiles == [2.0] + [scale] * (second != "the-same")
    assert len(list((tmp_path / "tpu_compiles").iterdir())) == (
        2 if second in ("another-module", "another-flag") else 1)
    assert vars(again.memory_analysis()) == vars(first.memory_analysis())
    if second == "the-same":
        assert again.as_text() == first.as_text()
