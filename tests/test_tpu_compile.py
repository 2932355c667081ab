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
from jax.sharding import SingleDeviceSharding
from hlo_checks import compile_step, lower_step, moves_of, stack_gathers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "examples"))

V5E_HBM_BYTES = 15.75e9  # what memory_stats() reports as bytes_limit


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e:2x2. The persistent compile
    cache is off around these compiles: a deviceless executable is
    written to it but cannot be read back without a chip (the next run
    would warn and compile again)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from dlrover_tpu.parallel.aot import _get_topology_desc_serialized

    try:
        topo = _get_topology_desc_serialized(topologies, "v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this box
        pytest.skip(f"a v5e:2x2 topology cannot be described here: {e}")
    devices = list(topo.devices)
    assert devices[0].device_kind == "TPU v5 lite" and len(devices) == 4
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield devices
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _resident_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _on(device, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(device))


def _kernel_names(text):
    """The instructions of a compiled program that are Mosaic kernels."""
    return {line.split(" = ")[0].strip() for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


def _entry_results(text):
    """The result types of the entry computation's instructions: the
    arrays that exist between operations, not inside a fusion."""
    body = text[text.index("\nENTRY "):]
    return [line.split(" = ", 1)[1]
            for line in body[:body.index("\n}")].splitlines()
            if " = " in line]


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
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2),
                                          has_aux=True)).lower(
        {name: {"kernel": on((held,) + shape, jnp.bfloat16)}
         for name, shape in (("gate", (d, f)), ("up", (d, f)),
                             ("down", (f, d)))},
        on((tokens, d), jnp.bfloat16), on((tokens, top_k), jnp.float32),
        on((tokens, top_k), jnp.int32)).compile()
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
    text = jax.jit(lambda p, b: run(p, b, None)).lower(
        params, batch).compile().as_text()
    assert " conditional(" in text
    assert any("gmm" in k for k in _kernel_names(text))


def test_axk1_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``a.x-k1-ep24-1chip`` configuration through its
    own job builder: the whole train step compiles for one v5e chip
    with the latent flash and grouped-matmul kernels in it, under the
    15.0 GB that ISSUE 34 and 35 allow of the chip's 15.75 (14.18 with
    16 heads, all 64 gave 16.82; 14.98 since the expert section exists
    at two row counts, the backward's outputs live through a branch)."""
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
    result.eval_step.lower(state, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), example)).compile()
    compiled = compile_step(result, example)
    text = compiled.as_text()
    for name in ("flash_mla_fwd", "flash_mla_bwd", "gmm", "gmm_dx",
                 "gmm_dw"):
        assert f"%{name}." in text, name
    assert "flash_mla_dkv" not in text and "flash_mla_dq" not in text
    resident = _resident_bytes(compiled)
    print(f"axk1 train_step: {resident / 1e9:.2f} GB")
    assert resident < 15.0e9, f"{resident / 1e9:.2f} GB"


def test_xing4_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``xing4.0-29b-a4b-ep4-1chip`` configuration
    through its own job builder: the whole train step (four streams
    through the layer scans, the prediction module and its head pass)
    and the forward-only step of the reference check compile for one
    v5e chip with the latent flash and grouped-matmul kernels in them,
    under the 15.0 GB ISSUE 36 allows of the chip's 15.75: 14.40 at
    2 + 5 layers since the streams are one flat residual (14.81 with a
    stream axis; then 2 + 4: 12.99; 2 + 6: 16.60, and 17.90 before a
    hyper-connection's pieces kept their arguments alone for the
    backward). And the carry ``[B, S, 4 * 3584]`` stays where it is:
    no ``copy`` under the hyper-connections' scopes moves it to another
    layout (with a stream axis 32 did, in the forward, the replay and
    the backward: XLA put that axis outermost and materialised the flat
    view the norm and the projection read)."""
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
    result.eval_step.lower(state, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), example)).compile()
    # a kernel body is lowered once a call site, at every boot whatever
    # the compile cache holds; the hyper-connections' call sites (two a
    # sublayer, in three scans, forward, replay and backward) share one
    # callable a kernel and shape, so the module holds each body once or
    # twice: 71 calls, 65 without the streams' kernels, 99 with a body
    # a site (ISSUE 46)
    lowered = lower_step(result, example)
    assert lowered.as_text().count("tpu_custom_call") <= 80
    compiled = lowered.compile()
    text = compiled.as_text()
    for name in ("flash_mla_fwd", "flash_mla_bwd", "gmm", "gmm_dx",
                 "gmm_dw"):
        assert f"%{name}." in text, name
    assert "flash_mla_dkv" not in text and "flash_mla_dq" not in text
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
    resident = _resident_bytes(compiled)
    print(f"xing4 train_step: {resident / 1e9:.2f} GB, carry-sized "
          f"copies {[(m.name, m.relayout) for m in moves]}")
    assert resident < 15.0e9, f"{resident / 1e9:.2f} GB"


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
    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        wide(dk), wide(dk), wide(dv), narrow, narrow).compile().as_text()
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
    result.eval_step.lower(state, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), example)).compile()
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
