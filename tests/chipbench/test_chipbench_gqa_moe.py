"""``chipbench/families/gqa_moe/``: the plain reference (float32
``jax.numpy``, dense masked softmax a head in blocks of query rows, the
held experts as a loop) against ``models/gqa_moe.py``, the code the
cell runs, at a toy size on the CPU: the loss and the hidden states;
the faults the comparison has to catch, each mechanism wrong in turn;
``flops.py`` by hand; the new readers; the configuration against what
its source publishes.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its Pallas kernels in the interpreter),
so they differ only by the order of float32 sums. Every gradient is
compared in ``tests/test_gqa_moe.py``, on both of the program's paths.
On the chip the same comparison runs in every first worker round at the
published widths, against bf16 compute, with the limits ``job.py``
gives.
"""

import copy
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import worker  # noqa: E402
from chipbench.families.gqa_moe import flops, job  # noqa: E402

sys.path.insert(0, HERE)
import gqa_moe_controls as controls  # noqa: E402

LOSS_TOL = 1e-5
CELL = "smallthinker-1chip.steady"


def toy():
    with open(os.path.join(HERE, "tiny_gqa_moe.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "smallthinker-21b-a3b-ep4-1chip.json")) as f:
        return json.load(f)


def perturbed(init_fn):
    """Initial weights with the norm scales moved off 1, so that a
    reference that dropped a norm would show."""
    return jax.jit(lambda key: jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                              a.shape, a.dtype),
        init_fn(key)))(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def built():
    model = toy()
    the_job = worker.build_job(model)
    params = perturbed(the_job.init_fn)
    batch = worker.batch_for(11, 0, the_job.vocab_size, 1, the_job.seq_len)
    return model, the_job, params, batch


def reference_loss(model, params, batch, hidden=None):
    return job.reference_loss_of(model, job.model_config(toy()), params,
                                 batch["input_ids"][0], batch["labels"][0],
                                 hidden=hidden)


def hidden_error(model, params, batch):
    """The program's final hidden states against those of the reference
    that ``model`` describes, as ``job.py``'s second limit reads them."""
    from dlrover_tpu.models import gqa_moe
    program = gqa_moe.apply_hidden(params, batch["input_ids"],
                                   job.model_config(toy()))[0][0]
    plain = []
    reference_loss(model, params, batch, plain)
    return job.hidden_error(program, plain[0])


def test_the_program_agrees_with_the_reference(built):
    model, the_job, params, batch = built
    system, aux = the_job.loss_fn(params, batch, None)
    assert abs(float(system) - reference_loss(model, params, batch)) < LOSS_TOL
    assert float(aux["moe_rows_dropped"]) == 0
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"] == 1e-4
    assert hidden_error(model, params, batch) < 1e-5
    assert (the_job.layers, the_job.seq_len, the_job.vocab_size) == (
        4, 64, 512)
    assert the_job.param_count == flops.param_count(model)


def test_the_jobs_table_is_the_models_at_the_files_std():
    """``assumed.embed_std`` is the benchmark's reading, not a field of
    the model: the job scales the table the model's ``init`` makes at
    std 1, and nothing else."""
    from dlrover_tpu.models import gqa_moe
    model = toy()
    assert model["assumed"]["embed_std"] == (
        cell_model()["assumed"]["embed_std"]) == 2.0
    the_job = worker.build_job(model)
    key = jax.random.PRNGKey(5)
    ours = the_job.init_fn(key)
    theirs = gqa_moe.init(key, job.model_config(model))
    table = theirs["embed_tokens"]["embedding"]
    assert 0.9 < float(jnp.std(table)) < 1.1
    theirs["embed_tokens"]["embedding"] = 2.0 * table
    jax.tree.map(np.testing.assert_array_equal, ours, theirs)
    assert the_job.init_fn.layer_kinds == {"attn_full": 2, "attn_window": 2}


def test_the_jobs_check_reads_nan_past_the_hidden_limit(built, capsys):
    """What ``worker.py`` calls: the reference's loss where the hidden
    states agree, NaN (which fails the worker's comparison) where they
    do not, the reading printed either way."""
    model, the_job, params, batch = built
    ids, labels = batch["input_ids"][0], batch["labels"][0]
    sound = the_job.reference_loss(params, ids, labels)
    assert sound == reference_loss(model, params, batch)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_hidden"
    assert line["median_token_error"] < line["tolerance"] == 1e-4
    with controls.applied(model, "e4m3 operands"):
        assert np.isnan(the_job.reference_loss(params, ids, labels))


def test_a_dropped_row_makes_the_jobs_loss_nan():
    """The cell promises no drops: with a row buffer a twentieth of
    what uniform routing needs the job's loss is NaN (the model's own
    stays finite, ``tests/test_gqa_moe.py``)."""
    the_job = job.build(toy(), expert_row_factor=0.05)
    params = the_job.init_fn(jax.random.PRNGKey(3))
    batch = worker.batch_for(11, 0, the_job.vocab_size, 2, the_job.seq_len)
    loss, aux = the_job.loss_fn(params, batch, None)
    assert float(aux["moe_rows_dropped"]) > 0
    assert np.isnan(float(loss))


@pytest.mark.parametrize("fault", controls.CONTROLS,
                         ids=[f.replace(" ", "-") for f in controls.CONTROLS])
def test_the_comparison_catches(built, fault):
    """Each control (ISSUE 41's seven mechanisms and the precision),
    put into the reference alone, moves the median token's hidden state
    away from the program's by 100 times this comparison's limit (1e-4
    in float32) and more: the hidden states are the limit that feels a
    mechanism (the loss at random weights hardly does, ``job.py``)."""
    model, _, params, batch = built
    with controls.applied(model, fault):
        apart = hidden_error(model, params, batch)
    print(fault, apart)
    assert apart > 100 * job.HIDDEN_TOL["float32"], (fault, apart)
    assert model == toy()  # the control is taken out again


def test_the_controls_script_gives_the_harness_verdicts(capsys):
    """``gqa_moe_controls.py`` as the chip runs it, at the toy size:
    the worker's own ``ReferenceCheck`` says ``ok`` of the sound
    reference on both seeds and not ``ok`` under every control."""
    assert controls.main(["--config", os.path.join(HERE, "tiny_gqa_moe.json"),
                          "--controls", "3000004311",
                          "--sound", "3000004312"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [(line["seed"], line["control"]) for line in lines] == [
        (3000004311, "sound")] + [(3000004311, c) for c in controls.CONTROLS
                                  ] + [(3000004312, "sound")]
    for line in lines:
        assert line["ok"] == (line["control"] == "sound"), line
        assert line["tolerance"] == line["hidden_tolerance"] == 1e-4


def test_the_cell_keeps_every_published_width():
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "smallthinker-21ba3b-instruct.json")) as f:
        published = json.load(f)["config"]
    cut = set(model["reduced"])
    assert cut == {"num_hidden_layers", "moe_num_primary_experts",
                   "vocab_size"}
    for key, value in published.items():
        if key not in cut:
            assert model[key] == value, key
    assert (model["hidden_size"], model["head_dim"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["moe_ffn_hidden_size"],
            model["moe_num_active_primary_experts"],
            model["sliding_window_size"], model["rope_theta"],
            model["max_position_embeddings"]) == (
        2560, 128, 28, 4, 768, 6, 4096, 1.5e6, 16384)
    # both lists whole, as published: 52 entries, one period of four
    assert model["sliding_window_layout"] == model["rope_layout"] == (
        [0, 1, 1, 1] * 13)
    dep = model["deployment"]
    assert dep["published_moe_num_primary_experts"] == 64
    assert dep["experts_held"] == list(range(16))
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] == 4
    assert model["vocab_size"] * dep["vocabulary_ways"] == 151936
    # the name under which a reader this PR cannot edit looks the held
    # experts up
    assert (model["n_routed_experts"] == model["moe_num_primary_experts"]
            == len(dep["experts_held"]) == 16)
    config = job.model_config(model)
    assert config.n_routed_experts == 64 and len(config.held) == 16
    # a whole number of periods, at least four layers
    from dlrover_tpu.models import gqa_moe
    assert len(gqa_moe.layer_plan(config)) == 4
    assert config.num_layers % 4 == 0 and config.num_layers >= 4
    assert (config.max_seq_len, model["assumed"]["batch"]) == (16384, 1)
    with pytest.raises(ValueError, match="n_routed_experts"):
        job.model_config(dict(model, n_routed_experts=64))


def test_the_arithmetic_by_hand():
    model = cell_model()
    depth = model["num_hidden_layers"]
    full, window = depth // 4, 3 * depth // 4
    attention = 2 * 2560 * (28 + 4) * 128
    expert = 3 * 2560 * 768
    layer = attention + 2560 * 64 + 16 * expert + 2 * 2560
    assert flops.param_count(model) == (
        depth * layer + 2 * 2560 * 37984 + 2560)
    assert flops.param_count(dict(model, num_hidden_layers=12)) == (
        1_580_628_480)
    # and the program's own count, by abstract evaluation
    assert worker.build_job(model).param_count == flops.param_count(model)
    assert flops.tokens_per_step(model) == 16384
    assert flops.held_rows_expected(model) == 16384 * 6 * 16 / 64 == 24576
    assert flops.layer_counts(model) == {"attn_full": full,
                                         "attn_window": window}
    active = (depth * (attention + 2560 * 64 + 6 * 16 / 64 * expert)
              + 2560 * 37984)
    assert flops.active_matmul_params(model) == pytest.approx(active)
    # a visible pair: 2 x 128 in the scores and 2 x 128 in PV a query
    # head forward, twice that backward
    causal = 16384 * 16385 // 2
    band = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert band / causal == pytest.approx(0.4375, abs=1e-3)  # 56% hidden
    assert flops.causal_flops_per_step(model) == (
        full * 3 * 28 * 4 * 128 * causal)
    assert flops.window_flops_per_step(model) == (
        window * 3 * 28 * 4 * 128 * band)
    assert flops.model_flops_per_step(model) == pytest.approx(
        6 * active * 16384 + 3 * 28 * 4 * 128 * (full * causal
                                                  + window * band))
    # q and o at 28 heads, k and v at 4, once forward; those, o and do
    # read and dq, dk, dv written backward; bf16
    q, kv = 28 * 128, 4 * 128
    one = 16384 * 2 * ((2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv))
    assert flops.causal_bytes_per_step(model) == full * one
    assert flops.window_bytes_per_step(model) == window * one
    rows = depth * 24576
    assert flops.gmm_flops(model, rows) == 3 * 3 * 2 * 2560 * 768 * rows
    assert flops.gmm_bytes(model, rows) == 2 * (
        3 * depth * 16 * expert + rows * 3 * (3 * 2560 + 3 * 768))
    assert flops.kernel_flops_per_step(model) == pytest.approx(
        flops.causal_flops_per_step(model)
        + flops.window_flops_per_step(model) + flops.gmm_flops(model, rows))
    assert flops.kernel_bytes_per_step(model) == pytest.approx(
        flops.causal_bytes_per_step(model)
        + flops.window_bytes_per_step(model) + flops.gmm_bytes(model, rows))


@pytest.mark.parametrize("which", ["toy", "cell"])
def test_no_share_counts_more_work_than_its_kernels_run(which):
    """A roofline's numerator is the work the model asks for; its
    kernels run at least that (whole blocks on the diagonal and at the
    band's edge, padded row tiles), so no share can pass 100: by hand,
    the visible pairs against the pairs of the blocks the grids hold,
    and a row's matmuls against its padded tile's."""
    model = toy() if which == "toy" else cell_model()
    config = job.model_config(model)
    seq, window = config.max_seq_len, config.sliding_window
    causal = seq * (seq + 1) // 2
    bq = min(config.flash_block_q, seq)
    bk = min(config.flash_block_k, seq)
    # a q block runs the k blocks that hold a key at or before its last
    run_causal = sum(bq * bk * -(-(i + 1) * bq // bk)
                     for i in range(seq // bq))
    assert causal <= run_causal
    block = min(config.window_block, seq)
    in_band = min(-(-(window - 1) // block) + 1, seq // block)
    run_band = sum(block * block * min(in_band, i + 1)
                   for i in range(seq // block))
    band = flops._pairs_window(seq, window)
    assert band <= run_band <= run_causal
    counts = flops.layer_counts(model)
    per_pair = 3 * 4 * config.head_dim * config.num_heads * (
        model["assumed"]["batch"])
    assert flops.causal_flops_per_step(model) == (
        counts["attn_full"] * per_pair * causal)
    assert flops.window_flops_per_step(model) == (
        counts["attn_window"] * per_pair * band)
    # every counted row is a row of a tile the grouped matmuls run
    rows = 1000
    assert flops.gmm_flops(model, rows) == (
        9 * 2 * config.hidden_size * config.moe_intermediate_size * rows)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_readers_read_a_reduced_trace_and_the_counters():
    """The two new readers and the six of this cell that other
    families brought, on a made-up reduced trace and ``profile_window``
    event, and on a run without their instructions or counters (the
    parent's program): nothing, not an error."""
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import arithmetic

    model = cell_model()
    depth = model["num_hidden_layers"]
    trace = {"devices": {"/device:TPU:0": {}}, "steps": 4, "device_ops": [
        ["fusion.1", 2.0], ["mosaic:flash_fwd.6", 0.16],
        ["mosaic:flash_dkv.3", 0.24], ["mosaic:flash_dq.3", 0.2],
        ["mosaic:flash_win_fwd.18", 0.3], ["mosaic:flash_win_dkv.9", 0.3],
        ["mosaic:flash_win_dq.9", 0.2], ["mosaic:flash_mla_fwd.1", 7.0],
        ["mosaic:flash_fwd_lookalike.1", 7.0], ["flash_fwd.99", 9.0],
        ["mosaic:gmm.108", 0.06], ["mosaic:gmm_dx.3", 0.02],
        ["mosaic:gmm_dw.7", 0.04]]}
    window = {"kind": "profile_window", "pid": 77, "steps": 6,
              "step_counters": {"moe_rows_held": 6 * depth * 24000.0,
                                "moe_rows_max": 6 * depth * 1800.0,
                                "moe_rows_dropped": 0.0}}
    run = {"worker": {"pid": 77}, "events": [window],
           "profile_window": window}
    ctx = {"trace": trace, "model": model, "flops": flops, "run": run,
           "arithmetic": arithmetic,
           "device": {"count": 1, "kind": "TPU v5 lite"}}
    # the three plain kernels alone: not the windowed, not the latent,
    # not a name that only starts alike, not an XLA fusion of that name
    assert _reader("full_attn_ms")(ctx) == pytest.approx(150.0)
    least = flops.causal_flops_per_step(model) / 197e12
    assert least > flops.causal_bytes_per_step(model) / 819e9  # compute
    assert _reader("full_attn_roofline")(ctx) == pytest.approx(
        100 * least / 0.15)
    assert _reader("window_attn_ms")(ctx) == pytest.approx(200.0)
    assert _reader("window_attn_roofline")(ctx) == pytest.approx(
        100 * flops.window_flops_per_step(model) / 197e12 / 0.2)
    assert _reader("expert_gmm_ms")(ctx) == pytest.approx(30.0)
    rows = depth * 24000
    least = max(flops.gmm_flops(model, rows) / 197e12,
                flops.gmm_bytes(model, rows) / 819e9)
    assert _reader("expert_gmm_roofline")(ctx) == pytest.approx(
        100 * least / 0.03)
    assert _reader("expert_load_imbalance")(ctx) == pytest.approx(
        1800 / (24000 / 16))
    assert _reader("expert_rows_dropped")(ctx) == 0.0
    bare = dict(ctx, trace=dict(trace, device_ops=[
        ["fusion.1", 2.0], ["mosaic:flash_mla_fwd.1", 1.0],
        ["mosaic:flash_win_fwd.1", 1.0]]),
        run={"worker": {"pid": 77}, "events": [], "profile_window": {
            "kind": "profile_window", "pid": 77, "steps": 6}})
    for name in ("full_attn_ms", "full_attn_roofline"):
        assert _reader(name)(bare) is None, name
        assert _reader(name)(dict(bare, trace=None)) is None, name
    # a family without ``causal_flops_per_step`` (the dense one's cells
    # run the same kernels): the time reads, the share does not
    from chipbench.families.dense_gqa import flops as dense
    assert not hasattr(dense, "causal_flops_per_step")
    assert _reader("full_attn_ms")(dict(ctx, flops=dense)) == pytest.approx(
        150.0)
    assert _reader("full_attn_roofline")(dict(ctx, flops=dense)) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    # what ISSUE 41 lists for the cell; a later PR may append a reader
    assert set(mine) >= {
        "tokens_per_s", "setup_s", "host_gap_ms", "step_device_ms",
        "step_mfu_pct", "mosaic_ms", "device_idle_pct", "dispatch_ms",
        "host_sync_ms", "input_wait_ms", "boot_import_s", "boot_backend_s",
        "boot_build_s", "window_attn_ms", "window_attn_roofline",
        "expert_gmm_ms", "expert_gmm_roofline", "expert_load_imbalance",
        "expert_rows_dropped", "full_attn_ms", "full_attn_roofline"}
    assert "flash_roofline" not in mine  # it divides by the gmms' time too
    for name in ("full_attn_ms", "full_attn_roofline"):
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"] and entry["layer"] == "kernels"
        assert entry["moves"] == "tokens_per_s"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b-ep4-1chip", "steady", 1)


def test_the_published_rule_finds_nothing_wrong_on_the_tree():
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import published_rule

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert published_rule.wrong(bench, REPO) == []
    mine = [c for c in bench["configs"]
            if c["name"] == "smallthinker-21b-a3b-ep4-1chip"]
    assert len(mine) == 1 and set(mine[0]["reduced"]) == set(
        cell_model()["reduced"])
    # and the rule bites on this configuration: a width cut is refused
    cut = copy.deepcopy(bench)
    cut["configs"] = [dict(mine[0], reduced=mine[0]["reduced"]
                           + ["moe_ffn_hidden_size"])]
    assert any("moe_ffn_hidden_size is a width" in line
               for line in published_rule.wrong(cut, REPO))
