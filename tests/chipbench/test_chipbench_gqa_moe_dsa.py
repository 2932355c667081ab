"""``chipbench/families/gqa_moe_dsa/``: the plain reference (float32
``jax.numpy``, dense scores in blocks of query rows, the selection by a
sort, the held experts as a loop) against ``models/gqa_moe.py`` with its
sparse switches, the code the cell runs, at a toy size on the CPU: the
loss, the indexer's loss, the hidden states and the selection; the
faults the comparison has to catch; ``flops.py`` by hand; the new
readers; the configuration against what its source publishes.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its Pallas kernels in the interpreter),
so they differ only by the order of float32 sums. Every gradient is
compared in ``tests/test_gqa_moe_dsa.py``. On the chip the same
comparison runs in every first worker round at the published widths,
against bf16 compute, with the limits ``job.py`` gives.
"""

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

from chipbench import arithmetic, published_rule, worker  # noqa: E402
from chipbench.families.gqa_moe_dsa import flops, job  # noqa: E402

sys.path.insert(0, HERE)
import gqa_moe_dsa_controls as controls  # noqa: E402

CELL = "keye-1chip.steady"
CONFIG = "keye-vl-2.0-30b-a3b-ep4-1chip"


def toy():
    with open(os.path.join(HERE, "tiny_gqa_moe_dsa.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def perturbed(init_fn):
    """Initial weights with the norm scales moved off 1, so that a
    reference that dropped a norm would show."""
    return jax.jit(lambda key: jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                              a.shape, a.dtype),
        init_fn(key)))(jax.random.PRNGKey(3))


# unequal position rows: an image's tokens keep the temporal position
# and count rows and columns
POS = np.stack([np.minimum(np.arange(64), 20),
                np.arange(64) // 8, np.arange(64) % 8])


@pytest.fixture(scope="module")
def built():
    model = toy()
    the_job = worker.build_job(model)
    params = perturbed(the_job.init_fn)
    batch = worker.batch_for(11, 0, the_job.vocab_size, 1, the_job.seq_len)
    return model, the_job, params, batch


def readings(model, params, batch, pos=None):
    return job.compare(model, job.model_config(toy()), params,
                       batch["input_ids"][0], batch["labels"][0], pos)


def test_the_program_agrees_with_the_reference(built):
    model, the_job, params, batch = built
    system, aux = the_job.loss_fn(params, batch, None)
    read = readings(model, params, batch)
    assert abs(float(system) - read["reference_loss"]) < 2e-5
    assert abs(float(system) - read["own_reference_loss"]) < 2e-5
    assert read["median_token_error"] < 1e-5
    assert read["index_kl_error"] < 1e-4
    assert float(aux["dsa_index_kl"]) == pytest.approx(
        read["reference_index_kl"], rel=1e-4)
    assert read["selection_agreement"] == 1.0
    assert float(aux["moe_rows_dropped"]) == 0
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"]
    assert (the_job.layers, the_job.seq_len, the_job.vocab_size) == (
        2, 64, 512)
    assert the_job.param_count == flops.param_count(model)
    assert the_job.init_fn.layer_kinds == {
        "attn_full": 0, "attn_window": 0, "attn_sparse": 2}


def test_unequal_position_rows_agree_too(built):
    """The three rotary sections under positions whose rows differ (an
    image's): the program's ``rope_sections`` against the reference's
    ``mrope_section``, and both away from text."""
    model, _, params, batch = built
    read = readings(model, params, batch, POS)
    assert read["median_token_error"] < 1e-5
    assert read["index_kl_error"] < 1e-4
    text = readings(model, params, batch)
    assert abs(read["reference_loss"] - text["reference_loss"]) > 1e-3


def test_the_jobs_check_reads_nan_past_a_limit(built, capsys):
    """What ``worker.py`` calls: the reference's loss where the three
    readings are within their limits, NaN (which fails the worker's
    comparison) where one is not, the readings printed either way."""
    model, the_job, params, batch = built
    ids, labels = batch["input_ids"][0], batch["labels"][0]
    sound = the_job.reference_loss(params, ids, labels)
    assert sound == readings(model, params, batch)["reference_loss"]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_hidden"
    assert line["median_token_error"] < line["tolerance"] == 2e-4
    assert line["selection_agreement"] >= line["agreement_floor"] == 0.995
    assert line["index_kl_error"] < line["index_kl_tolerance"] == 2e-4
    with controls.applied(model, "e4m3 operands"):
        assert np.isnan(the_job.reference_loss(params, ids, labels))


def test_a_dropped_row_makes_the_jobs_loss_nan():
    the_job = job.build(toy(), expert_row_factor=0.05)
    params = the_job.init_fn(jax.random.PRNGKey(3))
    batch = worker.batch_for(11, 0, the_job.vocab_size, 2, the_job.seq_len)
    loss, aux = the_job.loss_fn(params, batch, None)
    assert float(aux["moe_rows_dropped"]) > 0
    assert np.isnan(float(loss))


def fails(model, read, system):
    """The limits a reading is outside of, as ``job.py`` and
    ``worker.py`` apply them in float32."""
    out = []
    if read["median_token_error"] > job.HIDDEN_TOL["float32"]:
        out.append("hidden")
    if read["index_kl_error"] > job.INDEX_KL_TOL["float32"]:
        out.append("index_kl")
    if read["selection_agreement"] < job.AGREE_FLOOR["float32"]:
        out.append("agreement")
    if abs(system - read["reference_loss"]) > job.REFERENCE_TOL["float32"]:
        out.append("loss")
    return out


@pytest.mark.parametrize("fault", controls.CONTROLS,
                         ids=[f.replace(" ", "-") for f in controls.CONTROLS])
def test_the_comparison_catches(built, fault):
    """Each control (ISSUE 48's six and the precision below), put into
    the reference alone, fails at least one of the comparison's limits;
    the sections swapped under unequal position rows, which is the only
    place they can show."""
    model, the_job, params, batch = built
    pos = POS if fault in controls.TEXT_BLIND else None
    ids = batch["input_ids"]
    system = float(jax.jit(lambda p: the_job.loss_fn(p, dict(
        batch, **({} if pos is None else {"position_ids": jnp.asarray(
            pos)[None]})), None)[0])(params))
    assert fails(model, readings(model, params, batch, pos), system) == []
    with controls.applied(model, fault):
        caught = fails(model, readings(model, params, batch, pos), system)
    print(fault, caught)
    assert caught, fault
    assert model == toy()  # the control is taken out again
    assert ids.shape == (1, 64)


def test_the_controls_script_gives_the_harness_verdicts(capsys):
    """``gqa_moe_dsa_controls.py`` as the chip runs it, at the toy
    size: the worker's own ``ReferenceCheck`` says ``ok`` of the sound
    reference on both seeds and not ``ok`` under every control a row of
    text can show."""
    assert controls.main([
        "--config", os.path.join(HERE, "tiny_gqa_moe_dsa.json"),
        "--controls", "3000004811", "--sound", "3000004812"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    shown = [c for c in controls.CONTROLS if c not in controls.TEXT_BLIND]
    assert [(line["seed"], line["control"]) for line in lines] == [
        (3000004811, "sound")] + [(3000004811, c) for c in shown] + [
        (3000004812, "sound")]
    for line in lines:
        assert line["ok"] == (line["control"] == "sound"), line


def test_the_cell_keeps_every_published_width():
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        published = json.load(f)["config"]
    cut = set(model["reduced"])
    assert cut == {"num_hidden_layers", "num_experts", "num_local_experts",
                   "vocab_size"}
    for key, value in published.items():
        if key not in cut:
            assert model[key] == value, key
    assert (model["hidden_size"], model["head_dim"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["moe_intermediate_size"], model["intermediate_size"],
            model["num_experts_per_tok"], model["rope_theta"]) == (
        2048, 128, 32, 4, 768, 6144, 8, 1e7)
    assert model["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert model["rope_scaling"]["mrope_section"] == [16, 24, 24]
    dep = model["deployment"]
    assert dep["published_num_experts"] == 128
    assert dep["experts_held"] == list(range(32))
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] == 4
    assert model["vocab_size"] * dep["vocabulary_ways"] == 151936
    # the name under which a reader this PR cannot edit looks the held
    # experts up
    assert (model["n_routed_experts"] == model["num_experts"]
            == model["num_local_experts"] == len(dep["experts_held"]) == 32)
    for text in (model["stands_for"], json.dumps(dep)):
        assert "v5e-16" in text
    assert "quarter" in model["stands_for"]
    config = job.model_config(model)
    assert config.n_routed_experts == 128 and len(config.held) == 32
    from dlrover_tpu.models import gqa_moe
    assert gqa_moe.layer_plan(config) == [(gqa_moe.SPARSE, 1)]
    assert config.num_layers >= 4
    assert (config.max_seq_len, model["assumed"]["batch"]) == (16384, 1)
    assert (config.rope_sections, config.sparse_topk, config.index_heads,
            config.index_head_dim, config.qk_norm, config.router_input,
            config.expert_activation) == (
        (16, 24, 24), 2048, 16, 64, True, "post_norm", "silu")
    with pytest.raises(ValueError, match="n_routed_experts"):
        job.model_config(dict(model, n_routed_experts=128))


def test_the_published_rule_finds_nothing_wrong_with_the_configuration():
    found = [line for line in published_rule.wrong(bench(), REPO)
             if line.startswith(CONFIG)]
    assert found == []
    (entry,) = [c for c in bench()["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == list(cell_model()["reduced"])


def test_the_arithmetic_by_hand():
    model = cell_model()
    depth = model["num_hidden_layers"]
    attention = 2 * 2048 * (32 + 4) * 128
    indexer = 2048 * (16 * 64 + 64 + 16)
    expert = 3 * 2048 * 768
    assert (attention, indexer, expert) == (18_874_368, 2_260_992,
                                            4_718_592)
    layer = (attention + indexer + 2048 * 128 + 32 * expert + 2 * 2048
             + 2 * 128)
    assert flops.param_count(model) == (
        depth * layer + 2 * 2048 * 37984 + 2048)
    assert flops.param_count(dict(model, num_hidden_layers=8)) == (
        1_534_758_912)
    # and the program's own count, by abstract evaluation
    assert worker.build_job(model).param_count == flops.param_count(model)
    assert flops.tokens_per_step(model) == 16384
    assert flops.held_rows_expected(model) == 16384 * 8 * 32 / 128 == 32768
    assert flops.pairs_selected(16384, 2048) == 31_458_304 == (
        2048 * 2049 // 2 + 14336 * 2048)
    assert flops.pairs_causal(16384) == 134_225_920
    assert flops.selected_share(model) == pytest.approx(0.23437, abs=1e-5)
    assert flops.pairs_selected(1024, 2048) == flops.pairs_causal(1024)
    active = (depth * (attention + indexer + 2048 * 128
                       + 8 * 32 / 128 * expert) + 2048 * 37984)
    assert flops.active_matmul_params(model) == pytest.approx(active)
    selected, causal = depth * 31_458_304, depth * 134_225_920
    assert flops.dsa_attn_flops_per_step(model) == (
        3.5 * 4 * 128 * 32 * selected)
    assert flops.dsa_index_flops_per_step(model) == (
        2 * 64 * 16 * causal + 4 * 64 * 16 * selected
        + 2 * 128 * 32 * selected)
    assert flops.model_flops_per_step(model) == pytest.approx(
        6 * active * 16384 + 3 * 4 * 128 * 32 * selected
        + flops.dsa_index_flops_per_step(model))
    rows = depth * 16384 * 2
    qo, kv = 32 * 128 * rows, 4 * 128 * rows
    assert flops.dsa_attn_bytes_per_step(model) == (
        (2 * qo + 2 * kv) + (3 * qo + 2 * kv) + (qo + 2 * kv))
    assert flops.dsa_index_bytes_per_step(model) == (
        3 * (16 * 64 + 64 + 16) * rows + (32 + 4) * 128 * rows)
    held = depth * 32768
    assert flops.gmm_flops(model, held) == 6 * expert * held
    assert flops.kernel_flops_per_step(model) == (
        flops.dsa_attn_flops_per_step(model)
        + flops.dsa_index_flops_per_step(model)
        + flops.gmm_flops(model, held))
    # the FLOPs bind both new rooflines on a v5e
    for work, traffic in (
            (flops.dsa_attn_flops_per_step, flops.dsa_attn_bytes_per_step),
            (flops.dsa_index_flops_per_step,
             flops.dsa_index_bytes_per_step)):
        _, bound = arithmetic.roofline(work(model), traffic(model),
                                       "TPU v5 lite")
        assert bound == "compute"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def context(device_ops, counters=None, steps=2):
    model = cell_model()
    return {"trace": {"devices": ["tpu:0"], "steps": steps,
                      "device_ops": device_ops, "step_device_ms": 1500.0},
            "run": {"profile_window": None if counters is None else {
                "steps": steps, "step_counters": counters}},
            "flops": flops, "model": model, "arithmetic": arithmetic,
            "device": {"count": 1, "kind": "TPU v5 lite"}}


OPS = [["mosaic:dsa_attn_fwd.1", 0.6], ["mosaic:dsa_attn_dkv.2", 0.5],
       ["mosaic:dsa_attn_dq.3", 0.3], ["mosaic:dsa_index_select.4", 0.2],
       ["mosaic:dsa_index_kl_fwd.5", 0.1], ["mosaic:dsa_index_kl_bwd.6", 0.1],
       ["mosaic:gmm.7", 0.3], ["mosaic:flash_fwd.8", 0.2], ["fusion.9", 1.0]]


def test_the_time_readers_sum_their_own_kernels():
    ctx = context(OPS)
    assert reader("dsa_attn_ms")(ctx) == pytest.approx(1e3 * 1.4 / 2)
    assert reader("dsa_index_ms")(ctx) == pytest.approx(1e3 * 0.4 / 2)
    model = ctx["model"]
    for name, work, seconds in (
            ("dsa_attn_roofline", flops.dsa_attn_flops_per_step, 0.7),
            ("dsa_index_roofline", flops.dsa_index_flops_per_step, 0.2)):
        peak = arithmetic.peaks("TPU v5 lite")["bf16_flops_per_s"]
        assert reader(name)(ctx) == pytest.approx(
            100 * work(model) / peak / seconds)
        assert 0 < reader(name)(ctx) < 100


@pytest.mark.parametrize("name", [
    "dsa_attn_ms", "dsa_attn_roofline", "dsa_index_ms",
    "dsa_index_roofline", "dsa_selected_share", "dsa_index_kl"])
def test_a_reader_finds_nothing_where_there_is_nothing(name):
    """The parent's program under this PR's benchmark files: no such
    kernel in its trace, no such counter in its window, a family
    without the functions: the metric is left out, nothing raises."""
    other = [op for op in OPS if "dsa_" not in op[0]]
    assert reader(name)(context(other, {"moe_rows_held": 5.0})) is None
    assert reader(name)(dict(context(other), trace=None)) is None
    ctx = context(OPS, {})
    ctx["flops"] = object()
    if "roofline" in name:
        assert reader(name)(ctx) is None


def test_the_counter_readers():
    depth = cell_model()["num_hidden_layers"]
    counters = {"dsa_pairs_selected": 2 * depth * 31_458_304.0,
                "dsa_pairs_causal": 2 * depth * 134_225_920.0,
                "dsa_index_kl": 2 * depth * 0.08}
    ctx = context(OPS, counters)
    assert reader("dsa_selected_share")(ctx) == pytest.approx(
        flops.selected_share(ctx["model"]))
    assert reader("dsa_index_kl")(ctx) == pytest.approx(0.08)


def test_the_manifest_lists_the_cell_and_its_metrics():
    b = bench()
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "steady", 1)
    assert b["workloads"][-1] == cell and b["configs"][-1]["name"] == CONFIG
    new = ["dsa_attn_ms", "dsa_attn_roofline", "dsa_index_ms",
           "dsa_index_roofline", "dsa_selected_share", "dsa_index_kl"]
    assert [m["name"] for m in b["per_layer"][-6:]] == new
    for m in b["per_layer"][-6:]:
        assert (m["moves"], m["workloads"]) == ("tokens_per_s", [CELL])
        assert m["layer"] == ("kernels" if m["source"] == "device_trace"
                              else "step program")
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "layer_metrics", m["name"] + ".py"))
    reported = {m["name"] for m in b["end_to_end"] + b["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == set(new) | {
        "tokens_per_s", "setup_s", "host_gap_ms", "step_device_ms",
        "step_mfu_pct", "mosaic_ms", "device_idle_pct", "dispatch_ms",
        "host_sync_ms", "input_wait_ms", "boot_import_s", "boot_backend_s",
        "boot_build_s", "expert_gmm_ms", "expert_gmm_roofline",
        "expert_load_imbalance", "expert_rows_dropped"}
