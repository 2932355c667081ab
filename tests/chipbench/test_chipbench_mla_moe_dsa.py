"""``chipbench/families/mla_moe_dsa/``: the plain reference (float32
``jax.numpy``, dense scores in blocks of query rows, the selection by a
sort, the groups and the held experts by hand) against
``models/mla_moe.py`` with its sparse switches, the code the cell runs,
at a toy size on the CPU: the loss, the indexer's loss, the hidden
states, the selection and the groups; the faults the comparison has to
catch; ``flops.py`` by hand; the new reader; the configuration against
what its source publishes.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its Pallas kernels in the interpreter),
so they differ only by the order of float32 sums. Every gradient is
compared in ``tests/test_mla_moe_dsa.py``. On the chip the same
comparison runs in every first worker round at the published widths,
against bf16 compute, with the limits ``job.py`` gives.
"""

import ast
import importlib.util
import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import arithmetic, published_rule, worker  # noqa: E402
from chipbench.families.mla_moe_dsa import flops, job  # noqa: E402

sys.path.insert(0, HERE)
import mla_moe_dsa_controls as controls  # noqa: E402

CELL = "axk2-1chip.steady"
CONFIG = "a.x-k2-ep32-1chip"


def toy():
    with open(os.path.join(HERE, "tiny_mla_moe_dsa.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def perturbed(init_fn):
    """Initial weights with the norm scales moved off 1 and the
    indexer's key norm's bias off 0, so that a reference that dropped a
    norm would show."""
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


def readings(model, params, batch):
    return job.compare(model, job.model_config(toy()), params,
                       batch["input_ids"][0], batch["labels"][0])


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
    assert read["group_agreement"] == 1.0
    assert float(aux["moe_rows_dropped"]) == 0
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"]
    assert (the_job.layers, the_job.seq_len, the_job.vocab_size) == (
        3, 64, 512)
    assert the_job.param_count == flops.param_count(model)
    assert the_job.init_fn.layer_kinds == {"dense": 1, "moe": 2}


def test_the_jobs_check_reads_nan_past_a_limit(built, capsys):
    """What ``worker.py`` calls: the reference's loss where the four
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
    assert line["group_agreement"] >= line["groups_floor"] == 0.995
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


def fails(read, system):
    """The limits a reading is outside of, as ``job.py`` and
    ``worker.py`` apply them in float32."""
    out = []
    if read["median_token_error"] > job.HIDDEN_TOL["float32"]:
        out.append("hidden")
    if read["index_kl_error"] > job.INDEX_KL_TOL["float32"]:
        out.append("index_kl")
    if read["selection_agreement"] < job.AGREE_FLOOR["float32"]:
        out.append("agreement")
    if read["group_agreement"] < job.GROUPS_FLOOR["float32"]:
        out.append("groups")
    if abs(system - read["reference_loss"]) > job.REFERENCE_TOL["float32"]:
        out.append("loss")
    return out


@pytest.mark.parametrize("fault", controls.CONTROLS,
                         ids=[f.replace(" ", "-") for f in controls.CONTROLS])
def test_the_comparison_catches(built, fault):
    """Each control (ISSUE 51's six mechanisms and the precision
    below), put into the reference alone, fails at least one of the
    comparison's limits."""
    model, the_job, params, batch = built
    system = float(jax.jit(
        lambda p: the_job.loss_fn(p, batch, None)[0])(params))
    assert fails(readings(model, params, batch), system) == []
    with controls.applied(model, fault):
        caught = fails(readings(model, params, batch), system)
    print(fault, caught)
    assert caught, fault
    assert model == toy()  # the control is taken out again


def test_the_controls_script_gives_the_harness_verdicts(capsys):
    """``mla_moe_dsa_controls.py`` as the chip runs it, at the toy
    size: the worker's own ``ReferenceCheck`` says ``ok`` of the sound
    reference on both seeds and not ``ok`` under every control."""
    assert controls.main([
        "--config", os.path.join(HERE, "tiny_mla_moe_dsa.json"),
        "--controls", "3000005111", "--sound", "3000005112"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [(line["seed"], line["control"]) for line in lines] == [
        (3000005111, "sound")] + [
        (3000005111, c) for c in controls.CONTROLS] + [
        (3000005112, "sound")]
    for line in lines:
        assert line["ok"] == (line["control"] == "sound"), line


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(REPO, "chipbench", "families", "mla_moe_dsa",
                        "reference.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"math", "jax"}


def test_the_cell_keeps_every_published_width():
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "a.x-k2.json")) as f:
        published = json.load(f)["config"]
    cut = set(model["reduced"])
    assert {"num_hidden_layers", "n_routed_experts", "vocab_size"} <= cut
    assert cut - {"num_hidden_layers", "n_routed_experts",
                  "vocab_size"} <= {"num_attention_heads",
                                    "num_key_value_heads"}
    for key, value in published.items():
        if key not in cut:
            assert model[key] == value, key
    assert (model["hidden_size"], model["intermediate_size"],
            model["moe_intermediate_size"], model["q_lora_rank"],
            model["kv_lora_rank"], model["qk_nope_head_dim"],
            model["qk_rope_head_dim"], model["v_head_dim"],
            model["num_experts_per_tok"], model["gated_norm_rank"]) == (
        7168, 18432, 2048, 1536, 512, 128, 64, 128, 8, 16)
    assert (model["index_n_heads"], model["index_head_dim"],
            model["index_topk"], model["n_group"], model["topk_group"]) == (
        64, 128, 2048, 8, 4)
    assert model["rope_parameters"] == published["rope_parameters"]
    assert model["num_hidden_layers"] == 5
    assert model["num_attention_heads"] == model["num_key_value_heads"]
    assert model["num_attention_heads"] in (16, 32, 64)
    dep = model["deployment"]
    assert dep["published_n_routed_experts"] == 256
    assert dep["experts_held"] == list(range(8)) and (
        model["n_routed_experts"] == 8)
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] == 32
    assert model["vocab_size"] * dep["vocabulary_ways"] == 163840
    assert model["num_attention_heads"] * dep["attention_ways"] == 64
    for text in (model["stands_for"], json.dumps(model["reduced"]),
                 dep["how"]):
        assert "32" in text
    for reading in ("gated_norm", "attention_output_gate", "indexer",
                    "index_loss", "topk_method"):
        assert len(model["assumed"][reading]) > 100, reading
    for excluded in ("un-normed", "every norm"):
        assert excluded in model["assumed"]["gated_norm"]
    for excluded in ("one scalar a head", "read from c_q"):
        assert excluded in model["assumed"]["attention_output_gate"]
    for departure in ("Hadamard", "FP8"):
        assert departure in model["assumed"]["indexer"]
    config = job.model_config(model)
    assert config.n_routed_experts == 256 and config.held == tuple(range(8))
    assert (config.index_n_heads, config.index_head_dim, config.index_topk,
            config.attn_output_gate, config.gated_norm_rank, config.n_group,
            config.topk_group, config.router_bias, config.hc_mult,
            config.mtp_layers, config.balance_loss_weight) == (
        64, 128, 2048, True, 16, 8, 4, True, 1, 0, 0.0)
    assert (config.rope_theta, config.rope_factor,
            config.rope_original_max) == (1e6, 2, 131072)
    assert config.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(2) + 1) ** 2)
    assert (config.max_seq_len, model["assumed"]["batch"],
            config.remat_policy, config.expert_row_factor) == (
        8192, 1, "full", 4.0)
    with pytest.raises(ValueError, match="n_routed_experts"):
        job.model_config(dict(model, n_routed_experts=256))
    with pytest.raises(ValueError, match="group-limited"):
        job.model_config(dict(model, topk_method="none"))


def test_the_published_rule_finds_nothing_wrong_with_the_configuration():
    found = [line for line in published_rule.wrong(bench(), REPO)
             if line.startswith(CONFIG)]
    assert found == []
    (entry,) = [c for c in bench()["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == list(cell_model()["reduced"])
    # and it does find a width that was cut
    (ours,) = [c for c in bench()["configs"] if c["name"] == CONFIG]
    wrong = dict(bench(), configs=[dict(
        ours, reduced=ours["reduced"] + ["index_head_dim"])])
    assert any("index_head_dim is a width" in line
               for line in published_rule.wrong(wrong, REPO))


def test_the_arithmetic_by_hand():
    model = cell_model()
    h = model["num_attention_heads"]
    mla = (7168 * 1536 + 1536 * h * 192 + 7168 * 576 + 512 * h * 256
           + h * 128 * 7168 + 7168 * h * 128)
    indexer = 1536 * 64 * 128 + 7168 * 128 + 7168 * 64
    gated = 2 * 7168 * 16
    expert = 3 * 7168 * 2048
    assert (indexer, gated, expert) == (13_959_168, 229_376, 44_040_192)
    layer = (mla + indexer + 2 * 128 + 2 * (7168 + gated) + 1536 + 512)
    moe = 7168 * 256 + 256 + 9 * expert
    assert flops.param_count(model) == (
        5 * layer + 3 * 7168 * 18432 + 4 * moe + 2 * 7168 * 20480
        + 7168 + gated)
    assert flops.param_count(dict(
        model, num_attention_heads=16, num_key_value_heads=16)) == (
        2_611_733_760)
    # and the program's own count, by abstract evaluation
    assert worker.build_job(model).param_count == flops.param_count(model)
    assert flops.tokens_per_step(model) == 8192
    assert flops.held_rows_expected(model) == 8192 * 8 * 8 / 256 == 2048
    assert flops.pairs_selected(8192, 2048) == 14_681_088 == (
        2048 * 2049 // 2 + 6144 * 2048)
    assert flops.pairs_causal(8192) == 33_558_528
    assert flops.selected_share(model) == pytest.approx(0.43748, abs=1e-5)
    assert flops.pairs_selected(1024, 2048) == flops.pairs_causal(1024)
    active = (5 * (mla + indexer + 2 * gated) + 3 * 7168 * 18432
              + 4 * (7168 * 256 + expert + 8 * 8 / 256 * expert) + gated
              + 7168 * 20480)
    assert flops.active_matmul_params(model) == pytest.approx(active)
    selected, causal = 5 * 14_681_088, 5 * 33_558_528
    # 192 + 128 a head held, twice, forward and 2.5 times that backward
    assert flops.dsa_attn_flops_per_step(model) == (
        3.5 * 2 * (192 + 128) * h * selected)
    # 64 x 128 a causal pair: a contraction of 8192
    assert flops.dsa_index_flops_per_step(model) == (
        2 * 8192 * causal + 4 * 8192 * selected + 2 * 192 * h * selected)
    assert 2 * 8192 * 33_558_528 == pytest.approx(5.5e11, rel=0.01)
    assert flops.model_flops_per_step(model) == pytest.approx(
        6 * active * 8192 + 3 * 2 * 320 * h * selected
        + flops.dsa_index_flops_per_step(model))
    rows = 5 * 8192 * 2
    q, k, v = h * 192 * rows, (h * 128 + 64) * rows, h * 128 * rows
    assert flops.dsa_attn_bytes_per_step(model) == (
        (q + k + 2 * v) + (q + k + 3 * v) + (q + k + v))
    assert flops.dsa_index_bytes_per_step(model) == (
        3 * (64 * 128 + 128 + 64) * rows + (h * 320 + 64) * rows)
    held = 4 * 2048
    assert flops.gmm_flops(model, held) == 6 * expert * held
    assert flops.kernel_flops_per_step(model) == (
        flops.dsa_attn_flops_per_step(model)
        + flops.dsa_index_flops_per_step(model)
        + flops.gmm_flops(model, held))
    # the FLOPs bind both rooflines on a v5e
    for work, traffic in (
            (flops.dsa_attn_flops_per_step, flops.dsa_attn_bytes_per_step),
            (flops.dsa_index_flops_per_step,
             flops.dsa_index_bytes_per_step)):
        _, bound = arithmetic.roofline(work(model), traffic(model),
                                       "TPU v5 lite")
        assert bound == "compute"


def test_the_family_refuses_a_checkout_without_the_switches(tmp_path):
    """``run.py`` loads ``flops.py`` first: on the parent's tree (this
    PR's benchmark files laid over it) it stops there, with a reason."""
    root = tmp_path / "checkout"
    (root / "dlrover_tpu" / "models").mkdir(parents=True)
    family = root / "chipbench" / "families" / "mla_moe_dsa"
    family.mkdir(parents=True)
    with open(os.path.join(REPO, "chipbench", "families", "mla_moe_dsa",
                           "flops.py")) as f:
        (family / "flops.py").write_text(f.read())
    (root / "dlrover_tpu" / "models" / "mla_moe.py").write_text(
        "# the parent's: no sparse switch\n")
    spec = importlib.util.spec_from_file_location(
        "parents_flops", str(family / "flops.py"))
    with pytest.raises(SystemExit, match="index_n_heads"):
        spec.loader.exec_module(importlib.util.module_from_spec(spec))


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


OPS = [["mosaic:dsa_attn_fwd.1", 0.2], ["mosaic:dsa_attn_bwd.2", 0.4],
       ["mosaic:dsa_index_select.4", 0.5],
       ["mosaic:dsa_index_kl_fwd.5", 0.2], ["mosaic:dsa_index_kl_bwd.6", 0.5],
       ["mosaic:gmm.7", 0.3], ["fusion.9", 1.0]]


def test_the_dsa_readers_read_this_familys_work():
    """The six ``dsa_*`` readers PR 48 wrote find this family's kernels
    by the same prefixes and its work in its own ``flops.py``; the
    latent flash readers find nothing here, so nothing is counted
    twice."""
    ctx = context(OPS)
    assert reader("dsa_attn_ms")(ctx) == pytest.approx(1e3 * 0.6 / 2)
    assert reader("dsa_index_ms")(ctx) == pytest.approx(1e3 * 1.2 / 2)
    assert reader("mla_attn_ms")(ctx) is None
    assert reader("mla_attn_roofline")(ctx) is None
    model = ctx["model"]
    peak = arithmetic.peaks("TPU v5 lite")["bf16_flops_per_s"]
    for name, work, seconds in (
            ("dsa_attn_roofline", flops.dsa_attn_flops_per_step, 0.3),
            ("dsa_index_roofline", flops.dsa_index_flops_per_step, 0.6)):
        assert reader(name)(ctx) == pytest.approx(
            100 * work(model) / peak / seconds)
        assert 0 < reader(name)(ctx) < 100
    counters = {"dsa_pairs_selected": 2 * 5 * 14_681_088.0,
                "dsa_pairs_causal": 2 * 5 * 33_558_528.0,
                "dsa_index_kl": 2 * 5 * 0.08}
    ctx = context(OPS, counters)
    assert reader("dsa_selected_share")(ctx) == pytest.approx(
        flops.selected_share(model))
    assert reader("dsa_index_kl")(ctx) == pytest.approx(0.08)


def test_the_group_reach_reader():
    read = reader("moe_group_reach")
    ctx = context(OPS, {"moe_group_reach": 2 * 4 * 4000.0,
                        "moe_group_tokens": 2 * 4 * 8192.0})
    assert read(ctx) == pytest.approx(4000 / 8192)
    # the parent's program under this PR's benchmark files: no such
    # counter in its window, or no window at all: the metric is left
    # out, nothing raises
    assert read(context(OPS, {"moe_rows_held": 5.0})) is None
    assert read(context(OPS)) is None
    assert read(dict(context(OPS), run={})) is None


def test_the_manifest_lists_the_cell_and_its_metrics():
    b = bench()
    (cell,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "steady", 1)
    assert len(cell["why"]) <= 200
    (config,) = [c for c in b["configs"] if c["name"] == CONFIG]
    assert len(config["why"]) <= 200 and config["file"] == (
        f"chipbench/configs/{CONFIG}.json")
    (new,) = [m for m in b["per_layer"] if m["name"] == "moe_group_reach"]
    (like,) = [m for m in b["per_layer"]
               if m["name"] == "expert_load_imbalance"]
    assert (new["layer"], new["moves"], new["source"], new["workloads"]) == (
        like["layer"], "tokens_per_s", "program_counter", [CELL])
    reported = {m["name"] for m in b["end_to_end"] + b["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert reported >= {
        "tokens_per_s", "setup_s", "host_gap_ms", "step_device_ms",
        "step_mfu_pct", "mosaic_ms", "device_idle_pct", "dispatch_ms",
        "host_sync_ms", "input_wait_ms", "boot_import_s", "boot_backend_s",
        "boot_build_s", "expert_gmm_ms", "expert_gmm_roofline",
        "expert_load_imbalance", "expert_rows_dropped", "dsa_attn_ms",
        "dsa_attn_roofline", "dsa_index_ms", "dsa_index_roofline",
        "dsa_selected_share", "dsa_index_kl", "moe_group_reach"}
    for name in reported:
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py")) or name in (
                "tokens_per_s", "setup_s")
