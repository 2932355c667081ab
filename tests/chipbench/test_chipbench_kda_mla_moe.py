"""``chipbench/families/kda_mla_moe/``: the plain reference (float32
``jax.numpy``, the delta rule under a per-channel decay token by token,
a dense masked softmax a head, the held experts in a loop) against
``models/kda_mla_moe.py``, the code the cell runs, at a toy size on the
CPU: the loss, the hidden states and each kind of mixer alone; the
job's refusals and its NaN past each limit; the controls script;
``flops.py`` by hand; the new readers on a made-up trace; the
configuration against what its source publishes.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its Pallas kernels in the interpreter),
so they differ only by the order of float32 sums. Every gradient and
every control is compared in ``tests/test_kda_mla_moe.py``, on both of
the program's paths. On the chip the same comparison runs in every
first worker round at the published widths, against bf16 compute, with
the limits ``job.py`` gives.
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
from chipbench.families.kda_mla_moe import flops, job  # noqa: E402

sys.path.insert(0, HERE)
import kda_mla_moe_controls as controls  # noqa: E402

LOSS_TOL = 1e-5
CELL = "ling3flash-1chip.steady"
NAME = "ling-3.0-flash-1chip"
READERS = {"kda_ms": "kernels", "kda_roofline": "kernels",
           "kda_chunk_ms": "step program",
           "kda_log_decay_mean": "step program"}


def toy():
    with open(os.path.join(HERE, "tiny_kda_mla_moe.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def perturbed(init_fn):
    """Initial weights with the norm scales moved off their starting
    values, so that a reference that dropped one would show."""
    def moved(key):
        return jax.tree.map(
            lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                  a.shape, a.dtype),
            init_fn(key))

    return jax.jit(moved)(jax.random.PRNGKey(3))


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


def test_the_program_agrees_with_the_reference(built):
    from dlrover_tpu.models import kda_mla_moe

    model, the_job, params, batch = built
    system, aux = the_job.loss_fn(params, batch, None)
    assert abs(float(system) - reference_loss(model, params, batch)) < LOSS_TOL
    assert -0.2 < float(aux["kda_log_decay_mean"]) < -0.001
    assert float(aux["moe_rows_dropped"]) == 0
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"] == 1e-4
    program = kda_mla_moe.apply_hidden(params, batch["input_ids"],
                                       job.model_config(toy()))[0][0]
    plain = []
    reference_loss(model, params, batch, plain)
    assert job.hidden_error(program, plain[0]) < 1e-5
    assert (the_job.layers, the_job.seq_len, the_job.vocab_size) == (
        5, 64, 256)
    assert the_job.param_count == flops.param_count(model)
    assert the_job.init_fn.layer_kinds == {"kda": 4, "mla": 1, "dense": 1,
                                           "moe": 4}
    # the step moves the bias: the wrapper hands the buffers through
    assert the_job.loss_fn.step_buffers is not None


def test_the_job_refuses_what_the_model_does_not_compute():
    for key, value in (("q_lora_rank", 1536), ("use_qk_norm", False),
                       ("kda_safe_gate", False), ("no_kda_lora", False),
                       ("use_kda_lora", True), ("linear_silu", False),
                       ("group_norm_size", 4),
                       ("gated_attention_proj_granularity_type",
                        "elementwise"),
                       ("num_kv_heads_for_linear_attn", 8),
                       ("use_mla_nope", True), ("use_nGPT", True),
                       ("value_norm", True), ("up_proj_norm", True),
                       ("scale_router_input", True), ("use_bias", True),
                       ("tie_word_embeddings", True),
                       ("rope_scaling", {"factor": 2}),
                       ("score_function", "softmax"), ("seq_aux", False),
                       ("moe_router_enable_expert_bias", False),
                       ("mtp_loss_scaling_factor", 0.3),
                       ("rotary_dim", 16),
                       ("moe_shared_expert_intermediate_size", 64)):
        with pytest.raises(ValueError, match="models/kda_mla_moe.py"):
            job.model_config(dict(toy(), **{key: value}))
    # a kept layer whose SwiGLU is clamped
    for name in ("expert_swiglu_limit_list",
                 "share_expert_swiglu_limit_list"):
        with pytest.raises(ValueError, match="clamp"):
            job.model_config(dict(toy(), **{name: [0, 0, 0, 4, 0]}))
    # the published lists clamp layers 34 and up: the cut keeps none
    assert not any(cell_model()["expert_swiglu_limit_list"][:34])
    assert not any(cell_model()["share_expert_swiglu_limit_list"][:34])
    with pytest.raises(ValueError, match="no whole number of periods"):
        worker.build_job(dict(toy(), num_hidden_layers=4))
    with pytest.raises(ValueError, match="experts_held lists them"):
        job.model_config(dict(toy(), num_experts=4))


def test_the_jobs_check_reads_nan_past_each_limit(built, capsys):
    """What ``worker.py`` calls: the reference's loss where the three
    hidden-state numbers agree, NaN (which fails the worker's
    comparison) where one does not, the readings printed either way.
    A KDA mechanism moves the KDA mixer's number, an MLA mechanism the
    MLA mixer's, the router's the final hidden states alone."""
    model, the_job, params, batch = built
    ids, labels = batch["input_ids"][0], batch["labels"][0]
    sound = the_job.reference_loss(params, ids, labels)
    assert sound == reference_loss(model, params, batch)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_hidden"
    assert line["median_token_error"] < line["tolerance"] == 1e-4
    assert line["kda_token_error"] < line["kda_tolerance"] == 1e-5
    assert line["mla_token_error"] < line["mla_tolerance"] == 1e-5
    for control, moved, still in (
            ("the decay's mean over a head's channels", "kda", "mla"),
            ("the gate's bound left out", "kda", "mla"),
            ("the head-wise gate left out", "mla", "kda"),
            ("the QK norms left out", "mla", "kda")):
        with controls.applied(model, control):
            assert np.isnan(the_job.reference_loss(params, ids, labels))
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line[f"{moved}_token_error"] > 1e2 * line[
            f"{moved}_tolerance"], (control, line)
        # the other mixer's input is the reference's own: it stays
        assert line[f"{still}_token_error"] < line[f"{still}_tolerance"]
    with controls.applied(model, "the group limit left out"):
        assert np.isnan(the_job.reference_loss(params, ids, labels))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["median_token_error"] > 10 * line["tolerance"]


def test_the_controls_script_gives_the_harness_verdicts(capsys):
    """``kda_mla_moe_controls.py`` as the chip runs it, at the toy size:
    the worker's own ``ReferenceCheck`` says ``ok`` of the sound
    reference on both seeds and not ``ok`` under every control."""
    assert controls.main(["--config",
                          os.path.join(HERE, "tiny_kda_mla_moe.json"),
                          "--controls", "3000006211",
                          "--sound", "3000006212"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [(line["seed"], line["control"]) for line in lines] == [
        (3000006211, "sound")] + [(3000006211, c) for c in controls.CONTROLS
                                  ] + [(3000006212, "sound")]
    for line in lines:
        assert line["ok"] == (line["control"] == "sound"), line
        assert (line["tolerance"], line["hidden_tolerance"],
                line["kda_tolerance"], line["mla_tolerance"]) == (
                    1e-4, 1e-4, 1e-5, 1e-5)


def test_the_cell_keeps_every_published_width():
    """The configuration against the catalog's row: every key at its
    published value but the four cuts of scale, and the job the sizes
    ISSUE 62 counted."""
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "ling-3.0-flash.json")) as f:
        published = json.load(f)
    assert model["source"] == published["source"]
    cuts = {"num_hidden_layers", "first_k_dense_replace", "num_experts",
            "vocab_size"}
    for key, value in published["config"].items():
        if key not in cuts:
            assert model[key] == value, key
    assert set(model["reduced"]) == cuts
    assert (model["num_hidden_layers"], model["first_k_dense_replace"],
            model["vocab_size"]) == (7, 1, 157184 // 8)
    dep = model["deployment"]
    held = model["num_experts"]
    assert held in (32, 16) and model["n_routed_experts"] == held
    assert dep["experts_held"] == list(range(held))
    assert dep["published_num_experts"] == 512 == published["config"][
        "num_experts"]
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] == (
        512 // held)
    config = job.model_config(model)
    from dlrover_tpu.models import kda_mla_moe
    assert kda_mla_moe.layer_plan(config) == [("kda", 4), ("mla", 1),
                                              ("kda", 1)]
    assert kda_mla_moe.layer_kinds(config) == {"kda": 6, "mla": 1,
                                               "dense": 1, "moe": 6}
    assert (config.n_routed_experts, config.n_group, config.topk_group,
            config.num_experts_per_tok, config.routed_scaling_factor) == (
                512, 8, 4, 8, 2.5)
    assert (config.num_heads, config.head_dim, config.kda_lower_bound,
            config.conv_kernel, config.kv_lora_rank, config.rope_theta) == (
                32, 128, -5.0, 4, 512, 6e6)
    assert flops.param_count(model) == {32: 1_733_803_328,
                                        16: 1_167_572_480}[held]
    assert (model["assumed"]["seq_len"], model["chips"]) == (8192, 1)
    assert config.compute_dtype == config.param_dtype == jnp.bfloat16
    # the readings are named as readings
    for key in ("layer_kinds", "kda_layer", "mla_layer", "qk_norm", "router",
                "router_bias", "balance_loss", "swiglu_limits",
                "multi_token_prediction", "initialisation"):
        assert key in model["assumed"], key
    assert model["num_nextn_predict_layers"] == 1  # as published


def test_the_arithmetic_by_hand():
    model = cell_model()
    held, batch = model["num_experts"], model["assumed"]["batch"]
    tokens = batch * 8192
    assert flops.tokens_per_step(model) == tokens
    assert flops.layer_counts(model) == {"kda": 6, "mla": 1, "dense": 1,
                                         "moe": 6}
    assert flops.layer_counts(dict(model, num_hidden_layers=42,
                                   first_k_dense_replace=2)) == {
        "kda": 35, "mla": 7, "dense": 2, "moe": 40}
    kda = 2560 * (6 * 4096 + 32)
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
           + 4096 * 2560)
    expert = 3 * 2560 * 768
    assert flops.expert_params(model) == expert
    assert flops.held_rows_expected(model) == tokens * 8 * held / 512
    active = (6 * kda + mla + 3 * 2560 * 6144
              + 6 * (2560 * 512 + expert + 8 * held / 512 * expert)
              + 2560 * 19648)
    assert flops.active_matmul_params(model) == active
    # the rule: three products of 128 x 128 a token and head, forward
    # and twice backward, six layers of 32 heads
    rule = 6 * 3 * 3 * 2 * 128 * 128 * 32 * tokens
    assert flops.kda_flops_per_step(model) == rule
    assert flops.kda_bytes_per_step(model) == (
        6 * 3 * (4 * 128 * 2 + 129 * 4) * 32 * tokens)
    pairs = 8192 * 8193 // 2
    attention = 3 * 32 * (2 * 192 + 2 * 128) * pairs * batch
    assert flops.mla_flops_per_step(model) == attention
    rows = batch * 8192 * 2
    # q, k (the rotary key head once), v and o in each of three passes
    assert flops.mla_bytes_per_step(model) == (
        (32 * 192 + 32 * 128 + 64 + 2 * 32 * 128) * rows * 3)
    assert flops.model_flops_per_step(model) == (
        6 * active * tokens + attention + rule)
    assert flops.gmm_flops(model, 1000) == 6 * expert * 1000
    assert flops.gmm_bytes(model, 0) == 2 * 3 * 6 * held * expert
    assert flops.kernel_flops_per_step(model) == (
        attention + rule + flops.gmm_flops(
            model, 6 * flops.held_rows_expected(model)))
    assert flops.param_count(toy()) == worker.build_job(toy()).param_count


@pytest.mark.parametrize("which", ["toy", "cell"])
def test_no_share_counts_more_work_than_its_kernels_run(which):
    """A share over 100 would mean work counted that the kernels do not
    run. The chain kernels execute, a token and head at a chunk of 64:
    ``W S``, ``Qg S`` and ``Kd^T U`` of ``dk x dv`` each, which is what
    the roofline counts, and ``P U`` of ``64 x dv`` beside them; and
    they move five prepared operands of the inputs' width and the
    float32 states each chunk starts from, which are no fewer bytes
    than q, k, v, o, the gate and beta."""
    model = cell_model() if which == "cell" else toy()
    heads, hd = model["num_attention_heads"], model["head_dim"]
    layers, tokens = flops.layer_counts(model)["kda"], flops.tokens_per_step(
        model)
    chunk = 64
    counted_forward = flops.kda_flops_per_step(model) / 3
    run_forward = layers * 2 * (3 * hd * hd + chunk * hd) * heads * tokens
    assert counted_forward <= run_forward
    moved_forward = layers * tokens * heads * (
        (5 * hd + chunk) * 2 + 4 * hd  # the operands, the chunk's decay
        + 4 * hd * hd / chunk)  # the states, a chunk
    assert flops.kda_bytes_per_step(model) / 3 <= moved_forward
    seq = model["assumed"]["seq_len"]
    assert flops.mla_flops_per_step(model) <= (
        3 * heads * (2 * (model["qk_head_dim"]) + 2 * model["v_head_dim"])
        * seq * seq * model["assumed"]["batch"])


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_readers_read_a_reduced_trace_and_the_counters():
    """The four new readers and those this cell shares with the latent
    expert families, on a made-up reduced trace, ``step_scopes`` and
    ``profile_window`` events, and on a run without their instructions
    or counters (the parent's program): nothing, not an error."""
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import arithmetic

    model = cell_model()
    trace = {"devices": {"/device:TPU:0": {}}, "steps": 4, "device_ops": [
        ["fusion.1", 2.0], ["mosaic:flash_mla_fwd.1", 0.08],
        ["mosaic:flash_mla_bwd.1", 0.12], ["mosaic:kda_fwd.18", 0.12],
        ["mosaic:kda_bwd.6", 0.2], ["mosaic:jvp_kda_fwd_.3", 0.08],
        ["kda_fwd.99", 9.0], ["mosaic:gdn_fwd.1", 7.0],
        ["mosaic:ssd_fwd.1", 7.0], ["mosaic:gmm.3", 0.04],
        ["fusion.2", 0.4], ["fusion.3", 0.04], ["fusion.4", 1.0]]}
    scopes = {"kind": "step_scopes", "pid": 77, "instructions": {
        "forward|kda": ["fusion.4", "kda_fwd.18"],
        "forward|kda/kda_chunk": ["fusion.2"],
        "backward|kda/kda_chunk": ["fusion.3", "kda_bwd.6"],
        "forward|": ["fusion.1"]}}
    window = {"kind": "profile_window", "pid": 77, "steps": 6,
              "step_counters": {"kda_log_decay_mean": 6 * -0.04,
                                "moe_rows_held": 6 * 6 * 8192.0,
                                "moe_rows_max": 6 * 6 * 300.0,
                                "moe_rows_dropped": 0.0}}
    run = {"worker": {"pid": 77}, "events": [scopes, window],
           "profile_window": window}
    ctx = {"trace": trace, "model": model, "flops": flops, "run": run,
           "arithmetic": arithmetic,
           "device": {"count": 1, "kind": "TPU v5 lite"}}
    # every Mosaic call whose name holds kda_, and no XLA fusion of
    # that name, no other family's kernel
    assert _reader("kda_ms")(ctx) == pytest.approx(100.0)
    by_bytes = flops.kda_bytes_per_step(model) / 819e9
    by_flops = flops.kda_flops_per_step(model) / 197e12
    assert by_bytes > by_flops  # the bytes bind
    assert _reader("kda_roofline")(ctx) == pytest.approx(
        100 * by_bytes / 0.1)
    assert _reader("kda_roofline")(ctx) < 100
    # the innermost scope alone, its kernels among it
    assert _reader("kda_chunk_ms")(ctx) == pytest.approx(
        1e3 * (0.4 + 0.04 + 0.2) / 4)
    assert _reader("kda_log_decay_mean")(ctx) == pytest.approx(-0.04)
    assert _reader("mla_attn_ms")(ctx) == pytest.approx(50.0)
    assert _reader("mla_attn_roofline")(ctx) == pytest.approx(
        100 * flops.mla_flops_per_step(model) / 197e12 / 0.05)
    assert _reader("expert_gmm_ms")(ctx) == pytest.approx(10.0)
    assert _reader("expert_load_imbalance")(ctx) == pytest.approx(
        300.0 / (8192.0 / model["n_routed_experts"]))
    assert _reader("expert_rows_dropped")(ctx) == 0.0
    # the gated delta rule's readers do not count these kernels
    assert _reader("gdn_ms")(dict(ctx, trace=dict(trace, device_ops=[
        row for row in trace["device_ops"] if "gdn" not in row[0]]))) is None
    bare = dict(ctx, trace=dict(trace, device_ops=[
        ["fusion.1", 2.0], ["mosaic:flash_fwd.1", 1.0],
        ["mosaic:gdn_fwd.1", 1.0]]),
        run={"worker": {"pid": 77}, "events": [], "profile_window": {
            "kind": "profile_window", "pid": 77, "steps": 6}})
    for name in READERS:
        assert _reader(name)(bare) is None, name
        assert _reader(name)(dict(bare, trace=None, run={})) is None, name
    # a family without ``kda_flops_per_step``: the time reads, the
    # share does not
    from chipbench.families.dense_gqa import flops as dense
    assert _reader("kda_ms")(dict(ctx, flops=dense)) == pytest.approx(100.0)
    assert _reader("kda_roofline")(dict(ctx, flops=dense)) is None


def test_the_manifest_lists_the_cell_and_its_metrics_by_name():
    """Looked up by name: no place in a list and no count is held."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    # what ISSUE 62 lists for the cell; a later PR may append a reader
    assert set(mine) >= {
        "tokens_per_s", "setup_s", "host_gap_ms", "step_device_ms",
        "step_mfu_pct", "mosaic_ms", "device_idle_pct", "dispatch_ms",
        "host_sync_ms", "input_wait_ms", "boot_import_s", "boot_backend_s",
        "boot_build_s", "step_fwd_ms", "step_replay_ms", "step_bwd_ms",
        "step_optimizer_ms", "attn_xla_ms", "ffn_ms", "head_loss_ms",
        "step_unscoped_ms", "hbm_held_pct", "mla_attn_ms",
        "mla_attn_roofline", "expert_gmm_ms", "expert_gmm_roofline",
        "expert_load_imbalance", "expert_rows_dropped"} | set(READERS)
    # readers that would count another family's kernels with these, and
    # the two whose lists the benchmark's own tests hold to one cell
    for other in ("flash_roofline", "gdn_ms", "gdn_chunk_ms", "ssd_ms",
                  "moe_experts_xla_ms", "hc_ms", "moe_group_reach",
                  "router_bias_abs"):
        assert other not in mine
    for name, layer in READERS.items():
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL] and entry["layer"] == layer
        assert entry["moves"] == "tokens_per_s"
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "steady", 1)
    (config,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert config["file"] == f"chipbench/configs/{NAME}.json"
    assert set(config["reduced"]) == set(cell_model()["reduced"])
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200


def test_the_published_rule_finds_nothing_wrong_with_the_configuration():
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import published_rule

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [c for c in bench["configs"] if c["name"] == NAME]
    assert len(mine) == 1
    assert published_rule.wrong(dict(bench, configs=mine), REPO) == []
    # and the rule bites on this configuration: a width cut is refused
    for width in ("moe_intermediate_size", "kv_lora_rank", "hidden_size",
                  "num_experts_per_tok", "head_dim"):
        cut = copy.deepcopy(bench)
        cut["configs"] = [dict(mine[0], reduced=mine[0]["reduced"]
                               + [width])]
        assert published_rule.wrong(cut, REPO), width
