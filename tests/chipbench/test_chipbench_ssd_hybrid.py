"""``chipbench/families/ssd_hybrid/``: the plain reference (float32
``jax.numpy``, the state-space recurrence token by token, a dense
masked softmax a head in blocks of query rows, the tied head in blocks
of rows) against ``models/ssd_hybrid.py``, the code the cell runs, at a
toy size on the CPU: the loss and the hidden states; the job's refusals
and its NaN past the hidden limit; the controls script; ``flops.py`` by
hand; the new readers, on a made-up trace and on the rows of a
recorded one; the configuration against what its source publishes.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its Pallas kernels in the interpreter),
so they differ only by the order of float32 sums. Every gradient and
every control is compared in ``tests/test_ssd_hybrid.py``, on both of
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
from chipbench.families.ssd_hybrid import flops, job  # noqa: E402

sys.path.insert(0, HERE)
import ssd_hybrid_controls as controls  # noqa: E402

LOSS_TOL = 1e-5
CELL = "granite4h-1chip.steady"
NAME = "granite-4.0-h-micro-d20-1chip"
READERS = {"ssd_ms": "kernels", "ssd_roofline": "kernels",
           "ssd_xla_ms": "step program", "ssd_dt_mean": "step program"}


def toy():
    with open(os.path.join(HERE, "tiny_ssd_hybrid.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def perturbed(init_fn):
    """Initial weights with the norm scales, skips and biases moved off
    their starting values, so that a reference that dropped one would
    show."""
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
    from dlrover_tpu.models import ssd_hybrid

    model, the_job, params, batch = built
    system, aux = the_job.loss_fn(params, batch, None)
    assert abs(float(system) - reference_loss(model, params, batch)) < LOSS_TOL
    assert 0.01 < float(aux["ssd_dt_mean"]) < 0.1
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"] == 1e-4
    program = ssd_hybrid.apply_hidden(params, batch["input_ids"],
                                      job.model_config(toy()))[0][0]
    plain = []
    reference_loss(model, params, batch, plain)
    assert job.hidden_error(program, plain[0]) < 1e-6
    assert (the_job.layers, the_job.seq_len, the_job.vocab_size) == (
        4, 64, 512)
    assert the_job.param_count == flops.param_count(model)
    assert the_job.init_fn.layer_kinds == {"ssd": 2, "attn_full": 2}


def test_the_job_refuses_what_the_model_does_not_compute():
    for key, value in (("tie_word_embeddings", False),
                       ("attention_bias", True), ("hidden_act", "gelu"),
                       ("position_embedding_type", "rope"),
                       ("num_local_experts", 8), ("mamba_conv_bias", False),
                       ("mamba_proj_bias", True), ("mamba_expand", 4),
                       ("normalization_function", "layernorm")):
        with pytest.raises(ValueError, match="models/ssd_hybrid.py"):
            job.model_config(dict(toy(), **{key: value}))
    with pytest.raises(ValueError, match="no whole number of periods"):
        worker.build_job(dict(toy(), num_hidden_layers=3))
    # a file that leaves a multiplier out is not given a 1
    for key in ("embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling"):
        with pytest.raises(KeyError, match=key):
            job.model_config({k: v for k, v in toy().items() if k != key})


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
    assert line["median_token_error"] < line["tolerance"] == 1e-5
    assert line["attention_token_error"] < line["attention_tolerance"] == 1e-5
    for control in ("e4m3 operands", "a bf16 carried state"):
        with controls.applied(model, control):
            assert np.isnan(the_job.reference_loss(params, ids, labels))
    # the first attention layer's mixer alone is what sees that layer's
    # two mechanisms on the chip (near-even attention: ``job.py``)
    capsys.readouterr()
    for control in ("a scale of 1 / sqrt(head_dim)",
                    "rotary applied on the attention layers"):
        with controls.applied(model, control):
            assert np.isnan(the_job.reference_loss(params, ids, labels))
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["attention_token_error"] > 1e3 * line[
            "attention_tolerance"], (control, line)


def test_the_controls_script_gives_the_harness_verdicts(capsys):
    """``ssd_hybrid_controls.py`` as the chip runs it, at the toy size:
    the worker's own ``ReferenceCheck`` says ``ok`` of the sound
    reference on both seeds and not ``ok`` under every control."""
    assert controls.main(["--config",
                          os.path.join(HERE, "tiny_ssd_hybrid.json"),
                          "--controls", "3000005711",
                          "--sound", "3000005712"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [(line["seed"], line["control"]) for line in lines] == [
        (3000005711, "sound")] + [(3000005711, c) for c in controls.CONTROLS
                                  ] + [(3000005712, "sound")]
    for line in lines:
        assert line["ok"] == (line["control"] == "sound"), line
        assert (line["tolerance"], line["hidden_tolerance"],
                line["attention_tolerance"]) == (1e-4, 1e-5, 1e-5)


def test_the_cell_keeps_every_published_width():
    """The configuration against the catalog's row: every key at its
    published value but the one cut of scale, the depth two whole
    periods, and the job the sizes ISSUE 57 counted."""
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "granite-4.0-h-micro.json")) as f:
        published = json.load(f)
    assert model["source"] == published["source"]
    for key, value in published["config"].items():
        if key != "num_hidden_layers":
            assert model[key] == value, key
    assert set(model["reduced"]) == {"num_hidden_layers"}
    assert model["num_hidden_layers"] == 20
    assert published["config"]["num_hidden_layers"] == 40 == len(
        model["layer_types"])
    assert model["vocab_size"] == 100352 == model["deployment"][
        "published_vocab_size"]
    assert model["deployment"]["chips_sharing_a_layer"] == 1
    config = job.model_config(model)
    from dlrover_tpu.models import ssd_hybrid
    assert ssd_hybrid.layer_plan(config) == 5 * ["mamba"] + [
        "attention"] + 4 * ["mamba"]
    assert ssd_hybrid.layer_kinds(config) == {"ssd": 18, "attn_full": 2}
    assert (config.embedding_multiplier, config.residual_multiplier,
            config.attention_multiplier, config.logits_scaling) == (
                12.0, 0.22, 0.015625, 8.0)
    assert (config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state,
            config.mamba_n_groups, config.mamba_chunk_size,
            config.head_dim) == (64, 64, 128, 1, 256, 64)
    assert flops.param_count(model) == 1_698_459_520
    assert flops.param_count(dict(model, num_hidden_layers=40)) == (
        36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + 2048)
    assert (model["assumed"]["seq_len"], model["assumed"]["batch"],
            model["chips"]) == (8192, 1, 1)
    assert config.compute_dtype == config.param_dtype == jnp.bfloat16
    # what fewer layers do to the head's share is said, and is so
    head = 2048 * 100352
    assert "12.1%" in model["stands_for"] and "6.4%" in model["stands_for"]
    assert round(100 * head / flops.active_matmul_params(model), 1) == 12.1
    assert round(100 * head / flops.active_matmul_params(
        dict(model, num_hidden_layers=40)), 1) == 6.4


def test_the_arithmetic_by_hand():
    """``flops.py`` against the sizes written out: ISSUE 57's counts."""
    model = cell_model()
    assert flops.tokens_per_step(model) == 8192
    assert flops.layer_counts(model) == {"ssd": 18, "attn_full": 2}
    mlp = 2048 * 16384 + 8192 * 2048
    mamba = 2048 * (4096 + 4352 + 64) + 4096 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    head = 2048 * 100352
    assert (mlp, mamba, attention) == (50_331_648, 25_821_184, 10_485_760)
    assert flops.active_matmul_params(model) == (
        18 * (mamba + mlp) + 2 * (attention + mlp) + head) == 1_697_906_688
    # all parameters: those, the convolutions with their biases, three
    # vectors of 64 and the gated norm a Mamba layer, the norms
    assert flops.param_count(model) == 1_697_906_688 + 18 * (
        4352 * 5 + 192 + 4096) + 20 * 4096 + 2048 == 1_698_459_520
    pairs = 8192 * 8193 // 2
    causal = 2 * 3 * 32 * 4 * 64 * pairs
    assert flops.causal_flops_per_step(model) == causal
    assert 1.6e12 < causal < 1.7e12  # ISSUE 57's 1.6 TFLOP
    ssd = 18 * 3 * 2 * 2 * 64 * 128 * 64 * 8192
    assert flops.ssd_flops_per_step(model) == ssd
    assert 0.9e12 < ssd < 1.0e12  # ISSUE 57's 0.9 TFLOP
    assert flops.model_flops_per_step(model) == (
        6 * 1_697_906_688 * 8192 + causal + ssd)
    assert 8.5e13 < flops.model_flops_per_step(model) < 8.7e13
    # x and y of 4096 and B and C of 128 in bf16, dt of 64 in float32,
    # once forward and with their gradients once backward: three passes
    a_pass = (2 * 4096 + 2 * 128) * 2 + 64 * 4
    assert flops.ssd_bytes_per_step(model) == 18 * 3 * a_pass * 8192
    assert 7.5e9 < flops.ssd_bytes_per_step(model) < 7.6e9
    # the bytes bind: 9.2 ms at 819 GB/s against 4.8 ms at 197 TFLOP/s
    assert flops.ssd_bytes_per_step(model) / 819e9 > ssd / 197e12
    # GQA: q and o of 32 heads, k and v of 8; 4 arrays forward, 8 and
    # 3 gradients backward
    q, kv = 32 * 64 * 8192 * 2, 8 * 64 * 8192 * 2
    assert flops.causal_bytes_per_step(model) == 2 * (
        (2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv))
    assert flops.kernel_flops_per_step(model) == causal + ssd
    assert flops.kernel_bytes_per_step(model) == (
        flops.causal_bytes_per_step(model) + flops.ssd_bytes_per_step(model))
    # the toy, counted by its own init
    assert flops.param_count(toy()) == worker.build_job(toy()).param_count


@pytest.mark.parametrize("which", ["toy", "cell"])
def test_no_share_counts_more_work_than_its_kernels_run(which):
    """A share over 100 would mean work counted that the kernels do not
    run. The chunked kernels execute, a token and head, at a chunk of
    ``Q``: the state's read ``C H^T`` and its update ``(w x)^T B`` of
    ``P x N`` each, which is what the roofline counts, and ``(L * C
    B^T) x`` of ``Q x P`` beside them; and they move x, dt, B, C and y
    and the float32 states each chunk starts from, which are no fewer
    bytes than x, dt, B, C and y."""
    model = cell_model() if which == "cell" else toy()
    heads, p, n = (model["mamba_n_heads"], model["mamba_d_head"],
                   model["mamba_d_state"])
    chunk = model["mamba_chunk_size"]
    layers, tokens = flops.layer_counts(model)["ssd"], flops.tokens_per_step(
        model)
    counted_forward = flops.ssd_flops_per_step(model) / 3
    run_forward = layers * 2 * (2 * p * n + chunk * p) * heads * tokens
    assert counted_forward <= run_forward
    moved_forward = layers * tokens * (
        (2 * heads * p + 2 * n) * 2 + 2 * 4 * heads  # dt and its sums
        + 4 * heads * p * n / chunk)  # the states, a chunk
    assert flops.ssd_bytes_per_step(model) / 3 <= moved_forward
    # the attention layers: the causal half, as the other families count
    q_heads, hd = model["num_attention_heads"], model["assumed"]["head_dim"]
    seq = model["assumed"]["seq_len"]
    assert flops.causal_flops_per_step(model) <= (
        flops.layer_counts(model)["attn_full"] * 3 * q_heads * 4 * hd
        * seq * seq * model["assumed"]["batch"])


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_readers_read_a_reduced_trace_and_the_counters():
    """The four new readers and the two this cell shares with the
    ``gqa_moe`` and ``delta_hybrid`` families, on a made-up reduced
    trace, ``step_scopes`` and ``profile_window`` events, and on a run
    without their instructions or counters (the parent's program):
    nothing, not an error."""
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import arithmetic

    model = cell_model()
    trace = {"devices": {"/device:TPU:0": {}}, "steps": 4, "device_ops": [
        ["fusion.1", 2.0], ["mosaic:flash_fwd.6", 0.06],
        ["mosaic:flash_dkv.3", 0.08], ["mosaic:flash_dq.3", 0.06],
        ["mosaic:ssd_fwd.18", 0.12], ["mosaic:ssd_bwd.6", 0.2],
        ["mosaic:jvp_ssd_fwd_.3", 0.08], ["ssd_fwd.99", 9.0],
        ["mosaic:ssm_scan_fwd.1", 7.0], ["mosaic:gdn_fwd.1", 7.0],
        ["fusion.2", 0.4], ["fusion.3", 0.04], ["fusion.4", 1.0]]}
    scopes = {"kind": "step_scopes", "pid": 77, "instructions": {
        "forward|ssd": ["fusion.2", "ssd_fwd.18"],
        "backward|ssd/ssd_chunk": ["fusion.3", "ssd_bwd.6"],
        "forward|ffn": ["fusion.4"], "forward|": ["fusion.1"]}}
    window = {"kind": "profile_window", "pid": 77, "steps": 6,
              "step_counters": {"ssd_dt_mean": 6 * 0.03}}
    run = {"worker": {"pid": 77}, "events": [scopes, window],
           "profile_window": window}
    ctx = {"trace": trace, "model": model, "flops": flops, "run": run,
           "arithmetic": arithmetic,
           "device": {"count": 1, "kind": "TPU v5 lite"}}
    # every Mosaic call whose name holds ssd_, and no XLA fusion of
    # that name, no other kernel
    assert _reader("ssd_ms")(ctx) == pytest.approx(100.0)
    by_bytes = flops.ssd_bytes_per_step(model) / 819e9
    assert _reader("ssd_roofline")(ctx) == pytest.approx(
        100 * by_bytes / 0.1)
    assert _reader("ssd_roofline")(ctx) < 10
    # the two scopes' XLA, the kernels under them left out
    assert _reader("ssd_xla_ms")(ctx) == pytest.approx(1e3 * 0.44 / 4)
    assert _reader("ssd_dt_mean")(ctx) == pytest.approx(0.03)
    assert _reader("full_attn_ms")(ctx) == pytest.approx(50.0)
    assert _reader("full_attn_roofline")(ctx) == pytest.approx(
        100 * flops.causal_flops_per_step(model) / 197e12 / 0.05)
    # ``attn_xla_ms``'s fixed list does not know the two scopes
    assert _reader("attn_xla_ms")(ctx) == 0
    bare = dict(ctx, trace=dict(trace, device_ops=[
        ["fusion.1", 2.0], ["mosaic:flash_fwd.1", 1.0],
        ["mosaic:ssm_scan_fwd.1", 1.0]]),
        run={"worker": {"pid": 77}, "events": [], "profile_window": {
            "kind": "profile_window", "pid": 77, "steps": 6}})
    for name in READERS:
        assert _reader(name)(bare) is None, name
        assert _reader(name)(dict(bare, trace=None, run={})) is None, name
    # a family without ``ssd_flops_per_step``: the time reads, the
    # share does not
    from chipbench.families.dense_gqa import flops as dense
    assert _reader("ssd_ms")(dict(ctx, flops=dense)) == pytest.approx(100.0)
    assert _reader("ssd_roofline")(dict(ctx, flops=dense)) is None


def test_the_readers_give_what_a_recorded_run_printed():
    """``recorded_granite4h_ssd.json``: the ``ssd``, ``ssd_chunk`` and
    Mosaic rows of one traced run of the cell on the chip, with its
    ``step_scopes`` and ``profile_window`` events. The four new readers
    and the two shared ones give the values that run's result line
    had."""
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import arithmetic

    with open(os.path.join(HERE, "recorded_granite4h_ssd.json")) as f:
        recorded = json.load(f)
    window = recorded["profile_window"]
    ctx = {"trace": recorded["trace"], "model": cell_model(), "flops": flops,
           "arithmetic": arithmetic,
           "device": {"count": 1, "kind": "TPU v5 lite"},
           "run": {"worker": {"pid": window["pid"]},
                   "events": [recorded["step_scopes"], window],
                   "profile_window": window}}
    assert len(recorded["printed"]) == 6
    for name, printed in recorded["printed"].items():
        assert _reader(name)(ctx) == pytest.approx(printed, rel=1e-9), name
    names = [name for name, _ in recorded["trace"]["device_ops"]]
    # a period's nine Mamba layers, forward and replay, and backward
    assert sum(n.startswith("mosaic:ssd_fwd") for n in names) == 18
    assert sum(n.startswith("mosaic:ssd_bwd") for n in names) == 9
    assert 0 < recorded["printed"]["ssd_roofline"] < 100


def test_the_manifest_lists_the_cell_and_its_metrics_by_name():
    """Looked up by name: no place in a list and no count is held."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    # what ISSUE 57 lists for the cell; a later PR may append a reader
    assert set(mine) >= {
        "tokens_per_s", "setup_s", "host_gap_ms", "step_device_ms",
        "step_mfu_pct", "mosaic_ms", "device_idle_pct", "dispatch_ms",
        "host_sync_ms", "input_wait_ms", "boot_import_s", "boot_backend_s",
        "boot_build_s", "step_fwd_ms", "step_replay_ms", "step_bwd_ms",
        "step_optimizer_ms", "attn_xla_ms", "ffn_ms", "head_loss_ms",
        "step_unscoped_ms", "hbm_held_pct", "full_attn_ms",
        "full_attn_roofline"} | set(READERS)
    # readers that would count another family's kernels with these
    for other in ("flash_roofline", "ssm_scan_ms", "gdn_ms", "gdn_chunk_ms"):
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
    assert config["reduced"] == ["num_hidden_layers"]


def test_the_published_rule_finds_nothing_wrong_with_the_configuration():
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import published_rule

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [c for c in bench["configs"] if c["name"] == NAME]
    assert len(mine) == 1 and set(mine[0]["reduced"]) == set(
        cell_model()["reduced"])
    assert published_rule.wrong(dict(bench, configs=mine), REPO) == []
    # and the rule bites on this configuration: a width cut is refused,
    # and a published key moved without a word
    for width in ("mamba_d_state", "shared_intermediate_size", "hidden_size"):
        cut = copy.deepcopy(bench)
        cut["configs"] = [dict(mine[0], reduced=mine[0]["reduced"]
                               + [width])]
        said = published_rule.wrong(cut, REPO)
        assert said, width
