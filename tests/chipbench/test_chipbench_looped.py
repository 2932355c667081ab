"""``chipbench/families/looped/``: the plain reference (float32
``jax.numpy``, a Python loop over the passes and the layers, a dense
masked softmax a head and the whole logits in blocks of rows) against
``models/looped.py``, the code the cell runs, at a toy size on the CPU:
the loss, the cross entropy of every pass, the exit distribution and
the last pass's states; the job's refusals and its NaN past a limit;
the controls, each caught; ``flops.py`` by hand; the four readers on a
made-up context; the configuration against what its source publishes;
the manifest's entries by name.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its flash kernels in the interpreter), so
they differ only by the order of float32 sums. Every leaf's gradient is
compared in ``tests/test_looped.py``. On the chip the same comparison
runs in every first worker round at the published widths, against bf16
compute, with the limits ``job.py`` gives.
"""

import copy
import importlib.util
import json
import math
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
from chipbench.families.looped import flops, job  # noqa: E402

sys.path.insert(0, HERE)
import looped_controls as controls  # noqa: E402

CELL = "ouro26b-1chip.steady"
NAME = "ouro-2.6b-d12-1chip"
READERS = ("exit_gate_ms", "loop_exit_entropy", "loop_exit_mean_pass",
           "loop_loss_gain")


def toy():
    with open(os.path.join(HERE, "tiny_looped.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def perturbed(init_fn):
    """Initial weights with the norm scales and the gate's bias moved
    off their starting values, so that a reference that dropped one
    would show."""
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


def test_the_program_agrees_with_the_reference(built):
    model, the_job, params, batch = built
    system, aux = the_job.loss_fn(params, batch, None)
    plain = job.reference_parts(model, job.model_config(model), params,
                                batch["input_ids"][0], batch["labels"][0])
    assert abs(float(system) - float(plain["loss"])) < 1e-5
    assert plain["pass_losses"].shape == plain["exit_distribution"].shape == (
        3,)
    assert abs(float(aux["loop_loss_first"])
               - float(plain["pass_losses"][0])) < 1e-5
    assert abs(float(aux["loop_loss_last"])
               - float(plain["pass_losses"][-1])) < 1e-5
    assert abs(float(aux["loop_exit_mean_pass"]) - float(
        (np.arange(1, 4) * np.asarray(plain["exit_distribution"])).sum())
               ) < 1e-5
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"] == 1e-4
    assert (the_job.layers, the_job.seq_len, the_job.vocab_size) == (
        2, 64, 512)
    assert the_job.param_count == flops.param_count(model)
    assert the_job.init_fn.layer_kinds == {"attn_full": 2}
    assert the_job.init_fn.passes == 3


def test_the_job_refuses_what_the_model_does_not_compute():
    for key, value in (("tie_word_embeddings", True), ("hidden_act", "gelu"),
                       ("use_sliding_window", True), ("sliding_window", 128),
                       ("rope_scaling", {"type": "yarn"}),
                       ("layer_types", ["sliding_attention"] * 4)):
        with pytest.raises(ValueError, match="models/looped.py"):
            job.model_config(dict(toy(), **{key: value}))
    # a file that leaves the passes or the entropy's weight out is not
    # given a default
    with pytest.raises(KeyError, match="total_ut_steps"):
        job.model_config({k: v for k, v in toy().items()
                          if k != "total_ut_steps"})
    bare = toy()
    del bare["assumed"]["exit_entropy_beta"]
    with pytest.raises(KeyError, match="exit_entropy_beta"):
        job.model_config(bare)


def test_the_jobs_check_reads_nan_past_a_limit(built, capsys):
    """What ``worker.py`` calls: the reference's loss where every
    reading is inside its limit, NaN (which fails the worker's
    comparison) where one is not, the readings printed either way."""
    model, the_job, params, batch = built
    ids, labels = batch["input_ids"][0], batch["labels"][0]
    sound = the_job.reference_loss(params, ids, labels)
    assert abs(sound - float(the_job.loss_fn(params, batch, None)[0])) < 1e-5
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_hidden"
    assert line["reference_loss"] == sound
    for name, limit in line["tolerances"].items():
        assert line[name] < limit, name
    assert set(line["tolerances"]) == {
        "pass_loss_diff", "exit_diff", "median_token_error",
        "gate_grad_error", "head_grad_error"}
    with controls.applied(model, "e4m3 operands"):
        assert np.isnan(the_job.reference_loss(params, ids, labels))


@pytest.mark.parametrize("lost, reads", [
    ("weights", "gate_grad_error"), ("kernel", "head_grad_error")])
def test_a_fault_of_the_heads_backward_alone_fails_the_check(
        built, lost, reads, monkeypatch, capsys):
    """The timed step runs ``weighted_lm_head_loss`` under ``jax.grad``:
    a head whose backward loses the weights' cotangent (the gate then
    learns from the entropy alone) or the kernel's leaves every forward
    number where it was, and the check reads it in the gate's or the
    head's gradient against the reference's ``jax.grad``."""
    model, _, params, batch = built
    sound = job.looped.weighted_lm_head_loss

    def faulty(hidden, kernel, labels, weights, chunk):
        if lost == "weights":
            weights = jax.lax.stop_gradient(weights)
        else:
            kernel = jax.lax.stop_gradient(kernel)
        return sound(hidden, kernel, labels, weights, chunk)

    monkeypatch.setattr(job.looped, "weighted_lm_head_loss", faulty)
    loss = job.build(toy()).reference_loss(
        params, batch["input_ids"][0], batch["labels"][0])
    assert np.isnan(loss)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    failed = {name for name, limit in line["tolerances"].items()
              if not line[name] <= limit}
    assert failed == {reads}, line
    assert line[reads] > 0.3


@pytest.mark.parametrize("fault", controls.CONTROLS,
                         ids=[f.replace(" ", "-") for f in controls.CONTROLS])
def test_the_comparison_catches(built, fault, capsys):
    """Each control (ISSUE 65's seven and the precision below), put into
    the reference alone, fails at least one of ``correct``'s numbers by
    ten times its float32 limit and more; which numbers a control moves
    is said: a wrong exit distribution leaves the states and the ``L_t``
    alone, and only the loss and the distribution see it."""
    model, the_job, params, batch = built
    ids, labels = batch["input_ids"][0], batch["labels"][0]
    system = float(the_job.loss_fn(params, batch, None)[0])
    with controls.applied(model, fault):
        loss = the_job.reference_loss(params, ids, labels)
    assert model == toy()  # the control is taken out again
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    failed = {name for name, limit in line["tolerances"].items()
              if not line[name] <= 10 * limit}
    if not abs(system - line["reference_loss"]) <= 10 * the_job.reference_tol:
        failed.add("loss")
    print(fault, sorted(failed), line)
    assert failed, fault
    assert np.isnan(loss) == bool(failed - {"loss"})
    moves = {
        "three passes for four": {"pass_loss_diff", "exit_diff"},
        "the final norm left out between passes": {"median_token_error"},
        "the sandwich's output norms left out": {"median_token_error"},
        "the last pass gated too": {"exit_diff", "loss",
                                    "gate_grad_error"},
        "the entropy's sign turned": {"loss", "gate_grad_error"},
        "rotary base 1e4": {"median_token_error"},
        "the head's weights all 1": {"loss", "gate_grad_error",
                                     "head_grad_error"},
        "e4m3 operands": {"median_token_error", "head_grad_error"},
    }[fault]
    assert moves <= failed, (fault, failed)
    # what only the objective's own arithmetic changes leaves the
    # states, the exit distribution and the cross entropies alone: the
    # loss and the gradients see it (the entropy has no head in it)
    if fault in ("the entropy's sign turned", "the head's weights all 1"):
        assert failed == moves


def test_the_controls_script_gives_the_harness_verdicts(capsys):
    """``looped_controls.py`` as the chip runs it, at the toy size: the
    worker's own ``ReferenceCheck`` says ``ok`` of the sound reference
    on both seeds and not ``ok`` under every control."""
    assert controls.main(["--config", os.path.join(HERE, "tiny_looped.json"),
                          "--controls", "3000006511",
                          "--sound", "3000006512"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [(line["seed"], line["control"]) for line in lines] == [
        (3000006511, "sound")] + [(3000006511, c) for c in controls.CONTROLS
                                  ] + [(3000006512, "sound")]
    for line in lines:
        assert line["ok"] == (line["control"] == "sound"), line
        assert line["tolerance"] == 1e-4


def test_the_cell_keeps_every_published_width():
    """The configuration against the catalog's row: every key at its
    published value but the one cut of scale, all four passes, and the
    job the sizes ISSUE 65 counted."""
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "ouro-2.6b.json")) as f:
        published = json.load(f)
    assert model["source"] == published["source"]
    for key, value in published["config"].items():
        if key != "num_hidden_layers":
            assert model[key] == value, key
    assert set(model["reduced"]) == {"num_hidden_layers"}
    assert model["num_hidden_layers"] == 12
    assert published["config"]["num_hidden_layers"] == 48 == len(
        model["layer_types"])
    assert model["total_ut_steps"] == 4 == published["config"][
        "total_ut_steps"]
    assert model["vocab_size"] == 49152 == model["deployment"][
        "published_vocab_size"]
    config = job.model_config(model)
    assert (config.num_passes, config.num_layers, config.num_heads,
            config.num_kv_heads, config.head_dim, config.hidden_size,
            config.intermediate_size, config.rope_theta) == (
                4, 12, 16, 16, 128, 2048, 5632, 1e6)
    assert (model["assumed"]["seq_len"], model["assumed"]["batch"],
            model["assumed"]["head_chunk"], model["chips"]) == (
                8192, 1, 1024, 1)
    assert config.compute_dtype == config.param_dtype == jnp.bfloat16
    assert config.exit_entropy_beta == 0.05
    optimizer = model["assumed"]["optimizer"]
    assert (optimizer["name"], optimizer["b1"], optimizer["b2"],
            optimizer["weight_decay"], optimizer["mu_dtype"]) == (
                "adamw", 0.9, 0.95, 0.1, "float32")
    worker.build_optimizer(optimizer)  # optax takes every key
    # what fewer layers do to the head's share is said, and is so
    assert "11.0%" in model["stands_for"] and "21.9%" in model["stands_for"]
    total = flops.model_flops_per_step(model)
    head = 6 * 4 * 2048 * 49152 * 8192
    assert round(100 * head / total, 1) == 11.0
    assert round(100 * flops.causal_flops_per_step(model) / total, 1) == 21.9
    whole = dict(model, num_hidden_layers=48)
    assert round(100 * head / flops.model_flops_per_step(whole)) == 3


def test_the_arithmetic_by_hand():
    """``flops.py`` against the sizes written out: ISSUE 65's counts. A
    layer's matmul parameters and its attention count once a PASS, and
    so does the head: model FLOPs are not 6 x the parameters."""
    model = cell_model()
    assert flops.tokens_per_step(model) == 8192
    assert flops.layer_passes(model) == 48
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert flops.layer_matmul_params(model) == layer == 51_380_224
    head = 2048 * 49152
    assert head == 100_663_296
    assert flops.param_count(model) == (
        12 * (layer + 4 * 2048) + 2 * head + 2048 + 2049) == 817_991_681
    assert 12 * (layer + 8192) == 616_660_992
    assert flops.active_matmul_params(model) == 4 * 12 * layer + 4 * head
    pairs = 8192 * 8193 // 2
    causal = 48 * 3 * 16 * 4 * 128 * pairs
    assert flops.causal_flops_per_step(model) == causal
    # ISSUE 65 counts the pairs as seq^2 / 2: 6 x 48 x 8192 x 2048 a token
    assert abs(causal / 8192 - 6 * 48 * 8192 * 2048) < 1e-3 * causal / 8192
    assert 3.95e13 < causal < 3.97e13
    assert flops.model_flops_per_step(model) == (
        6 * (48 * layer + 4 * head) * 8192 + causal)
    a_token = flops.model_flops_per_step(model) / 8192
    assert round(6 * (48 * layer + 4 * head) / 1e9, 2) == 17.21
    assert round(a_token / 1e9, 2) == 22.05
    assert round(flops.model_flops_per_step(model) / 1e14, 3) == 1.806
    # far from 6 x the parameters: 4.9e9 a token
    assert a_token > 4 * 6 * flops.param_count(model)
    # MHA: q, k, v, o of 16 heads each; 4 arrays forward, 8 and 3
    # gradients backward, a layer pass
    one = 16 * 128 * 8192 * 2
    assert flops.causal_bytes_per_step(model) == 48 * (4 + 5 + 3) * one
    assert flops.kernel_flops_per_step(model) == causal
    assert flops.kernel_bytes_per_step(model) == (
        flops.causal_bytes_per_step(model))
    # the FLOPs bind the attention's roofline on a v5e
    assert causal / 197e12 > flops.causal_bytes_per_step(model) / 819e9
    # no share counts more than the kernels run: the causal half is at
    # most the whole square
    assert causal <= 48 * 3 * 16 * 4 * 128 * 8192 * 8192
    # the toy, counted by its own init
    assert flops.param_count(toy()) == worker.build_job(toy()).param_count
    assert flops.layer_passes(toy()) == 6


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_readers_read_a_reduced_trace_and_the_counters():
    """The four new readers and the ones this cell shares with the
    other families, on a made-up reduced trace, ``step_scopes`` and
    ``profile_window`` events, and on a run without their instructions
    or counters (the parent's program): nothing, not an error."""
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import arithmetic

    model = cell_model()
    trace = {"devices": {"/device:TPU:0": {}}, "steps": 4, "device_ops": [
        ["fusion.1", 2.0], ["mosaic:flash_fwd.6", 0.8],
        ["mosaic:flash_dkv.3", 0.7], ["mosaic:flash_dq.3", 0.5],
        ["fusion.2", 0.004], ["fusion.3", 0.006], ["fusion.4", 1.0],
        ["fusion.5", 0.6], ["fusion.6", 0.2]]}
    scopes = {"kind": "step_scopes", "pid": 77, "instructions": {
        "forward|exit_gate": ["fusion.2"],
        "backward|exit_gate": ["fusion.3"],
        "forward|ffn": ["fusion.4"], "forward|head_loss": ["fusion.5"],
        "forward|attn_full": ["fusion.6", "flash_fwd.6"],
        "forward|": ["fusion.1"]}}
    window = {"kind": "profile_window", "pid": 77, "steps": 6,
              "step_counters": {"loop_exit_entropy": 6 * 1.1,
                                "loop_exit_mean_pass": 6 * 2.0,
                                "loop_loss_first": 6 * 10.9,
                                "loop_loss_last": 6 * 10.8}}
    run = {"worker": {"pid": 77}, "events": [scopes, window],
           "profile_window": window}
    ctx = {"trace": trace, "model": model, "flops": flops, "run": run,
           "arithmetic": arithmetic,
           "device": {"count": 1, "kind": "TPU v5 lite"}}
    assert _reader("exit_gate_ms")(ctx) == pytest.approx(1e3 * 0.010 / 4)
    assert _reader("loop_exit_entropy")(ctx) == pytest.approx(1.1)
    assert 0 <= _reader("loop_exit_entropy")(ctx) <= math.log(4)
    assert _reader("loop_exit_mean_pass")(ctx) == pytest.approx(2.0)
    assert _reader("loop_loss_gain")(ctx) == pytest.approx(0.1)
    assert _reader("full_attn_ms")(ctx) == pytest.approx(500.0)
    assert _reader("full_attn_roofline")(ctx) == pytest.approx(
        100 * flops.causal_flops_per_step(model) / 197e12 / 0.5)
    assert _reader("full_attn_roofline")(ctx) < 100
    assert _reader("ffn_ms")(ctx) == pytest.approx(250.0)
    assert _reader("head_loss_ms")(ctx) == pytest.approx(150.0)
    assert _reader("attn_xla_ms")(ctx) == pytest.approx(50.0)
    # the parent's program: no such scope, no such counter
    bare_scopes = dict(scopes, instructions={
        "forward|ffn": ["fusion.4"], "forward|": ["fusion.1"]})
    bare_window = {"kind": "profile_window", "pid": 77, "steps": 6}
    bare = dict(ctx, run={"worker": {"pid": 77},
                          "events": [bare_scopes, bare_window],
                          "profile_window": bare_window})
    for name in READERS:
        assert _reader(name)(bare) is None, name
        assert _reader(name)(dict(bare, trace=None, run={})) is None, name
    # one of the two losses alone is no gain
    half = dict(window, step_counters={"loop_loss_first": 60.0})
    assert _reader("loop_loss_gain")(dict(ctx, run=dict(
        run, profile_window=half))) is None


def test_the_manifest_lists_the_cell_and_its_metrics_by_name():
    """Looked up by name: no place in a list and no count is held."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    # what ISSUE 65 lists for the cell; a later PR may append a reader
    assert set(mine) >= {
        "tokens_per_s", "setup_s", "host_gap_ms", "step_device_ms",
        "step_mfu_pct", "mosaic_ms", "device_idle_pct", "dispatch_ms",
        "host_sync_ms", "input_wait_ms", "boot_import_s", "boot_backend_s",
        "boot_build_s", "step_fwd_ms", "step_replay_ms", "step_bwd_ms",
        "step_optimizer_ms", "attn_xla_ms", "ffn_ms", "head_loss_ms",
        "step_unscoped_ms", "hbm_held_pct", "full_attn_ms",
        "full_attn_roofline"} | set(READERS)
    # readers that would count another family's kernels with these
    for other in ("flash_roofline", "ssm_scan_ms", "gdn_ms", "ssd_ms",
                  "kda_ms", "ckpt_stall_s"):
        assert other not in mine
    for name in READERS:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["layer"] == "step program"
        assert entry["moves"] == "tokens_per_s"
        assert entry["source"] == ("program_span" if name == "exit_gate_ms"
                                   else "program_counter")
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "steady", 1)
    assert "21.9%" in cell["why"] and "11.0%" in cell["why"]
    assert len(cell["why"]) <= 200
    (config,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert config["file"] == f"chipbench/configs/{NAME}.json"
    assert config["reduced"] == ["num_hidden_layers"]
    assert len(config["why"]) <= 200


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
    # and so is a cut of the passes that nobody lists
    for width in ("head_dim", "intermediate_size", "hidden_size"):
        cut = copy.deepcopy(bench)
        cut["configs"] = [dict(mine[0], reduced=mine[0]["reduced"]
                               + [width])]
        assert published_rule.wrong(cut, REPO), width
