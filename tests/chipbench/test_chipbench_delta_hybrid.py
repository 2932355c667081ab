"""``chipbench/families/delta_hybrid/``: the plain reference (float32
``jax.numpy``, the gated delta rule token by token, a dense masked
softmax a head in blocks of query rows) against
``models/delta_hybrid.py``, the code the cell runs, at a toy size on
the CPU: the loss and the hidden states; the faults the comparison has
to catch, each mechanism wrong in turn; ``flops.py`` by hand; the new
readers; the configuration against what its source publishes.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its Pallas kernels in the interpreter),
so they differ only by the order of float32 sums. Every gradient is
compared in ``tests/test_delta_hybrid.py``, on both of the program's
paths. On the chip the same comparison runs in every first worker round
at the published widths, against bf16 compute, with the limits
``job.py`` gives.
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
from chipbench.families.delta_hybrid import flops, job  # noqa: E402

sys.path.insert(0, HERE)
import delta_hybrid_controls as controls  # noqa: E402

LOSS_TOL = 1e-5
CELL = "olmohybrid-1chip.steady"
NAME = "olmo-hybrid-7b-d8-1chip"


def toy():
    with open(os.path.join(HERE, "tiny_delta_hybrid.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def perturbed(init_fn):
    """Initial weights with the norm scales moved off 1, so that a
    reference that dropped a norm would show, and a table of std 1."""
    def moved(key):
        params = jax.tree.map(
            lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                  a.shape, a.dtype),
            init_fn(key))
        params["embed_tokens"]["embedding"] *= 10.0
        return params

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


def hidden_error(model, params, batch):
    """The program's final hidden states against those of the reference
    that ``model`` describes, as ``job.py``'s second limit reads them."""
    from dlrover_tpu.models import delta_hybrid
    program = delta_hybrid.apply_hidden(params, batch["input_ids"],
                                        job.model_config(toy()))[0][0]
    plain = []
    reference_loss(model, params, batch, plain)
    return job.hidden_error(program, plain[0])


def test_the_program_agrees_with_the_reference(built):
    model, the_job, params, batch = built
    system, aux = the_job.loss_fn(params, batch, None)
    assert abs(float(system) - reference_loss(model, params, batch)) < LOSS_TOL
    assert 0.2 < float(aux["gdn_neg_eig"]) < 0.8
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"] == 1e-4
    assert hidden_error(model, params, batch) < 1e-5
    assert (the_job.layers, the_job.seq_len, the_job.vocab_size) == (
        4, 64, 512)
    assert the_job.param_count == flops.param_count(model)
    assert the_job.init_fn.layer_kinds == {"gdn": 2, "attn_full": 2}


def test_the_job_refuses_what_the_model_does_not_compute():
    for key, value in (("tie_word_embeddings", True),
                       ("attention_bias", True), ("hidden_act", "gelu"),
                       ("rope_parameters", {"rope_theta": 500000.0}),
                       ("linear_num_key_heads", 2)):
        with pytest.raises(ValueError, match="models/delta_hybrid.py"):
            job.model_config(dict(toy(), **{key: value}))
    with pytest.raises(ValueError, match="no whole number of periods"):
        worker.build_job(dict(toy(), num_hidden_layers=3))


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


@pytest.mark.parametrize("fault", controls.CONTROLS,
                         ids=[f.replace(" ", "-") for f in controls.CONTROLS])
def test_the_comparison_catches(built, fault):
    """Each control (ISSUE 43's nine mechanisms and the precision), put
    into the reference alone, moves the median token's hidden state
    away from the program's by 100 times this comparison's limit (1e-4
    in float32) and more: the hidden states are the limit that feels a
    mechanism (the loss at random weights hardly does, ``job.py``).
    Keys that are not at length 1 make the recurrence unstable under
    ``beta`` up to 2: there the reference overflows, and a reading that
    is no number is past every limit too."""
    model, _, params, batch = built
    with controls.applied(model, fault):
        apart = hidden_error(model, params, batch)
    print(fault, apart)
    assert not apart <= 100 * job.HIDDEN_TOL["float32"], (fault, apart)
    assert model == toy()  # the control is taken out again


def test_the_controls_script_gives_the_harness_verdicts(capsys):
    """``delta_hybrid_controls.py`` as the chip runs it, at the toy
    size: the worker's own ``ReferenceCheck`` says ``ok`` of the sound
    reference on both seeds and not ``ok`` under every control."""
    assert controls.main(["--config",
                          os.path.join(HERE, "tiny_delta_hybrid.json"),
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
    """The configuration against the catalog's row: every key at its
    published value but the two cuts of scale, the depth two whole
    periods, and the job the sizes ISSUE 43 counted."""
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "olmo-hybrid-7b.json")) as f:
        published = json.load(f)
    assert model["source"] == published["source"]
    for key, value in published["config"].items():
        if key not in ("num_hidden_layers", "vocab_size"):
            assert model[key] == value, key
    assert set(model["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert (model["num_hidden_layers"], model["vocab_size"]) == (8, 25088)
    assert 4 * model["vocab_size"] == published["config"]["vocab_size"] == (
        model["deployment"]["published_vocab_size"])
    assert len(model["layer_types"]) == 32
    config = job.model_config(model)
    from dlrover_tpu.models import delta_hybrid
    assert delta_hybrid.layer_plan(config) == 3 * ["linear_attention"] + [
        "full_attention"]
    assert delta_hybrid.layer_kinds(config) == {"gdn": 6, "attn_full": 2}
    assert flops.param_count(model) == 1_857_720_552
    assert flops.param_count(dict(model, vocab_size=100352)) == 2_435_748_072
    assert (model["assumed"]["seq_len"], model["assumed"]["batch"],
            model["chips"]) == (8192, 1, 1)
    assert config.compute_dtype == config.param_dtype == jnp.bfloat16


def test_the_arithmetic_by_hand():
    """``flops.py`` against the sizes written out: ISSUE 43's counts."""
    model = cell_model()
    assert flops.tokens_per_step(model) == 8192
    assert flops.layer_counts(model) == {"gdn": 6, "attn_full": 2}
    ffn = 3 * 3840 * 11008
    linear = 3840 * (2 * 2880 + 3 * 5760 + 2 * 30)
    full = 4 * 3840 * 3840
    head = 3840 * 25088
    assert flops.active_matmul_params(model) == (
        6 * (linear + ffn) + 2 * (full + ffn) + head) == 1_761_024_000
    pairs = 8192 * 8193 // 2
    causal = 2 * 3 * 30 * 4 * 128 * pairs
    assert flops.causal_flops_per_step(model) == causal
    gdn = 6 * 3 * 6 * 96 * 192 * 30 * 8192
    assert flops.gdn_flops_per_step(model) == gdn
    assert 4.8e11 < gdn < 5.0e11  # ISSUE 43's 4.9e11
    assert flops.model_flops_per_step(model) == (
        6 * 1_761_024_000 * 8192 + causal + gdn)
    assert 8.9e13 < flops.model_flops_per_step(model) < 9.1e13
    # q, k of 96 and v, o of 192 in bf16, g and beta in float32, once
    # forward and with their gradients once backward: three passes
    a_pass = (2 * 96 + 2 * 192) * 2 + 8
    assert flops.gdn_bytes_per_step(model) == 6 * 3 * a_pass * 30 * 8192
    assert 5.0e9 < flops.gdn_bytes_per_step(model) < 5.3e9
    # MHA: q, k, v, o the same size; 4 of them forward, 8 backward
    assert flops.causal_bytes_per_step(model) == (
        2 * 12 * 30 * 128 * 8192 * 2)
    assert flops.kernel_flops_per_step(model) == causal + gdn
    assert flops.kernel_bytes_per_step(model) == (
        flops.causal_bytes_per_step(model) + flops.gdn_bytes_per_step(model))
    # the toy, counted by its own init
    assert flops.param_count(toy()) == worker.build_job(toy()).param_count


@pytest.mark.parametrize("which", ["toy", "cell"])
def test_no_share_counts_more_work_than_its_kernels_run(which):
    """A share over 100 would mean work counted that the kernels do not
    run. The chunked kernels execute, a token and head, at a chunk of
    ``C``: ``W H``, ``Qg H`` and ``Kd^T U`` of ``dk x dv`` and ``P U``
    of ``C x dv`` forward (2 (3 dk + C) dv FLOPs), which is no less
    than the recurrence's three products the roofline counts; and they
    move the prepared operands, which are no fewer bytes than q, k, v
    and o."""
    model = cell_model() if which == "cell" else toy()
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    chunk = 64
    counted_forward = flops.gdn_flops_per_step(model) / 3
    run_forward = (flops.layer_counts(model)["gdn"]
                   * 2 * (3 * dk + chunk) * dv
                   * model["linear_num_value_heads"]
                   * flops.tokens_per_step(model))
    assert counted_forward <= run_forward
    # operands in: Qg, Kd, W (dk each), Ubar (dv), P (chunk); out: O
    moved_forward = (flops.layer_counts(model)["gdn"]
                     * (3 * dk + 2 * dv + chunk) * 2
                     * model["linear_num_value_heads"]
                     * flops.tokens_per_step(model))
    assert flops.gdn_bytes_per_step(model) / 3 <= moved_forward * 1.02
    # the full layers: the causal half, as the other families count it
    heads, hd = model["num_attention_heads"], model["assumed"]["head_dim"]
    seq = model["assumed"]["seq_len"]
    assert flops.causal_flops_per_step(model) <= (
        flops.layer_counts(model)["attn_full"] * 3 * heads * 4 * hd
        * seq * seq * model["assumed"]["batch"])


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_readers_read_a_reduced_trace_and_the_counters():
    """The three new readers and the two this cell shares with the
    ``gqa_moe`` family, on a made-up reduced trace and
    ``profile_window`` event, and on a run without their instructions
    or counters (the parent's program): nothing, not an error."""
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import arithmetic

    model = cell_model()
    trace = {"devices": {"/device:TPU:0": {}}, "steps": 4, "device_ops": [
        ["fusion.1", 2.0], ["mosaic:flash_fwd.6", 0.06],
        ["mosaic:flash_dkv.3", 0.08], ["mosaic:flash_dq.3", 0.06],
        ["mosaic:gdn_fwd.18", 0.12], ["mosaic:gdn_bwd.6", 0.2],
        ["mosaic:jvp_gdn_fwd_.3", 0.08], ["gdn_fwd.99", 9.0],
        ["mosaic:ssm_scan_fwd.1", 7.0], ["fusion.gdn_chunk", 5.0]]}
    window = {"kind": "profile_window", "pid": 77, "steps": 6,
              "step_counters": {"gdn_neg_eig": 6 * 0.5}}
    run = {"worker": {"pid": 77}, "events": [window],
           "profile_window": window}
    ctx = {"trace": trace, "model": model, "flops": flops, "run": run,
           "arithmetic": arithmetic,
           "device": {"count": 1, "kind": "TPU v5 lite"}}
    # every Mosaic call whose name holds gdn_, and no XLA fusion of
    # that name, no other kernel
    assert _reader("gdn_ms")(ctx) == pytest.approx(100.0)
    by_bytes = flops.gdn_bytes_per_step(model) / 819e9
    assert by_bytes > flops.gdn_flops_per_step(model) / 197e12  # memory
    assert _reader("gdn_roofline")(ctx) == pytest.approx(
        100 * by_bytes / 0.1)
    assert _reader("gdn_roofline")(ctx) < 10
    assert _reader("gdn_neg_eig_share")(ctx) == pytest.approx(0.5)
    assert _reader("full_attn_ms")(ctx) == pytest.approx(50.0)
    assert _reader("full_attn_roofline")(ctx) == pytest.approx(
        100 * flops.causal_flops_per_step(model) / 197e12 / 0.05)
    bare = dict(ctx, trace=dict(trace, device_ops=[
        ["fusion.1", 2.0], ["mosaic:flash_fwd.1", 1.0],
        ["mosaic:ssm_scan_fwd.1", 1.0]]),
        run={"worker": {"pid": 77}, "events": [], "profile_window": {
            "kind": "profile_window", "pid": 77, "steps": 6}})
    for name in ("gdn_ms", "gdn_roofline", "gdn_neg_eig_share"):
        assert _reader(name)(bare) is None, name
        assert _reader(name)(dict(bare, trace=None, run={})) is None, name
    # a family without ``gdn_flops_per_step``: the time reads, the
    # share does not
    from chipbench.families.dense_gqa import flops as dense
    assert _reader("gdn_ms")(dict(ctx, flops=dense)) == pytest.approx(100.0)
    assert _reader("gdn_roofline")(dict(ctx, flops=dense)) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    # what ISSUE 43 lists for the cell; a later PR may append a reader
    assert set(mine) >= {
        "tokens_per_s", "setup_s", "host_gap_ms", "step_device_ms",
        "step_mfu_pct", "mosaic_ms", "device_idle_pct", "dispatch_ms",
        "host_sync_ms", "input_wait_ms", "boot_import_s", "boot_backend_s",
        "boot_build_s", "full_attn_ms", "full_attn_roofline", "gdn_ms",
        "gdn_roofline", "gdn_neg_eig_share"}
    assert "flash_roofline" not in mine  # it divides by the gdn_* time too
    for name, layer in (("gdn_ms", "kernels"), ("gdn_roofline", "kernels"),
                        ("gdn_neg_eig_share", "step program")):
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL] and entry["layer"] == layer
        assert entry["moves"] == "tokens_per_s"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "steady", 1)


def test_the_published_rule_finds_nothing_wrong_on_the_tree():
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import published_rule

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert published_rule.wrong(bench, REPO) == []
    mine = [c for c in bench["configs"] if c["name"] == NAME]
    assert len(mine) == 1 and set(mine[0]["reduced"]) == set(
        cell_model()["reduced"])
    # and the rule bites on this configuration: a width cut is refused
    for width in ("linear_key_head_dim", "intermediate_size"):
        cut = copy.deepcopy(bench)
        cut["configs"] = [dict(mine[0], reduced=mine[0]["reduced"]
                               + [width])]
        assert any(f"{width} is a width" in line
                   for line in published_rule.wrong(cut, REPO))
