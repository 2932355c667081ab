"""``chipbench/families/mla_moe_gdla/``: the plain reference (float32
``jax.numpy``; a dense masked softmax a head, the subtraction, PolyNorm,
the held experts as a loop, the streams, one prediction module, the
bias rule) against ``models/mla_moe.py`` with its differential
switches, the code the cell runs, at a toy size on the CPU: the loss,
the hidden states and every gradient; the faults the comparison has to
catch, a float32 piece in bf16 among them; the bias after some steps;
the shares of a layer adding up to the whole; ``flops.py`` by hand; the
manifest's entries by name; the six new readers.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its Pallas kernels in the interpreter),
so they differ only by the order of float32 sums.
"""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from mla_moe_gdla_controls import CONTROLS, applied  # noqa: E402

from chipbench import published_rule, worker  # noqa: E402
from chipbench.families.mla_moe_gdla import flops, job, reference  # noqa: E402
from dlrover_tpu.models import mla_moe  # noqa: E402
from dlrover_tpu.ops import moe  # noqa: E402
from dlrover_tpu.parallel.accelerate import accelerate  # noqa: E402

LOSS_TOL = 2e-5
GRAD_RTOL, GRAD_ATOL = 2e-4, 1e-7
CONFIG, CELL = "motif-3-beta-1chip", "motif3-1chip.steady"
NEW = ("mla_win_attn_ms", "mla_win_attn_roofline", "attn_diff_ms",
       "polynorm_ms", "diff_lambda_mean", "router_bias_abs")


def toy():
    with open(os.path.join(HERE, "tiny_mla_moe_gdla.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def perturbed(init_fn):
    """Initial weights with the norm scales and PolyNorm's leaves moved
    off their start, so that a reference that dropped one would show."""
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


def reference_loss(model, params, batch, **lists):
    return job.reference_loss_of(model, job.model_config(toy()), params,
                                 batch["input_ids"][0], batch["labels"][0],
                                 **lists)


_PROGRAM = {}


def compared(model, params, batch):
    """``(the reference's loss, the hidden errors of the main model and
    of the module)``: one run of the reference that ``model``
    describes against the toy's program, whose side is computed once."""
    if "hidden" not in _PROGRAM:
        _PROGRAM["hidden"] = mla_moe.apply_all_hidden(
            params, batch["input_ids"], batch["labels"],
            job.model_config(toy()))[:, 0]
    plain = []
    loss = reference_loss(model, params, batch, hidden=plain)
    return loss, [job.hidden_error(a, b)
                  for a, b in zip(_PROGRAM["hidden"], plain)]


def hidden_errors(model, params, batch):
    return compared(model, params, batch)[1]


def test_the_program_agrees_with_the_reference_on_the_loss(built):
    model, the_job, params, batch = built
    system, aux = the_job.loss_fn(params, batch, None)
    lambdas, plain = [], []
    assert abs(float(system) - reference_loss(
        model, params, batch, lambdas=lambdas, hidden=plain)) < LOSS_TOL
    assert float(aux["moe_rows_dropped"]) == 0
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"] == 1e-4
    program = mla_moe.apply_all_hidden(
        params, batch["input_ids"], batch["labels"],
        job.model_config(model))[:, 0]
    assert max(job.hidden_error(a, b)
               for a, b in zip(program, plain)) < 1e-5
    # six lambdas, a layer's mean each, near a half at random weights
    assert len(lambdas) == 6
    assert float(aux["diff_lambda_mean"]) == pytest.approx(
        float(np.mean([float(x) for x in lambdas])), abs=1e-6)
    assert 0.4 < float(aux["diff_lambda_mean"]) < 0.6
    # local layer 2 (published 3) is the full one; the module's layer
    # (published 53) is a window layer
    config = job.model_config(model)
    assert config.full_attention_layers == (2,)
    assert mla_moe.attention_plan(config) == [
        "window", "window", "full", "window", "window", "window"]
    assert the_job.init_fn.layer_kinds == {
        "dense": 1, "moe": 4, "full": 1, "window": 4}
    # 2 rows x 10 heads x 5 window layers of the band's 7 tiles (64
    # tokens in tiles of 16, a window of 16: two tiles a q block)
    assert float(aux["attn_band_tiles"]) == 10 * 5 * 7


def test_the_jobs_check_reads_nan_past_the_hidden_limit(built, capsys):
    """What ``worker.py`` calls: the reference's loss where the hidden
    states agree, NaN (which fails the worker's comparison) where they
    do not, the readings printed either way."""
    model, the_job, params, batch = built
    ids, labels = batch["input_ids"][0], batch["labels"][0]
    sound = the_job.reference_loss(params, ids, labels)
    assert sound == reference_loss(model, params, batch)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_hidden"
    assert line["median_token_error"] == max(line["main"], line["module"])
    assert line["median_token_error"] < line["tolerance"] == 1e-4
    with applied(model, "e4m3 operands"):
        assert np.isnan(the_job.reference_loss(params, ids, labels))


def test_a_dropped_row_makes_the_jobs_loss_nan():
    the_job = job.build(toy(), expert_row_factor=0.05, expert_block_t=8)
    params = the_job.init_fn(jax.random.PRNGKey(3))
    batch = worker.batch_for(11, 0, the_job.vocab_size, 2, the_job.seq_len)
    loss, aux = the_job.loss_fn(params, batch, None)
    assert float(aux["moe_rows_dropped"]) > 0
    assert np.isnan(float(loss))


def test_the_program_agrees_with_the_reference_on_every_gradient(built):
    """The reference differentiated as it stands against the program's
    gradients through its kernels' own backward passes (the grouped and
    the band's latent kernels, the held experts' parameterised gate
    stage) and its checkpointed hyper-connections: every leaf, lambda's
    projection and PolyNorm's four numbers an FFN among them."""
    model, the_job, params, batch = built
    config = job.model_config(model)

    def ref(p):
        return reference.loss(
            model, batch["input_ids"][0], batch["labels"][0],
            p["embed_tokens"]["embedding"], job.reference_layers(p, config),
            p["norm"]["scale"], p["lm_head"]["kernel"],
            job.reference_mtp(p, config))

    got = jax.grad(lambda p: the_job.loss_fn(p, batch, None)[0])(params)
    want = jax.grad(ref)(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    names = []
    for (path, a), b in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        names.append(name)
        limit = GRAD_RTOL * float(jnp.abs(b).max()) + GRAD_ATOL
        assert float(jnp.abs(a - b).max()) < limit, name
        assert float(jnp.abs(b).max()) > 0, name
    for leaf in ("['lam_proj']['kernel']", "['experts']['act']['weight']",
                 "['shared']['act']['bias']", "['mlp']['act']['weight']"):
        assert any(n.endswith(leaf) for n in names), leaf
    assert not any("['router']['bias']" in n for n in names)


# the controls that the toy's readings show; at the toy's four streams
# of 64 one Sinkhorn iteration already leaves the mapping within the
# limit, and the chip's run is where that control is read
FELT = [c for c in CONTROLS if c != "one Sinkhorn iteration"]


@pytest.mark.parametrize("control", FELT,
                         ids=[c.replace(" ", "-") for c in FELT])
def test_the_comparison_catches(built, control):
    """Each control is a change to the reference alone; the sound
    program against it has to come out wrong by the hidden limit (or
    the loss's) of a float32 configuration."""
    model, the_job, params, batch = built
    model = copy.deepcopy(model)
    with applied(model, control):
        loss, errors = compared(model, params, batch)
    if "loss" not in _PROGRAM:
        _PROGRAM["loss"] = float(the_job.loss_fn(params, batch, None)[0])
    system = _PROGRAM["loss"]
    assert (max(errors) > job.HIDDEN_TOL["float32"]
            or abs(system - loss) > job.REFERENCE_TOL["float32"]), (
        control, errors, abs(system - loss))


def test_a_float32_piece_in_bf16_fails_the_float32_limit(built):
    """The precision test: the router's scores, lambda, PolyNorm, the
    mappings, the softmax's scores and the logits are float32 in the
    program and in the reference; rounded to bf16 in the reference
    (``reference.f32``) the hidden states differ by far more than the
    float32 limit, and by less than a wrong mechanism."""
    model, _, params, batch = built
    sound = max(hidden_errors(model, params, batch))
    with applied(model, "float32 pieces in bf16"):
        rounded = max(hidden_errors(model, params, batch))
    assert sound < 1e-5 < job.HIDDEN_TOL["float32"] < rounded < 0.2


def test_the_bias_after_some_steps_is_the_references(built):
    """The program's selection bias after two optimizer steps through
    ``accelerate`` equals the reference's, which is moved from the
    reference's own selections on the program's parameters of each
    step: the same loads, the same signs. It is above 0 after the first
    step and rises; adafactor never sees it."""
    model, the_job, _, _ = built
    config = job.model_config(model)
    example = worker.batch_for(5, 0, the_job.vocab_size, 1, the_job.seq_len)
    result = accelerate(the_job.init_fn, the_job.loss_fn,
                        optax.adafactor(1e-3), example,
                        strategy=the_job.strategy,
                        devices=jax.devices()[:1])
    state = result.init_fn(jax.random.PRNGKey(9))
    bias = np.zeros((5, 24), np.float32)  # four layers' and the module's
    seen = []
    for k in range(2):
        batch = worker.batch_for(5, k, the_job.vocab_size, 1,
                                 the_job.seq_len)
        chosen = []
        buffers = {"moe_layers": {"moe": {"router": {"bias": bias[:4]}}},
                   "mtp": {"layer": {"moe": {"router": {"bias": bias[4:]}}}}}
        job.reference_loss_of(model, config, state.params,
                              batch["input_ids"][0], batch["labels"][0],
                              selections=chosen, buffers=buffers)
        assert len(chosen) == 5
        bias = np.stack([np.asarray(reference.bias_update(
            bias[i], reference.expert_counts(chosen[i], model), model))
            for i in range(5)])
        state, metrics = result.train_step(
            state, result.shard_batch(batch), jax.random.PRNGKey(k))
        seen.append(float(metrics["router_bias_abs"]))
        assert "router_load" not in metrics
    program = np.concatenate([
        state.buffers["moe_layers"]["moe"]["router"]["bias"],
        state.buffers["mtp"]["layer"]["moe"]["router"]["bias"]])
    np.testing.assert_allclose(program, bias, atol=1e-7)
    assert 0 < seen[0] <= 1e-4 and seen[0] < seen[1] <= 2e-4
    assert np.abs(program.mean(axis=-1)).max() < 1e-7  # it keeps its mean
    # the optimizer holds moments for the parameters alone
    assert not any("bias" in jax.tree_util.keystr(path) and "router" in
                   jax.tree_util.keystr(path) for path, _ in
                   jax.tree_util.tree_leaves_with_path(state.opt_state))


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """24 experts over 4 shares of 6: the routed parts all the shares
    give, and what every chip computes alike (the shared expert)
    counted once, equal the uncut reference's expert layer; and the
    program's layer, told the same held set and bias, gives each
    share's part through its parameterised gate stage."""
    model = toy()
    whole = copy.deepcopy(model)
    whole["deployment"]["experts_held"] = list(range(24))
    key = jax.random.split(jax.random.PRNGKey(7), 6)
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    act = {"weight": jnp.asarray([0.5, 0.2, 0.4]), "bias": jnp.asarray([.1])}

    def glu_weights(key, lead=()):
        k = jax.random.split(key, 3)
        return {"w_gate": jax.random.normal(k[0], lead + (d, f)) * 0.2,
                "w_up": jax.random.normal(k[1], lead + (d, f)) * 0.2,
                "w_down": jax.random.normal(k[2], lead + (f, d)) * 0.2,
                "act": act}

    every = glu_weights(key[0], (24,))
    w = {"w_router": jax.random.normal(key[1], (d, 24)),
         "b_router": 0.3 * jax.random.normal(key[4], (24,)),
         "shared": glu_weights(key[2]), "experts": every}
    u = jax.random.normal(key[3], (64, d))
    activation = mla_moe.poly_norm(0.5, 0.5, 1e-6)
    with jax.default_matmul_precision("highest"):
        want, top_i = reference.expert_layer(u, w, whole)
        shared = reference.poly_glu(u, w["shared"], model)
        total = shared
        for share in range(4):
            held = list(range(6 * share, 6 * share + 6))
            part = copy.deepcopy(model)
            part["deployment"]["experts_held"] = held
            mine = dict(w, experts={
                **{k: every[k][held[0]:held[-1] + 1]
                   for k in ("w_gate", "w_up", "w_down")}, "act": act})
            out, _ = reference.expert_layer(u, mine, part)
            total = total + (out - shared)  # this share's routed part
            gate_i, gate_w, _ = moe.sigmoid_topk_routing(
                u @ w["w_router"], model["experts_top_k"],
                model["route_norm"], model["route_scale"], w["b_router"])
            program, stats = moe.held_expert_ffn(
                {"gate": {"kernel": mine["experts"]["w_gate"]},
                 "up": {"kernel": mine["experts"]["w_up"]},
                 "down": {"kernel": mine["experts"]["w_down"]},
                 "act": act},
                u, gate_i, gate_w, tuple(held),
                moe.held_row_bound(64, 4, 24, 6, 4.0, 8), 8, True,
                activation=activation)
            assert float(jnp.abs(program - (out - shared)).max()) < 1e-4
            assert float(stats["rows_dropped"]) == 0
    assert float(jnp.abs(total - want).max()) < 1e-4
    assert float(jnp.abs(want - shared).max()) > 0.1  # the experts count
    assert top_i.shape == (64, 4)


def test_the_cell_keeps_every_published_width():
    """The configuration against the catalog's row, by the rule and by
    hand; the manifest's entries looked up by name."""
    manifest = bench()
    (config,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "steady",
                    "chips": 1}
    assert config["reduced"] == ["num_hidden_layers", "n_dense_first_layers",
                                 "num_experts", "vocab_size"]
    assert not [line for line in published_rule.wrong(manifest, REPO)
                if line.startswith(CONFIG + ":")]
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "motif-3-beta.json")) as f:
        published = json.load(f)["config"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "head_dim",
                "qk_rope_head_dim", "v_head_dim", "sliding_window",
                "mhc_expansion_rate", "experts_top_k",
                "num_attention_heads", "num_key_value_heads",
                "num_noise_heads", "load_balance_coeff"):
        assert model[key] == published[key], key
    assert (model["hidden_size"], model["intermediate_size"],
            model["moe_intermediate_size"]) == (4096, 12288, 1280)
    dep = model["deployment"]
    assert dep["experts_held"] == list(range(model["num_experts"]))
    assert dep["expert_parallel"] * model["num_experts"] == 384 == (
        dep["published_num_experts"])
    assert model["n_routed_experts"] == model["num_experts"]
    assert model["vocab_size"] * dep["vocabulary_ways"] == 220160
    for reading in ("noise_heads", "head_order", "lambda", "output_gate",
                    "window", "carried_not_computed", "residual",
                    "polynorm", "router", "router_bias",
                    "multi_token_prediction", "initialisation"):
        assert model["assumed"][reading], reading
    # every metric that lists the cell moves an end-to-end metric the
    # cell reports, and the six new ones list this cell alone
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL], name
        assert metrics[name]["moves"] == "tokens_per_s"
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
    for name in ("mla_attn_ms", "mla_attn_roofline", "expert_gmm_ms",
                 "expert_gmm_roofline", "expert_load_imbalance",
                 "expert_rows_dropped", "hc_res_defect", "mtp_loss",
                 "step_mfu_pct", "hbm_held_pct"):
        assert CELL in metrics[name]["workloads"], name
    # ``moe_experts_xla_ms`` and ``hc_ms`` would read this cell too, and
    # a standing test holds their lists of cells letter for letter
    # (``test_chipbench_scope_time.py::test_the_manifest_lists_the_
    # twelve``): a ``benchmark`` PR's to add (``PERF.md`` section 7)
    for name in ("dsa_attn_ms", "window_attn_ms", "moe_group_reach",
                 "moe_experts_xla_ms", "hc_ms"):
        assert CELL not in metrics[name]["workloads"], name


def test_the_arithmetic_by_hand():
    model = cell_model()
    held = model["num_experts"]
    M = 1e6
    # ISSUE 55's count: the attention a layer, an expert layer outside
    # its routed experts, one routed expert, a dense layer
    s = flops._sizes(model)
    attention = flops._mla_params(s)
    assert attention == (4096 * 1024 + 1024 * 80 * 192 + 4096 * 576
                         + 512 * 16 * 256 + 4096 * 64
                         + 2 * 4096 * 8192)
    assert 91.7 < attention / M < 91.8
    assert flops.expert_params(model) == 3 * 4096 * 1280
    assert flops.hc_matmul_params(model) == 2 * 4 * 4096 * 24
    assert flops.layer_counts(model) == {"dense": 1, "moe": 4, "mtp": 1}
    assert flops.published_layers(model) == [1, 2, 3, 4, 5, 53]
    assert flops.full_layers(model) == [2]
    assert flops.attention_counts(model) == {"full": 1, "window": 5}
    assert flops.tokens_per_step(model) == 8192
    assert flops.held_rows_expected(model) == 8192 * 8 * held / 384
    the_job = worker.build_job(model)
    assert flops.param_count(model) == the_job.param_count
    # the band: 128 keys a query but for the first 127 queries
    band = 128 * 129 // 2 + (8192 - 128) * 128
    causal = 8192 * 8193 // 2
    per_pair = 2 * 192 + 2 * 128
    assert flops.mla_win_flops_per_step(model) == (
        5 * 3 * 80 * per_pair * band)
    assert flops.mla_flops_per_step(model) == (
        3 * 80 * per_pair * causal + flops.mla_win_flops_per_step(model))
    rows = 8192 * 2
    q, k, v, o = (80 * 192 * rows, (16 * 128 + 64) * rows, 16 * 128 * rows,
                  80 * 128 * rows)
    a_layer = (q + k + v + o) + (q + k + v + 2 * o) + (q + k + v)
    assert flops.mla_win_bytes_per_step(model) == 5 * a_layer
    assert flops.mla_bytes_per_step(model) == 6 * a_layer
    # the band's kernels are bound by their bytes, the full layer's by
    # its FLOPs (197 TFLOP/s over 819 GB/s is 240 FLOPs a byte)
    assert flops.mla_win_flops_per_step(model) / (5 * a_layer) < 240 < (
        3 * 80 * per_pair * causal / a_layer)
    assert flops.gmm_flops(model, 1000) == 6 * 3 * 4096 * 1280 * 1000
    assert flops.model_flops_per_step(model) == (
        6 * flops.active_matmul_params(model) * 8192
        + flops.mla_flops_per_step(model))


def _context(counters, steps=3, scopes=None, ops=()):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "arithmetic", os.path.join(REPO, "chipbench", "arithmetic.py"))
    arithmetic = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arithmetic)
    run = {"profile_window": {"steps": steps, "step_counters": counters},
           "worker": {"pid": 7}, "events": []}
    if scopes is not None:
        run["events"].append({"kind": "step_scopes", "pid": 7,
                              "instructions": scopes})
    trace = {"devices": 1, "steps": steps, "device_ops": list(ops)}
    return {"run": run, "trace": trace if ops else None,
            "model": cell_model(), "flops": flops, "arithmetic": arithmetic,
            "device": {"count": 1, "kind": "TPU v5 lite"}}


def _read(name, ctx):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "chipbench", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


def test_the_readers_read_the_spans_and_the_counters():
    ops = [("mosaic:flash_mla_win_fwd.1", 0.006),
           ("mosaic:flash_mla_win_dq.2", 0.003),
           ("mosaic:flash_mla_fwd.3", 0.09), ("fusion.4", 0.012),
           ("fusion.5", 0.03), ("fusion.6", 0.3)]
    scopes = {"forward|mla/attn_diff": ["fusion.4"],
              "backward|mtp/moe_experts/polynorm": ["fusion.5"],
              "forward|ffn": ["fusion.6"]}
    ctx = _context({"diff_lambda_mean": 1.5, "router_bias_abs": 6e-4},
                   scopes=scopes, ops=ops)
    assert _read("mla_win_attn_ms", ctx) == pytest.approx(3.0)
    assert _read("mla_attn_ms", ctx) == pytest.approx(33.0)
    assert _read("attn_diff_ms", ctx) == pytest.approx(4.0)
    assert _read("polynorm_ms", ctx) == pytest.approx(10.0)
    assert _read("diff_lambda_mean", ctx) == pytest.approx(0.5)
    assert _read("router_bias_abs", ctx) == pytest.approx(2e-4)
    # the band's share: its least time (the bytes bind) over 3 ms
    least = flops.mla_win_bytes_per_step(ctx["model"]) / 819e9
    assert _read("mla_win_attn_roofline", ctx) == pytest.approx(
        100 * least / 3e-3, rel=1e-3)
    assert 0 < _read("mla_attn_roofline", ctx) < 100
    # a program without the spans, the counters or the kernels (the
    # parent of this PR): nothing to read, and nothing raised
    old = _context({"moe_rows_held": 10.0}, scopes={"forward|ffn": ["f"]},
                   ops=[("fusion.6", 0.3), ("mosaic:flash_mla_fwd.3", 0.1)])
    for name in NEW:
        assert _read(name, old) is None, name
    bare = _context({})
    bare["run"]["profile_window"] = None
    for name in NEW:
        assert _read(name, bare) is None, name


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "chipbench", "families", "mla_moe_gdla",
                           "reference.py")) as f:
        text = f.read()
    import ast

    imports = [ast.unparse(node) for node in ast.walk(ast.parse(text))
               if isinstance(node, (ast.Import, ast.ImportFrom))]
    assert imports == ["import jax", "import jax.numpy as jnp"]
