"""``chipbench/families/mla_moe/``: the plain reference (float32
``jax.numpy``, dense masked softmax a head, the held experts as a loop)
against ``models/mla_moe.py``, the code the cell runs, at a toy size on
the CPU: the loss, the hidden states and every gradient; the faults the
comparison has to catch, by the loss and by the hidden states; the
shares of a layer adding up to the whole; ``flops.py`` by hand; the new
readers.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its Pallas kernels in the interpreter),
so they differ only by the order of float32 sums: losses near 6.7 agree
to 1e-5 and gradient leaves to 1e-4 of their largest entry. On the chip
the same comparison runs in every first worker round at the published
widths, against bf16 compute, with the tolerance ``job.py`` gives.
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
from chipbench.families.mla_moe import flops, job, reference  # noqa: E402

LOSS_TOL = 1e-5
GRAD_RTOL, GRAD_ATOL = 1e-4, 1e-7


def toy():
    with open(os.path.join(HERE, "tiny_mla_moe.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "a.x-k1-ep24-1chip.json")) as f:
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
    return job.reference_loss_of(model, job.model_config(model), params,
                                 batch["input_ids"][0], batch["labels"][0],
                                 hidden=hidden)


def hidden_error(model, params, batch):
    """The program's final hidden states against those of the reference
    that ``model`` describes, as ``job.py``'s second limit reads them."""
    from dlrover_tpu.models import mla_moe
    program = mla_moe.apply_hidden(params, batch["input_ids"],
                                   job.model_config(toy()))[0][0]
    plain = []
    reference_loss(model, params, batch, plain)
    return job.hidden_error(program, plain[0])


def test_the_program_agrees_with_the_reference_on_the_loss(built):
    model, the_job, params, batch = built
    system, aux = the_job.loss_fn(params, batch, None)
    assert abs(float(system) - reference_loss(model, params, batch)) < LOSS_TOL
    assert float(aux["moe_rows_dropped"]) == 0
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"] == 1e-4
    assert hidden_error(model, params, batch) < 1e-5


def test_the_jobs_check_reads_nan_past_the_hidden_limit(built, monkeypatch,
                                                        capsys):
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
    _eight_bit(monkeypatch)
    assert np.isnan(the_job.reference_loss(params, ids, labels))


def test_a_dropped_row_makes_the_jobs_loss_nan():
    """The cell promises no drops: with a row buffer a twentieth of
    what uniform routing needs the job's loss is NaN (the model's own
    stays finite, ``tests/test_mla_moe.py``)."""
    the_job = job.build(toy(), expert_row_factor=0.05, expert_block_t=8)
    params = the_job.init_fn(jax.random.PRNGKey(3))
    batch = worker.batch_for(11, 0, the_job.vocab_size, 1, the_job.seq_len)
    loss, aux = the_job.loss_fn(params, batch, None)
    assert float(aux["moe_rows_dropped"]) > 0
    assert np.isnan(float(loss))


def test_the_program_agrees_with_the_reference_on_every_gradient(built):
    """The reference differentiated as it stands (its layers handed over
    from the same parameters) against the program's gradients through
    its kernels' own backward passes."""
    model, the_job, params, batch = built
    config = job.model_config(model)

    def ref(p):
        return reference.loss(
            model, batch["input_ids"][0], batch["labels"][0],
            p["embed_tokens"]["embedding"], job.reference_layers(p, config),
            p["norm"]["scale"], p["lm_head"]["kernel"])

    got = jax.grad(lambda p: the_job.loss_fn(p, batch, None)[0])(params)
    want = jax.grad(ref)(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == 31
    for (path, a), b in zip(flat_got, flat_want):
        limit = GRAD_RTOL * float(jnp.abs(b).max()) + GRAD_ATOL
        assert float(jnp.abs(a - b).max()) < limit, jax.tree_util.keystr(path)
        assert float(jnp.abs(b).max()) > 0, jax.tree_util.keystr(path)


def _without_rotary(monkeypatch):
    monkeypatch.setattr(reference, "rotate",
                        lambda x, cos, sin: jnp.zeros_like(x))


def _without_m2(monkeypatch):
    monkeypatch.setattr(reference, "softmax_scale", lambda model: (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5)


def _eight_bit(monkeypatch):
    def mm(a, b):  # e4m3: 4 significant bits where bf16 has 8
        low = lambda t: t.astype(jnp.float8_e4m3fn).astype(  # noqa: E731
            jnp.float32)
        return jnp.matmul(low(a), low(b))

    monkeypatch.setattr(reference, "mm", mm)


FAULTS = {
    "the shared expert dropped": {"n_shared_experts": 0},
    "no renormalisation": {"norm_topk_prob": False},
    "no routed scale": {"routed_scaling_factor": 1.0},
    "the rotary key part dropped": _without_rotary,
    "yarn's m^2 dropped": _without_m2,
    "a wrong held set": {"deployment": {"experts_held": list(range(8, 16))}},
    "8-bit operands": _eight_bit,
}


@pytest.mark.parametrize("fault", FAULTS, ids=[f.replace(" ", "-")
                                               for f in FAULTS])
def test_the_comparison_catches(built, fault, monkeypatch):
    """Each fault, put into the reference alone, moves its loss away
    from the program's by 30 times this comparison's limit (1e-4 in
    float32) and more: 0.006 (the routed scale, at two expert layers of
    a toy) to 0.22; and the median token's hidden state by 100 times
    its limit and more."""
    model, the_job, params, batch = built
    change = FAULTS[fault]
    wrong = copy.deepcopy(model)
    if callable(change):
        change(monkeypatch)
    else:
        for key, value in change.items():
            if isinstance(value, dict):
                wrong[key].update(value)
            else:
                wrong[key] = value
    system = float(the_job.loss_fn(params, batch, None)[0])
    moved = abs(system - reference_loss(wrong, params, batch))
    apart = hidden_error(wrong, params, batch)
    print(fault, moved, apart)
    assert moved > 30 * job.REFERENCE_TOL["float32"], (fault, moved)
    assert apart > 100 * job.HIDDEN_TOL["float32"], (fault, apart)


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """24 experts over 3 shares of 8: the routed parts all the shares
    give, and what every chip computes alike (the shared expert)
    counted once, equal the uncut reference's expert layer."""
    model = toy()
    whole = copy.deepcopy(model)
    whole["deployment"]["experts_held"] = list(range(24))
    key = jax.random.split(jax.random.PRNGKey(7), 6)
    d, f = model["hidden_size"], model["moe_intermediate_size"]

    def swiglu_weights(key, lead=()):
        k = jax.random.split(key, 3)
        return {"w_gate": jax.random.normal(k[0], lead + (d, f)) * 0.2,
                "w_up": jax.random.normal(k[1], lead + (d, f)) * 0.2,
                "w_down": jax.random.normal(k[2], lead + (f, d)) * 0.2}

    every = swiglu_weights(key[0], (24,))
    w = {"w_router": jax.random.normal(key[1], (d, 24)),
         "shared": swiglu_weights(key[2]), "experts": every}
    u = jax.random.normal(key[3], (64, d))
    with jax.default_matmul_precision("highest"):
        want, _, top_i = reference.expert_layer(u, w, whole)
        shared = reference.swiglu(u, w["shared"])
        total = shared
        for share in range(3):
            held = list(range(8 * share, 8 * share + 8))
            part = copy.deepcopy(model)
            part["deployment"]["experts_held"] = held
            mine = dict(w, experts=jax.tree.map(
                lambda a: a[8 * share:8 * share + 8], every))
            out, _, _ = reference.expert_layer(u, mine, part)
            total = total + (out - shared)  # this share's routed part
            # and the program's layer, told the same held set, gives
            # the same part
            from dlrover_tpu.ops import moe
            gate_i, gate_w, _ = moe.sigmoid_topk_routing(
                u @ w["w_router"], model["num_experts_per_tok"],
                model["norm_topk_prob"], model["routed_scaling_factor"])
            program, stats = moe.held_expert_ffn(
                {"gate": {"kernel": mine["experts"]["w_gate"]},
                 "up": {"kernel": mine["experts"]["w_up"]},
                 "down": {"kernel": mine["experts"]["w_down"]}},
                u, gate_i, gate_w, tuple(held),
                moe.held_row_bound(64, 8, 24, 8, 4.0, 8), 8, True)
            assert float(jnp.abs(program - (out - shared)).max()) < 1e-4
            assert float(stats["rows_dropped"]) == 0
    assert float(jnp.abs(total - want).max()) < 1e-4
    assert float(jnp.abs(want - shared).max()) > 0.1  # the experts count
    assert top_i.shape == (64, 8)


def test_yarn_blends_between_the_published_pairs():
    """At A.X-K1's rotary settings pairs 0-10 turn as published, pairs
    23-31 thirty-two times slower, and the softmax scale carries m^2."""
    model = cell_model()
    cos, sin = reference.rotary_tables(model, 2)
    angle = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert np.allclose(angle[:11], plain[:11], rtol=1e-5)
    assert np.allclose(angle[23:], plain[23:] / 32, rtol=1e-4)
    assert (angle[11:23] < plain[11:23]).all()
    assert (angle[11:23] > plain[11:23] / 32).all()
    m = 0.1 * np.log(32) + 1
    assert reference.softmax_scale(model) == pytest.approx(192 ** -0.5 * m * m)
    # and the program's own tables, written apart, are the same numbers
    from dlrover_tpu.models import mla_moe
    config = job.model_config(model)
    assert np.allclose(mla_moe.yarn_inv_freq(config), angle, rtol=1e-5)
    assert config.softmax_scale == pytest.approx(
        reference.softmax_scale(model))


def test_the_cell_keeps_every_published_width():
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "a.x-k1.json")) as f:
        published = json.load(f)["config"]
    cut = set(model["reduced"])
    assert cut == {"num_hidden_layers", "n_routed_experts", "vocab_size",
                   "num_attention_heads", "num_key_value_heads"}
    for key, value in published.items():
        if key not in cut:
            assert model[key] == value, key
    assert (model["hidden_size"], model["intermediate_size"],
            model["moe_intermediate_size"], model["q_lora_rank"],
            model["kv_lora_rank"], model["num_experts_per_tok"]) == (
        7168, 18432, 2048, 1536, 512, 8)
    dep = model["deployment"]
    assert dep["published_n_routed_experts"] == 192
    assert dep["experts_held"] == list(range(8))
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] == 24
    assert model["vocab_size"] * dep["vocabulary_ways"] == 163840
    assert model["num_attention_heads"] * dep["attention_ways"] == 64
    config = job.model_config(model)
    assert config.n_routed_experts == 192 and len(config.held) == 8
    assert config.first_k_dense == 1 and config.moe_layers == 4


def test_the_arithmetic_by_hand():
    model = cell_model()
    mla = (7168 * 1536 + 1536 * 16 * 192 + 7168 * 576 + 512 * 16 * 256
           + 16 * 128 * 7168)
    expert = 3 * 7168 * 2048
    dense = 3 * 7168 * 18432
    moe = 7168 * 192 + 9 * expert
    norms = 5 * (2 * 7168 + 1536 + 512) + 7168
    assert flops.param_count(model) == (
        5 * mla + dense + 4 * moe + 2 * 7168 * 20480 + norms) == 2_464_177_152
    # and the program's own count, by abstract evaluation
    assert worker.build_job(model).param_count == flops.param_count(model)
    assert flops.tokens_per_step(model) == 8192
    assert flops.held_rows_expected(model) == pytest.approx(8192 * 8 / 24)
    active = (5 * mla + dense + 4 * (7168 * 192 + expert + expert / 3)
              + 7168 * 20480)
    assert flops.active_matmul_params(model) == pytest.approx(active)
    pairs = 8192 * 8193 // 2
    attention = 5 * 3 * 16 * (2 * 192 + 2 * 128) * pairs
    assert flops.mla_flops_per_step(model) == attention
    assert flops.model_flops_per_step(model) == pytest.approx(
        6 * active * 8192 + attention)
    # q, k (one rotary head), v, o once forward; those, o and do read
    # and dq, dk, dv written backward; bf16
    q, k, v = 16 * 192, 16 * 128 + 64, 16 * 128
    assert flops.mla_bytes_per_step(model) == 5 * 8192 * 2 * (
        (q + k + 2 * v) + (q + k + 3 * v) + (q + k + v))
    rows = 4 * 2731
    assert flops.gmm_flops(model, rows) == 3 * 3 * 2 * 7168 * 2048 * rows
    assert flops.gmm_bytes(model, rows) == 2 * (
        3 * 4 * 8 * expert + rows * 3 * (3 * 7168 + 3 * 2048))
    assert flops.kernel_flops_per_step(model) == pytest.approx(
        attention + flops.gmm_flops(model, 4 * 8192 * 8 / 24))


def test_the_new_readers_read_a_reduced_trace_and_the_counters():
    """The six readers on a made-up reduced trace and ``profile_window``
    event, and on a run without their instructions or counters (the
    parent's program): nothing, not an error."""
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import arithmetic

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, "chipbench", "layer_metrics",
                               name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    model = cell_model()
    trace = {"devices": {"/device:TPU:0": {}}, "steps": 4, "device_ops": [
        ["fusion.1", 2.0], ["mosaic:flash_mla_fwd.28", 0.08],
        ["mosaic:flash_mla_dkv.14", 0.2], ["mosaic:flash_mla_dq.14", 0.12],
        ["mosaic:gmm.108", 0.06], ["mosaic:gmm_dx.3", 0.02],
        ["mosaic:gmm_dw.7", 0.04], ["mosaic:flash_fwd.2", 0.5],
        ["gmm_lookalike_fusion", 9.0]]}
    window = {"kind": "profile_window", "pid": 77, "steps": 6,
              "step_counters": {"moe_rows_held": 6 * 11000.0,
                                "moe_rows_max": 6 * 4 * 500.0,
                                "moe_rows_dropped": 0.0}}
    run = {"worker": {"pid": 77}, "events": [window],
           "profile_window": window}
    ctx = {"trace": trace, "model": model, "flops": flops, "run": run,
           "arithmetic": arithmetic,
           "device": {"count": 1, "kind": "TPU v5 lite"}}
    assert reader("mla_attn_ms")(ctx) == pytest.approx(100.0)
    assert reader("expert_gmm_ms")(ctx) == pytest.approx(30.0)
    least = flops.mla_flops_per_step(model) / 197e12
    assert reader("mla_attn_roofline")(ctx) == pytest.approx(
        100 * least / 0.1)
    least = max(flops.gmm_flops(model, 11000) / 197e12,
                flops.gmm_bytes(model, 11000) / 819e9)
    assert reader("expert_gmm_roofline")(ctx) == pytest.approx(
        100 * least / 0.03)
    assert reader("expert_load_imbalance")(ctx) == pytest.approx(
        2000 / (11000 / 8))
    assert reader("expert_rows_dropped")(ctx) == 0.0
    bare = dict(ctx, trace=dict(trace, device_ops=[["fusion.1", 2.0]]),
                run={"worker": {"pid": 77}, "events": [], "profile_window": {
                    "kind": "profile_window", "pid": 77, "steps": 6}})
    names = ("mla_attn_ms", "mla_attn_roofline", "expert_gmm_ms",
             "expert_gmm_roofline", "expert_load_imbalance",
             "expert_rows_dropped")
    for name in names:
        assert reader(name)(bare) is None, name
        assert reader(name)(dict(bare, trace=None, run={
            "worker": {"pid": 77}, "events": []})) is None, name


def test_the_published_rule_finds_nothing_wrong_on_the_tree():
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import published_rule

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert published_rule.wrong(bench, REPO) == []
    mine = [c for c in bench["configs"] if c["name"] == "a.x-k1-ep24-1chip"]
    assert len(mine) == 1 and set(mine[0]["reduced"]) == set(
        cell_model()["reduced"])
