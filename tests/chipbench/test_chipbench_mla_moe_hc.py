"""``chipbench/families/mla_moe_hc/``: the plain reference (float32
``jax.numpy``; hyper-connections as ``[seq, 4, 4]`` mappings a token,
dense masked softmax a head, the held experts as a loop, one
prediction module) against ``models/mla_moe.py`` with streams, a
selection bias and a prediction module, the code the cell runs, at a
toy size on the CPU: the loss, the hidden states of the main model and
of the module, and every gradient (the table's and the head's with
their two sources); the faults the comparison has to catch; what the
Sinkhorn iterations leave; the prediction module's targets; the shares
of a layer adding up to the whole; the selection bias; ``flops.py`` by
hand; the two new readers.

Both sides compute in float32 here (the toy states float32 parameters
and compute; the program runs its Pallas kernels in the interpreter),
so they differ only by the order of float32 sums. On the chip the same
comparison runs in every first worker round at the published widths,
against bf16 compute, with the tolerances ``job.py`` gives.
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
from chipbench.families.mla_moe_hc import flops, job, reference  # noqa: E402
from dlrover_tpu.models import mla_moe  # noqa: E402
from dlrover_tpu.ops import moe  # noqa: E402

LOSS_TOL = 2e-5
GRAD_RTOL, GRAD_ATOL = 2e-4, 1e-7
CELL = "xing4.0-29b-a4b-ep4-1chip"


def toy():
    with open(os.path.join(HERE, "tiny_mla_moe_hc.json")) as f:
        return json.load(f)


def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           CELL + ".json")) as f:
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


def reference_loss(model, params, batch, hidden=None, selections=None):
    return job.reference_loss_of(model, job.model_config(model), params,
                                 batch["input_ids"][0], batch["labels"][0],
                                 selections, hidden)


def hidden_errors(model, params, batch):
    """The program's final hidden states, the main model's and the
    module's, against those of the reference that ``model`` describes,
    as ``job.py``'s second limit reads them."""
    program = mla_moe.apply_all_hidden(
        params, batch["input_ids"], batch["labels"],
        job.model_config(toy()))[:, 0]
    plain = []
    reference_loss(model, params, batch, plain)
    return [job.hidden_error(a, b) for a, b in zip(program, plain)]


def test_the_program_agrees_with_the_reference_on_the_loss(built):
    model, the_job, params, batch = built
    system, aux = the_job.loss_fn(params, batch, None)
    assert abs(float(system) - reference_loss(model, params, batch)) < LOSS_TOL
    assert float(aux["moe_rows_dropped"]) == 0
    assert the_job.reference_tol == job.REFERENCE_TOL["float32"] == 1e-4
    assert max(hidden_errors(model, params, batch)) < 1e-5
    # the module's loss is in the aux before its weight, near the main
    # loss at random weights, and the total holds it at 0.3
    assert 5.0 < float(aux["mtp_loss"]) < 8.0
    plain = copy.deepcopy(model)
    plain["assumed"]["mtp_loss_weight"] = 0.0
    main = reference_loss(plain, params, batch)
    assert float(system) == pytest.approx(
        main + 0.3 * float(aux["mtp_loss"]), abs=LOSS_TOL)


def test_the_jobs_check_reads_nan_past_the_hidden_limit(built, monkeypatch,
                                                        capsys):
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
    _eight_bit(monkeypatch)
    assert np.isnan(the_job.reference_loss(params, ids, labels))


def test_a_dropped_row_makes_the_jobs_loss_nan():
    """The cell promises no drops: with a row buffer a twentieth of
    what uniform routing needs (and its tile an expert: 141 rows for
    about 256) the job's loss is NaN."""
    the_job = job.build(toy(), expert_row_factor=0.05, expert_block_t=8)
    params = the_job.init_fn(jax.random.PRNGKey(3))
    batch = worker.batch_for(11, 0, the_job.vocab_size, 4, the_job.seq_len)
    loss, aux = the_job.loss_fn(params, batch, None)
    assert float(aux["moe_rows_dropped"]) > 0
    assert np.isnan(float(loss))


def test_the_program_agrees_with_the_reference_on_every_gradient(built):
    """The reference differentiated as it stands (its layers handed over
    from the same parameters) against the program's gradients through
    its kernels' own backward passes and its checkpointed
    hyper-connections. The table and the head take gradient from the
    main loss and from the module's; the selection bias takes none."""
    model, the_job, params, batch = built
    config = job.model_config(model)

    def ref(p, weight=None):
        m = model if weight is None else dict(model, assumed=dict(
            model["assumed"], mtp_loss_weight=weight))
        return reference.loss(
            m, batch["input_ids"][0], batch["labels"][0],
            p["embed_tokens"]["embedding"], job.reference_layers(p, config),
            p["norm"]["scale"], p["lm_head"]["kernel"], job.reference_mtp(p))

    got = jax.grad(lambda p: the_job.loss_fn(p, batch, None)[0])(params)
    want = jax.grad(ref)(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == 77
    for (path, a), b in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        limit = GRAD_RTOL * float(jnp.abs(b).max()) + GRAD_ATOL
        assert float(jnp.abs(a - b).max()) < limit, name
        if name.endswith("['router']['bias']"):
            assert float(jnp.abs(a).max()) == 0, name
        else:
            assert float(jnp.abs(b).max()) > 0, name
    # the two sources of the shared leaves: the module's share is what
    # the main loss alone does not give
    alone = jax.grad(lambda p: ref(p, 0.0))(params)
    for leaf in (("embed_tokens", "embedding"), ("lm_head", "kernel")):
        both, main = want[leaf[0]][leaf[1]], alone[leaf[0]][leaf[1]]
        module = float(jnp.abs(both - main).max())
        assert module > 0.05 * float(jnp.abs(main).max()), leaf


def _eight_bit(monkeypatch):
    def mm(a, b):  # e4m3: 4 significant bits where bf16 has 8
        low = lambda t: t.astype(jnp.float8_e4m3fn).astype(  # noqa: E731
            jnp.float32)
        return jnp.matmul(low(a), low(b))

    monkeypatch.setattr(reference, "mm", mm)


def _res_identity(monkeypatch):
    monkeypatch.setattr(reference, "sinkhorn", lambda m, iters: (
        jnp.broadcast_to(jnp.eye(m.shape[-1]), m.shape)))


def _post_without_its_two(monkeypatch):
    monkeypatch.setattr(reference, "post_mapping", jax.nn.sigmoid)


def _no_selection_bias(monkeypatch):
    route = reference.route
    monkeypatch.setattr(reference, "route", lambda u, w, bias, model: route(
        u, w, jnp.zeros_like(bias), model))


def _module_without_its_embedding(monkeypatch):
    mtp_input = reference.mtp_input
    monkeypatch.setattr(reference, "mtp_input", lambda h, e, w, eps: (
        mtp_input(h, jnp.zeros_like(e), w, eps)))


# what each fault is caught by: the loss ("loss"), the hidden states
# ("hidden"), or both
FAULTS = {
    "H_res the identity": (_res_identity, "both"),
    "H_post without its factor 2": (_post_without_its_two, "both"),
    "one Sinkhorn iteration": ({"hc_sinkhorn_iters": 1}, "hidden"),
    "no selection bias": (_no_selection_bias, "hidden"),
    "no routed scale": ({"routed_scaling_factor": 1.0}, "hidden"),
    "the module's loss left out": (
        {"assumed": {"mtp_loss_weight": 0.0}}, "loss"),
    "the module without its embedding": (
        _module_without_its_embedding, "hidden"),
    "a wrong held set": (
        {"deployment": {"experts_held": list(range(16, 32))}}, "both"),
    "8-bit operands": (_eight_bit, "both"),
}


@pytest.mark.parametrize("fault", FAULTS, ids=[f.replace(" ", "-")
                                               for f in FAULTS])
def test_the_comparison_catches(built, fault, monkeypatch):
    """Each fault, put into the reference alone, fails the comparison
    by one of its two limits at least (float32: 1e-4 each), by ten
    times the limit and more: the loss where the fault changes the
    loss's own form, the median token's hidden state (the main model's
    or the module's, whichever is larger) where it changes what a layer
    computes."""
    model, the_job, params, batch = built
    change, caught_by = FAULTS[fault]
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
    apart = max(hidden_errors(wrong, params, batch))
    print(fault, moved, apart)
    if caught_by in ("loss", "both"):
        assert moved > 10 * job.REFERENCE_TOL["float32"], (fault, moved)
    if caught_by in ("hidden", "both"):
        assert apart > 10 * job.HIDDEN_TOL["float32"], (fault, apart)


def test_sinkhorn_leaves_a_doubly_stochastic_mapping_after_twenty(built):
    """``H_res`` of the toy's first layer on a seeded row: over the
    row's tokens the largest ``|row or column sum - 1|`` is within 1e-3
    of 0 after 20 iterations in the mean (what the program's counter
    reads) and for 9 tokens of 10 (the worst of the 64, a matrix whose
    entries span e^3 and more, reads 6e-3), and visibly not after 1:
    0.37 in the mean, no token under 0.02; and the program's counter
    reads the same."""
    model, the_job, params, batch = built
    config = job.model_config(model)
    w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     next(job.reference_layers(params, config)))
    table = params["embed_tokens"]["embedding"]
    streams = reference.enter(table[batch["input_ids"][0]], model)
    off = {}
    for iters in (1, 20):
        _, _, h_res = reference.hyper_maps(
            streams, w["hc_attn"], dict(model, hc_sinkhorn_iters=iters))
        assert h_res.shape == (64, 4, 4) and float(h_res.min()) > 0
        off[iters] = np.asarray(jnp.maximum(
            jnp.abs(h_res.sum(axis=-1) - 1).max(axis=-1),
            jnp.abs(h_res.sum(axis=-2) - 1).max(axis=-1)))
    assert off[20].mean() < 1e-3 and np.quantile(off[20], 0.9) < 1e-3
    assert off[20].max() < 0.02 < off[1].min() and off[1].mean() > 0.1
    aux = the_job.loss_fn(params, batch, None)[1]
    assert 0 <= float(aux["hc_res_defect"]) < 1e-3
    one = job.build(model, hc_sinkhorn_iters=1).loss_fn(params, batch, None)
    assert float(one[1]["hc_res_defect"]) > 0.1


def test_the_modules_targets_are_two_ahead_and_the_tail_is_masked():
    """For a row ``t_0 .. t_S`` the batch holds ``input_ids = t_0 ..
    t_{S-1}`` and ``labels = t_1 .. t_S``: the module at position i
    takes ``t_{i+1}`` and predicts ``t_{i+2}``. Of the row's S + 1
    tokens the last two have no token two ahead: ``t_S`` is no input
    position, and position S - 1 is masked."""
    row = np.arange(100, 109, dtype=np.int32)  # t_0 .. t_8, S = 8
    ids, labels = row[None, :-1], row[None, 1:]
    next_ids, targets = mla_moe.mtp_targets(jnp.asarray(labels), 1)
    assert next_ids.shape == targets.shape == (1, 1, 8)
    assert list(next_ids[0, 0]) == list(row[1:])
    assert list(targets[0, 0][:-1]) == list(row[2:])
    assert int(targets[0, 0][-1]) == -100
    has_target = [i + 2 <= 8 for i in range(9)]  # by token position
    assert has_target == [True] * 7 + [False] * 2
    assert list(np.asarray(targets[0, 0]) != -100) == has_target[:8]
    # depth 2: the second module takes t_{i+2} and predicts t_{i+3}
    next_ids, targets = mla_moe.mtp_targets(jnp.asarray(labels), 2)
    assert list(next_ids[1, 0][:-1]) == list(row[2:])
    assert list(targets[1, 0]) == list(row[3:]) + [-100, -100]
    del ids


def test_the_masked_tail_moves_nothing(built):
    """The module's loss does not read its last position: another
    ``t_S`` there changes the main loss alone... and the program's
    module loss is the reference's mean over the first S - 1."""
    model, the_job, params, batch = built
    _, aux = the_job.loss_fn(params, batch, None)
    plain = copy.deepcopy(model)
    plain["assumed"]["mtp_loss_weight"] = 0.0
    main = reference_loss(plain, params, batch)
    whole = reference_loss(model, params, batch)
    assert float(aux["mtp_loss"]) == pytest.approx((whole - main) / 0.3,
                                                   abs=1e-4)


def test_the_four_shares_of_a_layer_add_up_to_the_whole_layer():
    """64 experts over 4 shares of 16 (experts 0-15, 16-31, 32-47,
    48-63): the routed parts all the shares give, and what every chip
    computes alike (the shared expert) counted once, equal the uncut
    reference's expert layer; and the program's layer, told the same
    held set and bias, gives each share's part."""
    model = toy()
    whole = copy.deepcopy(model)
    whole["deployment"]["experts_held"] = list(range(64))
    key = jax.random.split(jax.random.PRNGKey(7), 6)
    d, f = model["hidden_size"], model["moe_intermediate_size"]

    def swiglu_weights(key, lead=()):
        k = jax.random.split(key, 3)
        return {"w_gate": jax.random.normal(k[0], lead + (d, f)) * 0.2,
                "w_up": jax.random.normal(k[1], lead + (d, f)) * 0.2,
                "w_down": jax.random.normal(k[2], lead + (f, d)) * 0.2}

    every = swiglu_weights(key[0], (64,))
    w = {"w_router": jax.random.normal(key[1], (d, 64)),
         "b_router": 0.3 * jax.random.normal(key[4], (64,)),
         "shared": swiglu_weights(key[2]), "experts": every}
    u = jax.random.normal(key[3], (64, d))
    with jax.default_matmul_precision("highest"):
        want, top_i = reference.expert_layer(u, w, whole)
        shared = reference.swiglu(u, w["shared"])
        total = shared
        for share in range(4):
            held = list(range(16 * share, 16 * share + 16))
            part = copy.deepcopy(model)
            part["deployment"]["experts_held"] = held
            mine = dict(w, experts=jax.tree.map(
                lambda a: a[held[0]:held[-1] + 1], every))
            out, _ = reference.expert_layer(u, mine, part)
            total = total + (out - shared)  # this share's routed part
            gate_i, gate_w, _ = moe.sigmoid_topk_routing(
                u @ w["w_router"], model["num_experts_per_tok"],
                model["norm_topk_prob"], model["routed_scaling_factor"],
                w["b_router"])
            program, stats = moe.held_expert_ffn(
                {"gate": {"kernel": mine["experts"]["w_gate"]},
                 "up": {"kernel": mine["experts"]["w_up"]},
                 "down": {"kernel": mine["experts"]["w_down"]}},
                u, gate_i, gate_w, tuple(held),
                moe.held_row_bound(64, 4, 64, 16, 4.0, 8), 8, True)
            assert float(jnp.abs(program - (out - shared)).max()) < 1e-4
            assert float(stats["rows_dropped"]) == 0
    assert float(jnp.abs(total - want).max()) < 1e-4
    assert float(jnp.abs(want - shared).max()) > 0.1  # the experts count
    assert top_i.shape == (64, 4)


def test_the_selection_bias_moves_the_selected_set_and_never_a_weight():
    """Program and reference alike: with a bias some tokens select
    other experts; an expert selected with and without it has the
    weight its scores alone give; the bias takes no gradient."""
    model = toy()
    key = jax.random.split(jax.random.PRNGKey(5), 3)
    logits = jax.random.normal(key[0], (256, 64))
    bias = 0.2 * jax.random.normal(key[1], (64,))
    plain_i, plain_w, scores = moe.sigmoid_topk_routing(logits, 4, True, 2.0)
    top_i, top_w, _ = moe.sigmoid_topk_routing(logits, 4, True, 2.0, bias)
    changed = np.asarray(jnp.sort(plain_i) != jnp.sort(top_i)).any(axis=-1)
    assert 0.2 < changed.mean() < 1.0
    # the selected are the four largest of score + bias ...
    assert np.array_equal(np.sort(np.asarray(top_i)), np.sort(np.asarray(
        jax.lax.top_k(scores + bias, 4)[1])))
    # ... and weighed by the scores alone
    picked = jnp.take_along_axis(scores, top_i, axis=-1)
    assert np.allclose(top_w, 2.0 * picked / picked.sum(-1, keepdims=True),
                       rtol=1e-6)
    same = ~changed
    assert np.allclose(np.sort(np.asarray(top_w)[same]),
                       np.sort(np.asarray(plain_w)[same]), rtol=1e-6)
    grad = jax.grad(lambda b: moe.sigmoid_topk_routing(
        logits, 4, True, 2.0, b)[1].sum())(bias)
    assert float(jnp.abs(grad).max()) == 0
    # the reference, written apart, selects and weighs the same
    u = jax.random.normal(key[2], (256, 64))
    w_r = jax.random.normal(key[0], (64, 64)) / 8
    ref_i, ref_w = reference.route(u, w_r, bias, model)
    got_i, got_w, _ = moe.sigmoid_topk_routing(u @ w_r, 4, True, 2.0, bias)
    assert np.array_equal(ref_i, got_i) and np.allclose(ref_w, got_w,
                                                        rtol=1e-5)


def test_the_cell_keeps_every_published_width():
    model = cell_model()
    with open(os.path.join(REPO, "chipbench", "published",
                           "xing4.0-29b-a4b.json")) as f:
        published = json.load(f)["config"]
    cut = set(model["reduced"])
    assert cut == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in published.items():
        if key not in cut:
            assert model[key] == value, key
    assert (model["hidden_size"], model["intermediate_size"],
            model["moe_intermediate_size"], model["q_lora_rank"],
            model["kv_lora_rank"], model["num_experts_per_tok"],
            model["num_attention_heads"], model["hc_mult"],
            model["num_nextn_predict_layers"]) == (
        3584, 9216, 1024, 768, 512, 4, 32, 4, 1)
    dep = model["deployment"]
    assert dep["published_n_routed_experts"] == 64
    assert dep["experts_held"] == list(range(16))
    assert dep["chips_sharing_a_layer"] == dep["expert_parallel"] == 4
    assert model["vocab_size"] * dep["vocabulary_ways"] == 131072
    assert model["num_attention_heads"] * dep["attention_ways"] == 32
    assert (model["assumed"]["batch"], model["assumed"]["seq_len"]) == (
        2, 4096)
    config = job.model_config(model)
    assert config.n_routed_experts == 64 and len(config.held) == 16
    assert config.first_k_dense == 2 and config.moe_layers == 5
    assert (config.hc_mult, config.hc_sinkhorn_iters, config.hc_clamp,
            config.router_bias, config.mtp_layers, config.mtp_loss_weight,
            config.balance_loss_weight) == (4, 20, (-30, 30), True, 1, 0.3,
                                            0.0)
    assert moe.held_row_ladder(8192, 4, 64, 16, 4.0, 128) == (17408, 34816)
    # YaRN at this model's factor: the softmax scale carries m^2
    m = 0.1 * np.log(64) + 1
    assert reference.softmax_scale(model) == pytest.approx(192 ** -0.5 * m * m)
    assert config.softmax_scale == pytest.approx(
        reference.softmax_scale(model))
    cos, sin = reference.rotary_tables(model, 2)
    angle = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))
    assert np.allclose(mla_moe.yarn_inv_freq(config), angle, rtol=1e-5)


def test_the_arithmetic_by_hand():
    model = cell_model()
    mla = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
           + 32 * 128 * 3584)
    assert mla == 28_409_856
    phi = 2 * 4 * 3584 * 24  # a layer's two projections
    hc = phi + 2 * (4 * 3584 + 3 + 24)
    expert = 3 * 3584 * 1024
    dense = 3 * 3584 * 9216
    block = mla + hc + 2 * 3584 + 768 + 512
    moe_part = 3584 * 64 + 64 + 17 * expert
    module = 2 * 3584 * 3584 + 3 * 3584
    assert flops.param_count(model) == (
        8 * block + 2 * dense + 6 * moe_part + module
        + 2 * 3584 * 32768 + 3584) == 1_816_249_136
    # and the program's own count, by abstract evaluation
    assert worker.build_job(model).param_count == flops.param_count(model)
    assert flops.tokens_per_step(model) == 8192
    assert flops.layer_counts(model) == {"dense": 2, "moe": 5, "mtp": 1}
    assert flops.held_rows_expected(model) == 8192 * 4 * 16 / 64 == 8192
    active = (8 * (mla + phi) + 2 * dense
              + 6 * (3584 * 64 + expert + expert)
              + 2 * 3584 * 3584 + 2 * 3584 * 32768)
    assert flops.active_matmul_params(model) == pytest.approx(active)
    pairs = 4096 * 4097 // 2
    attention = 8 * 3 * 32 * (2 * 192 + 2 * 128) * pairs * 2
    assert flops.mla_flops_per_step(model) == attention
    assert flops.model_flops_per_step(model) == pytest.approx(
        6 * active * 8192 + attention)
    q, k, v = 32 * 192, 32 * 128 + 64, 32 * 128
    assert flops.mla_bytes_per_step(model) == 8 * 8192 * 2 * (
        (q + k + 2 * v) + (q + k + 3 * v) + (q + k + v))
    rows = 6 * 8000
    assert flops.gmm_flops(model, rows) == 3 * 3 * 2 * 3584 * 1024 * rows
    assert flops.gmm_bytes(model, rows) == 2 * (
        3 * 6 * 16 * expert + rows * 3 * (3 * 3584 + 3 * 1024))
    assert flops.kernel_flops_per_step(model) == pytest.approx(
        attention + flops.gmm_flops(model, 6 * 8192))
    assert flops.kernel_bytes_per_step(model) == pytest.approx(
        flops.mla_bytes_per_step(model)
        + flops.gmm_bytes(model, 6 * 8192))


def test_the_readers_read_the_counters():
    """The two new readers, and the six they share with the other
    latent-attention family fed by this family's ``flops.py``, on a
    made-up reduced trace and ``profile_window`` event; on a run
    without the counters (the parent's program): nothing, not an
    error."""
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
        ["fusion.1", 2.0], ["mosaic:flash_mla_fwd.28", 0.16],
        ["mosaic:flash_mla_dkv.14", 0.2], ["mosaic:flash_mla_dq.14", 0.2],
        ["mosaic:gmm.108", 0.1], ["mosaic:gmm_dx.3", 0.04],
        ["mosaic:gmm_dw.7", 0.06]]}
    window = {"kind": "profile_window", "pid": 77, "steps": 6,
              "step_counters": {"moe_rows_held": 6 * 48000.0,
                                "moe_rows_max": 6 * 6 * 700.0,
                                "moe_rows_dropped": 0.0,
                                "hc_res_defect": 6 * 2.5e-5,
                                "mtp_loss": 6 * 10.75}}
    run = {"worker": {"pid": 77}, "events": [window],
           "profile_window": window}
    ctx = {"trace": trace, "model": model, "flops": flops, "run": run,
           "arithmetic": arithmetic,
           "device": {"count": 1, "kind": "TPU v5 lite"}}
    assert reader("hc_res_defect")(ctx) == pytest.approx(2.5e-5)
    assert reader("mtp_loss")(ctx) == pytest.approx(10.75)
    assert reader("mla_attn_ms")(ctx) == pytest.approx(140.0)
    assert reader("expert_gmm_ms")(ctx) == pytest.approx(50.0)
    least = flops.mla_flops_per_step(model) / 197e12
    assert reader("mla_attn_roofline")(ctx) == pytest.approx(
        100 * least / 0.14)
    least = max(flops.gmm_flops(model, 48000) / 197e12,
                flops.gmm_bytes(model, 48000) / 819e9)
    assert reader("expert_gmm_roofline")(ctx) == pytest.approx(
        100 * least / 0.05)
    assert reader("expert_load_imbalance")(ctx) == pytest.approx(
        6 * 700 / (48000 / 16))
    assert reader("expert_rows_dropped")(ctx) == 0.0
    bare = dict(ctx, run={"worker": {"pid": 77}, "events": [],
                          "profile_window": {"kind": "profile_window",
                                             "pid": 77, "steps": 6}})
    for name in ("hc_res_defect", "mtp_loss"):
        assert reader(name)(bare) is None, name
        assert reader(name)(dict(bare, run={
            "worker": {"pid": 77}, "events": []})) is None, name


def test_the_published_rule_finds_nothing_wrong_on_the_tree():
    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import published_rule

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert published_rule.wrong(bench, REPO) == []
    mine = [c for c in bench["configs"] if c["name"] == CELL]
    assert len(mine) == 1 and set(mine[0]["reduced"]) == set(
        cell_model()["reduced"])
    cells = [w for w in bench["workloads"] if w["config"] == CELL]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        ("xing4-1chip.steady", "steady", 1)]
    assert len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # the family's flops.py fails at once where the program lacks the
    # module it measures: that is how the parent exits on the cell
    with open(os.path.join(REPO, "chipbench", "families", "mla_moe_hc",
                           "flops.py")) as f:
        assert "hyper_connections.py" in f.read()


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "chipbench", "families", "mla_moe_hc",
                           "reference.py")) as f:
        imports = [line for line in f.read().splitlines()
                   if line.startswith(("import ", "from "))]
    assert imports == ["import math", "import jax", "import jax.numpy as jnp"]
