"""How a scan-stacked layer is laid out under FSDP (ISSUE 27).

One definition, in ``parallel/sharding_rules.py``'s docstring: a
stacked ``layers/`` leaf never has ``fsdp`` on its layer axis (the scan
over layers slices that axis in every iteration, so a stack sharded
there is gathered whole to take one layer out); ``fsdp`` sits on the
kernel's hidden axis, ``tensor`` where Megatron's split puts it. Only
the ``*_pp_rules`` shard the layer axis, on ``pipe``.

Here on the 8-device CPU mesh: the specs of every rule set, the
compiled step's all-gathers, the arithmetic against one device, and a
checkpoint written under the old layout read back under the new.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from hlo_checks import compile_step, stack_gathers

from dlrover_tpu.checkpoint import ElasticCheckpointManager, abstract_like
from dlrover_tpu.models import bert, clip, glm, gpt2, gpt_neox, llama
from dlrover_tpu.parallel import strategy as strategy_module
from dlrover_tpu.parallel.accelerate import accelerate
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.sharding_rules import (
    ShardingRules,
    _flatten_with_paths,
    llama_rules,
)
from dlrover_tpu.parallel.strategy import RULE_SETS, Strategy

LAYERS = 4  # divisible by fsdp=4: the old rules bound the layer axis

MODELS = {
    "llama": (llama, llama.llama_tiny),
    "neox": (gpt_neox, gpt_neox.neox_tiny),
    "glm": (glm, glm.glm_tiny),
    "bert": (bert, bert.bert_tiny),
    "clip": (clip, clip.clip_tiny),
    "gpt2": (gpt2, gpt2.gpt2_tiny),
}
# rule set -> the model whose tree it lays out
FSDP_RULE_SETS = {"llama": "llama", "neox": "neox", "glm": "glm",
                  "bert": "bert", "clip": "clip", "moe": "llama",
                  "moe_ep": "llama"}
PP_RULE_SETS = {"llama_pp": "llama", "neox_pp": "neox", "glm_pp": "glm",
                "bert_pp": "bert", "gpt2_pp": "gpt2"}


def _stacked_leaves(model):
    """(path, shape) of every leaf under a ``layers/`` stack of the
    tiny model at LAYERS layers."""
    module, tiny = MODELS[model]
    if model == "clip":
        base = tiny()
        config = tiny(
            text=dataclasses.replace(base.text, num_layers=LAYERS),
            vision=dataclasses.replace(base.vision, num_layers=LAYERS))
    else:
        config = tiny(num_layers=LAYERS)
    shapes = jax.eval_shape(module.make_init_fn(config),
                            jax.random.PRNGKey(0))
    leaves = [(path, leaf.shape) for path, leaf
              in _flatten_with_paths(shapes) if "layers/" in path]
    assert leaves and all(shape[0] == LAYERS for _, shape in leaves)
    return leaves


def _axes_of(entry):
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


@pytest.mark.parametrize("tensor", [1, 2])
@pytest.mark.parametrize("rule_set", sorted(FSDP_RULE_SETS))
def test_fsdp_never_takes_the_layer_axis(rule_set, tensor):
    rules = RULE_SETS[rule_set]()
    sizes = {"pipe": 1, "data": 1, "fsdp": 4, "seq": 1, "tensor": tensor}
    for path, shape in _stacked_leaves(FSDP_RULE_SETS[rule_set]):
        spec = rules.spec_for(path, shape, sizes)
        assert spec[0] is None, (path, spec)
        if not path.endswith("/kernel") or len(shape) != 3:
            continue
        # a stacked [L, in, out] kernel: fsdp on one weight axis, and
        # tensor, where the mesh has it, on the other
        on_fsdp = [i for i in (1, 2) if "fsdp" in _axes_of(spec[i])]
        on_tensor = [i for i in (1, 2) if "tensor" in _axes_of(spec[i])]
        assert len(on_fsdp) == 1, (path, spec)
        if tensor > 1:
            assert len(on_tensor) == 1 and on_tensor != on_fsdp, (path,
                                                                  spec)


@pytest.mark.parametrize("rule_set", sorted(PP_RULE_SETS))
def test_a_pipeline_keeps_its_layers_on_pipe(rule_set):
    rules = RULE_SETS[rule_set]()
    sizes = {"pipe": 4, "data": 1, "fsdp": 2, "seq": 1, "tensor": 1}
    for path, shape in _stacked_leaves(PP_RULE_SETS[rule_set]):
        spec = rules.spec_for(path, shape, sizes)
        assert spec[0] == "pipe", (path, spec)
        assert "fsdp" not in [a for e in spec for a in _axes_of(e)], (
            path, spec)


@pytest.mark.parametrize("rule_set",
                         sorted({**FSDP_RULE_SETS, **PP_RULE_SETS}))
def test_one_chip_replicates_everything(rule_set):
    """Every mesh axis of size 1 collapses to ``None``: on one chip a
    change of the rules changes no spec and no program."""
    rules = RULE_SETS[rule_set]()
    model = {**FSDP_RULE_SETS, **PP_RULE_SETS}[rule_set]
    sizes = dict.fromkeys(("pipe", "data", "fsdp", "seq", "tensor"), 1)
    for path, shape in _stacked_leaves(model):
        assert rules.spec_for(path, shape, sizes) == (None,) * len(shape)


# -- the compiled step ------------------------------------------------------


def _rules_before_pr27() -> ShardingRules:
    """``llama_rules`` as every checkpoint before ISSUE 27 was written:
    the stacked layer axis on ``fsdp``."""
    old = {"q_proj|k_proj|v_proj": ("fsdp", None, "tensor"),
           "o_proj": ("fsdp", "tensor", None),
           "gate_proj|up_proj": ("fsdp", None, "tensor"),
           "down_proj": ("fsdp", "tensor", None)}
    rules = []
    for pattern, spec in llama_rules().rules:
        for names, old_spec in old.items():
            if pattern == rf"layers/.*({names})/kernel$" or (
                    pattern == rf"layers/.*{names}/kernel$"):
                spec = old_spec
        rules.append((pattern, spec))
    assert sum(s in old.values() for _, s in rules) == 4
    return ShardingRules(rules=rules)


@pytest.fixture
def old_rule_set(monkeypatch):
    monkeypatch.setitem(strategy_module.RULE_SETS, "llama_before_pr27",
                        _rules_before_pr27)
    return "llama_before_pr27"


def _tiny_job(rule_set, fsdp, optimizer=None, **config):
    cfg = llama.llama_tiny(num_layers=LAYERS, **config)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(8, 17))
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    result = accelerate(
        llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
        optimizer or optax.adafactor(1e-3), batch,
        strategy=Strategy(mesh=MeshPlan(data=1, fsdp=fsdp),
                          rule_set=rule_set),
        devices=jax.devices()[:fsdp])
    return cfg, result, batch


def test_the_step_gathers_no_whole_stack():
    """The scan's slice of a stacked kernel is local: no all-gather of
    the compiled step yields ``[num_layers, ...]``. (The CPU's
    partitioner is no witness of the old rules: at this size it reduces
    activations and, for a slice of a sharded axis, gathers one masked
    layer. The chip's compiler is asked in ``test_tpu_compile.py``;
    the line below is from the program it made before ISSUE 27.)"""
    _, new, batch = _tiny_job("llama", 4)
    text = compile_step(new, batch).as_text()
    assert "all-gather" in text and "while(" in text
    assert stack_gathers(text, LAYERS) == []
    before = ("  %all-gather.275 = bf16[20,4096,14336]{2,1,0:T(8,128)(2,1)}"
              " all-gather(%param.5), channel_id=9, dimensions={0}\n"
              "  %all-gather.9 = bf16[20,4096]{1,0} all-gather(%p), "
              "dimensions={1}\n"
              "  %ag = (bf16[5,8,8], bf16[20,8,8]{2,1,0}) "
              "all-gather-start(%q), dimensions={0}\n")
    assert stack_gathers(before, 20) == [(20, 4096, 14336), (20, 8, 8)]


# -- nothing else changed ---------------------------------------------------


def test_fsdp4_step_agrees_with_one_device():
    """Where the bytes of a weight live between steps changes, the
    arithmetic does not: loss and one optimizer step under ``fsdp=4``
    against one device, in float32."""
    outs = {}
    for fsdp in (1, 4):
        _, result, batch = _tiny_job(
            "llama", fsdp, optimizer=optax.sgd(0.1),
            param_dtype=jnp.float32, compute_dtype=jnp.float32)
        state = result.init_fn(jax.random.PRNGKey(0))
        state, metrics = result.train_step(
            state, result.shard_batch(batch), jax.random.PRNGKey(1))
        outs[fsdp] = (float(metrics["loss"]),
                      jax.tree.map(np.asarray, state.params))
    assert outs[4][0] == pytest.approx(outs[1][0], rel=1e-5)
    flat1 = dict(_flatten_with_paths(outs[1][1]))
    for path, leaf in _flatten_with_paths(outs[4][1]):
        np.testing.assert_allclose(leaf, flat1[path], rtol=1e-4,
                                   atol=1e-5, err_msg=path)


def test_a_checkpoint_of_the_old_layout_restores_under_the_new(
        tmp_path, old_rule_set):
    """Checkpoints written at one layout restore at any other: a train
    state saved under the rules of before ISSUE 27 (layer axis on
    ``fsdp``) comes back under today's rules with every leaf bitwise
    equal, and trains."""
    _, old, batch = _tiny_job(old_rule_set, 4)
    state = old.init_fn(jax.random.PRNGKey(0))
    state, _ = old.train_step(state, old.shard_batch(batch),
                              jax.random.PRNGKey(1))
    q_old = state.params["layers"]["q_proj"]["kernel"].sharding.spec
    assert tuple(q_old)[0] == "fsdp"
    mgr = ElasticCheckpointManager(str(tmp_path), async_save=False)
    mgr.save(int(state.step), state, force=True)
    mgr.wait()

    _, new, _ = _tiny_job("llama", 4)
    target = abstract_like(
        jax.eval_shape(new.init_fn, jax.random.PRNGKey(0)),
        new.state_sharding)
    restored = mgr.restore(target)["state"]
    q_new = restored.params["layers"]["q_proj"]["kernel"].sharding.spec
    assert tuple(q_new)[:2] == (None, "fsdp")
    saved = dict(_flatten_with_paths(state))
    for path, leaf in _flatten_with_paths(restored):
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(saved[path]), err_msg=path)
    restored, metrics = new.train_step(
        restored, new.shard_batch(batch), jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["loss"]))
    mgr.close()
