"""Deviceless AOT compile-and-fit proofs on virtual TPU topologies.

The BASELINE "Llama-2-7B on v5p-32" viability proof runs with no TPU at
all: XLA's TPU compiler is hermetic, so the full jitted train step is
compiled against a ``TopologyDescription`` and memory/cost analysis read
back (``parallel/aot.py``). Committed artifact: ``AOT_7B.json``.
"""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel.aot import (
    KNOWN_TOPOLOGIES,
    aot_compile_train_step,
)
from dlrover_tpu.parallel.mesh import MeshPlan


def test_tiny_llama_compiles_on_virtual_v5p_slice():
    config = llama.llama_tiny(use_flash=False)
    report = aot_compile_train_step(
        config, topology="v5p-16", tpu_gen="v5p", global_batch=16,
        model_name="llama_tiny",
    )
    assert report.n_devices == 8  # v5p-16 = 16 cores = 8 chips
    assert report.fits
    assert report.hbm_per_device_bytes < 1e9
    assert report.flops_per_step > 0
    assert report.params == llama.param_count(config)


def test_known_topology_aliases_cover_v5p_sizes():
    assert KNOWN_TOPOLOGIES["v5p-32"] == "v5:2x2x4"


@pytest.mark.slow
def test_tiny_moe_and_packed_ring_compile_deviceless():
    """The round-4 prover modes at test scale: switch-MoE with the moe
    rule set, and packed documents flowing through the ring with the
    segmented pair kernel — both against a virtual topology."""
    moe = llama.llama_tiny(use_flash=False, num_experts=4, moe_top_k=1)
    report = aot_compile_train_step(
        moe, topology="v5p-16", tpu_gen="v5p", global_batch=16,
        rule_set="moe", model_name="llama_tiny+moe4",
        mesh_plan=MeshPlan(data=2, fsdp=2, tensor=2),
    )
    assert report.fits and report.params == llama.param_count(moe)

    ring_cfg = llama.llama_tiny(
        use_flash=True, flash_interpret=False,  # force Mosaic lowering
        flash_block_q=64, flash_block_k=64,
    )
    report = aot_compile_train_step(
        ring_cfg, topology="v5p-16", tpu_gen="v5p", global_batch=16,
        mesh_plan=MeshPlan(fsdp=2, seq=2, tensor=2),
        ring=True, packed_doc_len=32, model_name="llama_tiny+ring",
    )
    assert report.fits


@pytest.mark.slow
def test_glm_prefix_ring_lowers_to_mosaic_deviceless():
    """The prefix-LM ring's production path — prefix kernel on the
    diagonal, pair kernel on visible future shards, inside shard_map —
    lowers to a real TPU executable with no devices. Pins that
    sequence-parallel prefix-LM is not an interpret-mode-only trick."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies

    from dlrover_tpu.models import glm
    from dlrover_tpu.parallel.accelerate import accelerate
    from dlrover_tpu.parallel.aot import _get_topology_desc_serialized
    from dlrover_tpu.parallel.strategy import Strategy

    topo = _get_topology_desc_serialized(topologies, "v5:2x2x2")
    devices = list(topo.devices)
    plan = MeshPlan(data=2, seq=2, tensor=2)
    cfg = glm.glm_tiny(
        use_flash=True, flash_interpret=False,  # force Mosaic
        flash_block_q=32, flash_block_k=32,
        seq_axis="seq", mesh=plan.build(devices),
    )
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 65))
    batch = {
        "input_ids": jnp.asarray(ids[:, :-1]),
        "labels": jnp.asarray(ids[:, 1:]),
        "prefix_len": jnp.asarray([17, 23, 40, 9, 5, 60, 33, 12],
                                  jnp.int32),
    }
    result = accelerate(
        glm.make_init_fn(cfg), glm.make_loss_fn(cfg),
        optax.adafactor(1e-3), batch,
        strategy=Strategy(mesh=plan, rule_set="glm",
                          remat_policy="none"),
        devices=devices,
    )
    abstract_state = jax.eval_shape(result.init_fn, jax.random.PRNGKey(0))
    abstract_batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch
    )
    compiled = result.train_step.lower(
        abstract_state, abstract_batch,
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


@pytest.mark.slow
def test_llama2_7b_fits_v5p_32():
    """The BASELINE row: real 7B config, 16-chip v5p-32, the artifact's
    mesh (data=8 x tensor=2 — AOT_7B.json), PRODUCTION attention path
    (Pallas flash — the hermetic TPU compiler lowers it deviceless)
    with dots_saveable remat. Asserts HBM fit via compiled
    memory_analysis — no hardware involved."""
    config = llama.llama2_7b(
        max_seq_len=4096,
        param_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16,
        remat_policy="dots_saveable",
        use_flash=True,
    )
    report = aot_compile_train_step(
        config, topology="v5p-32", tpu_gen="v5p", global_batch=16,
        mesh_plan=MeshPlan(data=8, fsdp=1, seq=1, tensor=2),
        model_name="llama2_7b",
    )
    assert report.n_devices == 16
    assert report.params > 6.7e9
    assert report.fits, (
        f"7B must fit v5p-32: {report.hbm_per_device_bytes / 1e9:.1f} GB "
        f"of {report.hbm_capacity_bytes / 1e9:.0f} GB"
    )
    # at least ~75% headroom consumed by state+activations is expected
    # to stay under capacity with margin
    assert report.hbm_per_device_bytes < 0.5 * report.hbm_capacity_bytes
    # both bounds: the target AND physical sanity (round-2 artifact
    # claimed 1.31 — an uncalibrated cost model must never pass again)
    assert 0.45 <= report.predicted_mfu < 1.0
    # cross-check the hand-rolled XLA memory sum against the planner's
    # analytic model: a double-counted donation or dropped term in either
    # shows up as a gross disagreement
    from dlrover_tpu.parallel import planner

    spec = planner.model_spec_from_llama(config, 16)
    score = planner.estimate(
        MeshPlan(data=8, fsdp=1, seq=1, tensor=2), spec,
        planner.TPU_SPECS["v5p"], remat_policy="dots_saveable",
    )
    ratio = report.hbm_per_device_bytes / score.memory_bytes
    assert 0.3 < ratio < 3.0, (
        f"XLA-measured {report.hbm_per_device_bytes/1e9:.1f} GB vs "
        f"planner-modeled {score.memory_bytes/1e9:.1f} GB"
    )


def test_grouped_matmul_lowers_to_mosaic_deviceless():
    """The dropless-MoE grouped-matmul kernel — forward plus BOTH
    backward kernels (dx re-grouped GEMM, dw expert-accumulation with
    scalar-prefetch output indexing) — lowers to a real TPU executable
    hermetically at production-like shapes. Pins that the "grouped"
    dispatch is not an interpret-mode-only trick."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dlrover_tpu.ops.grouped_matmul import grouped_matmul
    from dlrover_tpu.parallel.aot import _get_topology_desc_serialized

    topo = _get_topology_desc_serialized(topologies, "v5:2x2x1")
    dev = list(topo.devices)[:1]
    mesh = Mesh(np.array(dev).reshape(1), ("x",))
    repl = NamedSharding(mesh, P())

    e, d, f, bt = 8, 1024, 2816, 128
    tp = 16 * bt

    def loss(x, w, te):
        y = grouped_matmul(x, w, te, bt, 512, False)  # force Mosaic
        return (y ** 2).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1)),
                in_shardings=(repl, repl, repl),
                out_shardings=(repl, repl))
    compiled = g.lower(
        jax.ShapeDtypeStruct((tp, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((e, d, f), jnp.bfloat16),
        jax.ShapeDtypeStruct((tp // bt,), jnp.int32),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes > 0


def test_aot_lint_includes_concurrency_pass(monkeypatch):
    """`aot --lint` runs the DLR009-011 pass over the control plane and
    routes baseline-filtered findings into report.lint_findings (clean
    on HEAD; the injected finding pins the wiring)."""
    from dlrover_tpu.analysis import concurrency
    from dlrover_tpu.analysis.findings import Finding

    injected = Finding("DLR009", "fake/module.py", 7,
                       "rpc under a held lock", scope="C.m")
    monkeypatch.setattr(
        concurrency, "lint_paths_concurrency",
        lambda paths, root, rules=None, counters=None: [injected])
    config = llama.llama_tiny(use_flash=False)
    report = aot_compile_train_step(
        config, topology="v5p-16", tpu_gen="v5p", global_batch=16,
        model_name="llama_tiny", graph_lint=True,
    )
    assert report.lint_findings is not None
    dlr = [f for f in report.lint_findings
           if f.rule_id.startswith("DLR")]
    assert dlr == [injected]
    # and the serialized report carries it for the CLI exit path
    import json as _json

    data = _json.loads(report.to_json())
    assert any(e["rule"] == "DLR009"
               for e in data["lint_findings"])
