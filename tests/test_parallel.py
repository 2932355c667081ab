"""Parallelism library tests on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.parallel.accelerate import accelerate
from dlrover_tpu.parallel.mesh import MeshPlan, candidate_plans
from dlrover_tpu.parallel.sharding_rules import (
    FSDP_AUTO,
    REPLICATED,
    ShardingRules,
    llama_rules,
)
from dlrover_tpu.parallel.strategy import Strategy


class TestMeshPlan:
    def test_resolve_infers_axis(self):
        plan = MeshPlan(data=-1, fsdp=2, tensor=2).resolve(8)
        assert plan.data == 2 and plan.fsdp == 2 and plan.tensor == 2

    def test_resolve_rejects_indivisible(self):
        with pytest.raises(ValueError):
            MeshPlan(data=3, tensor=3).resolve(8)

    def test_build_mesh(self):
        mesh = MeshPlan(data=2, fsdp=2, tensor=2).build()
        assert mesh.devices.size == 8
        assert mesh.axis_names == ("pipe", "data", "fsdp", "seq", "tensor")

    def test_adjust_to_world_keeps_model_parallel(self):
        plan = MeshPlan(data=2, fsdp=2, tensor=2)
        smaller = plan.adjust_to_world(4)  # lost half the hosts
        assert smaller.tensor == 2
        assert smaller.dp_degree == 2
        bigger = plan.adjust_to_world(16)
        assert bigger.tensor == 2 and bigger.dp_degree == 8

    def test_candidate_plans_cover_device_count(self):
        plans = candidate_plans(8)
        for p in plans:
            assert p.resolve(8)
        assert any(p.tensor == 8 for p in plans)
        assert any(p.fsdp == 8 for p in plans)


class TestShardingRules:
    AXES = {"data": 2, "fsdp": 2, "tensor": 2}

    def test_explicit_rule(self):
        rules = llama_rules()
        spec = rules.spec_for(
            "model/layers_0/attn/q_proj/kernel", (64, 64), self.AXES
        )
        assert spec == (None, "tensor")

    def test_auto_fsdp_picks_largest_divisible(self):
        rules = ShardingRules()
        assert rules.spec_for("x/kernel", (6, 64), self.AXES) == (None, "fsdp")
        # indivisible dims replicate
        assert rules.spec_for("x/kernel", (3, 7), self.AXES) == (None, None)

    def test_replicated_rule(self):
        rules = llama_rules()
        assert rules.spec_for("model/norm/scale", (64,), self.AXES) == (None,)

    def test_collapsed_axis_replicates(self):
        rules = llama_rules()
        spec = rules.spec_for(
            "a/q_proj/kernel", (64, 64), {"tensor": 1, "fsdp": 2}
        )
        assert spec == (None, None)


def _mlp_init(rng):
    k1, k2 = jax.random.split(rng)
    return {
        "dense1": {"kernel": jax.random.normal(k1, (16, 64)) * 0.1,
                   "bias": jnp.zeros((64,))},
        "dense2": {"kernel": jax.random.normal(k2, (64, 4)) * 0.1,
                   "bias": jnp.zeros((4,))},
    }


def _mlp_loss(params, batch, rng):
    x, y = batch["x"], batch["y"]
    h = jnp.tanh(x @ params["dense1"]["kernel"] + params["dense1"]["bias"])
    logits = h @ params["dense2"]["kernel"] + params["dense2"]["bias"]
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    return loss, {}


def _batch(n=32, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "x": jnp.asarray(rng.randn(n, 16), jnp.float32),
        "y": jnp.asarray(rng.randint(0, 4, size=(n,))),
    }


class TestAccelerate:
    def _build(self, strategy):
        return accelerate(
            _mlp_init, _mlp_loss, optax.adam(1e-2), _batch(),
            strategy=strategy, rng=jax.random.PRNGKey(0),
        )

    def test_training_decreases_loss_on_3d_mesh(self):
        result = self._build(
            Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2))
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        batch = result.shard_batch(_batch())
        rng = jax.random.PRNGKey(1)
        losses = []
        for _ in range(20):
            state, metrics = result.train_step(state, batch, rng)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.7
        assert int(jax.device_get(state.step)) == 20

    def test_params_actually_sharded(self):
        result = self._build(Strategy(mesh=MeshPlan(data=1, fsdp=8)))
        state = result.init_fn(jax.random.PRNGKey(0))
        kernel = state.params["dense1"]["kernel"]  # (16, 64): 64 % 8 == 0
        # each device holds 1/8 of the kernel
        shard_shape = kernel.addressable_shards[0].data.shape
        assert shard_shape == (16, 8)

    def test_grad_accum_matches_full_batch(self):
        r1 = self._build(Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                                  grad_accum_steps=1))
        r4 = self._build(Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                                  grad_accum_steps=4))
        s1 = r1.init_fn(jax.random.PRNGKey(0))
        s4 = r4.init_fn(jax.random.PRNGKey(0))
        batch = _batch()
        s1, m1 = r1.train_step(s1, r1.shard_batch(batch), jax.random.PRNGKey(1))
        s4, m4 = r4.train_step(s4, r4.shard_batch(batch), jax.random.PRNGKey(1))
        # mean-reduced loss: averaging 4 microbatch grads == full-batch grad
        np.testing.assert_allclose(
            float(m1["loss"]), float(m4["loss"]), rtol=1e-5
        )
        k1 = jax.device_get(s1.params["dense1"]["kernel"])
        k4 = jax.device_get(s4.params["dense1"]["kernel"])
        np.testing.assert_allclose(k1, k4, rtol=1e-4, atol=1e-6)

    def test_eval_step(self):
        result = self._build(Strategy(mesh=MeshPlan(data=4, fsdp=2)))
        state = result.init_fn(jax.random.PRNGKey(0))
        metrics = result.eval_step(state, result.shard_batch(_batch()))
        assert float(metrics["loss"]) > 0


class TestShardedFlashAttention:
    """GSPMD cannot auto-partition a Mosaic custom call: under a
    multi-device mesh the llama forward must route flash through the
    shard_map wrapper (``ops.flash_attention.flash_attention_sharded``)
    and match the unsharded reference exactly."""

    def test_flash_under_mesh_matches_reference_path(self):
        import numpy as np

        from dlrover_tpu.models import llama

        ids = np.random.RandomState(0).randint(0, 256, size=(8, 65))
        batch = {
            "input_ids": jnp.asarray(ids[:, :-1]),
            "labels": jnp.asarray(ids[:, 1:]),
        }
        losses = {}
        for flash in (False, True):
            cfg = llama.llama_tiny(num_layers=2, max_seq_len=64,
                                   use_flash=flash)
            result = accelerate(
                llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
                optax.sgd(1e-2), batch,
                strategy=Strategy(
                    mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                    rule_set="llama",
                ),
            )
            state = result.init_fn(jax.random.PRNGKey(0))
            _, metrics = result.train_step(
                state, result.shard_batch(batch), jax.random.PRNGKey(1)
            )
            losses[flash] = float(jax.device_get(metrics["loss"]))
        assert abs(losses[True] - losses[False]) < 2e-3, losses

    # budget triage (PR 16): segment masking is pinned at the ops level
    # and mesh composition by the unsegmented sharded test; the
    # segmented-under-mesh cross product rides slow
    @pytest.mark.slow
    def test_segmented_flash_under_mesh_matches_reference_path(self):
        """Packed sequences on the production multi-chip path: llama with
        segment_ids + use_flash under a 2x2x2 mesh must route the
        segmented Mosaic kernel through shard_map and match the bias
        (use_flash=False) path."""
        import numpy as np

        from dlrover_tpu.models import llama

        rng = np.random.RandomState(0)
        ids = rng.randint(0, 256, size=(8, 64))
        seg = np.sort(rng.randint(0, 3, size=(8, 64)), axis=1)
        labels = np.where(
            np.concatenate([seg[:, :-1] == seg[:, 1:],
                            np.zeros((8, 1), bool)], axis=1),
            np.concatenate([ids[:, 1:], ids[:, :1]], axis=1), -100)
        batch = {
            "input_ids": jnp.asarray(ids),
            "labels": jnp.asarray(labels),
            "segment_ids": jnp.asarray(seg),
        }
        losses = {}
        for flash in (False, True):
            cfg = llama.llama_tiny(num_layers=2, max_seq_len=64,
                                   use_flash=flash, flash_interpret=True)
            result = accelerate(
                llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
                optax.sgd(1e-2), batch,
                strategy=Strategy(
                    mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                    rule_set="llama",
                ),
            )
            state = result.init_fn(jax.random.PRNGKey(0))
            _, metrics = result.train_step(
                state, result.shard_batch(batch), jax.random.PRNGKey(1)
            )
            losses[flash] = float(jax.device_get(metrics["loss"]))
        assert abs(losses[True] - losses[False]) < 2e-3, losses

    def test_partial_mesh_stays_on_plain_path(self):
        """A user-built mesh missing the data/fsdp/tensor axes must not
        crash the auto-router on an unbound shard_map axis — it stays on
        the plain pallas path (review regression)."""
        import numpy as np
        from jax.sharding import Mesh

        from dlrover_tpu.ops.flash_attention import (
            ambient_shard_mesh,
            flash_attention_auto,
        )

        devices = np.asarray(jax.devices()).reshape(8)
        with jax.sharding.set_mesh(Mesh(devices, ("data",))):
            assert ambient_shard_mesh() is None
            q = jnp.ones((2, 4, 64, 32), jnp.float32)
            out = flash_attention_auto(q, q, q, True)
        assert out.shape == q.shape

    def test_gqa_indivisible_kv_heads_legalized(self):
        import numpy as np

        from dlrover_tpu.models import llama

        # 8 query heads / 2 kv heads over tensor=4: needs kv repeat x2
        ids = np.random.RandomState(1).randint(0, 256, size=(4, 65))
        batch = {
            "input_ids": jnp.asarray(ids[:, :-1]),
            "labels": jnp.asarray(ids[:, 1:]),
        }
        cfg = llama.llama_tiny(
            num_layers=2, max_seq_len=64, hidden_size=64,
            num_heads=8, num_kv_heads=2, use_flash=True,
        )
        result = accelerate(
            llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
            optax.sgd(1e-2), batch,
            strategy=Strategy(
                mesh=MeshPlan(data=2, fsdp=1, tensor=4),
                rule_set="llama",
            ),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        _, metrics = result.train_step(
            state, result.shard_batch(batch), jax.random.PRNGKey(1)
        )
        assert jnp.isfinite(float(jax.device_get(metrics["loss"])))


class TestStrategy:
    def test_json_roundtrip(self, tmp_path):
        s = Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                     rule_set="llama", remat_policy="dots_saveable",
                     grad_accum_steps=4)
        path = str(tmp_path / "strategy.json")
        s.save(path)
        loaded = Strategy.load(path)
        assert loaded == s

    def test_adjust_to_world_scales_accum(self):
        s = Strategy(mesh=MeshPlan(data=4, fsdp=1, tensor=2),
                     grad_accum_steps=2)
        # 8 devices -> 4: dp halves, accum doubles => global batch fixed
        s2 = s.adjust_to_world(4, prev_num_devices=8)
        assert s2.mesh.dp_degree == 2
        assert s2.grad_accum_steps == 4


class TestAutoTune:
    def test_dryrun_reports_metrics(self):
        from dlrover_tpu.parallel.auto_tune import dryrun

        result = accelerate(
            _mlp_init, _mlp_loss, optax.adam(1e-2), _batch(),
            strategy=Strategy(mesh=MeshPlan(data=4, fsdp=2)),
        )
        report = dryrun(result, _batch(), profile_steps=2)
        assert report.ok
        assert report.step_time_s > 0
        assert report.compile_time_s > 0

    def test_search_picks_a_viable_mesh(self):
        from dlrover_tpu.parallel.auto_tune import search_strategy

        best, reports = search_strategy(
            _mlp_init, _mlp_loss, optax.adam(1e-2), _batch(),
            candidates=[
                MeshPlan(data=8), MeshPlan(data=4, fsdp=2),
                MeshPlan(data=2, fsdp=2, tensor=2),
            ],
            profile_steps=1,
        )
        assert best.mesh.resolve(8)
        assert sum(r.ok for r in reports) >= 1

    def test_planner_prior_orders_the_measured_budget(self):
        """With a ModelSpec, the analytic planner decides WHICH
        candidates get the limited dryrun compiles: the measured pool
        must be the planner's top picks, not enumeration order."""
        from dlrover_tpu.parallel import planner
        from dlrover_tpu.parallel.auto_tune import search_strategy

        spec = planner.ModelSpec(
            param_count=1_000_000, num_layers=2, hidden_size=64,
            seq_len=32, global_batch=32,
        )
        # enumeration puts tensor-heavy plans FIRST: without the prior,
        # max_candidates=1 would measure tensor=8 only
        cands = [MeshPlan(tensor=8), MeshPlan(data=2, tensor=4),
                 MeshPlan(data=8)]
        best, reports = search_strategy(
            _mlp_init, _mlp_loss, optax.adam(1e-2), _batch(),
            candidates=cands,
            profile_steps=1,
            max_candidates=1,
            model_spec=spec,
        )
        # the single measured candidate must be the planner's own top
        # pick (wiring check: ordering applied before the truncation)
        assert len(reports) == 1
        scored = [planner.estimate(p, spec) for p in cands]
        expected = sorted(
            scored, key=lambda s: (not s.fits, s.step_time_s)
        )[0].plan
        assert best.mesh.axis_sizes() == expected.axis_sizes()
        # and it is NOT simply the first enumerated candidate
        assert best.mesh.axis_sizes() != cands[0].axis_sizes()


class TestPutGlobalBatch:
    """put_global_batch: fully-addressable shardings stay on device_put;
    the multi-host assembly path validates its process-local row
    contract loudly."""

    def test_fully_addressable_device_put(self):
        from dlrover_tpu.parallel.accelerate import put_global_batch
        from dlrover_tpu.parallel.sharding_rules import batch_sharding

        mesh = MeshPlan(data=4, fsdp=2).build()
        spec = batch_sharding(mesh)
        out = put_global_batch({"x": jnp.ones((8, 4))}, spec,
                               global_rows=8)
        # pinned to the REQUESTED sharding, not merely any placement
        assert out["x"].sharding == spec
        assert out["x"].shape == (8, 4)

    def test_non_addressable_wrong_rows_raises(self):
        from dlrover_tpu.parallel.accelerate import put_global_batch

        class StubSharding:
            is_fully_addressable = False

        with pytest.raises(ValueError, match="PROCESS-LOCAL rows"):
            put_global_batch(
                {"x": jnp.ones((8, 4))}, StubSharding(), global_rows=4
            )
