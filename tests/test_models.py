"""Model family tests on the 8-device CPU mesh through accelerate()."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import deepfm, gpt2, llama, mnist_cnn
from dlrover_tpu.parallel.accelerate import accelerate
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy


def _lm_batch(b=4, s=32, vocab=256, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, size=(b, s + 1))
    return {
        "input_ids": jnp.asarray(ids[:, :-1]),
        "labels": jnp.asarray(ids[:, 1:]),
    }


class TestLlama:
    def test_forward_shapes(self):
        cfg = llama.llama_tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        logits, aux = llama.apply(
            params, jnp.zeros((2, 16), jnp.int32), cfg
        )
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_causality(self):
        """Changing a future token must not affect past logits."""
        cfg = llama.llama_tiny(remat_policy="none")
        params = llama.init(jax.random.PRNGKey(0), cfg)
        ids = jnp.zeros((1, 16), jnp.int32)
        ids2 = ids.at[0, 10].set(7)
        l1, _ = llama.apply(params, ids, cfg)
        l2, _ = llama.apply(params, ids2, cfg)
        np.testing.assert_allclose(l1[0, :10], l2[0, :10], atol=1e-5)
        assert not np.allclose(l1[0, 10:], l2[0, 10:], atol=1e-5)

    def test_packed_segments_equal_separate_documents(self):
        """The packed-sequence contract end to end through the model:
        two documents packed into one row (segment masking + RoPE
        positions restarting per segment) produce EXACTLY the logits
        each document gets in its own row."""
        cfg = llama.llama_tiny(remat_policy="none")
        params = llama.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        doc_a = rng.randint(0, cfg.vocab_size, (1, 10))
        doc_b = rng.randint(0, cfg.vocab_size, (1, 22))

        packed_ids = jnp.asarray(
            np.concatenate([doc_a, doc_b], axis=1))
        seg = jnp.asarray([[0] * 10 + [1] * 22])
        packed, _ = llama.apply(params, packed_ids, cfg, segment_ids=seg)

        alone_a, _ = llama.apply(params, jnp.asarray(doc_a), cfg)
        alone_b, _ = llama.apply(params, jnp.asarray(doc_b), cfg)
        np.testing.assert_allclose(packed[0, :10], alone_a[0],
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(packed[0, 10:], alone_b[0],
                                   atol=2e-5, rtol=2e-5)

    def test_segment_positions(self):
        seg = jnp.asarray([[0, 0, 0, 1, 1, 2, 2, 2]])
        pos = llama.segment_positions(seg)
        np.testing.assert_array_equal(
            np.asarray(pos), [[0, 1, 2, 0, 1, 0, 1, 2]])

    def test_packed_loss_fn_trains(self):
        import optax

        from dlrover_tpu.parallel.accelerate import accelerate
        from dlrover_tpu.parallel.mesh import MeshPlan
        from dlrover_tpu.parallel.strategy import Strategy

        cfg = llama.llama_tiny()
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)))
        seg = jnp.asarray(
            np.sort(rng.randint(0, 3, (4, 32)), axis=1))
        labels = jnp.where(
            jnp.concatenate(
                [seg[:, :-1] == seg[:, 1:],
                 jnp.zeros((4, 1), bool)], axis=1),
            jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1), -100)
        batch = {"input_ids": ids, "labels": labels, "segment_ids": seg}
        result = accelerate(
            llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
            optax.adam(1e-3), batch,
            strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                              rule_set="llama"),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        sb = result.shard_batch(batch)
        losses = []
        for i in range(12):
            state, m = result.train_step(state, sb, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.8

    def test_trains_through_accelerate_tensor_parallel(self):
        cfg = llama.llama_tiny()
        result = accelerate(
            llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
            optax.adamw(1e-3), _lm_batch(),
            strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                              rule_set="llama"),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        batch = result.shard_batch(_lm_batch())
        losses = []
        for i in range(10):
            state, m = result.train_step(state, batch, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_the_step_program_names_its_parts(self):
        """``jax.named_scope`` around forward, backward and optimizer
        (``parallel/accelerate.py``) and around attention and FFN
        (``models/llama.py``): metadata of the step's operations, which
        a profiler trace shows, and nothing the compiler computes."""
        import re

        cfg = llama.llama_tiny()
        result = accelerate(
            llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
            optax.adamw(1e-3), _lm_batch(),
            strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                              rule_set="llama"),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        lowered = result.train_step.lower(
            state, result.shard_batch(_lm_batch()), jax.random.PRNGKey(1))
        # the lowered text, not the compiled one: metadata is no part
        # of the compile cache's key, so a cached executable keeps the
        # names of whichever build compiled it first
        names = set(re.findall(r'loc\("([^"]*)"',
                               lowered.as_text(debug_info=True)))
        parts = {name.split("/")[1] for name in names
                 if name.startswith("jit(train_step)/")}
        assert {"forward", "backward", "optimizer"} <= parts, parts
        # the layer's body is a function of its own (scan + remat),
        # whose names start again at its blocks
        assert any(name.startswith("attention/") for name in names)
        assert any(name.startswith("ffn/") for name in names)
        assert any("rematted_computation/attention/" in name
                   for name in names)

    def test_stacked_params_sharded_on_tensor_axis(self):
        cfg = llama.llama_tiny()
        result = accelerate(
            llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
            optax.adamw(1e-3), _lm_batch(),
            strategy=Strategy(mesh=MeshPlan(data=2, tensor=4),
                              rule_set="llama"),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        qk = state.params["layers"]["q_proj"]["kernel"]  # [2, 64, 64]
        shard = qk.addressable_shards[0].data.shape
        assert shard[2] == qk.shape[2] // 4  # tensor-sharded output dim

    def test_gqa_kv_heads(self):
        cfg = llama.llama_tiny(num_kv_heads=1)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        logits, _ = llama.apply(params, jnp.zeros((1, 8), jnp.int32), cfg)
        assert logits.shape[-1] == cfg.vocab_size

    def test_moe_variant_trains(self):
        cfg = llama.llama_tiny(num_experts=4)
        result = accelerate(
            llama.make_init_fn(cfg), llama.make_loss_fn(cfg),
            optax.adamw(1e-3), _lm_batch(b=8),
            strategy=Strategy(mesh=MeshPlan(data=4, fsdp=2),
                              rule_set="llama"),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        batch = result.shard_batch(_lm_batch(b=8))
        state, m = result.train_step(state, batch, jax.random.PRNGKey(0))
        assert np.isfinite(float(m["loss"]))

    def test_chunked_head_loss_matches_full(self):
        cfg = llama.llama_tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 64)))
        labels = jnp.where(jnp.asarray(rng.rand(2, 64)) < 0.9, ids, -100)
        batch = {"input_ids": ids, "labels": labels}
        key = jax.random.PRNGKey(1)
        full, _ = llama.make_loss_fn(cfg)(params, batch, key)
        chunked, _ = llama.make_loss_fn(cfg, head_chunk=16)(
            params, batch, key
        )
        np.testing.assert_allclose(float(full), float(chunked), rtol=1e-5)
        # gradients agree too (the checkpointed scan recomputes logits)
        gf = jax.grad(lambda p: llama.make_loss_fn(cfg)(p, batch, key)[0])(
            params
        )
        gc = jax.grad(
            lambda p: llama.make_loss_fn(cfg, head_chunk=16)(
                p, batch, key
            )[0]
        )(params)
        np.testing.assert_allclose(
            np.asarray(gf["lm_head"]["kernel"]),
            np.asarray(gc["lm_head"]["kernel"]), atol=1e-5, rtol=1e-4,
        )

    def test_param_count_7b_in_range(self):
        n = llama.param_count(llama.llama2_7b())
        assert 6.5e9 < n < 7.5e9

    def test_param_count_llama3_8b_in_range(self):
        n = llama.param_count(llama.llama3_8b())
        assert 7.8e9 < n < 8.3e9
        cfg = llama.llama3_8b()
        assert cfg.num_heads // cfg.num_kv_heads == 4  # GQA group of 4

    def test_param_count_llama3_70b_in_range(self):
        n = llama.param_count(llama.llama3_70b())
        assert 69e9 < n < 72e9
        cfg = llama.llama3_70b()
        assert cfg.num_heads // cfg.num_kv_heads == 8


class TestGPT2:
    def test_forward_and_tied_head(self):
        cfg = gpt2.gpt2_tiny()
        params = gpt2.init(jax.random.PRNGKey(0), cfg)
        logits = gpt2.apply(params, jnp.zeros((2, 16), jnp.int32), cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert "lm_head" not in params  # tied to embed_tokens

    def test_trains_through_accelerate(self):
        cfg = gpt2.gpt2_tiny()
        result = accelerate(
            gpt2.make_init_fn(cfg), gpt2.make_loss_fn(cfg),
            optax.adamw(1e-3), _lm_batch(b=8),
            strategy=Strategy(mesh=MeshPlan(data=4, fsdp=2)),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        batch = result.shard_batch(_lm_batch(b=8))
        losses = []
        for i in range(8):
            state, m = result.train_step(state, batch, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]


class TestMnist:
    def test_trains(self):
        rng = np.random.RandomState(0)
        batch = {
            "image": jnp.asarray(rng.randn(16, 28, 28, 1), jnp.float32),
            "label": jnp.asarray(rng.randint(0, 10, (16,))),
        }
        result = accelerate(
            lambda r: mnist_cnn.init(r), mnist_cnn.make_loss_fn(),
            optax.adam(1e-3), batch,
            strategy=Strategy(mesh=MeshPlan(data=8)),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        b = result.shard_batch(batch)
        losses = []
        for i in range(10):
            state, m = result.train_step(state, b, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]


class TestDeepFM:
    def test_trains(self):
        cfg = deepfm.deepfm_tiny()
        rng = np.random.RandomState(0)
        batch = {
            "sparse": jnp.asarray(
                rng.randint(0, cfg.vocab_size, (32, cfg.num_sparse_features))
            ),
            "dense": jnp.asarray(
                rng.rand(32, cfg.num_dense_features), jnp.float32
            ),
            "label": jnp.asarray(rng.randint(0, 2, (32,))),
        }
        result = accelerate(
            deepfm.make_init_fn(cfg), deepfm.make_loss_fn(cfg),
            optax.adagrad(0.05), batch,
            strategy=Strategy(mesh=MeshPlan(data=4, fsdp=2)),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        b = result.shard_batch(batch)
        losses = []
        for i in range(15):
            state, m = result.train_step(state, b, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_embedding_sharded_on_fsdp(self):
        cfg = deepfm.deepfm_tiny()
        rng = np.random.RandomState(0)
        batch = {
            "sparse": jnp.asarray(rng.randint(0, 128, (8, 4))),
            "dense": jnp.asarray(rng.rand(8, 3), jnp.float32),
            "label": jnp.asarray(rng.randint(0, 2, (8,))),
        }
        result = accelerate(
            deepfm.make_init_fn(cfg), deepfm.make_loss_fn(cfg),
            optax.adam(1e-3), batch,
            strategy=Strategy(mesh=MeshPlan(data=1, fsdp=8)),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        table = state.params["embedding"]["table"]  # [128, 8]
        assert table.addressable_shards[0].data.shape[0] == 16


class TestGPT2Pipelined:
    """GPT-2 joins the pipelined decoder families (shared
    dispatch_pipeline formulation; tied head spread over pipe)."""

    def test_pipelined_matches_apply(self):
        cfg = gpt2.gpt2_tiny(num_layers=4)
        params = gpt2.init(jax.random.PRNGKey(0), cfg)
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 16))
        )
        plain = gpt2.apply(params, ids, cfg)
        piped = gpt2.apply_pipelined(
            params, ids, cfg, num_stages=2, num_microbatches=2
        )
        np.testing.assert_allclose(np.asarray(piped), np.asarray(plain),
                                   rtol=2e-4, atol=2e-4)

    def test_uneven_interleaved_matches_apply(self):
        cfg = gpt2.gpt2_tiny(num_layers=6)
        params = gpt2.init(jax.random.PRNGKey(0), cfg)
        ids = jnp.asarray(
            np.random.RandomState(1).randint(0, cfg.vocab_size, (4, 16))
        )
        plain = gpt2.apply(params, ids, cfg)
        piped = gpt2.apply_pipelined(
            params, ids, cfg, num_stages=2, num_microbatches=2,
            num_virtual=2, stage_depths=(2, 1, 2, 1),
        )
        np.testing.assert_allclose(np.asarray(piped), np.asarray(plain),
                                   rtol=2e-4, atol=2e-4)

    # budget triage (PR 16): pp-rule composition stays pinned tier-1 by
    # the llama/neox/glm pipelined tests and gpt2's apply-level parity;
    # this trains run rides slow
    @pytest.mark.slow
    def test_trains_with_gpt2_pp_rules_on_mesh(self):
        import optax

        from dlrover_tpu.parallel.accelerate import accelerate
        from dlrover_tpu.parallel.mesh import MeshPlan
        from dlrover_tpu.parallel.strategy import Strategy

        cfg = gpt2.gpt2_tiny(num_layers=4)

        def loss_fn(params, batch, rng):
            from dlrover_tpu.models.losses import masked_lm_loss

            logits = gpt2.apply_pipelined(
                params, batch["input_ids"], cfg,
                num_stages=2, num_microbatches=2,
            )
            return masked_lm_loss(logits, batch["labels"]), {}

        batch = {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size
            ),
            "labels": jax.random.randint(
                jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
            ),
        }
        strategy = Strategy(
            mesh=MeshPlan(pipe=2, data=2, tensor=2), rule_set="gpt2_pp"
        )
        result = accelerate(
            gpt2.make_init_fn(cfg), loss_fn,
            optax.adam(1e-2), batch, strategy=strategy,
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        sharded = result.shard_batch(batch)
        losses = []
        for i in range(3):
            state, metrics = result.train_step(
                state, sharded, jax.random.PRNGKey(i)
            )
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
