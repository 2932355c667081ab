"""BERT and CLIP model families: shapes, gradients, training, sharding."""

import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import bert, clip
from dlrover_tpu.parallel.accelerate import accelerate
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

_CLIP_TRAINING = """
import json
import jax, jax.numpy as jnp, numpy as np, optax
from dlrover_tpu.models import clip
from dlrover_tpu.parallel.accelerate import accelerate
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy

cfg = clip.clip_tiny()
rng = np.random.RandomState(0)
ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))
pix = jnp.asarray(rng.rand(8, 32, 32, 3), jnp.float32)
batch = {{"input_ids": ids, "pixel_values": pix}}
result = accelerate(
    clip.make_init_fn(cfg), clip.make_loss_fn(cfg),
    optax.adam(3e-3), batch,
    strategy=Strategy(mesh=MeshPlan({mesh}), rule_set="clip"),
)
state = result.init_fn(jax.random.PRNGKey(0))
sb = result.shard_batch(batch)
losses = []
for i in range(40):
    state, m = result.train_step(state, sb, jax.random.PRNGKey(i))
    losses.append(float(m["loss"]))
print(json.dumps(losses))
"""


class TestBert:
    def test_forward_shapes(self):
        cfg = bert.bert_tiny()
        params = bert.init(jax.random.PRNGKey(0), cfg)
        ids = jnp.zeros((2, 16), jnp.int32)
        seq, pooled = bert.apply(params, ids, cfg)
        assert seq.shape == (2, 16, cfg.hidden_size)
        assert pooled.shape == (2, cfg.hidden_size)
        logits = bert.apply_mlm(params, ids, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_attention_mask_changes_output(self):
        cfg = bert.bert_tiny()
        params = bert.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 8)))
        full, _ = bert.apply(params, ids, cfg)
        mask = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]] * 2)
        masked, _ = bert.apply(params, ids, cfg, attention_mask=mask)
        assert not np.allclose(np.asarray(full[:, 0]),
                               np.asarray(masked[:, 0]))

    # budget triage (PR 16): bert forward/masking are pinned by the
    # cheaper parity units; convergence representatives (llama/gpt2)
    # stay tier-1 — this overfit run rides slow
    @pytest.mark.slow
    def test_mlm_overfits_tiny_batch(self):
        cfg = bert.bert_tiny()
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 16)))
        labels = jnp.where(
            jnp.asarray(rng.rand(4, 16)) < 0.3, ids, -100
        )
        batch = {"input_ids": ids, "labels": labels}
        result = accelerate(
            bert.make_init_fn(cfg), bert.make_mlm_loss_fn(cfg),
            optax.adam(1e-3), batch,
            strategy=Strategy(mesh=MeshPlan(data=2, fsdp=2, tensor=2),
                              rule_set="bert"),
        )
        state = result.init_fn(jax.random.PRNGKey(0))
        sb = result.shard_batch(batch)
        losses = []
        for i in range(15):
            state, m = result.train_step(state, sb, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.7

    def test_param_count(self):
        assert bert.param_count(bert.bert_tiny()) > 0

    def test_bf16_compute_with_f32_params(self):
        # the production default: f32 params, bf16 compute — the scan
        # carry dtype must stay stable through the norms
        cfg = bert.bert_tiny(compute_dtype=jnp.bfloat16)
        params = bert.init(jax.random.PRNGKey(0), cfg)
        seq, _ = bert.apply(params, jnp.zeros((2, 8), jnp.int32), cfg)
        assert seq.dtype == jnp.bfloat16

    def test_clip_bf16_compute_with_f32_params(self):
        cfg = clip.clip_tiny(compute_dtype=jnp.bfloat16)
        params = clip.init(jax.random.PRNGKey(0), cfg)
        out = clip.encode_text(
            params, jnp.zeros((2, 8), jnp.int32), cfg
        )
        assert out.shape == (2, cfg.projection_dim)


class TestClip:
    def test_encoders_normalized(self):
        cfg = clip.clip_tiny()
        params = clip.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (3, 16)))
        pix = jnp.asarray(rng.rand(3, 32, 32, 3), jnp.float32)
        t = clip.encode_text(params, ids, cfg)
        v = clip.encode_image(params, pix, cfg)
        assert t.shape == (3, cfg.projection_dim)
        assert v.shape == (3, cfg.projection_dim)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(t), axis=-1), 1.0, rtol=1e-5
        )
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(v), axis=-1), 1.0, rtol=1e-5
        )

    def test_patchify_roundtrip_count(self):
        x = jnp.arange(2 * 32 * 32 * 3, dtype=jnp.float32).reshape(
            2, 32, 32, 3
        )
        patches = clip._patchify(x, 8)
        assert patches.shape == (2, 16, 8 * 8 * 3)

    @pytest.mark.slow  # PR 13 triage: an 11 s convergence loop — the
    # CLIP forward/loss contracts stay tier-1 via the encoder/metric
    # tests above and below
    @pytest.mark.parametrize("mesh, may_deadlock", [
        ("data=2, fsdp=2, tensor=2", True),
        ("fsdp=4, tensor=2", False),
    ], ids=["data2-fsdp2-tensor2", "fsdp4-tensor2"])
    def test_contrastive_training_aligns_pairs(self, mesh, may_deadlock):
        """In a process of its own, because XLA:CPU can end it. Its
        executor orders the collectives of one thunk sequence among
        themselves, but a ``while`` loop against nothing: a collective
        outside a loop and the collectives inside an independent loop
        are entered by each virtual device in the order its threads
        reach them. CLIP's two towers are such a pair (one tower's
        final-norm all-reduce over ``fsdp`` beside the other tower's
        scan over layers), and on three axes, since ISSUE 27 put
        ``fsdp`` on the kernels' hidden axis, five runs of six end with
        the in-process rendezvous aborting the process in step 1 (the
        layout before it: none of six; depth-first scheduling cures this
        program and deadlocks GPT-2's). A chip runs one stream in
        program order: four v5e chips train this model under ``fsdp=2 x
        tensor=2``, ``fsdp=4`` and ``data=2 x fsdp=2`` (PERF.md, PR 27).
        So on three axes a rendezvous abort is an expected failure."""
        child = subprocess.run(
            [sys.executable, "-c", _CLIP_TRAINING.format(mesh=mesh)],
            env={**os.environ, "XLA_FLAGS": os.environ["XLA_FLAGS"]
                 + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=10"
                 + " --xla_cpu_collective_call_terminate_timeout_seconds=20"},
            capture_output=True, text=True, timeout=300)
        if (may_deadlock and child.returncode == -signal.SIGABRT
                and "rendezvous" in child.stderr):
            pytest.xfail("XLA:CPU entered the two towers' collectives in "
                         "different orders on different virtual devices")
        assert child.returncode == 0, child.stderr[-2000:]
        losses = json.loads(child.stdout.splitlines()[-1])
        assert len(losses) == 40 and losses[-1] < losses[0] * 0.5

    def test_loss_metrics(self):
        cfg = clip.clip_tiny()
        params = clip.init(jax.random.PRNGKey(0), cfg)
        emb = jnp.eye(4, cfg.projection_dim)
        emb = emb / jnp.linalg.norm(emb, axis=-1, keepdims=True)
        loss, aux = clip.contrastive_loss(params, emb, emb)
        # identical aligned embeddings: accuracy 1
        assert float(aux["accuracy"]) == 1.0


class TestShardingRules:
    def test_bert_rules_bind_tensor_axis(self):
        from dlrover_tpu.parallel.sharding_rules import bert_rules

        mesh_sizes = {"fsdp": 2, "tensor": 2}
        rules = bert_rules()
        spec = rules.spec_for("layers/q_proj/kernel", (4, 32, 32),
                              mesh_sizes)
        assert spec == (None, "fsdp", "tensor")
        spec = rules.spec_for("embeddings/word/embedding", (128, 32),
                              mesh_sizes)
        assert spec == ("tensor", "fsdp")

    def test_clip_paths_bind_under_towers(self):
        from dlrover_tpu.parallel.sharding_rules import clip_rules

        spec = clip_rules().spec_for(
            "text/layers/q_proj/kernel", (2, 32, 32),
            {"fsdp": 2, "tensor": 2},
        )
        assert spec == (None, "fsdp", "tensor")


class TestBertPipelined:
    """BERT joins the pipelined families: the [B, S] attention mask
    rides the pipeline state beside its microbatch (GLM-prefix
    discipline), encoder blocks as GPipe/interleaved stages."""

    def test_pipelined_matches_apply_with_mask(self):
        cfg = bert.bert_tiny(num_layers=4)
        params = bert.init(jax.random.PRNGKey(0), cfg)
        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 16))
        )
        # per-example masks differ so each microbatch carries its own
        mask = jnp.asarray(
            np.random.RandomState(1).randint(0, 2, (4, 16)).astype(np.int32)
        ).at[:, 0].set(1)
        seq, pooled = bert.apply(params, ids, cfg, attention_mask=mask)
        seq_p, pooled_p = bert.apply_pipelined(
            params, ids, cfg, num_stages=2, num_microbatches=2,
            attention_mask=mask,
        )
        np.testing.assert_allclose(np.asarray(seq_p), np.asarray(seq),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(pooled_p), np.asarray(pooled),
                                   rtol=2e-4, atol=2e-4)

    def test_uneven_interleaved_matches_apply(self):
        cfg = bert.bert_tiny(num_layers=6)
        params = bert.init(jax.random.PRNGKey(0), cfg)
        ids = jnp.asarray(
            np.random.RandomState(2).randint(0, cfg.vocab_size, (4, 16))
        )
        seq, _ = bert.apply(params, ids, cfg)
        seq_p, _ = bert.apply_pipelined(
            params, ids, cfg, num_stages=2, num_microbatches=2,
            num_virtual=2, stage_depths=(1, 2, 1, 2),
        )
        np.testing.assert_allclose(np.asarray(seq_p), np.asarray(seq),
                                   rtol=2e-4, atol=2e-4)

    # budget triage (PR 16): the pipeline engine is model-agnostic and
    # stays pinned tier-1 by the llama/neox/glm pp tests; bert's mask
    # plumbing by its apply-level parity — this trains run rides slow
    @pytest.mark.slow
    def test_trains_with_bert_pp_rules_on_mesh(self):
        from dlrover_tpu.models.losses import masked_lm_loss

        cfg = bert.bert_tiny(num_layers=4)

        def loss_fn(params, batch, rng):
            seq, _ = bert.apply_pipelined(
                params, batch["input_ids"], cfg,
                num_stages=2, num_microbatches=2,
            )
            logits = seq @ params["mlm_head"]["kernel"].astype(seq.dtype) \
                + params["mlm_head"]["bias"].astype(seq.dtype)
            return masked_lm_loss(logits.astype(jnp.float32),
                                  batch["labels"]), {}

        batch = {
            "input_ids": jax.random.randint(
                jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size
            ),
            "labels": jax.random.randint(
                jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
            ),
        }
        strategy = Strategy(
            mesh=MeshPlan(pipe=2, data=2, tensor=2), rule_set="bert_pp"
        )
        result = accelerate(
            bert.make_init_fn(cfg), loss_fn,
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
