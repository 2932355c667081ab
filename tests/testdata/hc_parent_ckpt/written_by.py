"""What wrote this directory, run once from a checkout of commit 3376161
(the tree whose scans carried the streams as ``[B, S, n, C]``)::

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python \\
        tests/testdata/hc_parent_ckpt/written_by.py <out>

``ckpt/``: the toy four-stream configuration's parameters through the
checkpoint manager. ``hidden.npz``: the ids, and for 1 and 20 Sinkhorn
iterations what that tree's ``apply_all_hidden`` and loss function gave
on them (``iters<k>``; ``iters<k>_loss``: loss, ``hc_res_defect``,
``mtp_loss``). ``tests/test_hyper_connections.py`` restores it."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

TOY = dict(experts_held=tuple(range(8)), hc_mult=4, router_bias=True,
           mtp_layers=1, num_layers=2, first_k_dense=1, vocab_size=64,
           n_routed_experts=8, hidden_size=32, intermediate_size=64,
           moe_intermediate_size=16, num_heads=2, q_lora_rank=24,
           kv_lora_rank=16, param_dtype=jnp.float32,
           compute_dtype=jnp.float32)


def main(out):
    from dlrover_tpu.checkpoint.manager import ElasticCheckpointManager
    from dlrover_tpu.models import mla_moe
    from dlrover_tpu.telemetry.names import StepCounter

    ids = np.random.default_rng(7).integers(0, 64, (2, 33)).astype(np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    params = mla_moe.init(jax.random.PRNGKey(7), mla_moe.mla_moe_tiny(**TOY))
    gave = {"ids": ids}
    for iters in (1, 20):
        config = mla_moe.mla_moe_tiny(hc_sinkhorn_iters=iters, **TOY)
        gave[f"iters{iters}"] = np.asarray(mla_moe.apply_all_hidden(
            params, batch["input_ids"], batch["labels"], config))
        loss, aux = mla_moe.make_loss_fn(config, head_chunk=16)(
            params, batch, None)
        gave[f"iters{iters}_loss"] = np.asarray(
            [loss, aux[StepCounter.HC_RES_DEFECT],
             aux[StepCounter.MTP_LOSS]], np.float32)
    mgr = ElasticCheckpointManager(os.path.join(out, "ckpt"),
                                   async_save=False, staging_dir="")
    assert mgr.save(1, params, force=True)
    mgr.wait()
    mgr.close()
    np.savez_compressed(os.path.join(out, "hidden.npz"), **gave)


if __name__ == "__main__":
    main(sys.argv[1])
