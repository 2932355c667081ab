"""Train a latent-attention decoder with shared and routed gated
experts (A.X-K1's architecture: MLA in every layer, a leading dense
layer, then one shared expert plus the routed experts held here under a
sigmoid top-k router; ``dlrover_tpu/models/mla_moe.py``) elastically.

    # 8 virtual CPU devices, tiny model
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/train_mla_moe.py --steps 20

    # under the elastic launcher
    python -m dlrover_tpu.trainer.run --standalone --nnodes 1 \\
        examples/train_mla_moe.py --steps 20 --ckpt_dir /tmp/mla_moe_ckpt

What ``examples/train_llama.py`` is for ``models/llama.py``: the same
worker lines (``worker``, ``start``, ``step``), written by that file's
own code.
"""

import argparse
import os

import jax
import jax.numpy as jnp
import optax
from train_llama import StepLines, emit, synthetic_batches

from dlrover_tpu.checkpoint import CheckpointInterval
from dlrover_tpu.models import mla_moe
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.trainer.bootstrap import init_worker
from dlrover_tpu.trainer.conf import build_configuration
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.executor import TrainExecutor


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="tiny", choices=["tiny", "axk1"])
    p.add_argument("--layers", type=int, default=0,
                   help="depth, the leading dense layer among them "
                        "(0 = the preset's)")
    p.add_argument("--experts_held", type=int, default=0,
                   help="hold the first N of the routed experts and "
                        "leave out what the others would add (0 = all)")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--head_chunk", type=int, default=0)
    p.add_argument("--ckpt_dir", default="")
    p.add_argument("--ckpt_every", type=int, default=0)
    args = p.parse_args(argv)

    worker = init_worker()
    n, device = jax.device_count(), jax.devices()[0]
    depth = {"num_layers": args.layers} if args.layers else {}
    if args.experts_held:
        depth["experts_held"] = tuple(range(args.experts_held))
    config = (mla_moe.mla_moe_tiny(**depth) if args.preset == "tiny"
              else mla_moe.MlaMoeConfig(param_dtype=jnp.bfloat16, **depth))
    batches = synthetic_batches(config.vocab_size, args.batch,
                                config.max_seq_len)
    emit("worker", pid=os.getpid(), restart_round=worker.restart_round,
         platform=device.platform, device_kind=device.device_kind,
         device_count=n, params=mla_moe.param_count(config),
         layers=config.num_layers, batch=args.batch,
         layer_kinds=mla_moe.layer_kinds(config))
    trainer = ElasticTrainer(
        mla_moe.make_init_fn(config),
        mla_moe.make_loss_fn(config, head_chunk=args.head_chunk),
        optax.adafactor(1e-3), next(batches()),
        strategy=Strategy(
            mesh=MeshPlan(data=-1, fsdp=2 if n >= 4 else 1),
            rule_set="mla_moe",
            remat_policy="",  # the model remats per layer itself
        ),
        ckpt_dir=args.ckpt_dir,
        ckpt_interval=(CheckpointInterval(steps=args.ckpt_every)
                       if args.ckpt_every else None),
        master_client=worker.master_client,
    )
    out = TrainExecutor(
        trainer, train_iter_fn=batches, hooks=[StepLines()],
        conf=build_configuration({"train_steps": args.steps,
                                  "log_every_steps": 10}),
        master_client=worker.master_client,
    ).train_and_evaluate()
    print(f"finished at step {out['step']} "
          f"({mla_moe.param_count(config) / 1e6:.1f}M params, {n} devices)")


if __name__ == "__main__":
    main()
