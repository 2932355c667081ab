"""Train a SambaY decoder (Phi-4-mini-flash's architecture: Mamba
layers, window, full and cross differential attention, gated memory
units; ``dlrover_tpu/models/sambay.py``) elastically.

    # 8 virtual CPU devices, tiny model
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/train_sambay.py --steps 20

    # under the elastic launcher
    python -m dlrover_tpu.trainer.run --standalone --nnodes 1 \\
        examples/train_sambay.py --steps 20 --ckpt_dir /tmp/sambay_ckpt

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
from dlrover_tpu.models import sambay
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.trainer.bootstrap import init_worker
from dlrover_tpu.trainer.conf import build_configuration
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.executor import TrainExecutor


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="tiny", choices=["tiny", "phi4flash"])
    p.add_argument("--layers", type=int, default=0,
                   help="depth (a multiple of 4, at least 8; 0 = the "
                        "preset's)")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--head_chunk", type=int, default=0)
    p.add_argument("--ckpt_dir", default="")
    p.add_argument("--ckpt_every", type=int, default=0)
    args = p.parse_args(argv)

    worker = init_worker()
    n, device = jax.device_count(), jax.devices()[0]
    depth = {"num_layers": args.layers} if args.layers else {}
    config = (sambay.sambay_tiny(**depth) if args.preset == "tiny"
              else sambay.SambaYConfig(param_dtype=jnp.bfloat16, **depth))
    batches = synthetic_batches(config.vocab_size, args.batch,
                                config.max_seq_len)
    emit("worker", pid=os.getpid(), restart_round=worker.restart_round,
         platform=device.platform, device_kind=device.device_kind,
         device_count=n, params=sambay.param_count(config),
         layers=config.num_layers, batch=args.batch,
         layer_kinds=sambay.layer_kinds(config))
    trainer = ElasticTrainer(
        sambay.make_init_fn(config),
        sambay.make_loss_fn(config, head_chunk=args.head_chunk),
        optax.adafactor(1e-3), next(batches()),
        strategy=Strategy(
            mesh=MeshPlan(data=-1, fsdp=2 if n >= 4 else 1),
            rule_set="sambay",
            remat_policy="",  # the model remats per period itself
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
          f"({sambay.param_count(config) / 1e6:.1f}M params, {n} devices)")


if __name__ == "__main__":
    main()
