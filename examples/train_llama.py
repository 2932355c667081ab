"""Pretrain a Llama-family model elastically.

Run standalone on any host (CPU mesh for a smoke test, TPU in prod):

    # 8 virtual CPU devices, tiny model
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/train_llama.py --preset tiny --steps 20

    # under the elastic launcher (master-backed rendezvous, failover)
    python -m dlrover_tpu.trainer.run --standalone --nnodes 1 \\
        examples/train_llama.py --preset tiny --steps 20

One process drives all of a host's chips. Under the launcher the worker
connects to the job master through the agent's environment; after a
kill the agent restarts it and it resumes from ``--ckpt_dir``.

The worker writes one JSON object per line on stdout for whoever
supervises it (``chip_smoke.py``): ``{"event": "worker", ...}`` with
the device facts, ``{"event": "start", ...}`` with the step it resumed
from, and ``{"event": "step", ...}`` after each step has completed on
the device.

Role parity: the reference's ``examples/pytorch/llama2`` training scripts.
"""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.checkpoint import CheckpointInterval
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.models import llama
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.trainer.bootstrap import init_worker
from dlrover_tpu.trainer.conf import build_configuration
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.executor import TrainExecutor, TrainHook
from dlrover_tpu.utils.compile_cache import cache_traffic

_T_BOOT = time.time()


def emit(event, **fields):
    print(json.dumps({"event": event, "t": round(time.time(), 3),
                      **fields}), flush=True)


def synthetic_batches(vocab_size, batch, seq, seed=0):
    rng = np.random.RandomState(seed)

    def gen():
        while True:
            ids = rng.randint(0, vocab_size, size=(batch, seq + 1))
            yield {
                "input_ids": jnp.asarray(ids[:, :-1]),
                "labels": jnp.asarray(ids[:, 1:]),
            }

    return gen


class StepLines(TrainHook):
    """One JSON line per optimizer step, written once the step's metrics
    have reached the host — i.e. after the device finished it. The
    first step's ``seconds`` run from the start of training and so
    carry trace + compile (+ restore); later ones are the time between
    two completed steps."""

    def begin(self, executor):
        self._last = time.time()
        emit("start", resumed_step=int(executor.state.step),
             boot_seconds=round(self._last - _T_BOOT, 3))

    def after_step(self, step, metrics):
        now = time.time()
        cache = cache_traffic()
        mem = jax.local_devices()[0].memory_stats() or {}
        emit("step", step=int(step), loss=float(metrics["loss"]),
             seconds=round(now - self._last, 4),
             peak_bytes_in_use=mem.get("peak_bytes_in_use"),
             cache_hits=cache["hits"], cache_misses=cache["misses"])
        self._last = now


def build_parser():
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="tiny", choices=["tiny", "1b", "7b"])
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=0, help="0 = preset default")
    p.add_argument("--layers", type=int, default=0,
                   help="override the preset's layer count (e.g. 6 for "
                        "an uneven --pipe 2 --pipe_virtual 2 demo)")
    p.add_argument("--param_dtype", default="",
                   choices=["", "float32", "bfloat16"],
                   help="parameter dtype ('' = the preset's)")
    p.add_argument("--optimizer", default="adamw",
                   choices=["adamw", "adafactor"],
                   help="adamw keeps two f32 moments per parameter; "
                        "adafactor's factored second moment is what "
                        "fits a multi-billion model on one 16 GB chip")
    p.add_argument("--remat", default="",
                   help="per-layer remat policy ('' = the preset's; "
                        "none | full | dots_saveable | ...)")
    p.add_argument("--head_chunk", type=int, default=0,
                   help="fuse the lm head with the loss over sequence "
                        "chunks of this many tokens (0 = whole-sequence "
                        "logits)")
    p.add_argument("--ckpt_dir", default="")
    p.add_argument("--ckpt_every", type=int, default=0,
                   help="checkpoint every N steps (0 = only the final "
                        "save)")
    p.add_argument("--moe_experts", type=int, default=0)
    p.add_argument("--ring", type=int, default=0,
                   help="sequence-parallel ring size (long context): "
                        "adds a 'seq' mesh axis and runs ring "
                        "attention, e.g. --ring 2 --seq 512 on the "
                        "8-device CPU mesh")
    p.add_argument("--pipe", type=int, default=0,
                   help="pipeline stages: adds a 'pipe' mesh axis and "
                        "runs the decoder as a GPipe/interleaved "
                        "pipeline, e.g. --pipe 2 on the 8-device mesh. "
                        "NB: the pipelined MoE loss does not surface "
                        "the per-step load-balance metrics the plain "
                        "path reports (apply_pipelined has no metrics "
                        "output)")
    p.add_argument("--pipe_virtual", type=int, default=1,
                   help="virtual stages per physical stage (V>1 = "
                        "circular interleaved schedule)")
    p.add_argument("--pipe_depths", default="",
                   help="comma-separated per-chunk layer counts in "
                        "visit order (uneven stage split; default: "
                        "planner-balanced via plan_stage_depths)")
    return p


def model_config(args):
    """(LlamaConfig, sequence length) for the parsed arguments."""
    kw = {"num_experts": args.moe_experts}
    if args.layers:
        kw["num_layers"] = args.layers
    if args.param_dtype:
        kw["param_dtype"] = jnp.dtype(args.param_dtype)
    if args.remat:
        kw["remat_policy"] = args.remat
    if args.preset == "tiny":
        return llama.llama_tiny(**kw), args.seq or 128
    if args.preset == "1b":
        kw = {"param_dtype": jnp.bfloat16, "num_layers": 16, **kw}
        return llama.llama2_7b(
            hidden_size=2048, intermediate_size=5504,
            num_heads=16, num_kv_heads=16,
            compute_dtype=jnp.bfloat16, **kw,
        ), args.seq or 2048
    return llama.llama2_7b(**kw), args.seq or 4096


def build_job(args, plan):
    """Everything ``ElasticTrainer`` needs for the parsed arguments on
    the mesh ``plan``: (config, strategy, loss_fn, optimizer, batches).
    ``chip_smoke.py --chips 4`` builds the same job under its own plan."""
    config, seq = model_config(args)
    ring, pipe = max(1, args.ring), max(1, args.pipe)
    if ring > 1:
        # long context: the model runs ring attention over the "seq"
        # axis. Only the AXIS NAME goes on the config — the mesh itself
        # is picked up ambiently from whatever accelerate builds, so an
        # elastic world change (which re-runs accelerate over the new
        # devices) keeps working.
        from dataclasses import replace

        config = replace(config, seq_axis="seq")
    stage_depths = None
    if pipe > 1:
        if args.pipe_depths:
            stage_depths = tuple(
                int(d) for d in args.pipe_depths.split(",")
            )
        elif config.num_layers % (args.pipe_virtual * pipe):
            # indivisible layer count: planner-balanced uneven split
            from dlrover_tpu.parallel.planner import plan_stage_depths

            stage_depths = plan_stage_depths(
                [1.0] * config.num_layers, pipe, args.pipe_virtual
            )
    strategy = Strategy(
        mesh=plan,
        # llama_pp carries both the pipe-leading layer rules and the
        # expert submesh rules, so pipelined MoE resolves to it too
        rule_set=("llama_pp" if pipe > 1
                  else ("moe" if args.moe_experts else "llama")),
        remat_policy="",  # the model remats per layer internally
        num_virtual=args.pipe_virtual,
        stage_depths=stage_depths,
    )
    if pipe > 1:
        from dlrover_tpu.models.losses import masked_lm_loss

        num_mb = 2 * pipe

        def loss_fn(params, batch, rng):
            logits, aux = llama.apply_pipelined(
                params, batch["input_ids"], config,
                num_stages=pipe, num_microbatches=num_mb, rng=rng,
                num_virtual=strategy.num_virtual,
                stage_depths=strategy.stage_depths,
            )
            loss = masked_lm_loss(logits, batch["labels"])
            if config.num_experts > 0:
                # aux sums over microbatches as well as layers
                loss = loss + config.moe_aux_weight * aux / (
                    max(1, config.num_layers) * num_mb
                )
            return loss, {}
    else:
        loss_fn = llama.make_loss_fn(config, head_chunk=args.head_chunk)
    optimizer = (optax.adafactor(1e-3) if args.optimizer == "adafactor"
                 else optax.adamw(3e-4, weight_decay=0.1))
    batches = synthetic_batches(config.vocab_size, args.batch, seq)
    return config, strategy, loss_fn, optimizer, batches


def main(argv=None):
    p = build_parser()
    args = p.parse_args(argv)
    if args.pipe and args.ring:
        p.error("--pipe and --ring compose via a custom Strategy; this "
                "example drives one at a time")
    if args.pipe_virtual < 1:
        p.error(f"--pipe_virtual must be >= 1 (got {args.pipe_virtual})")

    # the agent's env contract: the master client, jax.distributed when
    # the job spans processes, and the persistent compile cache BEFORE
    # anything compiles. A bare ``python examples/train_llama.py`` has
    # no master and gets None.
    worker = init_worker()
    n = jax.device_count()
    device = jax.devices()[0]
    ring, pipe = max(1, args.ring), max(1, args.pipe)
    # fsdp only when devices remain after the ring/pipe axes take theirs
    fsdp = 2 if n >= 4 * ring * pipe else 1
    config, strategy, loss_fn, optimizer, batches = build_job(
        args, MeshPlan(data=-1, fsdp=fsdp, seq=ring, pipe=pipe))
    emit("worker", pid=os.getpid(), restart_round=worker.restart_round,
         master_addr=os.environ.get(NodeEnv.MASTER_ADDR, ""),
         platform=device.platform, device_kind=device.device_kind,
         device_count=n,
         bytes_limit=(device.memory_stats() or {}).get("bytes_limit"),
         params=llama.param_count(config),
         layers=config.num_layers, batch=args.batch,
         optimizer=args.optimizer)
    trainer = ElasticTrainer(
        llama.make_init_fn(config),
        loss_fn,
        optimizer,
        next(batches()),
        strategy=strategy,
        ckpt_dir=args.ckpt_dir,
        ckpt_interval=(CheckpointInterval(steps=args.ckpt_every)
                       if args.ckpt_every else None),
        master_client=worker.master_client,
    )
    executor = TrainExecutor(
        trainer,
        train_iter_fn=batches,
        hooks=[StepLines()],
        conf=build_configuration({
            "train_steps": args.steps, "log_every_steps": 10,
        }),
        master_client=worker.master_client,
    )
    out = executor.train_and_evaluate()
    print(f"finished at step {out['step']} "
          f"({llama.param_count(config) / 1e6:.1f}M params, "
          f"{n} devices)")


if __name__ == "__main__":
    main()
