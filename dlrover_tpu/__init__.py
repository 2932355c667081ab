"""dlrover_tpu: a TPU-native elastic distributed-training framework.

A ground-up JAX/XLA rebuild of the capabilities of DLRover (reference:
Major-333/dlrover): per-job master control plane (rendezvous, dynamic data
sharding, auto-scaling, fault diagnosis), per-host elastic agents that
bootstrap ``jax.distributed``, and a GSPMD/``pjit`` parallelism library in
place of DDP/FSDP/TP wrapper stacks.

Package layout:
  common/    shared types: node model, status flow, config, wire messages
  rpc/       codegen-free gRPC transport (JSON-framed dataclass messages)
  master/    per-job master: job manager, rendezvous, sharding, monitors
  agent/     per-host elastic agent: master client, rendezvous handler
  trainer/   user-facing training API (ElasticTrainer, tpurun CLI)
  parallel/  mesh planning, sharding rules, strategy, accelerate API
  ops/       Pallas kernels: flash attention, ring attention, MoE
  models/    model family: llama, gpt2, moe, deepfm, mnist
  checkpoint/ async Orbax elastic checkpointing
  diagnosis/ hang detection, profiling, failure classification
  native/    C++ host-side pieces (shm batch transport)
"""

__version__ = "0.1.0"

import os as _os

if (
    # PRIMARY platform is cpu — not merely present in a fallback spec
    # like "tpu,cpu", where the accelerator path must keep default
    # codegen and only an actual CPU client would reload CPU AOT
    _os.environ.get("JAX_PLATFORMS", "").lower().split(",")[0].strip()
    == "cpu"
):
    # CPU-pinned process: cap the XLA:CPU ISA BEFORE any jax client can
    # initialize, so persistent-cache entries reload silently and
    # portably (see utils/compile_cache.cap_cpu_isa_for_cache). Package
    # import is the earliest point the library controls — call sites
    # like accelerate() run after user code may already have built a
    # mesh (initializing the client), where the env change is a no-op.
    from dlrover_tpu.utils.compile_cache import (  # noqa: E402
        cap_cpu_isa_for_cache as _cap_cpu_isa,
    )

    _cap_cpu_isa()
    del _cap_cpu_isa

del _os
