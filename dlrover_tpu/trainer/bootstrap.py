"""Training-process bootstrap: env contract -> jax.distributed.

The agent hands every worker process its SPMD coordinates via environment
variables (``NodeEnv``); calling :func:`init_worker` inside the training
script wires them into ``jax.distributed.initialize`` — the TPU-native
replacement for the reference wiring torch's c10d store through the master
(``elastic_agent/torch/master_kv_store.py``).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Optional

import psutil

from dlrover_tpu.agent.master_client import (
    MasterClient,
    build_master_client,
)
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry import EventKind, emit_event

logger = get_logger("trainer.bootstrap")

# time.monotonic() when init_worker returned: what the script does from
# there to its trainer is a phase of the boot (``trainer_ready``)
worker_ready_mono: Optional[float] = None


@dataclass
class WorkerContext:
    process_id: int
    num_processes: int
    node_rank: int
    node_num: int
    local_rank: int
    local_world_size: int
    restart_round: int
    coordinator_addr: str
    master_client: Optional[MasterClient]

    @property
    def is_chief(self) -> bool:
        return self.process_id == 0


def process_start_ts() -> float:
    """This process's start on the wall clock that every record's ``ts``
    reads. The kernel keeps it in ticks of its boot-time clock
    (``/proc/self/stat``, taken at the middle of its tick), read
    against that clock itself: ``psutil``'s ``create_time()`` adds the
    ticks to ``/proc/stat``'s ``btime``, which is whole seconds, and so
    reads up to a second early on any given machine (the fallback where
    there is no ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - (ticks + 0.5) / os.sysconf("SC_CLK_TCK"))
        return time.time() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return psutil.Process().create_time()


def init_worker(platform: Optional[str] = None,
                cpu_collectives: str = "gloo") -> WorkerContext:
    """Initialize distributed JAX from the agent's env contract.

    ``platform``: force a jax platform (tests pass "cpu"); None keeps the
    process default (TPU in production).

    Puts the worker's boot on the event timeline (``worker_boot``): the
    process's start on the wall clock, the seconds from there to this
    call (interpreter and imports), the seconds of
    ``jax.distributed.initialize`` (0 on one host) and the seconds of
    the first ``jax.devices()``, which is the backend's (TPU)
    initialisation.
    """
    global worker_ready_mono
    t_entry = time.time()
    import jax

    from dlrover_tpu.utils.compile_cache import (
        cache_traffic,
        enable_compile_cache,
    )

    if platform == "cpu" or "cpu" in os.environ.get(
        "JAX_PLATFORMS", ""
    ).lower():
        # silent, portable persistent-cache reloads on CPU; must run
        # before the client boots (no-op afterwards)
        from dlrover_tpu.utils.compile_cache import cap_cpu_isa_for_cache

        cap_cpu_isa_for_cache()

    # persistent XLA cache: a restarted worker recompiling the same
    # program hits disk instead of the compiler (<90 s restore budget)
    enable_compile_cache()

    if platform:
        jax.config.update("jax_platforms", platform)
        if platform == "cpu" and cpu_collectives:
            jax.config.update(
                "jax_cpu_collectives_implementation", cpu_collectives
            )

    process_id = int(os.environ.get(NodeEnv.PROCESS_ID, "0"))
    num_processes = int(os.environ.get(NodeEnv.NUM_PROCESSES, "1"))
    coordinator = os.environ.get(NodeEnv.COORDINATOR_ADDR, "")
    ctx = WorkerContext(
        process_id=process_id,
        num_processes=num_processes,
        node_rank=int(os.environ.get(NodeEnv.NODE_RANK, "0")),
        node_num=int(os.environ.get(NodeEnv.NODE_NUM, "1")),
        local_rank=int(os.environ.get("LOCAL_RANK", "0")),
        local_world_size=int(os.environ.get("LOCAL_WORLD_SIZE", "1")),
        restart_round=int(os.environ.get(NodeEnv.RESTART_ROUND, "0")),
        coordinator_addr=coordinator,
        master_client=build_master_client(),
    )
    t_distributed = time.monotonic()
    if num_processes > 1 and coordinator:
        logger.info(
            "jax.distributed.initialize(%s, num_processes=%d, process_id=%d)",
            coordinator, num_processes, process_id,
        )
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    t_backend = time.monotonic()
    devices = jax.devices()
    backend_seconds = time.monotonic() - t_backend
    started = process_start_ts()
    emit_event(
        EventKind.WORKER_BOOT, process_start_ts=round(started, 3),
        import_seconds=round(t_entry - started, 6),
        distributed_seconds=round(t_backend - t_distributed, 6),
        backend_seconds=round(backend_seconds, 6),
        platform=devices[0].platform, device_count=len(devices),
        restart_round=ctx.restart_round, compile=cache_traffic(),
    )
    worker_ready_mono = time.monotonic()
    return ctx
