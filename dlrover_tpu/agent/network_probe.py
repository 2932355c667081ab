"""Standalone network/accelerator health probe.

Role parity: ``dlrover/trainer/torch/run_network_check.py`` (10x timed
allgather). TPU retarget: the probe validates the two fabrics a host
depends on --
  1. **chip health / ICI**: a jitted matmul + psum over the host's local
     chips (exercises the MXU and intra-host links);
  2. **host fabric (DCN/NIC)**: a gloo-backed CPU allgather across the probe
     group handed out by the NetworkCheckRendezvousManager.

Run as ``python -m dlrover_tpu.agent.network_probe`` with the coordinates in
argv; exits 0 when healthy, 1 otherwise, and prints the elapsed time so the
agent can report straggler timings.
"""

from __future__ import annotations

import argparse
import sys
import time


def probe_local_chips(platform: str) -> float:
    """Matmul+reduce on the local backend; returns elapsed seconds."""
    import jax
    import jax.numpy as jnp

    if platform:
        jax.config.update("jax_platforms", platform)
    t0 = time.time()
    n = jax.local_device_count()
    x = jnp.ones((256, 256), dtype=jnp.bfloat16)

    @jax.jit
    def _work(a):
        return (a @ a).astype(jnp.float32).sum()

    results = [jax.device_put(x, d) for d in jax.local_devices()]
    outs = [_work(r) for r in results]
    for o in outs:
        o.block_until_ready()
    elapsed = time.time() - t0
    print(f"probe: {n} local devices ok in {elapsed:.3f}s", flush=True)
    return elapsed


def probe_group_fabric(coordinator: str, process_id: int,
                      num_processes: int, rounds: int = 10) -> float:
    """Timed cross-host allgather over the probe group (CPU/gloo — checks
    the host NIC/DCN path without claiming TPU slices)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    t0 = time.time()
    for _ in range(rounds):
        local = jnp.arange(1024, dtype=jnp.float32) + process_id
        gathered = multihost_utils.process_allgather(local)
        assert gathered.shape[0] == num_processes
    elapsed = time.time() - t0
    print(f"probe: {rounds} allgathers over {num_processes} procs "
          f"in {elapsed:.3f}s", flush=True)
    return elapsed


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--coordinator", default="")
    parser.add_argument("--process_id", type=int, default=0)
    parser.add_argument("--num_processes", type=int, default=1)
    parser.add_argument("--platform", default="",
                        help="backend for the chip probe ('' = default)")
    parser.add_argument("--skip_chip_probe", action="store_true")
    parser.add_argument("--rounds", type=int, default=10)
    args = parser.parse_args(argv)

    elapsed = 0.0
    try:
        if not args.skip_chip_probe:
            elapsed += probe_local_chips(args.platform)
        if args.num_processes > 1 and args.coordinator:
            elapsed += probe_group_fabric(
                args.coordinator, args.process_id, args.num_processes,
                args.rounds,
            )
    except Exception as e:  # any probe failure marks this host suspect
        print(f"probe failed: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    print(f"PROBE_ELAPSED={elapsed:.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
