"""Test environment: force the JAX CPU backend with 8 virtual devices.

Multi-chip semantics (meshes, collectives, shardings) are exercised on a
virtual CPU mesh, mirroring the reference's gloo-on-CPU test strategy
(`atorch/atorch/tests/test_utils.py`). Must run before jax is imported.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# libtpu's init (reached by the deviceless-AOT tests through
# jax.experimental.topologies) probes the GCE metadata server for TPU
# worker hostnames; off-GCE that probe is a ~460 s silent network
# timeout at ~0% CPU — nearly half the tier-1 wall budget. Skip the
# query and point the metadata addresses at a fast-refusing local port
# (setdefault: a real TPU host can still override).
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
os.environ.setdefault("GCE_METADATA_IP", "127.0.0.1:1")
os.environ.setdefault("GCE_METADATA_HOST", "127.0.0.1:1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# AVX2 ISA cap: silent, portable persistent-cache reloads on CPU (test
# shapes are far too small for AVX512 to matter) — must precede jax
# import; see cap_cpu_isa_for_cache for the full rationale
from dlrover_tpu.utils.compile_cache import cap_cpu_isa_for_cache  # noqa: E402

cap_cpu_isa_for_cache()
# One fixed compile-cache directory OUTSIDE the tree: a fresh checkout
# (the driver's, the chip tool's copy) then reuses what an earlier
# tier-1 run compiled, and the in-checkout default stays small. JAX
# reads the variable itself; no code sets a directory.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.expanduser("~"), ".cache", "dlrover_tpu",
                 "xla_cache"),
)
os.environ.setdefault("DLROVER_TPU_LOG_LEVEL", "WARNING")

# A SIGKILLed tier-1 run (timeout, OOM-killer) leaves a stale
# /tmp/libtpu_lockfile behind; libtpu's init in LATER runs then waits
# on it silently — the suite looks hung at 0% CPU before a single test
# collects. Remove a leftover at session import — but only after an
# flock probe proves no LIVE process holds it (os.remove succeeds on a
# held flock, so an unconditional unlink would strip a concurrent
# run's lock — the very conflict the file serializes). See
# docs/operations.md "Troubleshooting".
_lock = os.environ.get("LIBTPU_LOCKFILE", "/tmp/libtpu_lockfile")
try:
    if os.path.exists(_lock):
        import fcntl

        with open(_lock) as _fh:
            fcntl.flock(_fh, fcntl.LOCK_EX | fcntl.LOCK_NB)  # probe
            os.remove(_lock)  # stale: nothing holds it
except OSError:
    pass  # held by a live process (or not ours to remove): leave it
