"""Test environment: force the JAX CPU backend with 8 virtual devices.

Multi-chip semantics (meshes, collectives, shardings) are exercised on a
virtual CPU mesh, mirroring the reference's gloo-on-CPU test strategy
(`atorch/atorch/tests/test_utils.py`). Must run before jax is imported.
"""

import os

import pytest

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


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e:2x2. The persistent compile
    cache is off around these compiles: a deviceless executable is
    written to it but cannot be read back without a chip (the next run
    would warn and compile again). Module scope: each
    ``test_tpu_compile*.py`` describes the topology once and turns the
    cache on again after its last test. The compiler's threads are the
    suite's one many-core load, and a whole-step compile holds them for
    minutes: they run at ``nice 10`` (threads inherit it from the one
    that starts them), the priority the benchmark's rehearsals give
    their own jobs, so that the files with clocks beside them keep
    their share of the cores."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from dlrover_tpu.parallel.aot import _get_topology_desc_serialized

    priority = os.getpriority(os.PRIO_PROCESS, 0)
    os.setpriority(os.PRIO_PROCESS, 0, max(priority, 10))
    try:
        try:
            topo = _get_topology_desc_serialized(topologies, "v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler on this box
            pytest.skip(f"a v5e:2x2 topology cannot be described here: {e}")
        devices = list(topo.devices)
        assert devices[0].device_kind == "TPU v5 lite" and len(devices) == 4
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield devices
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    finally:
        try:
            os.setpriority(os.PRIO_PROCESS, 0, priority)
        except PermissionError:
            pass  # raising a priority again takes a privilege: stay low
