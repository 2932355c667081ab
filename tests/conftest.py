"""Test environment: force the JAX CPU backend with 8 virtual devices.

Multi-chip semantics (meshes, collectives, shardings) are exercised on a
virtual CPU mesh, mirroring the reference's gloo-on-CPU test strategy
(`atorch/atorch/tests/test_utils.py`). Must run before jax is imported.
"""

import contextlib
import fcntl
import fnmatch
import os
import sys

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
        with open(_lock) as _fh:
            fcntl.flock(_fh, fcntl.LOCK_EX | fcntl.LOCK_NB)  # probe
            os.remove(_lock)  # stale: nothing holds it
except OSError:
    pass  # held by a live process (or not ours to remove): leave it


# How tier-1 is handed out (PR 54; ROADMAP.md D9, docs/operations.md
# "Troubleshooting"). pytest-xdist's ``load`` scheduler gives each
# worker a first chunk of ``tests // workers // 4`` CONTIGUOUS tests of
# the collection and refills in contiguous chunks: six files of
# ``tests/chipbench/`` that sit side by side were 926 s on one worker
# while five stood idle. So the run is handed out a test at a time, from
# an order that leads with the files known to be long, by the file
# names the repo keeps to for them: a benchmark's CPU rehearsal, then a
# configuration's whole step compiled for the described v5e (a file of
# its own each, many-threaded: all of them at once and early, since a
# compile beside the other tests slows both), then the kernels' compiles.
# A new long test takes one of these names.
LONG_FIRST = (
    "*/chipbench/*_rehearsal.py",
    "*/test_tpu_compile_*.py",
    "*/test_tpu_compile.py",
)


def long_first_rank(nodeid):
    """Which of ``LONG_FIRST`` the test's file matches, counted from 0,
    and ``len(LONG_FIRST)`` for every other test: a pure function of the
    node id, so that every xdist worker sorts its collection alike."""
    path = "/" + nodeid.split("::", 1)[0]
    for rank, pattern in enumerate(LONG_FIRST):
        if fnmatch.fnmatchcase(path, pattern):
            return rank
    return len(LONG_FIRST)


def one_test_at_a_time(option):
    """``--maxschedchunk 1`` where xdist is loaded and the command line
    gave none: ``LoadScheduling`` then starts each worker with two tests
    and sends one for each that ends. Under ``-p no:xdist`` the option
    does not exist and nothing is set."""
    if getattr(option, "maxschedchunk", 1) is None:
        option.maxschedchunk = 1


@contextlib.contextmanager
def its_turn(path):
    """Holds an exclusive ``flock`` on the test's own file while the
    test runs. A rehearsal file's tests share its cell's work directory
    and logs under the checkout
    (``tests/chipbench/test_chipbench_rehearsal.py`` counts the
    processes left there): a chunk of the collection ran them one after
    another on one worker, a test at a time starts them on four."""
    with open(path) as fh:
        fcntl.flock(fh, fcntl.LOCK_EX)
        yield


def mappings():
    """How many memory mappings this process holds, and 0 where
    ``/proc`` does not say."""
    try:
        with open("/proc/self/maps", "rb") as fh:
            return sum(1 for _ in fh)
    except OSError:
        return 0


def mappings_allowed():
    """``vm.max_map_count``, the most a process may hold (65,530 where
    nobody raised it)."""
    try:
        with open("/proc/sys/vm/max_map_count") as fh:
            return int(fh.read())
    except (OSError, ValueError):
        return 65530


def let_go_of_executables(held, allowed):
    """Drops JAX's in-memory executables once the process holds half
    the mappings it may. An executable that XLA:CPU has loaded holds
    3 to 18 mappings for as long as JAX's caches keep it, and past
    ``vm.max_map_count`` ``mmap`` fails: the loader then logs ``LLVM
    compilation error: Cannot allocate memory`` and dies of a
    segmentation fault inside ``deserialize_executable``, or goes on
    with a program it could not load whole (PR 54: a worker of a whole
    run came to 60,845 of 65,530; in nine whole runs three workers died
    there and three agreement tests read a wrong number once each; the
    parent's order came as close, 59,572, and was never seen to cross).
    What is dropped comes back from the persistent cache when it is
    next called for."""
    if 2 * held > allowed and "jax" in sys.modules:
        sys.modules["jax"].clear_caches()
        return True
    return False


def pytest_configure(config):
    one_test_at_a_time(config.option)


def pytest_collection_modifyitems(items):
    items.sort(key=lambda item: long_first_rank(item.nodeid))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    rehearsal = long_first_rank(item.nodeid) == 0
    with its_turn(item.path) if rehearsal else contextlib.nullcontext():
        yield
    let_go_of_executables(mappings(), mappings_allowed())


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e:2x2. The persistent compile
    cache is off around these compiles: a deviceless executable is
    written to it but cannot be read back without a chip (the next run
    would warn and compile again); what a test reads of a whole step's
    or a long kernel's compile is kept beside it instead, by the lowered
    module (``hlo_checks.compile_once``, PR 58), so an unchanged program
    costs its lowering alone and a changed one what is said below.
    Module scope: each
    ``test_tpu_compile*.py`` describes the topology once a worker and
    turns the cache on again after its last test there. The compiler's
    threads are the suite's one many-core load, and a whole-step compile
    holds them for minutes: they run at ``nice 10`` (threads inherit it
    from the one that starts them), the priority the benchmark's
    rehearsals give their own jobs, so that the tests beside them keep
    their share of the cores (PR 54, the compiles at the head of the
    run: 1,343 s without it for 1,198-1,200 with, and a rehearsal's
    launcher starved to death)."""
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
