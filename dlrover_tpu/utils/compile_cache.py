"""Persistent XLA compilation cache — the TPU recovery accelerant.

Role parity: the reference's restore path (``docs/blogs/
stabilize_llm_training_cn.md:209-216``) wins its <2 min pod recovery by
restarting *processes*, not jobs; on TPU the equivalent dominant cost is
XLA recompilation after the restart (SURVEY §7: the <90 s restore budget
"forces aggressive compile caching"). Writing compiled executables to a
persistent on-disk cache makes the second compile of the same (program,
topology) a file read: a preempted-and-rescheduled worker skips straight
to restore + step — the warm half of the recovery decision tree in
``docs/operations.md`` (the live half never leaves the process at all,
``ElasticTrainer.live_reshard``).

One rule places the cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this module sets no directory; where it is not,
the cache is ``DEFAULT_CACHE_DIR``, one fixed git-ignored directory
inside the checkout. The directory is part of JAX's cache key, so it
never moves: no hash, pid, clock or temporary name goes into it.
Turning the cache off is JAX's own switch
(``JAX_ENABLE_COMPILATION_CACHE=false``). Enabled automatically by
``trainer.bootstrap.init_worker`` and ``parallel.accelerate``. Cache
traffic is observable: hit/miss counters ride the telemetry registry
(``jax.monitoring`` listener) and ``tpurun cache`` prints the live
stats. The same listener keeps a compile ledger: for every program the
process asked the compiler for, the seconds of tracing, of lowering, of
XLA and of reading the cache (``cache_traffic``, ``compile_programs``),
which the boot's events carry phase by phase (docs/observability.md).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

from dlrover_tpu.common.log import get_logger

logger = get_logger("utils.compile_cache")

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"  # JAX's own variable
# the one place the CPU ISA cap is spelled (cap_cpu_isa_for_cache and
# every harness that builds a child-process XLA_FLAGS from scratch)
CPU_ISA_CAP_FLAG = "--xla_cpu_max_isa=AVX2"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".xla_cache",
)
_enabled = False
# process-local cache traffic, mirrored into the telemetry registry by
# the monitoring listener; kept here too so cache_stats() works even
# with telemetry off
_traffic = {"hits": 0, "misses": 0, "requests": 0,
            # the compile ledger: executables asked of the backend, and
            # where the seconds of getting them went
            "programs": 0, "trace_seconds": 0.0, "lower_seconds": 0.0,
            "backend_seconds": 0.0, "cache_read_seconds": 0.0}
# calls of the three listeners so far: what the ledger itself costs
# (``cache_stats``, not on any event)
_cost = {"listener_calls": 0}
# per program name, the same four seconds, how often it was asked for
# and how the cache answered; pruned to the dearest PROGRAM_ROWS
PROGRAM_ROWS = 16
_programs: Dict[str, Dict] = {}


class _Pending(threading.local):
    """What one thread's compile has reported since its last backend
    event (JAX reports a hit, its retrieval time and a miss BEFORE the
    backend duration that names the program), and how many traces it
    is inside of."""

    outcome: Optional[str] = None
    read = 0.0
    tracing = 0


_pending = _Pending()

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def resolve_cache_dir() -> str:
    """The directory the persistent cache lives in for this process,
    without touching jax: the variable when set, else the fixed
    in-checkout default. Two processes of one checkout always resolve
    the same path."""
    return os.environ.get(ENV_CACHE_DIR) or DEFAULT_CACHE_DIR


def cap_cpu_isa_for_cache() -> None:
    """Append ``--xla_cpu_max_isa=AVX2`` to ``XLA_FLAGS`` (idempotent).

    Default XLA:CPU tuning embeds AVX512-only pseudo-features
    (``+prefer-no-scatter``/``+prefer-no-gather``) that the AOT
    loader's host-feature detection never reports, so even SAME-host
    persistent-cache reloads log "machine features don't match …
    SIGILL" errors. The AVX2 cap makes cached CPU executables reload
    silently and portably. Callers decide cpu-ness (env hints differ
    per harness) and must call this before the CPU client initializes;
    afterwards it is a harmless no-op.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + CPU_ISA_CAP_FLAG).strip()


def _program_row(fun_name: str) -> Dict:
    """The table's row for a program. JAX names a traced function
    ``f`` and its module ``jit(f)``: one row holds both."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    row = _programs.get(fun_name)
    if row is None:
        if len(_programs) >= 4 * PROGRAM_ROWS:
            for cheap in sorted(_programs.values(), key=_row_seconds)[
                    :len(_programs) - PROGRAM_ROWS]:
                del _programs[cheap["fun_name"]]
        row = _programs[fun_name] = {
            "fun_name": fun_name, "programs": 0, "hits": 0, "misses": 0,
            "trace_seconds": 0.0, "lower_seconds": 0.0,
            "backend_seconds": 0.0, "cache_read_seconds": 0.0}
    return row


def _row_seconds(row: Dict) -> float:
    return (row["trace_seconds"] + row["lower_seconds"]
            + row["backend_seconds"] + row["cache_read_seconds"])


def _register_cache_monitor() -> None:
    """Mirror jax's compilation-cache monitoring events into the
    telemetry registry (and the process-local traffic counters); called
    once, by the first ``enable_compile_cache``.

    A warm restart that truly skipped recompilation shows hits > 0 and
    misses == 0 here — the machine-checkable form of the "zero
    recompiles on a same-topology resume" recovery claim.

    The duration listener keeps the compile ledger. JAX (0.9.0) reports
    ``jaxpr_trace_duration`` (``pjit.py``), ``jaxpr_to_mlir_module_
    duration`` and ``backend_compile_duration`` (``pxla.py``) with the
    program's ``fun_name``; the last is taken around
    ``compiler.compile_or_get_cached``, so on a hit it CONTAINS the
    cache's ``cache_retrieval_time_sec`` (reported just before it, with
    no name): the ledger subtracts it, and ``backend_seconds`` is XLA
    alone (on a miss with the cache write). A jitted function traced
    inside another reports its own duration too, so ``trace_seconds``
    sums the outermost traces only: JAX announces a trace's start
    through ``record_scalar``, which is how the depth is known. The
    listeners run when JAX traces or compiles and never on a step
    served from the jit cache.
    """
    from jax import monitoring

    from dlrover_tpu.telemetry import get_registry, names as tm

    def _on_event(event: str, **_kw) -> None:
        _cost["listener_calls"] += 1
        reg = get_registry()
        if event == "/jax/compilation_cache/cache_hits":
            _traffic["hits"] += 1
            _pending.outcome = "hits"
            reg.counter(tm.COMPILE_CACHE_HITS,
                        help="persistent-cache compiles served from "
                             "disk").inc()
        elif event == "/jax/compilation_cache/cache_misses":
            _traffic["misses"] += 1
            _pending.outcome = "misses"
            reg.counter(tm.COMPILE_CACHE_MISSES,
                        help="compiles that went to XLA and were "
                             "written back").inc()
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            _traffic["requests"] += 1

    def _count(phase: str, row: Optional[Dict], seconds: float) -> None:
        key = phase + "_seconds"
        _traffic[key] += seconds
        if row is not None:
            row[key] += seconds
        get_registry().counter(
            tm.COMPILE_SECONDS, labels={"phase": phase},
            help="seconds this process spent getting its programs: "
                 "tracing, lowering, in XLA, reading the compile cache",
        ).inc(seconds)

    def _on_duration(event: str, duration: float, fun_name: str = "",
                     **_kw) -> None:
        _cost["listener_calls"] += 1
        if event == _TRACE_EVENT:
            # a row keeps its whole trace, nested or not
            _program_row(fun_name)["trace_seconds"] += duration
            _pending.tracing = max(0, _pending.tracing - 1)
            if not _pending.tracing:
                _count("trace", None, duration)
        elif event == _LOWER_EVENT:
            _count("lower", _program_row(fun_name), duration)
        elif event == _CACHE_READ_EVENT:
            _pending.read += duration
        elif event == _BACKEND_EVENT:
            row = _program_row(fun_name)
            read, outcome = _pending.read, _pending.outcome
            _pending.read, _pending.outcome = 0.0, None
            _traffic["programs"] += 1
            row["programs"] += 1
            if outcome:
                row[outcome] += 1
            if read:
                _count("cache_read", row, read)
            _count("backend", row, max(0.0, duration - read))

    def _on_start(event: str, _value, **_kw) -> None:
        _cost["listener_calls"] += 1
        if event == _TRACE_EVENT:
            _pending.tracing += 1

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_scalar_listener(_on_start)


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on at
    ``resolve_cache_dir()``. With ``JAX_COMPILATION_CACHE_DIR`` set JAX
    has already read it and no directory is set in code. Idempotent;
    returns the directory."""
    global _enabled
    cache_dir = resolve_cache_dir()
    if _enabled:
        return cache_dir
    if "cpu" in os.environ.get("JAX_PLATFORMS", "").lower():
        # every cache user on a CPU-pinned process gets the ISA cap —
        # this is the chokepoint, so ad-hoc scripts (not just
        # conftest/bench/dryrun) produce and reload clean entries;
        # best-effort (no-op if the CPU client already initialized)
        cap_cpu_isa_for_cache()
    _register_cache_monitor()

    import jax

    if not os.environ.get(ENV_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every executable: recovery time is dominated by the big
    # train-step compile, but warm-starting the small ones is free
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled = True
    logger.info("persistent XLA compile cache at %s", cache_dir)
    return cache_dir


def cache_entries(cache_dir: Optional[str] = None) -> int:
    """Number of cached executables on disk in ``cache_dir`` (default:
    ``resolve_cache_dir()``); 0 if the dir is absent."""
    d = cache_dir or resolve_cache_dir()
    if not os.path.isdir(d):
        return 0
    return sum(
        1 for name in os.listdir(d)
        if name.endswith("-cache") and os.path.isfile(os.path.join(d, name))
    )


def cache_traffic() -> Dict[str, float]:
    """This process's persistent-cache hits, misses and requests so far,
    and the compile ledger's totals: ``programs`` asked of the backend,
    ``trace_seconds``, ``lower_seconds``, ``backend_seconds`` and
    ``cache_read_seconds`` (counters only: cheap enough for a per-step
    line)."""
    return {k: round(v, 6) if isinstance(v, float) else v
            for k, v in _traffic.items()}


def compile_programs() -> List[Dict]:
    """The ledger's table: the ``PROGRAM_ROWS`` dearest programs so far
    by total seconds, each with its name, how often the backend was asked
    for it, the cache's hits and misses, and its four seconds
    (``trace_seconds`` with the traces nested in it). Functions that
    were traced inside a program and never compiled alone have no
    row."""
    rows = [dict(row, **{k: round(v, 6) for k, v in row.items()
                         if isinstance(v, float)})
            for name, row in _programs.items() if row["programs"]]
    rows.sort(key=_row_seconds, reverse=True)
    return rows[:PROGRAM_ROWS]


def cache_stats(cache_dir: Optional[str] = None) -> Dict:
    """One snapshot for operators (``tpurun cache``): where the cache
    lives, how many executables it holds, and this process's traffic.
    Also refreshes the entry-count gauge in the telemetry registry."""
    from dlrover_tpu.telemetry import get_registry, names as tm

    entries = cache_entries(cache_dir)
    get_registry().gauge(
        tm.COMPILE_CACHE_ENTRIES,
        help="executables in the persistent compile cache",
    ).set(entries)
    return {
        "dir": cache_dir or resolve_cache_dir(),
        # active: enable_compile_cache() ran in THIS process — the
        # difference matters when debugging "why did the warm restart
        # recompile": not active means nothing ever turned the cache
        # on here.
        "active": _enabled,
        "entries": entries,
        **cache_traffic(),
        **_cost,
    }
