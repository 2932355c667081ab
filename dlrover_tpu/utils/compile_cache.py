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
stats.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

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
_traffic = {"hits": 0, "misses": 0, "requests": 0}


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


def _register_cache_monitor() -> None:
    """Mirror jax's compilation-cache monitoring events into the
    telemetry registry (and the process-local traffic counters); called
    once, by the first ``enable_compile_cache``.

    A warm restart that truly skipped recompilation shows hits > 0 and
    misses == 0 here — the machine-checkable form of the "zero
    recompiles on a same-topology resume" recovery claim.
    """
    from jax import monitoring

    from dlrover_tpu.telemetry import get_registry, names as tm

    def _on_event(event: str, **_kw) -> None:
        reg = get_registry()
        if event == "/jax/compilation_cache/cache_hits":
            _traffic["hits"] += 1
            reg.counter(tm.COMPILE_CACHE_HITS,
                        help="persistent-cache compiles served from "
                             "disk").inc()
        elif event == "/jax/compilation_cache/cache_misses":
            _traffic["misses"] += 1
            reg.counter(tm.COMPILE_CACHE_MISSES,
                        help="compiles that went to XLA and were "
                             "written back").inc()
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            _traffic["requests"] += 1

    monitoring.register_event_listener(_on_event)


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


def cache_traffic() -> Dict[str, int]:
    """This process's persistent-cache hits, misses and requests so far
    (counters only: cheap enough for a per-step line)."""
    return dict(_traffic)


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
    }
