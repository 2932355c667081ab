"""Persistent XLA compile cache: one rule places it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and the code
sets no directory; where it is not, the cache is one fixed directory
inside the checkout. Each case runs in fresh processes: the cache
config is process-global and read once.
"""

import json
import os
import subprocess
import sys

import pytest

from dlrover_tpu.utils.compile_cache import (
    CPU_ISA_CAP_FLAG,
    DEFAULT_CACHE_DIR,
    ENV_CACHE_DIR,
    cache_entries,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# records every jax.config.update the program makes, enables the cache
# the way bootstrap/accelerate do, compiles one program, prints facts
PROG = """
import json, os
import jax
updates = []
_update = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), _update(k, v))[1]
from dlrover_tpu.utils.compile_cache import cache_stats, enable_compile_cache
active = enable_compile_cache()
import jax.numpy as jnp
y = jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0))
stats = cache_stats()
print("FACTS " + json.dumps({
    "y3": float(y[3]), "active": active, "updates": updates,
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "hits": stats["hits"], "misses": stats["misses"],
}))
"""


def _run(cache_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    # the CPU-harness convention (conftest/dryrun/bench smoke): AVX2 cap
    # keeps cached CPU executables free of machine-feature mismatch
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=1 " + CPU_ISA_CAP_FLAG
    )
    env.pop(ENV_CACHE_DIR, None)
    if cache_dir:
        env[ENV_CACHE_DIR] = cache_dir
    out = subprocess.run(
        [sys.executable, "-c", PROG], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("FACTS ")][-1]
    return json.loads(line[len("FACTS "):]), out.stderr


@pytest.fixture(scope="module")
def cold_run(tmp_path_factory):
    """One process against an empty directory named by the variable."""
    root = str(tmp_path_factory.mktemp("cc"))
    facts, _ = _run(root)
    return root, facts


def test_env_dir_is_left_to_jax(cold_run):
    """Variable set: the code sets no directory (JAX read the variable
    itself) and the entries land there."""
    root, facts = cold_run
    assert facts["y3"] == 7.0
    assert facts["active"] == root and facts["jax_dir"] == root
    assert "jax_compilation_cache_dir" not in facts["updates"]
    assert facts["misses"] >= 1 and cache_entries(root) >= 1


def test_unset_resolves_the_fixed_in_checkout_dir():
    """Variable unset: two processes resolve the same directory inside
    the checkout — nothing in the path comes from a hash, a pid, a
    clock or a temporary name."""
    first, _ = _run(None)
    second, _ = _run(None)
    assert first["active"] == second["active"] == DEFAULT_CACHE_DIR
    assert first["jax_dir"] == DEFAULT_CACHE_DIR
    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".xla_cache")


def test_second_process_reloads_with_zero_misses(cold_run):
    """The warm-restart gate: a fresh process compiling the same program
    against the same directory serves EVERY compile from disk."""
    root, _ = cold_run
    warm, stderr = _run(root)
    assert warm["misses"] == 0 and warm["hits"] >= 1, warm
    # no loader noise on a warm reload
    assert "machine features" not in stderr.lower()


# the compile ledger: ``warm`` is compiled by an earlier process into
# the directory, ``cold`` (which traces ``inner`` inside itself) is not
LEDGER_PROG = """
import json, sys
import jax, jax.numpy as jnp
from dlrover_tpu.telemetry import get_registry, names as tm
from dlrover_tpu.utils.compile_cache import (
    cache_stats, cache_traffic, compile_programs, enable_compile_cache)
enable_compile_cache()
x = jnp.arange(8.0)
before = cache_traffic()
@jax.jit
def warm(x): return jnp.sin(x) * 2
@jax.jit
def inner(x): return jnp.cos(x) + 1
@jax.jit
def cold(x): return inner(x) + jnp.where(x > 0, x, 0).sum()
warm(x).block_until_ready()
if "both" in sys.argv:
    cold(x).block_until_ready()
    warm(x).block_until_ready()  # the jit cache: no event at all
reg = get_registry()
print("FACTS " + json.dumps({
    "before": before, "after": cache_traffic(),
    "table": compile_programs(),
    "listener_calls": cache_stats()["listener_calls"],
    "registry": {p: getattr(reg.get(tm.COMPILE_SECONDS, {"phase": p}),
                            "value", None)
                 for p in ("trace", "lower", "backend", "cache_read")}}))
"""


def _run_ledger(cache_dir, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=1 " + CPU_ISA_CAP_FLAG)
    env[ENV_CACHE_DIR] = cache_dir
    out = subprocess.run([sys.executable, "-c", LEDGER_PROG, *args],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("FACTS ")][-1]
    return json.loads(line[len("FACTS "):])


def test_the_ledger_splits_a_hit_and_a_miss(tmp_path):
    """Two jitted functions, one served from a warm cache directory and
    one compiled: the ledger's four totals, its table by program, and
    the registry's counter family."""
    root = str(tmp_path)
    _run_ledger(root)  # an earlier process compiled ``warm``
    facts = _run_ledger(root, "both")
    before, after = facts["before"], facts["after"]
    new = {k: after[k] - before[k] for k in after}
    # ``warm`` was read, ``cold`` compiled; ``inner`` was traced inside
    # ``cold`` and is no program of its own
    assert (new["programs"], new["hits"], new["misses"]) == (2, 1, 1)
    assert after["programs"] == after["hits"] + after["misses"]
    assert after["requests"] == after["programs"]
    for key in ("trace_seconds", "lower_seconds", "backend_seconds",
                "cache_read_seconds"):
        assert new[key] > 0, key
    rows = {r["fun_name"]: r for r in facts["table"]}
    assert "inner" not in rows
    warm, cold = rows["warm"], rows["cold"]
    assert (warm["programs"], warm["hits"], warm["misses"]) == (1, 1, 0)
    assert (cold["programs"], cold["hits"], cold["misses"]) == (1, 0, 1)
    # a hit's retrieval is reported inside JAX's backend duration: the
    # ledger takes it out, so what is left of a hit is next to nothing
    assert warm["cache_read_seconds"] > 0 and cold["cache_read_seconds"] == 0
    assert warm["backend_seconds"] < cold["backend_seconds"]
    for row in rows.values():
        assert set(row) == {"fun_name", "programs", "hits", "misses",
                            "trace_seconds", "lower_seconds",
                            "backend_seconds", "cache_read_seconds"}
        assert row["trace_seconds"] > 0 and row["lower_seconds"] > 0
    # the totals are the rows' sums: ``inner``'s trace, nested in
    # ``cold``'s, is counted once (a trace that is never compiled
    # alone and is nested in nothing would be in the total only)
    for key in ("trace_seconds", "lower_seconds", "backend_seconds",
                "cache_read_seconds"):
        assert after[key] == pytest.approx(
            sum(r[key] for r in rows.values()), abs=1e-4), key
    # dearest first, and bounded
    cost = [sum(v for k, v in r.items() if k.endswith("_seconds"))
            for r in facts["table"]]
    assert cost == sorted(cost, reverse=True)
    assert len(facts["table"]) <= 16
    for phase, value in facts["registry"].items():
        assert value == pytest.approx(after[phase + "_seconds"], abs=1e-4)
    # what the ledger itself cost is the operator's snapshot's to
    # say, and rides no event
    assert facts["listener_calls"] > 0 and "listener_calls" not in after
