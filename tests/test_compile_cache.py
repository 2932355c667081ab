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
