"""Ambient-mesh helpers shared by every op that builds a shard_map
inside the model (flash/ring attention, MoE expert parallelism) and by
the pipeline constraints, plus the fp8 wire capability probe.

  * ``ambient_mesh()`` — the mesh the surrounding program established
    (``jax.sharding.set_mesh``, what ``parallel.accelerate`` traces
    under), as ``jax.sharding.get_abstract_mesh()`` reports it. None
    when unsharded.
  * ``ambient_mesh_with_axes()`` — the same, when it carries the
    axes a consumer shards over and none is already bound *manually*
    (an enclosing shard_map): an ambient consumer must not build a
    nested shard_map over them.

pallas_call outputs carry no varying-mesh-axes metadata, so every
shard_map around a kernel passes ``check_vma=False``.
"""

from __future__ import annotations

from typing import Optional

import jax


def ambient_mesh():
    """The ambient mesh, or None. No axis filtering here — callers
    layer their own relevance checks (axis presence, size, manualness)
    on top."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


_FP8_WIRE_SUPPORTED: Optional[bool] = None


def fp8_wire_supported() -> bool:
    """Whether this backend can carry block-scaled fp8 on the wire:
    ``float8_e4m3fn`` exists and a tiny cast round-trip executes on the
    default backend. Probed ONCE per process (the result cannot change
    under a fixed jaxlib+backend); ``ops.moe`` falls back to the bf16
    wire — logged, never raised — when the probe fails, so a
    ``moe_precision=fp8`` config degrades instead of killing the job on
    an old toolchain."""
    global _FP8_WIRE_SUPPORTED
    if _FP8_WIRE_SUPPORTED is not None:
        return _FP8_WIRE_SUPPORTED
    try:
        import numpy as np

        import jax.numpy as jnp

        dt = jnp.float8_e4m3fn
        # the probe is usually reached at TRACE time (ops.moe resolves
        # the knob inside the jitted step): compile-time eval keeps the
        # round-trip off the ambient trace, concrete and checkable
        with jax.ensure_compile_time_eval():
            x = jnp.asarray(np.asarray([0.5, -448.0, 0.0], np.float32))
            back = jax.jit(
                lambda v: v.astype(dt).astype(jnp.float32))(x)
            jax.block_until_ready(back)
            _FP8_WIRE_SUPPORTED = bool(np.asarray(back)[0] == 0.5)
    except Exception:  # noqa: BLE001 — any failure = not supported
        import logging

        logging.getLogger("dlrover_tpu.ops.shard_compat").warning(
            "fp8 wire probe failed; quantized MoE precision will fall "
            "back to the bf16 wire", exc_info=True,
        )
        _FP8_WIRE_SUPPORTED = False
    return _FP8_WIRE_SUPPORTED


def ambient_mesh_with_axes(axes, min_size: int = 2) -> Optional[object]:
    """The ambient mesh when it carries every axis in ``axes``,
    none of them already manual, with combined size >= ``min_size``;
    else None."""
    import math

    mesh = ambient_mesh()
    if mesh is None:
        return None
    if any(a not in mesh.axis_names for a in axes):
        return None
    if set(mesh.manual_axes) & set(axes):
        return None
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    if math.prod(sizes[a] for a in axes) < min_size:
        return None
    return mesh
