"""Device-mesh planning.

Role parity: ``atorch/atorch/distributed/distributed.py:318-402``
(``create_parallel_group`` building nested NCCL process groups from
``[("tensor",4),("pipe",2),("data",2)]``). TPU-first: the same nested
topology is a single ``jax.sharding.Mesh`` whose axis order controls which
axes ride the fast ICI links; XLA lowers collectives from shardings, so no
process groups are ever materialized.

Axis convention (outer -> inner):
  "pipe"   pipeline stages            (DCN-friendly, least traffic)
  "data"   pure data parallel         (gradient psum only)
  "fsdp"   data parallel + param/optimizer sharding (ZeRO-3 analogue)
  "seq"    sequence/context parallel  (ring attention neighbors on ICI)
  "tensor" megatron-style op sharding (most traffic, innermost => ICI)
  "expert" MoE expert parallel (aliases fsdp/data in most configs)
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dlrover_tpu.common.log import get_logger

logger = get_logger("parallel.mesh")

MESH_AXES = ("pipe", "data", "fsdp", "seq", "tensor")
EXPERT_AXIS = "expert"


@dataclass
class MeshPlan:
    """Declarative mesh shape; -1 on at most one axis means 'infer'.

    ``expert`` does not get its own mesh dimension: expert parallelism
    reuses the (data x fsdp) submesh (the reference's expert process groups
    are also carved out of the data-parallel ranks,
    ``atorch/modules/moe/moe_layer.py:29``).
    """

    pipe: int = 1
    data: int = -1
    fsdp: int = 1
    seq: int = 1
    tensor: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {
            "pipe": self.pipe, "data": self.data, "fsdp": self.fsdp,
            "seq": self.seq, "tensor": self.tensor,
        }

    def resolve(self, num_devices: int) -> "MeshPlan":
        """Fill the -1 axis so the product equals num_devices."""
        sizes = self.axis_sizes()
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one -1 axis allowed: {sizes}")
        known = math.prod(v for v in sizes.values() if v != -1)
        if unknown:
            if num_devices % known:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes "
                    f"{sizes}"
                )
            sizes[unknown[0]] = num_devices // known
        elif known != num_devices:
            raise ValueError(
                f"mesh {sizes} wants {known} devices, have {num_devices}"
            )
        return MeshPlan(**sizes)

    def adjust_to_world(self, num_devices: int) -> "MeshPlan":
        """Refit for a new world size after elastic scale up/down.

        Parity with ``atorch/auto/accelerate.py:309-356``
        (``adjust_strategy`` refits the data-parallel degree and keeps the
        model-parallel axes): tensor/seq/pipe are topology-bound choices,
        so the data and fsdp axes absorb the change, preferring fsdp.
        """
        model_par = self.pipe * self.seq * self.tensor
        if num_devices % model_par:
            raise ValueError(
                f"world of {num_devices} devices cannot hold model-parallel "
                f"factor {model_par} (pipe x seq x tensor)"
            )
        dp_total = num_devices // model_par
        old_fsdp = max(1, self.fsdp)
        # keep fsdp as close to the old degree as divisibility allows:
        # the largest divisor of dp_total not exceeding the old degree
        # (shrinking fsdp raises per-device param memory, so shrink least).
        fsdp = max(
            (d for d in _divisors(dp_total) if d <= old_fsdp), default=1
        )
        data = dp_total // fsdp
        return MeshPlan(pipe=self.pipe, data=data, fsdp=fsdp,
                        seq=self.seq, tensor=self.tensor)

    def build(self, devices: Optional[Sequence] = None):
        """Materialize a ``jax.sharding.Mesh``.

        Axis order is outer->inner so the most communication-hungry axis
        ("tensor") maps to the most-adjacent devices (ICI neighbors on a
        TPU torus; ``mesh_utils`` handles the physical assignment).
        """
        import jax
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh

        devices = list(devices) if devices is not None else jax.devices()
        plan = self.resolve(len(devices))
        shape = tuple(plan.axis_sizes()[a] for a in MESH_AXES)
        try:
            device_array = mesh_utils.create_device_mesh(
                shape, devices=devices
            )
        except (ValueError, AssertionError):
            device_array = np.asarray(devices).reshape(shape)
        return Mesh(device_array, MESH_AXES)

    @property
    def dp_degree(self) -> int:
        return max(1, self.data) * max(1, self.fsdp)


def topology_key(devices: Optional[Sequence] = None) -> str:
    """Stable identity of a device set, for compiled-program caching.

    Two worlds with the same key can reuse each other's compiled SPMD
    programs verbatim (same platform, same device identities, same
    order ⇒ same HLO, same executable). ``ElasticTrainer`` keys its
    in-process program cache on this so a live reshard BACK to a
    topology it already compiled for — the scale-down-then-recover
    pattern — pays zero recompiles. (The persistent on-disk cache
    needs no such key of ours: JAX's own cache key covers the devices.)
    """
    import jax

    devices = list(devices) if devices is not None else jax.devices()
    return "|".join(
        f"{getattr(d, 'platform', '?')}:{getattr(d, 'id', '?')}"
        for d in devices
    )


def mesh_axes_key(plan: MeshPlan) -> str:
    """Stable identity of a mesh FACTORIZATION ("pipe.data.fsdp.seq.
    tensor") — the one format shared by the trainer's program-cache key,
    the runtime optimizer's candidate/cooldown keys, and mesh dedup, so
    an axis added to MeshPlan cannot silently diverge them."""
    return (f"{plan.pipe}.{plan.data}.{plan.fsdp}"
            f".{plan.seq}.{plan.tensor}")


def single_device_plan() -> MeshPlan:
    return MeshPlan(pipe=1, data=1, fsdp=1, seq=1, tensor=1)


def candidate_plans(num_devices: int,
                    max_model_parallel: Optional[int] = None) -> List[MeshPlan]:
    """Enumerate plausible mesh shapes for the auto-tuner.

    Parity with the strategy-generation half of atorch's search engine
    (``auto/engine/sg_algo/combination_sg.py``): candidates are the
    divisor factorizations of the device count over (fsdp, tensor), with
    data absorbing the rest; seq/pipe candidates are added by the tuner
    only when the model asks for them (long context / stages).
    """
    plans = []
    max_mp = max_model_parallel or num_devices
    for tensor in _divisors(num_devices):
        if tensor > max_mp:
            continue
        rest = num_devices // tensor
        for fsdp in _divisors(rest):
            data = rest // fsdp
            plans.append(
                MeshPlan(pipe=1, data=data, fsdp=fsdp, seq=1, tensor=tensor)
            )
    return plans


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]
