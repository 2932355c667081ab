"""The acceleration strategy object.

Role parity: atorch's strategy — an ordered list of optimization methods
(``atorch/atorch/auto/strategy.py``, picklable, re-fit to the world size by
``adjust_strategy``). On TPU the whole wrapper catalog (DDP/ZeRO/FSDP/TP/
AMP/checkpointing) collapses into four declarative knobs:

  mesh      : how devices are arranged        (parallel_mode/zero/tp/pp)
  rules     : where tensors live on the mesh  (fsdp wrap policy, tp plan)
  remat     : what activations to save        (checkpoint_optimization)
  dtypes    : what precision to compute in    (amp/half optimization)

plus ``grad_accum_steps`` — the elasticity lever that keeps the global
batch fixed when the world shrinks (``trainer/torch/elastic.py:387-401``).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Optional, Tuple

from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.sharding_rules import (
    ShardingRules,
    bert_pp_rules,
    bert_rules,
    clip_rules,
    delta_hybrid_rules,
    kda_mla_moe_rules,
    looped_rules,
    glm_pp_rules,
    gqa_moe_rules,
    glm_rules,
    gpt2_pp_rules,
    llama_pp_rules,
    llama_rules,
    mla_moe_rules,
    moe_ep_rules,
    moe_rules,
    neox_pp_rules,
    neox_rules,
    sambay_rules,
    ssd_hybrid_rules,
)

RULE_SETS = {
    "fsdp": lambda: ShardingRules(),
    "llama": llama_rules,
    "llama_pp": llama_pp_rules,
    "moe": moe_rules,
    # dropless expert-parallel ("grouped_ep" dispatch): expert FFN dims
    # unsharded so the grouped Pallas kernel stays per-shard inside its
    # shard_map; experts over (data x fsdp) as in "moe"
    "moe_ep": moe_ep_rules,
    "bert": bert_rules,
    "bert_pp": bert_pp_rules,
    "clip": clip_rules,
    "neox": neox_rules,
    "neox_pp": neox_pp_rules,
    "glm": glm_rules,
    "glm_pp": glm_pp_rules,
    "gpt2_pp": gpt2_pp_rules,
    "sambay": sambay_rules,
    "mla_moe": mla_moe_rules,
    "gqa_moe": gqa_moe_rules,
    "delta_hybrid": delta_hybrid_rules,
    "ssd_hybrid": ssd_hybrid_rules,
    "kda_mla_moe": kda_mla_moe_rules,
    "looped": looped_rules,
}


@dataclass
class DtypePolicy:
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    output_dtype: str = "float32"


@dataclass
class Strategy:
    mesh: MeshPlan = field(default_factory=MeshPlan)
    rule_set: str = "fsdp"
    remat_policy: str = ""  # "", "full", "dots_saveable", "nothing_saveable"
    dtypes: DtypePolicy = field(default_factory=DtypePolicy)
    grad_accum_steps: int = 1
    # pipeline schedule: virtual stages per physical stage (V>1 = the
    # circular/interleaved schedule, PiPPy StageInterleaver parity —
    # bubble shrinks (P-1)/(M+P-1) -> (P-1)/(V*M+P-1)). Consumed by
    # model forwards via ``apply_pipelined(..., num_virtual=...)``.
    num_virtual: int = 1
    # uneven pipeline stage split: per-stage-chunk layer counts (V*P
    # entries in visit order, summing to the model's layer count). None
    # = even split. Lets the planner place a lighter first/last stage
    # (embed/head-adjacent) or handle L % (V*P) != 0 — reference's
    # uneven stage placement (atorch base_stage_planner.py:125).
    # Consumed by ``apply_pipelined(..., stage_depths=...)``.
    stage_depths: Optional[Tuple[int, ...]] = None
    # global batch row count; accelerate() validates the example batch
    # against it and adjust_to_world keeps accum a divisor of it.
    # 0 = derived from the example batch at accelerate() time.
    global_batch_size: int = 0

    def rules(self) -> ShardingRules:
        factory = RULE_SETS.get(self.rule_set)
        if factory is None:
            raise ValueError(
                f"unknown rule set {self.rule_set!r}; "
                f"have {sorted(RULE_SETS)}"
            )
        return factory()

    # -- elasticity ---------------------------------------------------------

    def adjust_to_world(self, num_devices: int,
                        prev_num_devices: Optional[int] = None) -> "Strategy":
        """Re-fit after a membership change, keeping the global batch fixed.

        The DP degree changes with the world; grad_accum_steps scales
        inversely so batch_per_device * dp * accum stays constant
        (ElasticTrainer semantics, ``elastic.py:387-401``).
        """
        new_mesh = self.mesh.adjust_to_world(num_devices)
        accum = self.grad_accum_steps
        if prev_num_devices and prev_num_devices != num_devices:
            old_dp = max(1, self.mesh.adjust_to_world(prev_num_devices).dp_degree)
            new_dp = max(1, new_mesh.dp_degree)
            accum = max(1, round(self.grad_accum_steps * old_dp / new_dp))
            if self.global_batch_size > 0:
                # accum must divide the per-step batch or the microbatch
                # reshape in accelerate() fails: snap to the nearest
                # divisor of the global batch.
                divisors = [
                    d for d in range(1, self.global_batch_size + 1)
                    if self.global_batch_size % d == 0
                ]
                accum = min(divisors, key=lambda d: abs(d - accum))
        return dataclasses.replace(self, mesh=new_mesh,
                                   grad_accum_steps=accum)

    # -- persistence (reference strategies are picklable; ours are JSON) ----

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)

    @classmethod
    def from_json(cls, text: str) -> "Strategy":
        raw = json.loads(text)
        raw["mesh"] = MeshPlan(**raw.get("mesh", {}))
        raw["dtypes"] = DtypePolicy(**raw.get("dtypes", {}))
        if raw.get("stage_depths") is not None:
            raw["stage_depths"] = tuple(raw["stage_depths"])
        return cls(**raw)

    def save(self, path: str):
        with open(path, "w") as f:
            f.write(self.to_json())

    @classmethod
    def load(cls, path: str) -> "Strategy":
        with open(path) as f:
            return cls.from_json(f.read())
