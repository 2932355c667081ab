"""Is a configuration's train step the same program in two trees?

Lowers the train step of each named configuration of the tree given
(``chipbench/configs/<name>.json`` through ``worker.build_job``, Mosaic
forced, for a described ``v5e:2x2``, no chip) and writes a sha256 of
what means something in it: the module printed without locations, its
kernel bodies taken out, and every ``tpu_custom_call``'s body decoded
and printed the same way (a Pallas body carries the path, the lines and
the columns of its call stack: the raw text differs between two
checkouts that compute alike, ``.claude/skills/verify/SKILL.md``).

    python tests/lowered_step_digests.py <tree> <out.json> <config> ...

once a tree (a ``git archive`` of the parent under ``_parent/``, and
``.``), each in a process of its own; equal files, equal programs. A
PR that adds a model runs it over the configurations whose modules it
touched and says the counts in ``CHANGES.md`` (PR 57: four
configurations, 4 + 28 + 16 + 97 kernels).
"""

import base64
import functools
import hashlib
import json
import os
import re
import sys

BODY = r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22'


def main(tree, out, names):
    tree = os.path.abspath(tree)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [tree, os.path.join(tree, "tests")]
    os.chdir(tree)
    import jax
    import numpy as np
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    from jax.experimental import topologies

    import importlib

    from chipbench import worker
    from dlrover_tpu.parallel.accelerate import accelerate
    from hlo_checks import lower_step

    jax.config.update("jax_enable_compilation_cache", False)
    devices = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)
    # traced on a CPU host: the interpreter would be taken unless told.
    # A tree from before a module (the parent of the PR that added it)
    # goes without it
    for module, config, switch in (
            ("gqa_moe", "GqaMoeConfig", "kernel_interpret"),
            ("mla_moe", "MlaMoeConfig", "kernel_interpret"),
            ("ssd_hybrid", "SsdHybridConfig", "kernel_interpret"),
            ("kda_mla_moe", "KdaMlaMoeConfig", "kernel_interpret"),
            ("sambay", "SambaYConfig", "kernel_interpret"),
            ("delta_hybrid", "DeltaHybridConfig", "kernel_interpret"),
            ("looped", "LoopedConfig", "kernel_interpret"),
            ("llama", "LlamaConfig", "flash_interpret")):
        if os.path.exists(os.path.join(tree, "dlrover_tpu", "models",
                                       module + ".py")):
            module = importlib.import_module("dlrover_tpu.models." + module)
            setattr(module, config, functools.partial(
                getattr(module, config), **{switch: False}))

    def plain(raw):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            return ir.Module.parse(raw).operation.get_asm(
                enable_debug_info=False)

    def sha(text):
        return hashlib.sha256(text.encode()).hexdigest()

    digests = {}
    for name in names:
        with open(os.path.join(tree, "chipbench", "configs",
                               name + ".json")) as f:
            model = json.load(f)
        job = worker.build_job(model)
        rows = model["assumed"]["batch"]
        example = {key: np.zeros((rows, job.seq_len), np.int32)
                   for key in ("input_ids", "labels")}
        lowered = lower_step(accelerate(
            job.init_fn, job.loss_fn,
            worker.build_optimizer(model["assumed"]["optimizer"]), example,
            strategy=job.strategy, devices=devices[:model["chips"]]), example)
        text = lowered.compiler_ir("stablehlo").operation.get_asm(
            enable_debug_info=False)
        bodies = [plain(base64.b64decode(m.group(1)))
                  for m in re.finditer(BODY, text)]
        digests[name] = {"module_sha": sha(re.sub(BODY, "body", text)),
                         "kernels": len(bodies),
                         "bodies_sha": sha("\n".join(bodies))}
        print(name, digests[name], flush=True)
    with open(out, "w") as f:
        json.dump(digests, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], sys.argv[3:])
