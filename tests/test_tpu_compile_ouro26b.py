"""The ``ouro-2.6b-d12-1chip`` configuration's whole train step, asked
of the v5e's own compiler with no chip attached (see
``test_tpu_compile.py``). Cold (nothing in ``~/.cache/dlrover_tpu/
xla_cache/tpu_compiles/``) the compile takes 19 s alone on this 8-core
box (PR 65); a later run reads the record and costs the step's
lowering, so the test is not marked slow: the whole run that a builder
makes before finishing leaves the driver's run the record.
"""

import json
import os
import re

import numpy as np
from hlo_checks import _peak_bytes, _resident_bytes, compile_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_ouro26b_step_fits_one_v5e(v5e, monkeypatch):
    """The benchmark's ``ouro-2.6b-d12-1chip`` configuration through its
    own job builder: the whole train step (12 layers scanned inside the
    scan over four passes, each layer its own checkpoint, the exit gate
    and the head over all four passes' states under the exit
    distribution's weights, AdamW) compiles for one v5e chip at the
    configuration's one row of 8192, with the plain flash kernels in it
    and no float score matrix; the three kernels have ONE layer's call
    sites (the forward pass's ``flash_fwd`` and its replay's, one
    ``flash_dkv`` and one ``flash_dq``), not 4 x 12 of them: the loop is
    no Python unrolling of four stacks; what the compiler allocates at
    the step's peak is at or under the 15.0 GB ISSUE 65 allowed
    (``hlo_checks._peak_bytes``; ``_resident_bytes`` is printed beside
    it; the configuration's ``reduced`` has the reading at each rung;
    ``OURO_COMPILE_OPTIMIZER``, a JSON object, and
    ``OURO_COMPILE_LAYERS`` try another)."""
    import functools

    from chipbench import worker
    from dlrover_tpu.models import looped
    from dlrover_tpu.parallel.accelerate import accelerate

    with open(os.path.join(REPO, "chipbench", "configs",
                           "ouro-2.6b-d12-1chip.json")) as fh:
        model = json.load(fh)
    committed = (model["assumed"]["optimizer"], model["num_hidden_layers"])
    optimizer = json.loads(os.environ.get(
        "OURO_COMPILE_OPTIMIZER", json.dumps(committed[0])))
    layers = int(os.environ.get("OURO_COMPILE_LAYERS", committed[1]))
    model["num_hidden_layers"] = layers
    monkeypatch.setattr(looped, "LoopedConfig", functools.partial(
        looped.LoopedConfig, kernel_interpret=False))
    job = worker.build_job(model)
    assert (job.seq_len, job.layers) == (8192, layers)
    if layers == 12:
        assert job.param_count == 817_991_681
    example = {"input_ids": np.zeros((1, job.seq_len), np.int32),
               "labels": np.zeros((1, job.seq_len), np.int32)}
    result = accelerate(
        job.init_fn, job.loss_fn, worker.build_optimizer(optimizer),
        example, strategy=job.strategy, devices=v5e[:1],
    )
    compiled = compile_step(result, example)
    text = compiled.as_text()
    peak = _peak_bytes(compiled)
    print(f"ouro26b train_step at {layers} layers under "
          f"{optimizer['name']} ({job.param_count / 1e9:.3f} B "
          f"parameters): {peak / 1e9:.2f} GB allocated at the peak, "
          f"{_resident_bytes(compiled) / 1e9:.2f} GB estimated")
    if os.environ.get("OURO_COMPILE_TEXT"):
        with open(os.environ["OURO_COMPILE_TEXT"], "w") as fh:
            fh.write(text)
    # one layer's call sites: the forward kernel in the forward pass and
    # in the layer's replay, the backward's two once each
    assert [len(re.findall(rf"%{name}\.\d+ = ", text)) for name in (
        "flash_fwd", "flash_dkv", "flash_dq")] == [2, 1, 1]
    # a scope is a component of an ``op_name``, the outermost one inside
    # ``jvp(...)``
    for scope in ("attn_full", "ffn", "exit_gate", "head_loss"):
        assert re.search(rf"[/(]{scope}[/)]", text), scope
    # no score matrix a head
    assert not re.search(r"(f32|bf16)\[(\d,)?16,8192,8192\]", text)
    assert peak <= 15.0e9, f"{peak / 1e9:.2f} GB"
