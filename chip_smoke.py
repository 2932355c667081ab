#!/usr/bin/env python3
"""chip_smoke.py — the standing check that the main path starts on the chip.

    python chip_smoke.py             # one chip: kernels, train, resume
    python chip_smoke.py --chips 4   # four chips: fsdp=4 against one device

The main path of this system is a training job under the elastic
launcher: ``tpurun --standalone`` -> local master subprocess ->
``ElasticTrainingAgent`` -> ``WorkerGroup`` -> worker
(``examples/train_llama.py``) -> ``ElasticTrainer`` -> ``accelerate``
-> ``TrainExecutor.train_and_evaluate``, with a checkpoint, a killed
worker, and a restart that resumes. The default run drives it once at
the full width of Llama-2-7B, with one process on the chip at a time:

  kernels  one child: the Mosaic flash forward and backward at the
           model's head shape against ``ops/attention_ref.mha_reference``
           in float32; the lowered text contains ``tpu_custom_call``.
  train    ``tpurun --standalone --nnodes 1`` on the worker: every loss
           finite, seconds per completed step, compile apart, peak bytes.
  resume   in that same launcher run, after the first committed
           checkpoint the worker is SIGKILLed; the agent must restart
           it, the restart must get the chip, restore the committed
           step and train past it.

This process never imports JAX (a parent that has touched JAX holds the
chip, and the child that needs it then fails or hangs): the device
facts come from the lines the children write. Every phase prints one
JSON line; any failed phase makes the exit code non-zero. Finding no
TPU fails within seconds, before any compile. The last line of stdout
is exactly ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": N}}``, or ``{"ok": false, ...}``.

No number printed here is a benchmark result: it is what a smoke run
observed, on random weights made from a seed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# -- the size, defined once ---------------------------------------------------
# Llama-2-7B at its published widths: hidden 4096, FFN 11008, 32 heads
# of 128, vocabulary 32000, 4096-token rows (the worker's ``7b`` preset),
# bf16 parameters and compute, Mosaic flash attention on.
# THE CUT, forced by one 16 GB v5e (15.75 GB usable), settled with the
# deviceless v5e compile (tests/test_tpu_compile.py), not on the chip:
#   depth      8 of 32 layers (1,881,214,976 parameters; 12 layers need
#              19.4 GB);
#   optimizer  adafactor in place of the example's adamw (two f32
#              moments per parameter: 17.4 GB at this depth);
#   memory     full per-layer remat and the lm head fused with the loss
#              over 1024-token chunks (no [B, S, V] f32 logits).
MODEL_ARGS = (
    "--preset", "7b", "--layers", "8", "--seq", "4096",
    "--param_dtype", "bfloat16", "--optimizer", "adafactor",
    "--remat", "full", "--head_chunk", "1024",
)
CUT = {"layers": "8 of 32", "optimizer": "adafactor (example: adamw)",
       "remat": "full", "head_chunk": 1024, "param_dtype": "bfloat16"}
ONE_CHIP_BATCH = 2  # 11.2 GB by the deviceless compile
FOUR_CHIP_BATCH = 4  # 6.0 GB a chip at fsdp=4; 14.8 GB on one device
TRAIN_STEPS = 20  # far enough that the kill lands mid-run
CKPT_EVERY = 5  # 3.8 GB a save: four clean steps between two stalls
COMPARE_STEPS = 3

# Flash kernel against the float32 reference, on bf16 inputs: both take
# the same bf16 q/k/v, so what differs is the kernel's own rounding —
# probabilities and dS are cast to bf16 (8 significant bits, 2^-8 =
# 0.0039 relative) before their MXU matmuls, and the outputs are stored
# in bf16. Row 0 attends one key, so |out| reaches |v| ~ 4 there and one
# bf16 rounding is already 0.016. |err| <= TOL * (1 + |ref|) bounds a
# few such roundings and is ~50x below the error of a wrong mask or a
# dropped block (those are O(1)).
KERNEL_TOL = 2e-2
# fsdp=4 against one device, same seed and global batch: the weights
# and the batches are the same, but four devices sum the batch's rows
# and the gradients in another order (per-device partials, then a
# reduction), in bf16 matmuls — so the f32 losses agree closely, not
# bitwise, and the updates carry the difference forward. The first run
# on the chip differed by 1.0e-4 at a loss of 10.86; 5e-3 is 50x that
# and still far below what a shard trained on the wrong rows or a
# dropped gradient would show (0.1 and up).
LOSS_TOL = 5e-3


class PhaseFailed(RuntimeError):
    pass


def _child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _json_lines(text):
    out = []
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass  # a torn or foreign line
    return out


def _kill_tree(proc):
    """Stop ``proc`` and everything it started (workers run in their own
    sessions, so a process-group kill would miss them)."""
    import psutil

    try:
        procs = psutil.Process(proc.pid).children(recursive=True)
    except psutil.NoSuchProcess:
        procs = []
    for p in procs + [proc]:
        try:
            p.kill()
        except (psutil.NoSuchProcess, ProcessLookupError):
            pass
    proc.wait()


def _run_child(name, timeout):
    """Re-run this script as one child that owns the chip; returns the
    phase line it printed (``ok`` false if it failed its checks). Its
    stderr passes through."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", name]
    proc = subprocess.Popen(cmd, env=_child_env(), cwd=REPO,
                            stdout=subprocess.PIPE, text=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise PhaseFailed(f"{name} child exceeded its {timeout:.0f}s "
                          "time limit")
    finally:
        if proc.poll() is None:
            _kill_tree(proc)
    lines = _json_lines(out)
    line = next((ln for ln in reversed(lines) if "phase" in ln), None)
    if line is None:
        err = next((ln["error"] for ln in reversed(lines)
                    if "error" in ln), "")
        raise PhaseFailed(f"{name} child exited {proc.returncode}"
                          + (f": {err}" if err else ""))
    line["ok"] = bool(line["ok"]) and proc.returncode == 0
    return line


# -- phase: kernels -----------------------------------------------------------


def _require_tpu(count):
    import jax

    devices = jax.devices()
    facts = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if facts["platform"] != "tpu" or facts["count"] != count:
        print(json.dumps({"error": f"need {count} TPU device(s), JAX "
                                   f"found {facts}"}), flush=True)
        sys.exit(3)
    return facts


def _child_kernels():
    device = _require_tpu(1)  # before any compile
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.ops.attention_ref import mha_reference
    from dlrover_tpu.ops.flash_attention import flash_attention

    # the 7B head shape (32 heads of 128 over 4096 tokens) and the
    # model's own flash tiles
    cfg = LlamaConfig()
    shape = (ONE_CHIP_BATCH, cfg.num_heads, cfg.max_seq_len, cfg.head_dim)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, shape, jnp.bfloat16)
                   for kk in keys)

    def flash(q, k, v):
        # interpret=None: what the model path passes — on this backend
        # that must be the Mosaic kernel, which the lowered text proves
        return flash_attention(q, k, v, True, None, cfg.flash_block_q,
                               cfg.flash_block_k, None)

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(flash, q, k, v)
        return (out, *vjp(do))

    step = jax.jit(fwd_bwd)
    lowered = step.lower(q, k, v, do)
    custom_calls = lowered.as_text().count("tpu_custom_call")
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    got = jax.block_until_ready(compiled(q, k, v, do))
    t0 = time.time()
    got = jax.block_until_ready(compiled(q, k, v, do))
    run_s = time.time() - t0

    # the float32 reference on a seeded sample of (batch, head) pairs:
    # heads are independent (32 kv heads), so each pair's output and
    # gradients depend on that pair's slices alone
    rng = np.random.RandomState(0)
    sample = sorted(rng.choice(shape[1], size=4, replace=False).tolist())

    @jax.jit
    def ref_fwd_bwd(q, k, v, do):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(
                lambda q, k, v: mha_reference(q, k, v, causal=True),
                q, k, v)
            return (out, *vjp(do))

    errs = {}
    for b in range(shape[0]):
        pick = lambda x: x[b:b + 1, sample].astype(jnp.float32)  # noqa: E731
        ref = ref_fwd_bwd(pick(q), pick(k), pick(v), pick(do))
        for name, g, r in zip(("out", "dq", "dk", "dv"), got, ref):
            g = np.asarray(pick(g))
            r = np.asarray(r)
            errs[name] = max(errs.get(name, 0.0), float(np.max(
                np.abs(g - r) / (1.0 + np.abs(r)))))
    finite = all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
                 for x in got)
    ok = (custom_calls >= 1 and finite
          and all(e <= KERNEL_TOL for e in errs.values()))
    print(json.dumps({
        "phase": "kernels", "ok": ok,
        "shape": list(shape), "dtype": "bfloat16",
        "tiles": [cfg.flash_block_q, cfg.flash_block_k],
        "tpu_custom_call": custom_calls, "finite": finite,
        "sampled_heads": sample,
        "max_err_over_1_plus_ref": {k: round(e, 5)
                                    for k, e in errs.items()},
        "tolerance": KERNEL_TOL,
        "compile_s": round(compile_s, 2),
        "fwd_bwd_s": round(run_s, 4),
        "device": device,
    }), flush=True)
    return 0 if ok else 1


# -- phases: train and resume -------------------------------------------------


class _WorkerLog:
    """The JSON lines one worker round wrote to its redirected log."""

    def __init__(self, log_dir, restart_round):
        self.path = os.path.join(log_dir, f"worker_0_r{restart_round}.log")

    def lines(self):
        try:
            with open(self.path, errors="replace") as f:
                return _json_lines(f.read())
        except OSError:
            return []

    def first(self, event):
        return next((r for r in self.lines()
                     if r.get("event") == event), None)

    def steps(self):
        return [r for r in self.lines() if r.get("event") == "step"]

    def tail(self, n=25):
        try:
            with open(self.path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""


def _committed_step(ckpt_dir):
    """Newest COMMITTED checkpoint: Orbax renames its temporary
    directory to ``<step>`` when the write is complete."""
    try:
        steps = [int(n) for n in os.listdir(ckpt_dir) if n.isdigit()]
    except OSError:
        return None
    return max(steps) if steps else None


def train_and_resume(model_args, batch, steps, ckpt_every, work_dir,
                     log_dir, timeout=840.0):
    """Drive one ``tpurun --standalone`` job through train, kill and
    resume. Returns (train_line, resume_line, device_facts); raises
    PhaseFailed with the reason otherwise. The size comes from the
    caller: the script's own run passes ``MODEL_ARGS``."""
    from dlrover_tpu.diagnosis.fault_injection import kill_workers

    ckpt_dir = os.path.join(work_dir, "ckpt")
    os.makedirs(log_dir, exist_ok=True)
    for name in os.listdir(log_dir):
        if name.startswith("worker_") or name == "events.jsonl":
            os.remove(os.path.join(log_dir, name))
    events_file = os.path.join(log_dir, "events.jsonl")
    cmd = [
        sys.executable, "-m", "dlrover_tpu.trainer.run", "--standalone",
        "--nnodes", "1", "--log_dir", log_dir,
        "--events_file", events_file,
        # synchronous loop: each step's line is written when THAT step
        # has completed on the device, so the seconds between two lines
        # are one whole step (the default window of 4 lets the device
        # run ahead of the host and the lines arrive in bursts)
        "--train_window", "0",
        os.path.join(REPO, "examples", "train_llama.py"),
        *model_args, "--batch", str(batch), "--steps", str(steps),
        "--ckpt_dir", ckpt_dir, "--ckpt_every", str(ckpt_every),
    ]
    deadline = time.monotonic() + timeout
    first = _WorkerLog(log_dir, 0)
    # the agent's default budget is three restarts: a restart that
    # needed a second attempt is reported, not hidden
    restarts = [_WorkerLog(log_dir, r) for r in (1, 2, 3)]
    launcher_log = open(os.path.join(log_dir, "tpurun.log"), "w")
    launcher = subprocess.Popen(cmd, env=_child_env(), cwd=REPO,
                                stdout=launcher_log,
                                stderr=subprocess.STDOUT)

    def wait_for(what, probe):
        while time.monotonic() < deadline:
            got = probe()
            if got is not None:
                return got
            if launcher.poll() is not None:
                got = probe()  # one last look at what it flushed
                if got is not None:
                    return got
                raise PhaseFailed(
                    f"launcher exited {launcher.returncode} before "
                    f"{what}")
            time.sleep(0.1)
        raise PhaseFailed(f"time limit reached before {what}")

    try:
        worker = wait_for("the worker reported its device",
                          lambda: first.first("worker"))
        if not worker["master_addr"]:
            raise PhaseFailed("the launcher ran the script without a "
                              "master")
        device = {"platform": worker["platform"],
                  "kind": worker["device_kind"],
                  "count": worker["device_count"]}
        committed = wait_for("the first committed checkpoint",
                             lambda: _committed_step(ckpt_dir))
        # the train phase reads its steady steps from this round
        wait_for("the worker completed four steps",
                 lambda: first.steps()[3:] or None)
        committed = _committed_step(ckpt_dir)  # as of the kill
        if not kill_workers([worker["pid"]]):
            raise PhaseFailed("the worker was gone before the kill "
                              "(it finished or crashed: see its log)")
        t_kill = time.time()
        wait_for("the agent restarted the worker",
                 lambda: restarts[0].first("worker"))
        second = wait_for(
            "a restarted worker completed a step",
            lambda: next((log for log in restarts if log.steps()), None))
        restarted, start = second.first("worker"), second.first("start")
        first_step = second.steps()[0]
        try:
            rc = launcher.wait(timeout=max(1.0,
                                           deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            raise PhaseFailed("time limit reached before the job ended")
    except PhaseFailed:
        for log in [first, *restarts]:
            if os.path.exists(log.path):
                sys.stderr.write(f"--- {log.path}\n{log.tail()}")
        raise
    finally:
        if launcher.poll() is None:
            _kill_tree(launcher)
        launcher_log.close()

    before, after = first.steps(), second.steps()
    try:
        with open(events_file) as f:
            events = _json_lines(f.read())
    except OSError:
        events = []

    def event(kind, pid=None):
        return next((e for e in events if e.get("kind") == kind
                     and pid in (None, e.get("pid"))), {})

    losses = [r["loss"] for r in before + after]
    # one whole step each: not a round's first step (trace + compile),
    # not a step that ends in a checkpoint's device-to-host copy
    clean = sorted(r["seconds"] for r in before[1:] + after[1:]
                   if r["step"] % ckpt_every)
    median = clean[len(clean) // 2] if clean else None
    train = {
        "phase": "train", "with_master": True,
        "ok": median is not None and all(
            isinstance(x, float) and x == x and abs(x) != float("inf")
            for x in losses),
        "params": worker["params"], "layers": worker["layers"],
        "batch": batch, "optimizer": worker["optimizer"],
        "steps_before_kill": len(before),
        "losses_before_kill": [round(r["loss"], 4) for r in before],
        "first_step_s": before[0]["seconds"],
        "step_s_median": median, "step_s_min": clean[0] if clean else None,
        "step_s_max": clean[-1] if clean else None,
        "steps_timed": len(clean),
        "checkpoint_step_s": [r["seconds"] for r in before[1:] + after[1:]
                              if not r["step"] % ckpt_every],
        # the first step less one steady step: trace, the attribution
        # plane's AOT compile and the step's own compile (or cache read)
        "compile_s": (round(before[0]["seconds"] - median, 2)
                      if median is not None else None),
        # the allocator's peak (memory_stats); the compiled program's
        # own residency is what XLA's memory analysis says of the step
        "peak_bytes_in_use": max(
            (r["peak_bytes_in_use"] or 0 for r in before + after),
            default=0) or None,
        "bytes_limit": worker.get("bytes_limit"),
        "compiled_step_bytes": int(event("attribution_captured").get(
            "peak_hbm_mb", 0) * 1024 * 1024) or None,
        "device": device,
    }
    resumed = start["resumed_step"]
    final = after[-1]["step"] if after else None
    detected = event("worker_failed").get("ts")
    saves_after = [e["step"] for e in events
                   if e.get("kind") == "ckpt_save"
                   and e.get("pid") == restarted["pid"]]
    resume = {
        "phase": "resume",
        "killed_pid": worker["pid"], "committed_step_at_kill": committed,
        "resumed_step": resumed,
        "restart_round": restarted["restart_round"],
        "restarted_pid": restarted["pid"],
        "kill_to_first_step_s": round(first_step["t"] - t_kill, 2),
        # its parts: the agent saw the exit; the new process reached
        # train start (of which the restore); its first step
        "kill_to_detected_s": (round(detected - t_kill, 2)
                               if detected else None),
        "restart_boot_s": start["boot_seconds"],
        "restore_s": event("ckpt_restore", restarted["pid"]).get(
            "restore_seconds"),
        "restart_first_step_s": first_step["seconds"],
        "cache_hits": first_step["cache_hits"],
        "cache_misses": first_step["cache_misses"],
        "first_save_after_resume": saves_after[0] if saves_after else None,
        "losses_after_resume": [round(r["loss"], 4) for r in after],
        "final_step": final, "launcher_rc": rc,
    }
    problems = []
    if resumed <= 0:
        problems.append("the restore fell back to a fresh init (step 0)")
    elif resumed < committed:
        problems.append(f"resumed at {resumed}, before the committed "
                        f"step {committed}")
    if restarted["restart_round"] < 1:
        problems.append("the restart round did not rise")
    if not restarted["master_addr"]:
        problems.append("the restart ran without a master")
    if saves_after and saves_after[0] < min(resumed + ckpt_every, steps):
        problems.append(f"a save at step {saves_after[0]}, right after "
                        f"the restore of step {resumed}")
    if rc != 0:
        problems.append(f"the launcher exited {rc}")
    if final != steps:
        problems.append(f"the job ended at step {final}, not {steps}")
    elif final <= resumed:
        problems.append("no step completed past the checkpoint")
    resume["ok"] = not problems
    if problems:
        resume["error"] = "; ".join(problems)
    return train, resume, device


# -- four chips ---------------------------------------------------------------


def _run_job(plan, devices, batch, steps):
    """A few steps of the smoke's model through ``ElasticTrainer`` on
    ``devices`` under ``plan``, in this process. A fresh job each time:
    the same seed gives the same weights and the same batches."""
    import gc

    import jax

    sys.path.insert(0, os.path.join(REPO, "examples"))
    import train_llama

    from dlrover_tpu.models import llama
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    args = train_llama.build_parser().parse_args(
        [*MODEL_ARGS, "--batch", str(batch)])
    config, strategy, loss_fn, optimizer, batches = train_llama.build_job(
        args, plan)
    trainer = ElasticTrainer(
        llama.make_init_fn(config), loss_fn, optimizer, next(batches()),
        strategy=strategy, devices=devices)
    state = trainer.prepare()
    stream = batches()
    losses, seconds = [], []
    for _ in range(steps):
        t0 = time.time()
        state, metrics = trainer.step(state, next(stream))
        losses.append(float(metrics["loss"]))  # waits for the step
        seconds.append(round(time.time() - t0, 3))
    leaves = jax.tree.leaves(state.params)
    facts = {
        "losses": losses, "seconds": seconds,
        "param_bytes": sum(x.nbytes for x in leaves),
        # what each device holds of the parameters, by their shards
        "param_bytes_per_device": [
            sum(s.data.nbytes for x in leaves
                for s in x.addressable_shards if s.device == d)
            for d in devices],
        "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                         for d in devices],
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices],
        # the compiled step's collectives, by the repo's own parse
        "collective_bytes": {
            k: int(v) for k, v in
            trainer.attribution().collective_bytes.items()},
    }
    del state, trainer, leaves
    gc.collect()
    return facts


def _child_four_chips():
    device = _require_tpu(4)  # before any compile
    import jax
    import numpy as np

    sys.path.insert(0, REPO)
    from dlrover_tpu.parallel.mesh import MeshPlan

    devices = jax.devices()
    sharded = _run_job(MeshPlan(data=1, fsdp=4), devices,
                       FOUR_CHIP_BATCH, COMPARE_STEPS)
    single = _run_job(MeshPlan(data=1, fsdp=1), devices[:1],
                      FOUR_CHIP_BATCH, COMPARE_STEPS)
    diffs = [abs(a - b) for a, b in zip(sharded["losses"],
                                        single["losses"])]
    ok = (all(np.isfinite(sharded["losses"] + single["losses"]))
          and max(diffs) <= LOSS_TOL
          # a quarter each, but for the norm scales (replicated)
          and max(sharded["param_bytes_per_device"])
          <= 0.26 * sharded["param_bytes"]
          and sum(sharded["collective_bytes"].values()) > 0)
    print(json.dumps({
        "phase": "four_chips", "ok": bool(ok),
        "plan": "MeshPlan(data=1, fsdp=4)", "batch": FOUR_CHIP_BATCH,
        "losses_fsdp4": sharded["losses"],
        "losses_one_device": single["losses"],
        "max_loss_diff": max(diffs), "tolerance": LOSS_TOL,
        "param_bytes": sharded["param_bytes"],
        "param_bytes_per_device": sharded["param_bytes_per_device"],
        "bytes_in_use_per_device": sharded["bytes_in_use"],
        "peak_bytes_in_use_per_device": sharded["peak_bytes_in_use"],
        "collective_bytes_per_step": sharded["collective_bytes"],
        "one_device_peak_bytes_in_use": single["peak_bytes_in_use"][0],
        "step_s_fsdp4": sharded["seconds"],
        "step_s_one_device": single["seconds"],
        "device": device,
    }), flush=True)
    return 0 if ok else 1


# -- entry --------------------------------------------------------------------


def _finish(ok, device=None, error=""):
    last = {"ok": bool(ok)}
    if device:
        last["device"] = device
    if error:
        last["error"] = error
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=[1, 4],
                        help="4 runs ONLY the fsdp=4 path and the "
                             "one-device run it is compared with")
    parser.add_argument("--child", default="", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        return {"kernels": _child_kernels,
                "four_chips": _child_four_chips}[args.child]()
    if not os.path.isdir(os.path.join(REPO, "dlrover_tpu")):
        sys.stderr.write("chip_smoke.py checks the repository it sits "
                         "in; there is none here\n")
        return _finish(False, error="no repository beside the script")

    print(json.dumps({"model": "Llama-2-7B widths", "args": MODEL_ARGS,
                      "cut": CUT, "chips": args.chips}), flush=True)
    device = None
    try:
        if args.chips == 4:
            line = _run_child("four_chips", timeout=1100.0)
            print(json.dumps(line), flush=True)
            return _finish(line["ok"], line["device"])
        line = _run_child("kernels", timeout=240.0)
        print(json.dumps(line), flush=True)
        device = line["device"]
        if not line["ok"]:
            return _finish(False, device, "kernels phase failed")
        work_dir = tempfile.mkdtemp(prefix="chip_smoke_")
        try:
            train, resume, device = train_and_resume(
                MODEL_ARGS, ONE_CHIP_BATCH, TRAIN_STEPS, CKPT_EVERY,
                work_dir,
                log_dir=os.path.join(REPO, "chiprun_out", "chip_smoke"))
        finally:
            shutil.rmtree(work_dir, ignore_errors=True)
            _remove_staging_mirror(os.path.join(work_dir, "ckpt"))
        print(json.dumps(train), flush=True)
        print(json.dumps(resume), flush=True)
        ok = (train["ok"] and resume["ok"]
              and device["platform"] == "tpu" and device["count"] == 1)
        return _finish(ok, device,
                       "" if ok else resume.get("error", "train failed"))
    except PhaseFailed as e:
        return _finish(False, device, str(e))


def _remove_staging_mirror(ckpt_dir):
    """The checkpoint manager mirrors the newest step into host DRAM
    (``/dev/shm``); a smoke run must not leave gigabytes there."""
    import hashlib

    shutil.rmtree(os.path.join(
        "/dev/shm", "dlrover_tpu_ckpt",
        hashlib.md5(os.path.abspath(ckpt_dir).encode()).hexdigest()[:12],
    ), ignore_errors=True)


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(main())
