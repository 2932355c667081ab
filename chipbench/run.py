#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python chipbench/run.py --workload <name> --seed <n> \\
        --seconds <run_seconds> --trace <0|1|2>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``configs/<config>.json``) under a traffic mix
(``traffic/<traffic>.json``). This process starts the program's own
launcher (``tpurun --standalone`` -> master -> agent -> worker) on
the configuration's worker (``worker.py``), reads the worker's JSON
lines and the launcher's ``events.jsonl``, kills the worker where the
mix says so, fixes the measured window, and prints the result as the
last line of stdout. It never imports JAX: the worker needs the chip.

Set-up runs from the start of this command to the start of the window:
launcher, master, worker boot, state init, compile or cache read, the
reference check, the warm-up steps and, in a mix that kills, the save,
the SIGKILL, the agent's restart and the restore. The window starts
when the last warm-up step's loss has reached the host (in a mix that
kills: the restarted worker's) and ends with the last step that
completed within ``--seconds`` of that: rates are taken over all the
steps and all the time between those two step completions.

``--trace 0`` prints the end-to-end metrics. ``--trace 2`` is the same
run up to the end of the window, with the same numbers from it; then,
before the stream is stopped, it opens the program's own profiling
window in the worker (the executor's ``profile_signal``), waits for
the worker's ``profile_window`` event, reduces that trace and prints
the per-layer metrics beside the end-to-end ones. ``--trace 1`` is the
older form: a run of its own whose worker traces the first steps after
the warm-up through the benchmark's hook, per-layer metrics only.

Without a TPU (and without ``--rehearsal``, which the tests use) it
fails within seconds: exit code 3, the reason on stderr, no result.
"""

import argparse
import glob
import hashlib
import importlib.util
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".chipbench_work")  # checkpoints, raw traces
LOGS = os.path.join(ROOT, "chiprun_out", "chipbench")  # small, kept

SETUP_LIMIT_S = 1100.0  # a cell's first run in a checkout compiles
AFTER_WINDOW_LIMIT_S = 120.0  # drain, the closing save, exit
# the steps run twice (after the restored step, before the kill) run
# the same compiled program on the same state and the same batches, so
# they reproduce bitwise (and did, on the chip and on the CPU); 1e-5 of
# the loss allows a last-digit difference in the float32 loss and
# nothing else
REPLAY_TOL = 1e-5


class RunFailed(RuntimeError):
    pass


def load(path):
    with open(path) as f:
        return json.load(f)


def json_lines(path):
    out = []
    try:
        with open(path, errors="replace") as f:
            for line in f:
                line = line.strip()
                if line.startswith("{"):
                    try:
                        out.append(json.loads(line))
                    except json.JSONDecodeError:
                        pass  # a torn or foreign line
    except OSError:
        pass
    return out


class Round:
    """The JSON lines one worker round wrote to its redirected log."""

    def __init__(self, log_dir, restart_round):
        self.path = os.path.join(log_dir, f"worker_0_r{restart_round}.log")

    def lines(self):
        return json_lines(self.path)

    def first(self, event):
        return next((r for r in self.lines() if r.get("event") == event),
                    None)

    def steps(self):
        return [r for r in self.lines() if r.get("event") == "step"]


def kill_tree(proc):
    """Stop ``proc`` and everything it started (workers run in their own
    sessions, so a process-group kill would miss them), and wait."""
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
    psutil.wait_procs(procs, timeout=30)


def committed_steps(ckpt_dir):
    """Committed checkpoints: Orbax renames its temporary directory to
    ``<step>`` when the write is complete."""
    try:
        return sorted(int(n) for n in os.listdir(ckpt_dir) if n.isdigit())
    except OSError:
        return []


def staging_mirror(ckpt_dir):
    """Where the checkpoint manager mirrors the newest step into host
    DRAM; a run must not leave gigabytes there."""
    return os.path.join(
        "/dev/shm", "dlrover_tpu_ckpt",
        hashlib.md5(os.path.abspath(ckpt_dir).encode()).hexdigest()[:12])


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path):
    """A file under ``chipbench/`` found by name, as a module."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + re.sub(r"\W", "_", os.path.relpath(path, HERE)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def drive(args, cell, model, model_file, traffic, log_dir, work_dir):
    """Launch the job, kill and resume where the mix says so, fix the
    window. Returns everything the metrics are computed from."""
    ckpt_dir = os.path.join(work_dir, "ckpt")
    stop_file = os.path.join(work_dir, "stop")
    trace_dir = os.path.join(work_dir, "trace") if args.trace else ""
    events_file = os.path.join(log_dir, "events.jsonl")
    saves = bool(traffic.get("save_every_steps"))
    kills = traffic.get("kill", "never") != "never"
    cmd = [sys.executable, "-m", "dlrover_tpu.trainer.run", "--standalone",
           "--nnodes", "1", "--log_dir", log_dir,
           "--events_file", events_file]
    if "train_window" in traffic:  # absent: the launcher's default
        cmd += ["--train_window", str(traffic["train_window"])]
    cmd += [os.path.join(HERE, model["worker"]), "--config", model_file,
            "--traffic", os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json"),
            "--seed", str(args.seed), "--stop_file", stop_file]
    if saves:
        cmd += ["--ckpt_dir", ckpt_dir]
    if args.trace == 1:
        cmd += ["--trace_dir", trace_dir]
    elif args.trace == 2:  # arms the program's control, no more
        cmd += ["--profile_dir", trace_dir]
    if args.rehearsal:
        cmd += ["--rehearsal"]
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")

    rounds = [Round(log_dir, r) for r in range(4)]
    first = rounds[0]
    launcher_log = open(os.path.join(log_dir, "tpurun.log"), "w")
    launcher = subprocess.Popen(cmd, env=env, cwd=ROOT,
                                stdout=launcher_log,
                                stderr=subprocess.STDOUT)
    deadline = [T0 + SETUP_LIMIT_S]

    def wait_for(what, probe, poll=0.05):
        while time.time() < deadline[0]:
            got = probe()
            if got is not None:
                return got
            error = first.first("error")
            if error is not None:
                raise RunFailed(error["error"])
            if launcher.poll() is not None:
                got = probe()  # one last look at what it flushed
                if got is not None:
                    return got
                raise RunFailed(f"the launcher exited "
                                f"{launcher.returncode} before {what}")
            time.sleep(poll)
        raise RunFailed(f"time limit reached before {what}")

    def step_line(log, step):
        return next((r for r in log.steps() if r["step"] == step), None)

    out = {"t_kill": None, "committed_at_kill": None}
    try:
        worker = wait_for("the worker reported its device",
                          lambda: first.first("worker"))
        if not worker["master_addr"]:
            raise RunFailed("the launcher ran the worker without a master")
        measured = first
        if kills:
            saved = traffic["first_save_step"]

            def ready_to_kill():
                done = committed_steps(ckpt_dir)
                if (done and done[-1] >= saved and step_line(
                        first, saved + traffic["steps_before_kill"])):
                    return done[-1]
                return None

            out["committed_at_kill"] = wait_for(
                "the first save committed", ready_to_kill)
            out["t_kill"] = time.time()
            os.kill(worker["pid"], signal.SIGKILL)
            measured = wait_for(
                "a restarted worker began to train", lambda: next(
                    (log for log in rounds[1:] if log.first("start")),
                    None))
            worker = measured.first("worker")
        begun = wait_for("the worker began to train",
                         lambda: measured.first("start"))["resumed_step"]
        start = wait_for("the warm-up steps completed", lambda: step_line(
            measured, begun + traffic["warmup_steps"]))
        t_start = start["t"]
        t_limit = t_start + args.seconds
        deadline[0] = t_limit + AFTER_WINDOW_LIMIT_S
        wait_for("the window ended", lambda: next(
            (r for r in measured.steps() if r["t"] > t_limit), None))
        if args.trace == 2:
            # the window is closed and its numbers stand; the worker
            # goes on with the same traffic, and its next steps are
            # traced through the program's own control
            os.kill(worker["pid"], signal.SIGUSR2)
            out["profile_window"] = wait_for(
                "the profiling window closed", lambda: next(
                    (e for e in json_lines(events_file)
                     if e.get("kind") == "profile_window"
                     and e.get("pid") == worker["pid"]), None), poll=0.2)
        open(stop_file, "w").close()
        try:
            out["launcher_rc"] = launcher.wait(
                timeout=max(1.0, deadline[0] - time.time()))
        except subprocess.TimeoutExpired:
            raise RunFailed("time limit reached before the job ended")
    except RunFailed:
        for log in rounds:
            if os.path.exists(log.path):
                with open(log.path, errors="replace") as f:
                    sys.stderr.write(f"--- {log.path}\n"
                                     + "".join(f.readlines()[-25:]))
        raise
    finally:
        if launcher.poll() is None:
            kill_tree(launcher)
        launcher_log.close()
    out.update(
        worker=worker, t_start=t_start, t_limit=t_limit,
        rounds=[log.lines() for log in rounds],
        measured=rounds.index(measured),
        events=json_lines(events_file),
        committed_end=committed_steps(ckpt_dir), trace_dir=trace_dir)
    return out


def finite(x):
    return isinstance(x, float) and math.isfinite(x)


def steps_of(lines):
    return [r for r in lines if r.get("event") == "step"]


def window_facts(run, tokens_per_step):
    """The window, its steps and saves, and the end-to-end numbers that
    come from step lines and save events alone."""
    steps = steps_of(run["rounds"][run["measured"]])
    inside = [r for r in steps
              if run["t_start"] < r["t"] <= run["t_limit"]]
    if len(inside) < 2:
        raise RunFailed("fewer than two steps completed in the window")
    t_end = inside[-1]["t"]
    window_s = t_end - run["t_start"]
    facts = {"steps": inside, "t_end": t_end, "window_s": window_s,
             "tokens_per_s": len(inside) * tokens_per_step / window_s,
             "saves": [], "plain_step_s": None, "ckpt_stall_s": None}
    pid = run["worker"]["pid"]
    # a save is in the window if its device-to-host staging began there
    saves = [e for e in run["events"]
             if e.get("kind") == "ckpt_save" and e.get("pid") == pid
             and run["t_start"] <= e["ts"] - e["stage_seconds"] <= t_end]
    facts["saves"] = saves
    if saves:
        # with steps in flight the host enters a save before its copy
        # begins, and the lines of those steps arrive after the copy:
        # the steps whose lines came before it are the plain ones
        began = min(e["ts"] - e["stage_seconds"] for e in saves)
        plain = [r for r in inside if r["t"] < began]
        if len(plain) < 2:
            raise RunFailed("no plain steps before the first save")
        facts["plain_step_s"] = (plain[-1]["t"] - run["t_start"]) / len(plain)
        facts["ckpt_stall_s"] = (
            window_s - len(inside) * facts["plain_step_s"]) / len(saves)
    return facts


def resume_facts(run, traffic):
    """The restart, as the events and the restarted worker's lines show
    it; ``problems`` lists what makes the resume a failed operation."""
    restarted = [i for i in range(1, 4)
                 if any(r.get("event") == "worker" for r in run["rounds"][i])]
    lines = run["rounds"][run["measured"]]
    worker = next((r for r in lines if r["event"] == "worker"), {})
    start = next((r for r in lines if r["event"] == "start"), {})
    after = steps_of(lines)
    failed = [e["ts"] for e in run["events"]
              if e.get("kind") == "worker_failed"
              and e["ts"] >= run["t_kill"]]
    restores = [e["restore_seconds"] for e in run["events"]
                if e.get("kind") == "ckpt_restore"
                and e.get("pid") == worker.get("pid")]
    facts = {"worker": worker, "start": start, "steps": after,
             "resume_s": after[0]["t"] - run["t_kill"] if after else None,
             # when the agent saw the exit; the restarted worker's restore
             "t_failed": min(failed) if failed else None,
             "restore_s": sum(restores) if restores else None}
    problems = []
    resumed = start.get("resumed_step", 0)
    if len(restarted) != 1:
        problems.append(f"the restart took {len(restarted)} attempts")
    if resumed <= 0:
        problems.append("the restore fell back to a fresh init (step 0)")
    elif resumed != run["committed_at_kill"]:
        problems.append(f"resumed at {resumed}, not at the committed step "
                        f"{run['committed_at_kill']}")
    if worker.get("restart_round", 0) < 1:
        problems.append("the restart round did not rise")
    # every save event says ``forced`` (the trainer checks its cadence
    # itself), so the job's closing save is told apart by its step
    every = traffic["save_every_steps"]
    final = after[-1]["step"] if after else 0
    early = [e["step"] for e in run["events"]
             if e.get("kind") == "ckpt_save"
             and e.get("pid") == worker.get("pid")
             and e["step"] < resumed + every and e["step"] != final]
    if early:
        problems.append(f"a save at step {early[0]}, right after the "
                        f"restore of step {resumed}")
    before = {r["step"]: r["loss"] for r in steps_of(run["rounds"][0])}
    replayed = [(r["step"], before[r["step"]], r["loss"]) for r in after
                if r["step"] in before]
    facts["replayed"] = replayed
    if not replayed:
        problems.append("no step was run both before the kill and after "
                        "the resume")
    for step, a, b in replayed:
        if not abs(a - b) <= REPLAY_TOL * max(1.0, abs(a)):
            problems.append(f"step {step} gave loss {a} before the kill "
                            f"and {b} after the resume")
    if run.get("launcher_rc") != 0:
        problems.append(f"the launcher exited {run.get('launcher_rc')}")
    facts["problems"] = problems
    return facts


def reduce_trace(trace_dir, out_file, keep=False):
    """``trace_reduce.py`` in a child held to the CPU (the job has ended
    and freed the chip, but this process still never imports JAX)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"), trace_dir,
         out_file], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    if proc.returncode != 0:
        raise RunFailed("the trace reduction failed: "
                        + proc.stderr.strip()[-2000:])
    if keep:
        for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True):
            shutil.copy(path, os.path.dirname(out_file))
    return load(out_file)


def read_layer_metric(name, context):
    """The metric's own reader, found by name; a reader that finds
    nothing to read returns None and the metric is left out."""
    return load_module(
        os.path.join(HERE, "layer_metrics", name + ".py")).read(context)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1, 2], required=True)
    p.add_argument("--rehearsal", action="store_true",
                   help="tests only: allow a platform that is not a TPU")
    p.add_argument("--keep_trace", action="store_true",
                   help="copy the raw .xplane.pb beside the logs, to "
                        "look at by hand")
    p.add_argument("--config_file", default="",
                   help="tests only: a toy configuration in place of "
                        "the cell's")
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "dlrover_tpu")):
        sys.stderr.write("chipbench measures the repository it sits in; "
                         "there is none here\n")
        return 2
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find(bench["workloads"], args.workload, "workload")
    config = find(bench["configs"], cell["config"], "configuration")
    model_file = args.config_file or os.path.join(ROOT, config["file"])
    model = load(model_file)
    traffic = load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    # what belongs to no model, and what a step of this architecture
    # costs: the readers take both
    arithmetic = load_module(os.path.join(HERE, "arithmetic.py"))
    flops = load_module(os.path.join(HERE, "families", model["family"],
                                     "flops.py"))
    if not args.config_file and model["chips"] != cell["chips"]:
        raise SystemExit(f"{config['file']} is laid out for "
                         f"{model['chips']} chip(s), the cell asks for "
                         f"{cell['chips']}")

    tag = f"{args.workload}.s{args.seed}.t{args.trace}"
    log_dir = os.path.join(LOGS, tag)
    work_dir = os.path.join(WORK, args.workload)
    for d in (log_dir, work_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    try:
        try:
            run = drive(args, cell, model, model_file, traffic, log_dir,
                        work_dir)
        finally:
            mirror = staging_mirror(os.path.join(work_dir, "ckpt"))
            shutil.rmtree(mirror, ignore_errors=True)
            try:  # the manager's own parent directory, if now empty
                os.rmdir(os.path.dirname(mirror))
            except OSError:
                pass
        window = window_facts(run, flops.tokens_per_step(model))
        killed = run["t_kill"] is not None
        resume = resume_facts(run, traffic) if killed else None
        reduced = (reduce_trace(run["trace_dir"],
                                os.path.join(log_dir, "trace_reduced.json"),
                                args.keep_trace)
                   if args.trace else None)
    except RunFailed as e:
        sys.stderr.write(json.dumps({"correct": False, "error": str(e)})
                         + "\n")
        return 3
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    worker = run["worker"]
    all_steps = [r for lines in run["rounds"] for r in steps_of(lines)]
    reference = next((r for r in run["rounds"][0]
                      if r.get("event") == "reference"), None)
    # Orbax commits in order, so a save is committed once any save at
    # or after its step has its directory
    newest = max(run["committed_end"], default=0)
    uncommitted = [e["step"] for e in window["saves"]
                   if e["step"] > newest]
    nonfinite = [r["step"] for r in window["steps"] if not finite(r["loss"])]
    attempted = len(window["steps"]) + len(window["saves"]) + int(killed)
    failed = (len(nonfinite) + len(uncommitted)
              + int(bool(resume and resume["problems"])))
    problems = []
    if reference is None or not reference["ok"]:
        problems.append(f"the reference check failed: {reference}")
    if not all(finite(r["loss"]) for r in all_steps):
        problems.append("a loss was not finite")
    if resume:
        problems += resume["problems"]
    elif run.get("launcher_rc") != 0:
        problems.append(f"the launcher exited {run.get('launcher_rc')}")
    if uncommitted:
        problems.append(f"saves never committed: {uncommitted}")
    min_saves = traffic.get("min_saves_in_window", 0)
    if len(window["saves"]) < min_saves:
        problems.append(f"{len(window['saves'])} saves in the window, "
                        f"the mix needs {min_saves}")

    compiled = next((int(e["peak_hbm_mb"] * 1024 * 1024)
                     for e in run["events"]
                     if e.get("kind") == "attribution_captured"
                     and e.get("peak_hbm_mb")), 0)
    allocator = max((r.get("peak_bytes_in_use") or 0 for r in all_steps),
                    default=0)
    device = {"platform": worker["platform"], "kind": worker["device_kind"],
              "count": worker["device_count"],
              # measured: the allocator's peak on the fullest chip, read
              # at every step line. XLA's compile-time estimate of the
              # step's residency stands beside it and is no measurement
              "memory_peak_bytes": allocator,
              "compiled_step_bytes": compiled}
    values = {
        "setup_s": run["t_start"] - T0,
        "tokens_per_s": window["tokens_per_s"],
        "ckpt_stall_s": window["ckpt_stall_s"],
    }
    result = {"correct": not problems, "attempted": attempted,
              "failed": failed, "metrics": {}, "device": device}
    if args.trace != 1:
        for metric in bench["end_to_end"]:
            if args.workload not in metric.get("workloads",
                                               [args.workload]):
                continue
            if values.get(metric["name"]) is None:
                problems.append(f"{metric['name']} could not be taken")
                result["correct"] = False
                continue
            result["metrics"][metric["name"]] = {
                "value": values[metric["name"]], "unit": metric["unit"]}
    if args.trace:
        context = {"run": run, "window": window, "resume": resume,
                   "trace": reduced, "model": model, "traffic": traffic,
                   "device": device, "arithmetic": arithmetic,
                   "flops": flops}
        for metric in bench["per_layer"]:
            if args.workload not in metric.get("workloads",
                                               [args.workload]):
                continue
            value = read_layer_metric(metric["name"], context)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
        if reduced and reduced.get("busy_s"):
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"][:10],
                "idle_gaps": reduced["idle_gaps"][:10]}
    facts = {"values": values, "window": {
        k: v for k, v in window.items() if k not in ("steps", "saves")},
        "steps_in_window": len(window["steps"]),
        "saves_in_window": [e["step"] for e in window["saves"]],
        "reference": reference, "problems": problems}
    if resume:
        facts["resume"] = {k: resume[k] for k in
                           ("resume_s", "replayed", "problems")}
    if run.get("profile_window"):
        facts["profile_window"] = run["profile_window"]
    # what the result was computed from, for whoever reads the log
    print(json.dumps({"facts": facts}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(main())
