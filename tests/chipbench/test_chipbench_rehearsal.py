"""``chipbench/run.py`` rehearsed where there is no chip.

The runner must refuse to measure without a TPU: fast, a non-zero exit
code, the reason on stderr and no result on stdout. With its rehearsal
switch and a toy configuration (``tiny.json`` beside this file, which
the test passes: the runner has no size option of its own) two cells
run end to end on the CPU, side by side: the elastic cell traced (the
program's launcher, master, agent and worker, the save, the SIGKILL,
the restart and the restore in set-up, then a window with saves) and a
steady cell untraced, and the last line of stdout has the keys the
driver reads. A steady cell then runs with ``--trace 2``: traced in the
run that measured, through the program's own profiling window.
"""

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RUN = [sys.executable, os.path.join(REPO, "chipbench", "run.py")]
CELL = "mistral7b-d8.elastic"
STEADY = "mistral7b-d8.steady"


def start(args, tmp_path, **env):
    # a home of its own, as the driver gives each side; the compile
    # cache stays where the test session's variable points
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               BENCH_RUN="ignored", **env)
    # niced: the job's processes yield this box's cores to the timing
    # gates of the test files that run beside this one
    return subprocess.Popen(RUN + args, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            cwd=str(tmp_path),
                            preexec_fn=lambda: os.nice(10))


def finish(proc):
    try:
        proc.stdout_text, proc.stderr_text = proc.communicate(timeout=280)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise
    return proc


def test_no_chip_is_a_fast_failure(tmp_path):
    t0 = time.monotonic()
    proc = finish(start(["--workload", CELL, "--seed", "1", "--seconds",
                         "45", "--trace", "0"], tmp_path))
    assert proc.returncode not in (0, 2), proc.stderr_text[-2000:]
    assert time.monotonic() - t0 < 60  # seconds: before any compile
    assert '"correct"' not in proc.stdout_text
    assert '"metrics"' not in proc.stdout_text
    reason = json.loads(proc.stderr_text.strip().splitlines()[-1])
    assert reason["correct"] is False and "TPU" in reason["error"], reason


def test_two_cells_end_to_end_on_the_cpu(tmp_path):
    seed = 2 ** 31 + 17  # more than 32 signed bits hold
    procs = {}
    for cell, trace in ((CELL, 1), (STEADY, 0)):
        procs[cell] = start(
            ["--workload", cell, "--seed", str(seed), "--seconds", "8",
             "--trace", str(trace), "--rehearsal", "--config_file",
             os.path.join(HERE, "tiny.json")], tmp_path,
            # one compute thread a device: the other test files' timing
            # gates share this box's cores with the job
            XLA_FLAGS="--xla_force_host_platform_device_count=2 "
                      "--xla_cpu_multi_thread_eigen=false "
                      "intra_op_parallelism_threads=1",
            OMP_NUM_THREADS="1")
    results, facts = {}, {}
    for cell, proc in procs.items():
        finish(proc)
        assert proc.returncode == 0, proc.stderr_text[-3000:]
        lines = proc.stdout_text.strip().splitlines()
        results[cell] = json.loads(lines[-1])
        # the line before the result says what it was computed from
        facts[cell] = json.loads(lines[-2])["facts"]
        assert results[cell]["correct"] is True, lines[-2][-3000:]

    last = results[STEADY]
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["failed"] == 0 and last["attempted"] >= 10
    assert set(last["metrics"]) == {"tokens_per_s", "setup_s"}
    for name, metric in last["metrics"].items():
        assert set(metric) == {"value", "unit"}, name
        assert metric["value"] > 0
    assert last["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert last["device"]["platform"] == "cpu"  # named, never hidden
    assert last["device"]["count"] == 2
    # the allocator's own peak: a CPU keeps no such count, and XLA's
    # estimate for the step does not stand in for it
    assert last["device"]["memory_peak_bytes"] == 0
    assert last["device"]["compiled_step_bytes"] > 0

    # the traced run reports the per-layer metrics that need no device
    # trace (a CPU has no device plane, so the readers of the trace find
    # nothing and their metrics are left out, as is the breakdown)
    traced = results[CELL]
    assert traced["failed"] == 0
    assert set(traced["metrics"]) == {
        "resume_s", "detect_s", "respawn_s", "boot_s", "restore_s",
        "save_block_s", "restart_first_step_s", "restart_cache_misses",
        # the restarted worker's own account of its boot
        "boot_import_s", "boot_backend_s", "boot_build_s"}
    assert "breakdown" not in traced and "busy_s" not in traced["device"]
    t = {k: v["value"] for k, v in traced["metrics"].items()}
    assert min(t["detect_s"], t["restore_s"], t["boot_s"]) > 0
    assert t["resume_s"] > t["detect_s"] + t["boot_s"]
    # boot_s runs from the script's first line to the start of training
    # less the restore; the program's events split what lies before
    # (the interpreter, the imports) and inside it
    assert t["boot_build_s"] + t["boot_backend_s"] < t["boot_s"]
    assert t["boot_import_s"] > 0
    assert traced["metrics"]["restart_cache_misses"]["unit"] == "count"
    # the kill, the restart and the restore are part of set-up; the
    # window is the restarted worker's and holds the saves
    elastic = facts[CELL]
    assert elastic["values"]["setup_s"] > t["resume_s"]
    assert elastic["values"]["ckpt_stall_s"] is not None
    assert len(elastic["saves_in_window"]) >= 3
    assert min(elastic["saves_in_window"]) == 4 + 13  # restored at 4
    assert [s for s, _, _ in elastic["resume"]["replayed"]][:3] == [5, 6, 7]
    # steps in the window, the saves, one resume
    assert traced["attempted"] == (elastic["steps_in_window"]
                                   + len(elastic["saves_in_window"]) + 1)
    # nothing the runs started is left: every process of the job
    # carries the checkout's work directory on its command line
    import psutil

    time.sleep(0.5)
    work = os.path.join(REPO, ".chipbench_work")
    left = [p.info["cmdline"] for p in psutil.process_iter(["cmdline"])
            if any(arg.startswith(work) for arg in p.info["cmdline"] or [])]
    assert not left, left
    for cell in (CELL, STEADY):
        assert not os.path.exists(os.path.join(work, cell))


def test_a_steady_cell_traced_in_the_run_that_measured(tmp_path):
    """``--trace 2``: the run of ``--trace 0`` up to the end of its
    window, then the program's own profiling window opened by a signal,
    and one last line with both kinds of metric."""
    seed = 2 ** 31 + 18
    proc = finish(start(
        ["--workload", STEADY, "--seed", str(seed), "--seconds", "6",
         "--trace", "2", "--rehearsal", "--config_file",
         os.path.join(HERE, "tiny.json")], tmp_path,
        XLA_FLAGS="--xla_force_host_platform_device_count=2"))
    assert proc.returncode == 0, proc.stderr_text[-3000:]
    lines = proc.stdout_text.strip().splitlines()
    last, facts = json.loads(lines[-1]), json.loads(lines[-2])["facts"]
    assert last["correct"] is True and last["failed"] == 0, lines[-2][-3000:]
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    # the end-to-end metrics of a --trace 0 line, then the per-layer
    # metrics that the program's events give (a CPU has no device
    # plane: the trace's readers find nothing and leave theirs out)
    assert list(last["metrics"]) == [
        "tokens_per_s", "setup_s", "dispatch_ms", "host_sync_ms",
        "input_wait_ms", "boot_import_s", "boot_backend_s", "boot_build_s"]
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert m["tokens_per_s"] == facts["values"]["tokens_per_s"] > 0
    assert m["boot_import_s"] > 0 and m["boot_build_s"] > 0
    assert m["dispatch_ms"] > 0 and m["host_sync_ms"] >= 0

    log_dir = os.path.join(REPO, "chiprun_out", "chipbench",
                           f"{STEADY}.s{seed}.t2")
    worker = [json.loads(line) for line in open(os.path.join(
        log_dir, "worker_0_r0.log")) if line.startswith("{")]
    pid = next(r["pid"] for r in worker if r["event"] == "worker")
    events = [json.loads(line)
              for line in open(os.path.join(log_dir, "events.jsonl"))]
    (window,) = [e for e in events if e["kind"] == "profile_window"]
    assert window == facts["profile_window"] and window["pid"] == pid
    # opened by the signal once the measured window had closed, six
    # steps long (the mix's trace_steps), closed before the stop file
    assert window["start_ts"] > facts["window"]["t_end"]
    assert window["steps"] == 6 and window["saves_begun"] == 0
    assert window["dir"].endswith(os.path.join(STEADY, "trace"))
    assert not os.path.exists(window["dir"])  # reduced, then removed
    # the worker ended by itself, when the stop file ended its stream
    finished = [r for r in worker if r["event"] == "finished"]
    assert finished and finished[0]["step"] > window["last_step"]
    assert worker[-1]["event"] == "finished"
    kinds = [e["kind"] for e in events if e.get("pid") == pid]
    assert kinds.index("worker_boot") < kinds.index("trainer_ready") \
        < kinds.index("train_start") < kinds.index("profile_window")


def test_a_directory_with_the_benchmark_alone_is_refused(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chipbench" / "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "45",
         "--trace", "0"], capture_output=True, text=True, timeout=60,
        cwd=str(tmp_path), env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
