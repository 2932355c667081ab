"""The elastic cell traced in the run that measured (``--trace 2``),
rehearsed on the CPU at the toy size: the nine metrics that split the
set-up by the program's own events are printed beside the old ones,
every second of the restarted worker's boot is in a named phase, and
the operator's ``mttr`` ends where the benchmark's ``resume_s`` does."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "mistral7b-d8.elastic"
NINE = ["first_start_s", "state_init_s", "hooks_begin_s",
        "restart_programs", "restart_compile_s", "restart_cache_read_s",
        "restart_capture_s", "restore_gb_per_s", "boot_unattributed_s"]
PHASES = (("worker_boot", "import_seconds"),
          ("worker_boot", "distributed_seconds"),
          ("worker_boot", "backend_seconds"),
          ("trainer_ready", "script_seconds"),
          ("trainer_ready", "ckpt_manager_seconds"),
          ("trainer_ready", "build_seconds"),
          ("trainer_ready", "state_seconds"),
          ("train_start", "hooks_begin_seconds"),
          ("compile_first_step", "seconds"))


def checkout_of_links(at):
    """A second root for the harness, made of links to this one: it
    keeps its work and its logs under its root by the cell's name, and
    ``test_chipbench_rehearsal.py`` may be running the same cell in
    this checkout at the same moment."""
    os.makedirs(at)
    for name in ("chipbench", "dlrover_tpu", "BENCHMARK.json"):
        os.symlink(os.path.join(REPO, name), os.path.join(at, name))
    return at


def test_the_elastic_cell_splits_its_set_up(tmp_path):
    seed = 2 ** 31 + 19
    root = checkout_of_links(str(tmp_path / "checkout"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               BENCH_RUN="ignored", OMP_NUM_THREADS="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=2 "
                         "--xla_cpu_multi_thread_eigen=false "
                         "intra_op_parallelism_threads=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "8",
         "--trace", "2", "--rehearsal", "--config_file",
         os.path.join(HERE, "tiny.json")], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=280,
        preexec_fn=lambda: os.nice(10))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0, lines[-2][-3000:]
    # the end-to-end metrics, the eleven the cell had, then the nine
    assert list(last["metrics"]) == [
        "ckpt_stall_s", "setup_s", "resume_s", "detect_s", "respawn_s",
        "boot_s", "restore_s", "save_block_s", "restart_first_step_s",
        "restart_cache_misses", "boot_import_s", "boot_backend_s",
        "boot_build_s"] + NINE
    m = {k: v["value"] for k, v in last["metrics"].items()}
    units = {k: v["unit"] for k, v in last["metrics"].items()}
    assert units["restart_programs"] == "count"
    assert units["restore_gb_per_s"] == "GB/s"

    log_dir = os.path.join(root, "chiprun_out", "chipbench",
                           f"{CELL}.s{seed}.t2")
    events = [json.loads(line)
              for line in open(os.path.join(log_dir, "events.jsonl"))]
    rounds = []
    for r in (0, 1):
        with open(os.path.join(log_dir, f"worker_0_r{r}.log")) as f:
            rounds.append([json.loads(line) for line in f
                           if line.startswith("{")])
    pids = [next(r["pid"] for r in lines if r["event"] == "worker")
            for lines in rounds]

    # in each worker's events the phases sum to the stretch from the
    # process's start to its first trained step, within 3% or 0.5 s
    for pid in pids:
        first = {}
        for e in events:
            if e.get("pid") == pid:
                first.setdefault(e["kind"], e)
        total = (first["compile_first_step"]["ts"]
                 - first["worker_boot"]["process_start_ts"])
        named = sum(first[kind][field] for kind, field in PHASES)
        assert abs(total - named) < max(0.5, 0.03 * total), (pid, first)
        assert first["compile_first_step"]["programs"][0]["fun_name"]
    # the first worker: a job's start, its fresh state, the reference
    # check among the hooks
    assert m["first_start_s"] > m["state_init_s"] + m["hooks_begin_s"] > 0
    reference = next(r for r in rounds[0] if r["event"] == "reference")
    assert m["hooks_begin_s"] >= reference["seconds"]
    # the restarted worker: nothing of its set-up is unnamed, the
    # ledger counts at least what the step line's counters saw, and
    # the attribution pass is part of the first step
    assert abs(m["boot_unattributed_s"]) < 1.0
    step_line = next(r for r in rounds[1] if r["event"] == "step")
    assert m["restart_programs"] >= (step_line["cache_hits"]
                                     + step_line["cache_misses"]) > 0
    assert m["restart_cache_misses"] == 0
    assert m["restart_compile_s"] > 0 and m["restart_cache_read_s"] > 0
    assert 0 < m["restart_capture_s"] < m["restart_first_step_s"]
    restore = next(e for e in events if e["kind"] == "ckpt_restore"
                   and e["pid"] == pids[1])
    assert restore["source"] in ("staging", "directory")
    assert m["restore_gb_per_s"] > 0
    assert restore["bytes"] / 1e9 / m["restore_gb_per_s"] == \
        m["restore_s"] or abs(restore["bytes"] / 1e9 / m["restore_gb_per_s"]
                              - m["restore_s"]) < 1e-6

    # the operator's tool on the same timeline: one worker failure,
    # followed to the restarted worker's first trained step
    tool = subprocess.run(
        [sys.executable, "-m", "dlrover_tpu.telemetry", "mttr", "--events",
         os.path.join(log_dir, "events.jsonl")], capture_output=True,
        text=True, timeout=60, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=REPO))
    assert tool.returncode == 0, tool.stderr[-2000:]
    report = json.loads(tool.stdout.strip().splitlines()[-1])
    (incident,) = report["detail"]["to_first_step"]
    assert incident["scenario"] == "worker_failure"
    assert abs(incident["first_step_seconds"]
               - (m["resume_s"] - m["detect_s"])) < 0.5
    assert incident["phases"]["restore"] == round(m["restore_s"], 3)
