"""The cell ``ouro26b-1chip.steady`` rehearsed where there is no chip:
``chipbench/run.py`` with its rehearsal switch and the toy of the
looped decoder (``tiny_looped.json`` beside this file: two layers run
three times a step, a row of two head chunks, the flash kernels in
interpret mode), untraced and then traced in the run that measured. The
program's launcher, master, agent and worker run the new family's job;
the reference check runs; the last line of stdout has the keys the
driver reads. The counters the loss function returns reach the
``profile_window`` event and the three readers that need no device
trace; a CPU has no device plane, so the trace readers (``exit_gate_ms``
among them) find nothing and leave their metrics out.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "ouro26b-1chip.steady"


def test_the_cell_untraced_and_traced_on_the_cpu(tmp_path):
    # a checkout of its own, by links: the work directory and the logs
    # are then this test's, and ``test_chipbench_rehearsal.py``, which
    # may run beside it and counts the processes left under the
    # repository's work directory, does not see these
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("BENCHMARK.json", "chipbench", "dlrover_tpu"):
        os.symlink(os.path.join(REPO, name), root / name)
    seeds = {0: 2 ** 31 + 6537, 2: 2 ** 31 + 6541}
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               BENCH_RUN="ignored",
               XLA_FLAGS="--xla_force_host_platform_device_count=1 "
                         "--xla_cpu_multi_thread_eigen=false "
                         "intra_op_parallelism_threads=1",
               OMP_NUM_THREADS="1")
    last = {}
    # one after the other: a cell's runs share its work directory
    for trace, seed in seeds.items():
        proc = subprocess.Popen(
            [sys.executable, str(root / "chipbench" / "run.py"),
             "--workload", CELL, "--seed", str(seed), "--seconds", "4",
             "--trace", str(trace), "--rehearsal", "--config_file",
             os.path.join(HERE, "tiny_looped.json")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=str(tmp_path), preexec_fn=lambda: os.nice(10))
        try:
            out, err = proc.communicate(timeout=280)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
        assert proc.returncode == 0, err[-3000:]
        lines = out.strip().splitlines()
        last[trace] = json.loads(lines[-1])
        facts = json.loads(lines[-2])["facts"]
        assert last[trace]["correct"] is True, lines[-2][-3000:]
        assert last[trace]["failed"] == 0
        # the reference check ran, on the toy's 64 tokens, in float32
        assert facts["reference"]["ok"] and facts["reference"]["tokens"] == 64
        assert facts["reference"]["abs_diff"] < 1e-4
        assert last[trace]["device"]["platform"] == "cpu"
    assert set(last[0]["metrics"]) == {"tokens_per_s", "setup_s"}
    # every metric that needs no device trace, and none the manifest
    # does not list for the cell (a later PR may append a reader)
    assert set(last[2]["metrics"]) >= {
        "tokens_per_s", "setup_s", "dispatch_ms", "host_sync_ms",
        "input_wait_ms", "boot_import_s", "boot_backend_s", "boot_build_s",
        "loop_exit_entropy", "loop_exit_mean_pass", "loop_loss_gain"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(last[2]["metrics"]) <= {
        m["name"] for m in bench["end_to_end"] + bench["per_layer"]
        if CELL in m.get("workloads", [CELL])}
    # three passes at random weights: the entropy inside (0, ln 3), the
    # expected exit pass inside (1, 3), and an untrained loop gains
    # next to nothing
    metrics = {k: v["value"] for k, v in last[2]["metrics"].items()}
    assert 0.0 < metrics["loop_exit_entropy"] < 1.0987
    assert 1.0 < metrics["loop_exit_mean_pass"] < 3.0
    assert abs(metrics["loop_loss_gain"]) < 0.5
    # the program says what layers it built and how often a step runs
    # them, and its profiling window what the loop counted
    log_dir = str(root / "chiprun_out" / "chipbench"
                  / f"{CELL}.s{seeds[2]}.t2")
    assert not os.path.exists(root / ".chipbench_work" / CELL)
    events = [json.loads(line)
              for line in open(os.path.join(log_dir, "events.jsonl"))]
    ready = [e for e in events if e["kind"] == "trainer_ready"]
    assert ready and ready[0]["layer_kinds"] == {"attn_full": 2}
    assert ready[0]["passes"] == 3
    (window,) = [e for e in events if e["kind"] == "profile_window"]
    assert set(window["step_counters"]) == {
        "loop_exit_entropy", "loop_exit_mean_pass", "loop_loss_first",
        "loop_loss_last"}
    assert window["step_counters"]["loop_exit_entropy"] == (
        metrics["loop_exit_entropy"] * window["steps"])
    (scopes,) = [e for e in events if e["kind"] == "step_scopes"]
    paths = {key.split("|")[1] for key in scopes["instructions"]}
    assert {"attn_full", "ffn", "exit_gate", "head_loss"} <= paths
    worker = [json.loads(line) for line in open(os.path.join(
        log_dir, "worker_0_r0.log")) if line.startswith("{")]
    assert next(r for r in worker if r["event"] == "worker")["layers"] == 2
