"""The cell ``motif3-1chip.steady`` rehearsed where there is no chip:
``chipbench/run.py`` with its rehearsal switch and the toy of the
grouped differential latent-attention decoder (``tiny_mla_moe_gdla.json``
beside this file: ten query heads on two latent KV heads, a window of
16 on all layers but one, PolyNorm FFNs, four streams, 6 of 24 experts
held behind a bias the step moves, one prediction module, the Pallas
kernels in interpret mode), traced in the run that measured (``--trace
2``: the driver's second pair; the untraced run is the same run without
its profiling window, which the other families' rehearsals cover). The program's launcher, master, agent and worker run the new
family's job; the reference check runs; the last line of stdout has the
keys the driver reads. The counters the loss function and the step
return (the held experts' rows, the defect of ``H_res``, the module's
loss, lambda's mean, the bias's mean magnitude) reach the
``profile_window`` event and the readers that need no device trace; a
CPU has no device plane, so the trace readers of the kernels and of
the scopes find nothing and leave their metrics out.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "motif3-1chip.steady"


def test_the_cell_traced_in_the_run_that_measured_on_the_cpu(tmp_path):
    # a checkout of its own, by links: the work directory and the logs
    # are then this test's, and ``test_chipbench_rehearsal.py``, which
    # may run beside it and counts the processes left under the
    # repository's work directory, does not see these
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("BENCHMARK.json", "chipbench", "dlrover_tpu"):
        os.symlink(os.path.join(REPO, name), root / name)
    seeds = {2: 2 ** 31 + 73}
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
             os.path.join(HERE, "tiny_mla_moe_gdla.json")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=str(tmp_path), preexec_fn=lambda: os.nice(10))
        try:
            out, err = proc.communicate(timeout=400)
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
    assert list(last[2]["metrics"]) == [
        "tokens_per_s", "setup_s", "dispatch_ms", "host_sync_ms",
        "input_wait_ms", "boot_import_s", "boot_backend_s", "boot_build_s",
        "expert_load_imbalance", "expert_rows_dropped", "hc_res_defect",
        "mtp_loss", "diff_lambda_mean", "router_bias_abs"]
    metrics = {k: v["value"] for k, v in last[2]["metrics"].items()}
    assert metrics["expert_rows_dropped"] == 0
    assert 1.0 <= metrics["expert_load_imbalance"] < 6
    assert 0 <= metrics["hc_res_defect"] < 1e-2
    assert 4.5 < metrics["mtp_loss"] < 8.0
    assert 0.4 < metrics["diff_lambda_mean"] < 0.6
    # the program says what kinds of layer it built, and its profiling
    # window what its expert layers and its step counted
    log_dir = str(root / "chiprun_out" / "chipbench"
                  / f"{CELL}.s{seeds[2]}.t2")
    assert not os.path.exists(root / ".chipbench_work" / CELL)
    events = [json.loads(line)
              for line in open(os.path.join(log_dir, "events.jsonl"))]
    ready = [e for e in events if e["kind"] == "trainer_ready"]
    assert ready and ready[0]["layer_kinds"] == {
        "dense": 1, "moe": 4, "full": 1, "window": 4}
    (window,) = [e for e in events if e["kind"] == "profile_window"]
    counted = window["step_counters"]
    assert counted["moe_rows_dropped"] == 0
    assert counted["attn_band_tiles"] == 2 * 10 * 5 * 7 * window["steps"]
    # the bias moves every step, by at most the rate a step: the
    # window's steps come after the warm-up's, so it is above 0 there
    # and under the rate times the last step's number
    steps = [json.loads(line) for line in open(os.path.join(
        log_dir, "worker_0_r0.log")) if line.startswith("{")]
    last_step = max(r["step"] for r in steps if r["event"] == "step")
    assert 0 < metrics["router_bias_abs"] <= 1e-4 * last_step
    assert next(r for r in steps if r["event"] == "worker")["layers"] == 5
