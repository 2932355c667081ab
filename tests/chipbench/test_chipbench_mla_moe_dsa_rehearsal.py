"""The cell ``axk2-1chip.steady`` rehearsed where there is no chip:
``chipbench/run.py`` with its rehearsal switch and the toy of the
latent-attention decoder with learned sparse attention, gates and a
group-limited router (``tiny_mla_moe_dsa.json`` beside this file: a
dense and two expert layers, 24 of a row's 64 keys selected, 2 of 4
groups kept, 6 of 24 experts held, the Pallas kernels in interpret
mode), untraced and then traced in the run that measured. The program's
launcher, master, agent and worker run the new family's job; the
reference check runs (the reference given the program's choices and on
its own); the last line of stdout has the keys the driver reads. The
counters the loss function returns reach the ``profile_window`` event
and the readers that need no device trace, the new one among them; a
CPU has no device plane, so the trace readers find nothing and leave
their metrics out.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "axk2-1chip.steady"


def test_the_cell_untraced_and_traced_on_the_cpu(tmp_path):
    # a checkout of its own, by links: the work directory and the logs
    # are then this test's, and ``test_chipbench_rehearsal.py``, which
    # may run beside it and counts the processes left under the
    # repository's work directory, does not see these
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("BENCHMARK.json", "chipbench", "dlrover_tpu"):
        os.symlink(os.path.join(REPO, name), root / name)
    seeds = {0: 2 ** 31 + 5137, 2: 2 ** 31 + 5141}
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
             os.path.join(HERE, "tiny_mla_moe_dsa.json")],
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
        assert facts["reference"]["abs_diff"] < 2e-4
        assert last[trace]["device"]["platform"] == "cpu"
    assert set(last[0]["metrics"]) == {"tokens_per_s", "setup_s"}
    # every metric that needs no device trace, and none the manifest
    # does not list for the cell (a later PR may append a reader)
    assert set(last[2]["metrics"]) == {
        "tokens_per_s", "setup_s", "dispatch_ms", "host_sync_ms",
        "input_wait_ms", "boot_import_s", "boot_backend_s", "boot_build_s",
        "expert_load_imbalance", "expert_rows_dropped",
        "dsa_selected_share", "dsa_index_kl", "moe_group_reach"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(last[2]["metrics"]) <= {
        m["name"] for m in bench["end_to_end"] + bench["per_layer"]
        if CELL in m.get("workloads", [CELL])}
    assert last[2]["metrics"]["expert_rows_dropped"]["value"] == 0
    assert 1.0 <= last[2]["metrics"]["expert_load_imbalance"]["value"] < 8
    # 24 of up to 64 keys a query: sum_t min(t + 1, 24) over 64 * 65 / 2
    assert last[2]["metrics"]["dsa_selected_share"]["value"] == (
        (24 * 25 // 2 + 40 * 24) / 2080)
    assert 0 < last[2]["metrics"]["dsa_index_kl"]["value"] < 5
    # 2 of 4 groups kept, the held experts all of group 0
    assert 0.2 < last[2]["metrics"]["moe_group_reach"]["value"] < 0.8
    # the program says what kinds of layer it built, and its profiling
    # window what its layers counted
    log_dir = str(root / "chiprun_out" / "chipbench"
                  / f"{CELL}.s{seeds[2]}.t2")
    assert not os.path.exists(root / ".chipbench_work" / CELL)
    events = [json.loads(line)
              for line in open(os.path.join(log_dir, "events.jsonl"))]
    ready = [e for e in events if e["kind"] == "trainer_ready"]
    assert ready and ready[0]["layer_kinds"] == {"dense": 1, "moe": 2}
    (window,) = [e for e in events if e["kind"] == "profile_window"]
    counted = window["step_counters"]
    # 2 rows of 64 tokens, 4 selections each, 2 expert layers, a
    # quarter of the experts held: about 256 rows a step
    assert 75 * window["steps"] < counted["moe_rows_held"] < (
        768 * window["steps"])
    # the dense layer selects too: 3 layers
    assert counted["dsa_pairs_causal"] == 2 * 3 * 2080 * window["steps"]
    assert counted["dsa_tiles_visited"] > 0 == counted["dsa_tiles_skipped"]
    assert counted["moe_group_tokens"] == 2 * 2 * 64 * window["steps"]
    assert 0 < counted["moe_group_reach"] < counted["moe_group_tokens"]
    assert counted["dsa_attn_kept_bytes"] > 0
    assert counted["moe_rows_dropped"] == 0
    worker = [json.loads(line) for line in open(os.path.join(
        log_dir, "worker_0_r0.log")) if line.startswith("{")]
    assert next(r for r in worker if r["event"] == "worker")["layers"] == 3
