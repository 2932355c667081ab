"""The cell ``ling3flash-1chip.steady`` rehearsed where there is no
chip: ``chipbench/run.py`` with its rehearsal switch and the toy of the
decoder of Kimi-delta-attention and latent-attention layers with held
experts (``tiny_kda_mla_moe.json`` beside this file: a leading dense
KDA layer and one group of two KDA, an MLA and a KDA expert layer, two
rows of one chunk, the Pallas kernels in interpret mode), untraced and
then traced in the run that measured. The program's launcher, master,
agent and worker run the new family's job; the reference check runs,
its three hidden-state comparisons among it; the last line of stdout
has the keys the driver reads. The counters the loss function returns
reach the ``profile_window`` event and the readers that need no device
trace; a CPU has no device plane, so the trace readers (``kda_ms``,
``kda_roofline`` and ``kda_chunk_ms`` among them) find nothing and
leave their metrics out.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "ling3flash-1chip.steady"


def test_the_cell_untraced_and_traced_on_the_cpu(tmp_path):
    # a checkout of its own, by links: the work directory and the logs
    # are then this test's, and ``test_chipbench_rehearsal.py``, which
    # may run beside it and counts the processes left under the
    # repository's work directory, does not see these
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("BENCHMARK.json", "chipbench", "dlrover_tpu"):
        os.symlink(os.path.join(REPO, name), root / name)
    seeds = {0: 2 ** 31 + 6237, 2: 2 ** 31 + 6241}
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
             os.path.join(HERE, "tiny_kda_mla_moe.json")],
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
        "kda_log_decay_mean", "expert_load_imbalance",
        "expert_rows_dropped"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(last[2]["metrics"]) <= {
        m["name"] for m in bench["end_to_end"] + bench["per_layer"]
        if CELL in m.get("workloads", [CELL])}
    # the bounded gate at the assumed initialisation: inside (-5, 0)
    # and a few hundredths under 0
    assert -0.2 < last[2]["metrics"]["kda_log_decay_mean"]["value"] < -0.001
    assert last[2]["metrics"]["expert_rows_dropped"]["value"] == 0
    # the program says what kinds of layer it built, and its profiling
    # window what its layers counted: the group limit's reach (two of
    # four groups kept, this chip's experts in one) and the bias the
    # step moved
    log_dir = str(root / "chiprun_out" / "chipbench"
                  / f"{CELL}.s{seeds[2]}.t2")
    assert not os.path.exists(root / ".chipbench_work" / CELL)
    events = [json.loads(line)
              for line in open(os.path.join(log_dir, "events.jsonl"))]
    ready = [e for e in events if e["kind"] == "trainer_ready"]
    assert ready and ready[0]["layer_kinds"] == {
        "kda": 4, "mla": 1, "dense": 1, "moe": 4}
    (window,) = [e for e in events if e["kind"] == "profile_window"]
    counters = window["step_counters"]
    assert 0.3 < counters["moe_group_reach"] / counters[
        "moe_group_tokens"] < 0.7
    assert counters["router_bias_abs"] > 0
    assert "router_load" not in counters
    worker = [json.loads(line) for line in open(os.path.join(
        log_dir, "worker_0_r0.log")) if line.startswith("{")]
    assert next(r for r in worker if r["event"] == "worker")["layers"] == 5
