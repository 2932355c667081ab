"""chip_smoke.py, rehearsed where there is no chip.

The script itself must refuse to run without a TPU (fast, non-zero,
``"ok": false`` on its last line). Its train/kill/resume phases are
plain functions of the model arguments, so the test drives them with
``llama_tiny`` on the CPU mesh: the same launcher, master, agent,
worker, checkpoint, SIGKILL and restart as on the chip — the test
steers the size, the script has no size option.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_chip_is_a_fast_failure():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60  # seconds: before any compile
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "TPU" in last["error"], last
    assert '"ok": true' not in proc.stdout


def test_train_kill_resume_on_the_cpu_mesh(tmp_path, monkeypatch):
    import chip_smoke

    # a two-device CPU mesh keeps the tiny steps short
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    steps = 10
    train, resume, device = chip_smoke.train_and_resume(
        ["--preset", "tiny"], batch=8, steps=steps, ckpt_every=2,
        work_dir=str(tmp_path / "work"), log_dir=str(tmp_path / "logs"),
        timeout=300,
    )
    assert device == {"platform": "cpu", "kind": "cpu", "count": 2}
    # train: under the master, steady steps timed, every loss finite
    assert train["ok"] and train["with_master"], train
    assert train["steps_before_kill"] >= 4 and train["steps_timed"] >= 4
    assert train["step_s_median"] > 0
    assert train["first_step_s"] > train["step_s_median"]
    # resume: the agent restarted the killed worker, which restored a
    # committed step (not 0) and trained past it to the end
    assert resume["ok"], resume
    assert resume["restart_round"] >= 1
    assert resume["restarted_pid"] != resume["killed_pid"]
    assert resume["resumed_step"] >= resume["committed_step_at_kill"] >= 2
    assert resume["final_step"] == steps > resume["resumed_step"]
    assert resume["launcher_rc"] == 0
    assert resume["kill_to_first_step_s"] > resume["kill_to_detected_s"] > 0
    # the save cadence counts from the restored step: no save one step
    # after the restore (the chip showed it as a stall in the recovery)
    assert resume["first_save_after_resume"] >= resume["resumed_step"] + 2
    # what the killed worker compiled, the restart read from disk (the
    # restore's own small programs may still miss on a cold cache)
    assert resume["cache_hits"] > 0, resume
    # nothing the phase started is left running: every process of the
    # job carries this test's directory on its command line
    import psutil

    time.sleep(0.5)  # the launcher terminates its master as it exits
    left = [p.info["cmdline"] for p in psutil.process_iter(["cmdline"])
            if str(tmp_path) in " ".join(p.info["cmdline"] or [])]
    assert not left, left
