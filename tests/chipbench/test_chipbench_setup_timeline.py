"""The nine readers of the set-up timeline against hand-made events: a
first and a restarted worker, the run that measured (it has a
``profile_window``) and one that did not, and a program that writes
none of the new fields."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                       "chipbench", "layer_metrics")
FIRST, RESTARTED = 11, 22


def read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(READERS, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


def worker_events(pid, start, restart_round, **changed):
    """One worker round, from its process's start at ``start``."""
    ledger = {"programs": 0, "hits": 0, "misses": 0, "trace_seconds": 0.0,
              "lower_seconds": 0.0, "backend_seconds": 0.0,
              "cache_read_seconds": 0.0}
    events = [
        {"kind": "worker_boot", "ts": start + 12.0, "pid": pid,
         "process_start_ts": start, "import_seconds": 5.0,
         "distributed_seconds": 0.0, "backend_seconds": 6.5,
         "restart_round": restart_round, "compile": dict(ledger)},
        {"kind": "trainer_ready", "ts": start + 40.0, "pid": pid,
         "script_seconds": 1.5, "ckpt_manager_seconds": 12.0,
         "build_seconds": 0.25, "state_seconds": 14.0,
         "compile": dict(ledger, programs=3, hits=3)},
        {"kind": "train_start", "ts": start + 42.0, "pid": pid,
         "hooks_begin_seconds": 2.0,
         "compile": dict(ledger, programs=5, hits=5)},
        {"kind": "compile_first_step", "ts": start + 48.0, "pid": pid,
         "seconds": 6.0, "capture_seconds": 2.0,
         "compile": dict(ledger, programs=9, hits=9, trace_seconds=1.5,
                         lower_seconds=0.75, backend_seconds=0.25,
                         cache_read_seconds=1.25)},
    ]
    if restart_round:
        events.insert(1, {"kind": "ckpt_restore", "ts": start + 39.0,
                          "pid": pid, "restore_seconds": 10.0,
                          "bytes": 4_000_000_000, "source": "directory"})
    for event in events:
        event.update(changed.get(event["kind"], {}))
    return events


def context(measured=True, **changed):
    run = {"rounds": [[{"event": "worker", "pid": FIRST}], []],
           "worker": {"pid": RESTARTED},
           "events": (worker_events(FIRST, 1000.0, 0)
                      + worker_events(RESTARTED, 1100.0, 1, **changed))}
    if measured:
        run["profile_window"] = {"kind": "profile_window", "steps": 3}
    return {"run": run, "resume": {"restore_s": 10.0}}


EXPECTED = {
    "first_start_s": 48.0, "state_init_s": 14.0, "hooks_begin_s": 2.0,
    "restart_programs": 9, "restart_compile_s": 2.5,
    "restart_cache_read_s": 1.25, "restart_capture_s": 2.0,
    "restore_gb_per_s": 0.4,
    # 48 s less 5 + 0 + 6.5 + 1.5 + 12 + 0.25 + 14 + 2 + 6
    "boot_unattributed_s": 0.75,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_reads_its_worker(name):
    assert read(name, context()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_run_that_did_not_measure_reports_none(name):
    """Under ``--trace 1`` no ``setup_s`` is printed, so the parts of
    that run's set-up are parts of nothing reported."""
    assert read(name, context(measured=False)) is None


RESTARTED_ONES = ["restart_programs", "restart_compile_s",
                  "restart_cache_read_s", "restart_capture_s",
                  "restore_gb_per_s", "boot_unattributed_s"]


@pytest.mark.parametrize("name", RESTARTED_ONES)
def test_a_program_without_the_fields_reports_none(name):
    """The parent of the PR that brought these fields writes the four
    events without them: the reader finds nothing and does not raise."""
    ctx = context()
    for event in ctx["run"]["events"]:
        for key in ("compile", "capture_seconds", "script_seconds",
                    "hooks_begin_seconds", "distributed_seconds",
                    "bytes", "source"):
            event.pop(key, None)
    assert read(name, ctx) is None
    # nor where the cell has no restarted worker at all
    ctx = context()
    ctx["resume"] = None
    assert read(name, ctx) is None


def test_the_restarted_workers_events_are_the_ones_read():
    ctx = context(compile_first_step={"capture_seconds": 3.5})
    assert read("restart_capture_s", ctx) == 3.5
    assert read("boot_unattributed_s", ctx) == pytest.approx(0.75)
    assert read("first_start_s", ctx) == 48.0  # the first worker's


def test_the_remainder_is_taken_from_the_phases_the_tool_reports():
    """The reader stands alone, since the benchmark runs it against a
    program that has no ``BOOT_PHASES``; its list is the tool's all the
    same (``restore`` is nested in the state's seconds and named by no
    field of its own here)."""
    from dlrover_tpu.telemetry.mttr import BOOT_PHASES

    spec = importlib.util.spec_from_file_location(
        "boot_unattributed_s",
        os.path.join(READERS, "boot_unattributed_s.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.PHASES == tuple(
        (kind, field) for name, kind, fields in BOOT_PHASES
        for field in fields if name != "restore")
