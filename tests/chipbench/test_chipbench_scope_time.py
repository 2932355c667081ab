"""``chipbench/scope_time.py`` and the twelve readers over it: a traced
step's device time by phase, by scope and by kind of instruction, from
the program's ``step_scopes`` event joined to the reduced trace's
``device_ops``; and ``hbm_held_pct`` from ``profile_window.memory``.
Plain Python over a table and an event made by hand (two steps, so a
second of ``device_ops`` is 500 ms a step); no job is started."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import run  # noqa: E402

PID = 4242
# milliseconds a step, by instruction; a kernel's under ``mosaic:``
OPS_MS = {
    "fusion.1": 40.0,            # forward|attention
    "mosaic:flash_fwd.2": 30.0,  # forward|attention, a kernel
    "fusion.3": 20.0,            # forward|ffn
    "fusion.4": 12.0,            # forward| (a scan's slice)
    "fusion.5": 25.0,            # replay|attention
    "mosaic:flash_fwd.6": 30.0,  # replay|attention, a kernel
    "fusion.7": 9.0,             # replay|head_loss
    "fusion.8": 70.0,            # backward|moe_experts
    "mosaic:gmm_dx.9": 45.0,     # backward|moe_experts, a kernel
    "fusion.10": 8.0,            # backward|moe_router
    "fusion.11": 6.0,            # backward|mtp/head_loss
    "fusion.12": 16.0,           # backward|gdn/gdn_chunk
    "mosaic:hc_enter_bwd.13": 18.0,  # backward|hc_map/hc_map
    "fusion.14": 4.0,            # backward|mtp/hc_mix/mla
    "fusion.15": 11.0,           # optimizer|
    "copy.16": 3.0,              # none|
    "fusion.17": 2.0,            # the event does not name it
}
INSTRUCTIONS = {
    "forward|attention": ["fusion.1", "flash_fwd.2"],
    "forward|ffn": ["fusion.3"],
    "forward|": ["fusion.4"],
    "replay|attention": ["fusion.5", "flash_fwd.6"],
    "replay|head_loss": ["fusion.7"],
    "backward|moe_experts": ["fusion.8", "gmm_dx.9"],
    "backward|moe_router": ["fusion.10"],
    "backward|mtp/head_loss": ["fusion.11"],
    "backward|gdn/gdn_chunk": ["fusion.12"],
    "backward|hc_map/hc_map": ["hc_enter_bwd.13"],
    "backward|mtp/hc_mix/mla": ["fusion.14"],
    "optimizer|": ["fusion.15"],
    "none|": ["copy.16", "never_ran.18"],
}
EXPECTED = {
    "step_fwd_ms": 40.0 + 30.0 + 20.0 + 12.0,
    "step_replay_ms": 25.0 + 30.0 + 9.0,
    "step_bwd_ms": 70.0 + 45.0 + 8.0 + 6.0 + 16.0 + 18.0 + 4.0,
    "step_optimizer_ms": 11.0,
    # innermost scope a mixer's, kernels left out
    "attn_xla_ms": 40.0 + 25.0 + 4.0,
    "ffn_ms": 20.0 + 8.0,
    "moe_experts_xla_ms": 70.0,
    "head_loss_ms": 9.0 + 6.0,
    "gdn_chunk_ms": 16.0,
    # anywhere in the path, kernels included
    "hc_ms": 18.0 + 4.0,
    # unnamed, none, and forward without a scope
    "step_unscoped_ms": 2.0 + 3.0 + 12.0,
    "hbm_held_pct": 100.0 * (3_190 + 7_630) / 16_900,
}
MEMORY = {"bytes_in_use": 3_190, "bytes_reserved": 7_630,
          "peak_bytes_in_use": 5_780, "bytes_limit": 16_900}


def context(device_plane=True, event=True, memory=True):
    steps = 2
    trace = {"devices": {"/device:TPU:0": {}} if device_plane else {},
             "busy_s": 1.0 if device_plane else None, "window_s": 1.0,
             "steps": steps,
             "device_ops": [[name, ms * steps / 1e3]
                            for name, ms in OPS_MS.items()]}
    if not device_plane:
        trace = {"devices": {}, "busy_s": None, "window_s": None}
    events = [{"kind": "profile_window", "pid": PID}]
    if event:
        events = [
            # another worker's table, and this worker's older one
            {"kind": "step_scopes", "pid": 1, "program": "other",
             "instructions": {"optimizer|": list(OPS_MS)}},
            {"kind": "step_scopes", "pid": PID, "program": "old",
             "instructions": {"none|": ["fusion.1"]}},
            {"kind": "step_scopes", "pid": PID, "program": "p",
             "instructions": INSTRUCTIONS,
             "mixed_phase": {"count": 0, "names": []}},
        ] + events
    window = {"kind": "profile_window", "pid": PID, "steps": steps}
    if memory:
        window["memory"] = MEMORY
    return {"trace": trace, "run": {
        "worker": {"pid": PID}, "events": events,
        "profile_window": window}}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_sums_its_rows(name):
    assert run.read_layer_metric(name, context()) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_finds_nothing_without_its_source(name):
    """No device plane (a CPU rehearsal), or a program from before the
    event (the parent commit): the metric is left out, nothing raises.
    ``hbm_held_pct`` needs neither, and the event's ``memory``."""
    if name == "hbm_held_pct":
        assert run.read_layer_metric(name, context(memory=False)) is None
        ctx = context()
        ctx["run"]["profile_window"] = None  # a --trace 1 run
        assert run.read_layer_metric(name, ctx) is None
        return
    assert run.read_layer_metric(name, context(device_plane=False)) is None
    assert run.read_layer_metric(name, context(event=False)) is None
    ctx = context()
    ctx["trace"] = None  # an untraced run
    assert run.read_layer_metric(name, ctx) is None


def test_the_phases_close_over_the_trace():
    """The four phases and the unnamed and ``none`` parts are every
    millisecond of ``device_ops``, each once."""
    helper = run.load_module(os.path.join(REPO, "chipbench",
                                          "scope_time.py"))
    ctx = context()
    rows = helper.rows(ctx)
    assert len(rows) == len(OPS_MS)
    phases = sum(run.read_layer_metric(name, ctx) for name in (
        "step_fwd_ms", "step_replay_ms", "step_bwd_ms",
        "step_optimizer_ms"))
    rest = helper.total_ms(ctx, lambda phase, path, kernel: phase in (
        helper.UNNAMED, "none"))
    assert rest == pytest.approx(2.0 + 3.0)
    assert phases + rest == pytest.approx(sum(OPS_MS.values()))
    # a kernel's row says so, and its name joins without the prefix
    assert (30.0, "forward", ("attention",), True) in rows
    assert (2.0, helper.UNNAMED, (), False) in rows
    assert (4.0, "backward", ("mtp", "hc_mix", "mla"), False) in rows


def test_the_manifest_lists_the_twelve():
    b = bench()
    steady = [w["name"] for w in b["workloads"] if w["traffic"] == "steady"]
    experts = ["axk1-1chip.steady", "xing4-1chip.steady",
               "smallthinker-1chip.steady", "keye-1chip.steady",
               "axk2-1chip.steady"]
    cells = {"moe_experts_xla_ms": experts,
             "gdn_chunk_ms": ["olmohybrid-1chip.steady"],
             "hc_ms": ["xing4-1chip.steady"]}
    listed = {m["name"]: m for m in b["per_layer"]}
    for name in EXPECTED:
        m = listed[name]
        assert (m["moves"], m["better"]) == ("tokens_per_s", "lower")
        assert m["workloads"] == cells.get(name, steady), name
        if name == "hbm_held_pct":
            assert (m["layer"], m["unit"], m["source"]) == (
                "device", "%", "program_counter")
        else:
            assert (m["layer"], m["unit"]) == ("step program", "ms")
    # the scopes the readers name are scopes the program opens
    from dlrover_tpu.telemetry.names import DeviceScope

    for name, attr in (("attn_xla_ms", "MIXERS"), ("ffn_ms", "SCOPES")):
        reader = run.load_module(os.path.join(
            REPO, "chipbench", "layer_metrics", name + ".py"))
        assert set(getattr(reader, attr)) <= set(DeviceScope.ALL)
    assert {"head_loss", "gdn_chunk", "hc_map", "hc_mix",
            "moe_experts"} <= set(DeviceScope.ALL)


def test_the_helper_reads_the_programs_own_table():
    """The event's keys are what ``step_scope_table`` writes: a table
    built by the program from HLO text goes through the helper."""
    from dlrover_tpu.telemetry.attribution import step_scope_table

    text = """HloModule m

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} negate(%p), metadata={op_name="jit(train_step)/forward/jvp(mtp)/mla/neg"}
  ROOT %gmm.2 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/backward/transpose(jvp())/moe_experts/gmm/pallas_call"}
}
"""
    helper = run.load_module(os.path.join(REPO, "chipbench",
                                          "scope_time.py"))
    ctx = {"trace": {"devices": {"d": {}}, "steps": 1, "device_ops": [
        ["fusion.1", 0.002], ["mosaic:gmm.2", 0.005]]},
        "run": {"worker": {"pid": 1}, "events": [
            {"kind": "step_scopes", "pid": 1, **step_scope_table(text)}]}}
    assert sorted(helper.rows(ctx)) == [
        (2.0, "forward", ("mtp", "mla"), False),
        (5.0, "backward", ("moe_experts",), True)]
