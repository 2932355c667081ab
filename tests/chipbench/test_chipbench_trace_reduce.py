"""The reduction from a profiler trace to busy time, per-operation
time, idle gaps and exposed collective time.

``hand_made.xspace.txt`` beside this file is a trace of two chips and a
host thread written by hand in the profiler's own text form, small
enough to work out on paper (times below are in its microseconds);
``jax.profiler.ProfileData`` reads it exactly as it reads a recorded
``.xplane.pb``. ``recorded_v5e_fsdp4.xspace.txt`` is a recorded one: two
of the four chips' planes and two whole steps of the traced run of
``mistral7b-d20.fsdp4-steady`` on a v5e host (PR 24), thinned to the
operations of 5 ms and more (kernels, containers and module runs kept
whole, operand lists cut) so that it can be kept here."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import trace_reduce as tr  # noqa: E402

US = 1e-6


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "hand_made.xspace.txt")) as f:
        return tr.read_trace(ProfileData.from_text_proto(f.read()))


def test_union_total_clip_and_subtract():
    assert tr.union([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == [(0, 2.5), (3, 4)]
    assert tr.total([(0, 2.5), (3, 4)]) == 3.5
    assert tr.clip([(0, 2.5), (3, 4)], 2, 3.5) == [(2, 2.5), (3, 3.5)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_exposed_collective_time_on_a_two_line_case():
    """A collective of 0-10 with compute over 2-5 and 8-12: the chip does
    nothing else during 0-2 and 5-8, so 5 of its 10 are exposed."""
    collective = tr.union([(0, 10)])
    compute = tr.union([(2, 5), (8, 12)])
    exposed = tr.subtract(collective, compute)
    assert exposed == [(0, 2), (5, 8)] and tr.total(exposed) == 5


FUSION = ("%fusion.123 = (f32[2,4096]{1,0:T(2,128)}, bf16[2,4096,4096]"
          "{1,2,0:T(8,128)(2,1)}) fusion(bf16[2,4096,4096]{1,2,0:T(8,128)"
          "(2,1)S(1)} %get-tuple-element.1), kind=kOutput, calls=%fc.1")
FLASH = ("%checkpoint.19 = bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)} "
         "custom-call(bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)} %q), "
         'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
CONCAT = ("%custom-call.28 = bf16[2,8,4096,128]{3,2,1,0} custom-call("
          "bf16[1,8,4096,128]{3,2,1,0} %a), "
          'custom_call_target="ConcatBitcast"')
WHILE = ("%while.16 = (s32[]{:T(128)}, bf16[2,4096,4096]{1,2,0}) while("
         "(s32[]{:T(128)}, bf16[2,4096,4096]{1,2,0}) %tuple.3), "
         "condition=%cond, body=%body")
GATHER = ("%all-gather-start.3 = (bf16[2,128]{1,0}, bf16[8,128]{1,0}) "
          "all-gather-start(bf16[2,128]{1,0} %p), dimensions={0}")


def test_names_as_the_trace_gives_them():
    assert tr.op_name(FUSION) == "fusion.123"
    assert tr.opcode(FUSION) == "fusion"
    assert tr.opcode(WHILE) == "while" and tr.is_container(WHILE)
    assert not tr.is_container(FUSION)
    assert tr.opcode(FLASH) == "custom-call" and tr.is_mosaic(FLASH)
    assert tr.op_name(FLASH) == "checkpoint.19"
    assert not tr.is_mosaic(CONCAT) and not tr.is_mosaic(FUSION)
    assert tr.opcode(GATHER) == "all-gather-start"
    assert tr.is_collective(GATHER) and not tr.is_collective(FUSION)
    assert tr.is_collective("%reduce-scatter.1 = f32[8]{0} reduce-scatter("
                            "f32[32]{0} %g), dimensions={0}")
    # a bare name (no HLO text) falls back to the name without its number
    assert tr.opcode("while.1") == "while" and tr.opcode("copy") == "copy"


def test_the_hand_made_trace_reads_as_written(planes):
    assert sorted(planes) == ["/device:TPU:0", "/device:TPU:1",
                              "/host:CPU"]
    modules = sorted(planes["/device:TPU:0"]["XLA Modules"],
                     key=lambda e: e[1])
    assert [m[0] for m in modules if m[0].startswith("jit_train")] == [
        "jit_train_step(7)"] * 5
    name, start, end = modules[2]
    assert (start, end) == (pytest.approx(100 * US), pytest.approx(180 * US))
    assert sorted(planes["/device:TPU:0"]) == [
        "Async XLA Ops", "XLA Modules", "XLA Ops"]


def test_device_zero_by_hand(planes):
    spans = [("chipbench:step_line", 185 * US, 195 * US)]
    d = tr.reduce_device(planes["/device:TPU:0"], spans)
    # the step program runs at 20-80 (cut by the trace's start, dropped),
    # 100-180, 200-280, 300-380 and 400-405 (cut by its end; only its
    # start is used): the window is 100-400, three periods of 100
    assert d["step_module"] == "jit_train_step(7)" and d["periods"] == 3
    assert d["window_s"] == pytest.approx(300 * US)
    # a step at s: while s..s+70 (a container, no work of its own);
    # fusion.1 s..s+30 with a copy-start of a nanosecond inside it; the
    # Mosaic kernel s+30..s+50; all-gather-start issued at s+50 and in
    # flight until s+65 (async line); fusion.2 s+50..s+60 meanwhile;
    # all-gather-done waits s+60..s+65; ConcatBitcast s+65..s+66;
    # fusion.3 s+72..s+80; the rng split of the next step s+82..s+83.
    # Busy: s..s+66, s+72..s+80, s+82..s+83 = 75 of each 100
    assert d["busy_s"] == pytest.approx(3 * 75 * US)
    assert d["host_gap_s"] == pytest.approx(3 * 20 * US)
    assert d["mosaic_s"] == pytest.approx(3 * 20 * US)
    assert d["collective_s"] == pytest.approx(3 * 15 * US)
    # fusion.2 hides 10 of the gather's 15; the wait of 5 is exposed
    assert d["collective_exposed_s"] == pytest.approx(3 * 5 * US, rel=1e-3)
    assert d["by_op"]["fusion.1"] == pytest.approx(3 * 30 * US)
    assert d["by_op"]["mosaic:checkpoint.4"] == pytest.approx(3 * 20 * US)
    assert d["by_op"]["custom-call.9"] == pytest.approx(3 * 1 * US)
    assert not any(k.startswith("while") for k in d["by_op"])
    # idle: s+66..s+72 inside each step; s+80..s+82 and s+83..s+100
    # between steps. The host's span at 185-195 names the gap 183-200
    assert d["idle"]["inside_step:unattributed"] == pytest.approx(
        3 * 6 * US)
    assert d["idle"]["between_steps:chipbench:step_line"] == (
        pytest.approx(17 * US))
    assert d["idle"]["between_steps:unattributed"] == pytest.approx(
        (3 * 19 - 17) * US)


def test_means_over_chips_and_steps(planes):
    r = tr.reduce_planes(planes)
    assert r["chips"] == 2 and r["steps"] == 3
    # chip 1 has no fusion.2: its all-gather-done waits s+50..s+65, so
    # all 15 are exposed; busy is the same 75
    assert r["step_device_ms"] == pytest.approx(75e-3)
    assert r["step_period_ms"] == pytest.approx(100e-3)
    assert r["host_gap_ms"] == pytest.approx(20e-3)
    assert r["mosaic_ms"] == pytest.approx(20e-3)
    assert r["collective_ms"] == pytest.approx(15e-3)
    assert r["collective_exposed_ms"] == pytest.approx(
        (5 + 15) / 2 * 1e-3, rel=1e-3)
    assert r["busy_s"] / r["window_s"] == pytest.approx(0.75)
    assert r["device_ops"][0][0] == "fusion.1"
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_the_programs_spans_name_idle_gaps_without_their_tails(planes):
    """The program's ``telemetry.tracing.span`` reads ``dlrover:<name>``
    with the annotation's arguments as a ``#key=value#`` tail: it names
    a gap beside the worker's own spans, the tail cut. A save that
    spans the gap after the step at 200 (283-300) wins it from the
    shorter step line."""
    host = {"python3": [
        ("chipbench:step_line", 285 * US, 290 * US),
        ("dlrover:ckpt_save#step=56#", 281 * US, 301 * US),
        ("dlrover:host_sync#step=52#", 100 * US, 180 * US),
        ("$threading.py:1 wait", 0.0, 400 * US)]}
    r = tr.reduce_planes({"/device:TPU:0": planes["/device:TPU:0"],
                          "/host:CPU": host})
    idle = dict(r["idle_gaps"])
    assert idle["between_steps:dlrover:ckpt_save"] == pytest.approx(
        (2 + 17) * US)
    # the gaps inside the step at 100 (166-172) fall under host_sync
    assert idle["inside_step:dlrover:host_sync"] == pytest.approx(6 * US)
    assert not any("#" in name or "threading" in name for name in idle)


def test_a_trace_with_no_device_plane_gives_nothing():
    r = tr.reduce_planes({"/host:CPU": {"python3": [
        ("chipbench:input", 0.0, 1.0)]}})
    assert r["devices"] == {} and r["busy_s"] is None


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "recorded_v5e_fsdp4.xspace.txt")) as f:
        return tr.reduce_planes(tr.read_trace(
            ProfileData.from_text_proto(f.read())))


def test_the_recorded_fsdp4_trace(recorded):
    """What the full trace gave on the chip, the thinned one still
    gives, but for the busy time of the operations thinned away: a step
    of 3.9 s of which 2.6 s are all-gathers with nothing beside them."""
    r = recorded
    assert r["chips"] == 2 and r["steps"] == 2
    assert all(d["step_module"].startswith("jit_train_step")
               for d in r["devices"].values())
    assert r["step_period_ms"] == pytest.approx(3903.6, rel=1e-4)
    assert r["host_gap_ms"] < 0.1
    assert r["mosaic_ms"] == pytest.approx(147.6, rel=1e-3)
    assert r["collective_ms"] == pytest.approx(2594.7, rel=1e-3)
    assert r["collective_exposed_ms"] == pytest.approx(
        r["collective_ms"], rel=1e-6)
    assert 0.7 < r["busy_s"] / r["window_s"] < 1.0
    assert r["device_ops"][0][0].startswith("all-gather")
    assert any(name.startswith("mosaic:") for name, _ in r["device_ops"])
    assert not any(tr.is_container(name) for name, _ in r["device_ops"])
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_the_readers_of_the_trace_on_the_recorded_one(recorded):
    """Every per-layer metric that BENCHMARK.json takes from the device
    trace, through its own reader, as ``run.py`` calls it: with the
    configuration of the cell the trace was recorded in, its family's
    arithmetic and the device the run reported."""
    import json

    from chipbench import run

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "mistral7b-d20.fsdp4-steady"
    with open(os.path.join(REPO, "chipbench", "configs",
                           "mistral-7b-v0.3-d20-fsdp4.json")) as f:
        model = json.load(f)
    chipbench = os.path.join(REPO, "chipbench")
    context = {
        "trace": recorded, "model": model, "resume": None,
        "device": {"kind": "TPU v5 lite", "count": 4},
        "arithmetic": run.load_module(
            os.path.join(chipbench, "arithmetic.py")),
        "flops": run.load_module(os.path.join(
            chipbench, "families", model["family"], "flops.py"))}
    names = [m["name"] for m in bench["per_layer"]
             if m["source"] == "device_trace" and cell in m["workloads"]]
    assert len(names) == 8
    got = {name: run.read_layer_metric(name, context) for name in names}
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    assert got["mosaic_ms"] == recorded["mosaic_ms"]
    assert got["collective_exposed_ms"] == recorded["collective_exposed_ms"]
    # 6 half squares x 20 layers x 4 rows x 32 heads of 4096 x 4096 x
    # 128, a quarter of it a chip, at 197 TFLOP/s: 41.9 ms of 147.6
    assert got["flash_roofline"] == pytest.approx(28.36, rel=1e-3)
    # 4.750e14 model FLOPs over four chips in the step's busy time (the
    # thinned trace holds 3.1 of the step's 3.9 s: 15.4 on the chip)
    assert got["step_mfu_pct"] == pytest.approx(
        100 * 4.750e14 / (4 * 197e12) / (recorded["step_device_ms"] / 1e3),
        rel=1e-3)
    assert 15.0 < got["step_mfu_pct"] < 25.0
    assert got["device_idle_pct"] == pytest.approx(
        100 * (1 - recorded["busy_s"] / recorded["window_s"]))
    # with no device plane every one of them leaves its metric out
    context["trace"] = tr.reduce_planes({})
    assert all(run.read_layer_metric(name, context) is None
               for name in names)
