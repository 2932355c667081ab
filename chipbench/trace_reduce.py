#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers take: device busy time, time a step, idle gaps, time by
operation, Mosaic and collective time.

    python chipbench/trace_reduce.py <trace dir or .xplane.pb> <out.json>

Reading the file needs ``jax.profiler.ProfileData`` and nothing else of
JAX; ``run.py`` runs this in a child held to the CPU. Everything after
``read_trace`` is plain Python over (name, start, end) tuples in
seconds, which the tests drive with hand-made lines.

What a trace of a TPU holds (looked at by hand, PR 24, one v5e): one
plane a chip, ``/device:TPU:<n>``. Its line ``XLA Modules`` has one
event for every run of a compiled program; a run that the trace's
start or end cut is there too, shortened. Its line ``XLA Ops`` has one
event for every HLO instruction that ran, named by the instruction's
whole text (``%fusion.4 = bf16[...] fusion(...), kind=...``). A
``while`` (``call``, ``conditional``) spans the instructions of its
body and is no work of its own; every other event is. Instructions
that only issue or await a transfer (``copy-start``, ``slice-start``,
``all-gather-start``, their ``-done``) are events of a few
nanoseconds inside the fusion that runs meanwhile; the transfer itself
is an event of the line ``Async XLA Ops``, from start to done. A Mosaic
(Pallas) kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``. Host threads are lines of the
plane ``/host:CPU``; the worker's own spans
(``jax.profiler.TraceAnnotation``) are the events there whose names
begin with ``chipbench:``, the program's (``telemetry.tracing.span``)
those that begin with ``dlrover:``.
"""

import glob
import json
import os
import re
import sys

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = ("chipbench:", "dlrover:")  # the worker's, the program's
ASYNC_LINE = "Async XLA Ops"
CONTAINERS = ("while", "call", "conditional")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read_trace(profile):
    """``ProfileData`` -> {plane name: {line name: [(name, start_s,
    end_s)]}}; lines of one name within a plane are merged."""
    planes = {}
    for plane in profile.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                start = e.start_ns * 1e-9
                events.append((e.name, start,
                               start + e.duration_ns * 1e-9))
    return planes


# -- intervals ----------------------------------------------------------------


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals):
    return sum(end - start for start, end in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(intervals, holes):
    """The part of ``intervals`` (disjoint, sorted) outside ``holes``
    (disjoint, sorted)."""
    out = []
    for start, end in intervals:
        at = start
        for h_start, h_end in holes:
            if h_end <= at:
                continue
            if h_start >= end:
                break
            if h_start > at:
                out.append((at, h_start))
            at = max(at, h_end)
            if at >= end:
                break
        if at < end:
            out.append((at, end))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] given disjoint, sorted ``busy``."""
    return subtract([(lo, hi)], busy)


def op_name(text):
    """``%fusion.4 = bf16[...] fusion(...)`` -> ``fusion.4``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def opcode(text):
    """The HLO opcode of an event of the ops lines: the word before the
    operand list, or, for a bare name, the name without its number."""
    if " = " in text:
        found = re.search(r"[\]\})] ([a-z][a-z\-]*)\(", text)
        if found:
            return found.group(1)
    head, _, tail = op_name(text).rpartition(".")
    return head if head and tail.isdigit() else op_name(text)


def is_container(text):
    return opcode(text) in CONTAINERS


def is_collective(text):
    return opcode(text).startswith(COLLECTIVES)


def is_mosaic(text):
    return (opcode(text) == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in text)


# -- one device ---------------------------------------------------------------


def step_module(modules):
    """The compiled program that is the training step: the module whose
    runs take most of the time."""
    by_name = {}
    for name, start, end in modules:
        by_name[name] = by_name.get(name, 0.0) + end - start
    return max(by_name, key=by_name.get) if by_name else None


def reduce_device(lines, spans):
    """One chip's lines -> its numbers. The first run of the step
    program in a trace is cut by the trace's start, so the traced window
    runs from the start of the second run to the start of the last:
    whole periods, each a step and the gap after it."""
    modules = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
    name = step_module(modules)
    runs = [m for m in modules if m[0] == name][1:]
    if len(runs) < 2:
        return None
    lo, hi = runs[0][1], runs[-1][1]
    # every instruction that ran in the window, classified once:
    # (start, end, name, is it a collective, is it a Mosaic kernel)
    inside = [(start, end, op_name(text), is_collective(text),
               is_mosaic(text))
              for text, start, end in lines.get(OPS_LINE, [])
              if end > lo and start < hi and not is_container(text)]
    if not inside:
        return None
    periods = len(runs) - 1

    def spans_of(events):
        return union(clip([(e[0], e[1]) for e in events], lo, hi))

    busy = spans_of(inside)
    mosaic = spans_of(e for e in inside if e[4])
    # a collective is at work from its start to its done (the async
    # line), or for as long as its own instruction runs
    collective = union(
        spans_of(e for e in inside if e[3])
        + spans_of((start, end) for text, start, end
                   in lines.get(ASYNC_LINE, []) if is_collective(text)))
    compute = spans_of(e for e in inside if not e[3])
    between = union(clip(
        [(a[2], b[1]) for a, b in zip(runs, runs[1:])], lo, hi))
    by_op = {}
    for start, end, op, _, is_kernel in inside:
        key = ("mosaic:" if is_kernel else "") + op
        by_op[key] = by_op.get(key, 0.0) + min(end, hi) - max(start, lo)
    idle = {}
    for start, end in gaps(busy, lo, hi):
        where = ("between_steps" if total(clip(between, start, end))
                 > 0.5 * (end - start) else "inside_step")
        doing, most = "unattributed", 0.0
        for span_name, s, e in spans:
            overlap = min(e, end) - max(s, start)
            if overlap > most:
                doing, most = span_name, overlap
        key = f"{where}:{doing}"
        idle[key] = idle.get(key, 0.0) + end - start
    return {
        "step_module": name, "periods": periods, "window_s": hi - lo,
        "busy_s": total(busy),
        "host_gap_s": total(between),
        "mosaic_s": total(mosaic),
        "collective_s": total(collective),
        "collective_exposed_s": total(subtract(collective, compute)),
        "by_op": by_op, "idle": idle,
    }


def reduce_planes(planes):
    """All planes -> the reduced trace ``run.py`` hands to the readers:
    per-step means over the chips, and the breakdown."""
    spans = sorted(
        (name.split("#", 1)[0], start, end)  # without its #key=value# tail
        for plane, lines in planes.items() if plane.startswith("/host")
        for events in lines.values()
        for name, start, end in events if name.startswith(SPAN_PREFIX))
    devices = {}
    for plane, lines in sorted(planes.items()):
        if plane.startswith("/device:TPU:") and OPS_LINE in lines:
            reduced = reduce_device(lines, spans)
            if reduced:
                devices[plane] = reduced
    if not devices:
        return {"devices": {}, "busy_s": None, "window_s": None}
    n = len(devices)
    periods = min(d["periods"] for d in devices.values())

    def mean(key):
        return sum(d[key] for d in devices.values()) / n

    def per_step(key):
        """Milliseconds a step, mean over the chips."""
        return 1e3 * sum(d[key] / d["periods"]
                         for d in devices.values()) / n

    def merged(key):
        out = {}
        for d in devices.values():
            for name, seconds in d[key].items():
                out[name] = out.get(name, 0.0) + seconds / n
        return sorted(([k, v] for k, v in out.items()),
                      key=lambda kv: -kv[1])

    return {
        "devices": devices, "chips": n, "steps": periods,
        "busy_s": mean("busy_s"), "window_s": mean("window_s"),
        "step_device_ms": per_step("busy_s"),
        "step_period_ms": per_step("window_s"),
        "host_gap_ms": per_step("host_gap_s"),
        "mosaic_ms": per_step("mosaic_s"),
        "collective_ms": per_step("collective_s"),
        "collective_exposed_ms": per_step("collective_exposed_s"),
        "device_ops": merged("by_op"), "idle_gaps": merged("idle"),
    }


def main(argv):
    from jax.profiler import ProfileData

    source, out_file = argv
    planes = read_trace(ProfileData.from_file(find_xplane(source)))
    with open(out_file, "w") as f:
        json.dump(reduce_planes(planes), f)


if __name__ == "__main__":
    main(sys.argv[1:])
