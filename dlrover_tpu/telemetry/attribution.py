"""Performance attribution: device-time & HBM accounting per compiled
program.

Step time is one opaque number until something says where the device
time and the HBM went. This module captures an **attribution record**
per compiled train-step program — exact FLOPs and bytes-accessed from
``compiled.cost_analysis()``, compiled peak HBM from
``memory_analysis()`` (the same AOT artifacts the G106 graph lint
reads), per-collective bytes parsed from the optimized HLO, and
predicted per-collective seconds (the planner's
``predicted_collective_bytes`` formula when a ModelSpec is known, the
HLO-measured bytes over link bandwidth otherwise). At runtime the
executor fuses the record with measured step times into derived gauges:

  live MFU             compiled FLOPs/step over (measured step seconds
                       x device peak) — ``utils/prof.derived_mfu``, ONE
                       formula shared with the one-shot profiler
  arithmetic intensity FLOPs / bytes-accessed (HBM-bound when low)
  exposed-comm frac    clamped (1 - ideal compute s / measured step s):
                       an UPPER bound on un-overlapped communication
  HBM headroom         device bytes_limit - bytes_in_use where the
                       backend exposes memory stats

A second, optional source — a ``jax.profiler`` trace in Chrome
trace-event format (the ``*.trace.json(.gz)`` files a profile dump
contains) — is parsed into per-op-category device-time buckets
(collective vs compute vs infeed vs idle), giving *measured* overlap
where traces exist; committed fixtures keep the parser tested without
backend trace support.

Capture cost: one ``lower()`` (tracing is shared with the call path)
plus one XLA compile that the persistent compile cache typically serves
warm — ~0.1-0.2s on the CPU mesh, paid once per (topology, knob)
program-cache entry, never per step.
"""

from __future__ import annotations

import functools
import gzip
import json
import os
import re
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from dlrover_tpu.common.config import get_context
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry.events import emit_event
from dlrover_tpu.telemetry.names import DeviceScope, EventKind
from dlrover_tpu.utils.prof import derived_mfu

logger = get_logger("telemetry.attribution")

_MB = 1024 * 1024


def attribution_enabled() -> bool:
    """The capture gate: the attribution knob AND the telemetry master
    switch (a capture whose gauges land in the null registry would be
    pure compile cost)."""
    ctx = get_context()
    return bool(getattr(ctx, "attribution_enabled", True)) and bool(
        getattr(ctx, "telemetry_enabled", True)
    )


# ``jax.devices()[0].device_kind`` as each generation reports it ->
# the planner's spec row. A kind that is not here has no datasheet in
# this repo: its utilization is not published (gauge absent), never
# priced against another chip's peak.
_DEVICE_KIND_TO_GEN = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}


def resolve_device_spec():
    """The planner ``DeviceSpec`` for the ambient accelerator, matched
    on its real ``device_kind``; None for a kind this repo has no
    datasheet for (the CPU included) — set
    ``Context.device_peak_flops`` / ``device_hbm_budget_bytes`` to get
    derived quantities there."""
    import jax

    from dlrover_tpu.parallel import planner

    gen = _DEVICE_KIND_TO_GEN.get(jax.devices()[0].device_kind)
    return planner.TPU_SPECS[gen] if gen else None


def resolve_peak_flops(device_spec=None) -> float:
    """Per-device peak FLOPs/s for the MFU denominator:
    ``Context.device_peak_flops`` when set, else the device spec; 0.0
    (no utilization published) for an unknown device."""
    ctx_peak = float(getattr(get_context(), "device_peak_flops", 0.0))
    if ctx_peak > 0:
        return ctx_peak
    spec = device_spec or resolve_device_spec()
    return float(spec.flops_per_s) if spec is not None else 0.0


def resolve_hbm_budget(device_spec=None) -> float:
    """Per-device HBM budget in bytes for G107 / the optimizer's
    memory gate: ``Context.device_hbm_budget_bytes`` when set, else the
    device spec's capacity; 0.0 (no gate) for an unknown device."""
    ctx_budget = float(
        getattr(get_context(), "device_hbm_budget_bytes", 0.0))
    if ctx_budget > 0:
        return ctx_budget
    spec = device_spec or resolve_device_spec()
    return float(spec.hbm_bytes) if spec is not None else 0.0


@dataclass
class AttributionRecord:
    """One compiled program's cost facts (all per DEVICE, per optimizer
    STEP)."""

    flops_per_step: float = 0.0  # executed FLOPs (XLA cost model)
    bytes_accessed_per_step: float = 0.0  # HBM traffic
    peak_hbm_bytes: int = 0  # compiled residency (args+temps+out-alias)
    # per-collective-kind bytes parsed from the optimized HLO
    # (trip-count-weighted, per step — the G106 measured side)
    collective_bytes: Dict[str, float] = field(default_factory=dict)
    # per-family predicted collective seconds; keys are planner
    # families ("tp", "fsdp", ...) when source == "planner", HLO kinds
    # ("all-gather", ...) when source == "hlo"
    predicted_comm_s: Dict[str, float] = field(default_factory=dict)
    predicted_comm_total_s: float = 0.0
    # ideal compute seconds: flops_per_step / peak — the subtrahend of
    # the exposed-comm bound
    predicted_compute_s: float = 0.0
    peak_flops_per_s: float = 0.0
    hbm_budget_bytes: float = 0.0
    n_devices: int = 1
    source: str = "hlo"  # comm-bytes provenance: "planner" | "hlo"
    capture_seconds: float = 0.0
    # the program-cache key the trainer captured this record under
    program_key: str = ""
    # ``step_scope_table`` of the compiled step, kept only where the
    # capture was asked for it (a process that can open a profiling
    # window); no part of ``to_dict``: the ``step_scopes`` event is
    # its one way out
    step_scopes: Optional[Dict[str, Any]] = None

    @property
    def arithmetic_intensity(self) -> float:
        if self.bytes_accessed_per_step <= 0:
            return 0.0
        return self.flops_per_step / self.bytes_accessed_per_step

    def mfu(self, step_time_s: float) -> float:
        """Live MFU for one measured step time (shared formula)."""
        return derived_mfu(self.flops_per_step, step_time_s,
                           self.peak_flops_per_s)

    def exposed_comm_fraction(self, step_time_s: float) -> float:
        """Clamped (measured - ideal compute) / measured: the share of
        the step NOT explained by compute at peak — an upper bound on
        un-overlapped communication (plus every other inefficiency,
        which is why it is a bound, not a measurement)."""
        if step_time_s <= 0:
            return 0.0
        frac = 1.0 - self.predicted_compute_s / step_time_s
        return min(max(frac, 0.0), 1.0)

    def hbm_headroom_bytes(self) -> Optional[float]:
        """Budget minus compiled peak (static headroom); None when no
        budget is known."""
        if self.hbm_budget_bytes <= 0:
            return None
        return self.hbm_budget_bytes - self.peak_hbm_bytes

    def to_dict(self) -> Dict[str, Any]:
        return {
            "flops_per_step": self.flops_per_step,
            "bytes_accessed_per_step": self.bytes_accessed_per_step,
            "arithmetic_intensity": round(self.arithmetic_intensity, 4),
            "peak_hbm_mb": round(self.peak_hbm_bytes / _MB, 3),
            "collective_bytes": dict(self.collective_bytes),
            "predicted_comm_s": {
                k: round(v, 6) for k, v in self.predicted_comm_s.items()
            },
            "predicted_comm_total_s": round(
                self.predicted_comm_total_s, 6),
            "predicted_compute_s": round(self.predicted_compute_s, 9),
            "peak_flops_per_s": self.peak_flops_per_s,
            "hbm_budget_bytes": self.hbm_budget_bytes,
            "n_devices": self.n_devices,
            "source": self.source,
            "capture_seconds": round(self.capture_seconds, 3),
        }


# -- which phase and scope each instruction of a step belongs to --------------

# an instruction's phase: the first of these that is a component of its
# ``op_name`` (``parallel/accelerate.py`` names the halves of
# ``train_step``; JAX names a checkpoint's replay). In this order
# because a transposed operation carries its primal's ``forward`` inside
# ``backward/transpose(jvp(...))``, and a replayed one both
_PHASE_OF_COMPONENT = (("rematted_computation", "replay"),
                       ("backward", "backward"),
                       ("optimizer", "optimizer"), ("forward", "forward"))
PHASE_NONE = "none"

_INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME_RE = re.compile(r'\bop_name="([^"]*)"')
# ``= <shape> opcode(``: the first such match of an instruction's line
_OPCODE_RE = re.compile(r"[\]\})] ([a-z][a-z\-]*)\(")
# what a trace never shows at work: a ``while``, ``call`` or
# ``conditional`` spans the instructions of its body and is no work of
# its own, and the rest only name a value (half the instructions of a
# step, and no event of any trace of the chip)
_NOT_TRACED = frozenset(("while", "call", "conditional", "parameter",
                         "get-tuple-element", "tuple", "constant",
                         "bitcast"))
_FUSION_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
# ``jit(f)`` wraps a function's name, no part of the name stack
_JIT_NAME_RE = re.compile(r"\bp?jit\([^()]*\)")


@functools.lru_cache(maxsize=4096)  # a step repeats few ``op_name``s often
def scope_key(op_name: str) -> str:
    """``phase|scope/path`` of one ``op_name``: the transform wrappers
    (``jvp(...)``, ``transpose(...)``, ``checkpoint``, ``while``,
    ``body``) fall away, the ``DeviceScope`` names stay in their order,
    outermost first (``jvp(mtp)/mla/...`` -> ``mtp/mla``)."""
    components = [c for c in re.split(
        r"[/()]+", _JIT_NAME_RE.sub("", op_name)) if c]
    phase = next((p for c, p in _PHASE_OF_COMPONENT if c in components),
                 PHASE_NONE)
    return phase + "|" + "/".join(
        c for c in components if c in DeviceScope.ALL)


def step_scope_table(optimized_hlo: str) -> Dict[str, Any]:
    """For every instruction a device trace can show at work in a
    compiled step (those of the entry, the ``while`` bodies and the
    called computations; a fusion's body is the fusion's, a ``while``
    is its body's instructions, and a parameter, a constant, a tuple,
    its element or a bitcast runs nothing), its phase and scope
    path from its own ``op_name``, grouped::

        {"instructions": {"backward|mtp/mla": ["fusion.12", ...], ...},
         "mixed_phase": {"count": 3, "names": ["fusion.7", ...]}}

    A fusion the compiler gave no metadata goes where most of its
    body's named instructions are; any other instruction without an
    ``op_name`` is ``none|``. ``mixed_phase`` are the fusions whose
    body holds instructions of more than one phase: a trace gives such
    a fusion's whole time to one of them."""
    from dlrover_tpu.analysis.graph_lint import _computations

    # computation -> [(instruction or "" for one a trace never shows,
    #                  its key or None, the body it fuses)]
    rows: Dict[str, List[Tuple[str, Optional[str], str]]] = {}
    for comp, text in _computations(optimized_hlo).items():
        found = rows.setdefault(comp.lstrip("%"), [])
        for line in text.splitlines():
            head = _INSTRUCTION_RE.match(line)
            opcode = _OPCODE_RE.search(line) if head else None
            if not opcode:
                continue
            op_name = _OP_NAME_RE.search(line)
            calls = (_FUSION_CALLS_RE.search(line)
                     if opcode.group(1) == "fusion" else None)
            found.append((
                "" if opcode.group(1) in _NOT_TRACED else head.group(1),
                scope_key(op_name.group(1)) if op_name else None,
                calls.group(1) if calls else ""))
    fused = {body for found in rows.values() for _, _, body in found
             if body}
    instructions: Dict[str, List[str]] = {}
    mixed = []
    for comp, found in rows.items():
        if comp in fused:
            continue
        for name, key, body in found:
            if not name:
                continue
            inside = [k for _, k, _ in rows.get(body, ())
                      if k and k != PHASE_NONE + "|"]
            if key is None:
                key = (max(inside, key=inside.count) if inside
                       else PHASE_NONE + "|")
            instructions.setdefault(key, []).append(name)
            if len({k.split("|", 1)[0] for k in inside}
                   - {PHASE_NONE}) > 1:
                mixed.append(name)
    return {"instructions": instructions,
            "mixed_phase": {"count": len(mixed), "names": sorted(mixed)}}


def capture_attribution(
    result,
    example_batch: Any = None,
    model_spec=None,
    device_spec=None,
    mesh_plan=None,
    emit: bool = True,
    step_scopes: bool = False,
) -> AttributionRecord:
    """Build the attribution record for an ``AccelerateResult``'s
    compiled step program through the AOT path (the same lower+compile
    the G106 audit reads — tracing is shared with the call path and the
    persistent compile cache serves the XLA compile warm).

    ``model_spec``/``mesh_plan``: when both are known (the aot CLI, a
    trainer constructed with one) the per-collective comm seconds come
    from the planner's ``predicted_collective_bytes`` formula — the one
    set of formulas the G106 audit also prices. Without a ModelSpec the
    comm profile falls back to the compiled HLO's OWN collective bytes
    over link bandwidth (``source="hlo"``).

    ``step_scopes``: also keep ``step_scope_table`` of the compiled
    text on the record; asked for by a process that can open a
    profiling window, whose ``step_scopes`` event carries it.
    """
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.analysis.graph_lint import collective_bytes_by_kind
    from dlrover_tpu.utils.prof import compiled_peak_bytes

    if example_batch is None:
        raise ValueError("capture_attribution needs the example batch "
                         "to rebuild the step's abstract signature")
    spec = device_spec or resolve_device_spec()
    peak_flops = resolve_peak_flops(spec)
    budget = resolve_hbm_budget(spec)

    t0 = time.monotonic()
    abstract_state = jax.eval_shape(
        lambda r: result.init_fn(r), jax.random.PRNGKey(0)
    )
    abstract_batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        example_batch,
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    compiled = result.train_step.lower(
        abstract_state, abstract_batch, key).compile()

    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    peak_hbm = compiled_peak_bytes(compiled)
    scope_table = None
    try:
        text = compiled.as_text()
        coll = collective_bytes_by_kind(text)
        if step_scopes:
            t_table = time.monotonic()
            scope_table = step_scope_table(text)
            logger.info(
                "step scope table: %d instructions of %d bytes of HLO "
                "in %.3fs", sum(len(v) for v in
                                scope_table["instructions"].values()),
                len(text), time.monotonic() - t_table)
    except Exception:  # noqa: BLE001 — text dump is backend-dependent
        logger.debug("compiled text parse failed", exc_info=True)
        coll = {}
    coll_per_step = {name: float(v) for name, v in coll.items()}

    mesh_plan = mesh_plan if mesh_plan is not None else getattr(
        getattr(result, "strategy", None), "mesh", None)
    source = "hlo"
    if spec is None:
        # unknown device: no link bandwidth to price collectives with
        comm_s = {}
    elif model_spec is not None and mesh_plan is not None:
        from dlrover_tpu.parallel import planner

        predicted = planner.predicted_collective_bytes(
            mesh_plan, model_spec, spec)
        comm_s = {
            fam: b / (spec.dcn_bw if fam == "pipe" else spec.ici_bw)
            for fam, b in predicted.items() if b > 0
        }
        source = "planner"
    else:
        comm_s = {name: b / spec.ici_bw
                  for name, b in coll_per_step.items() if b > 0}

    mesh = getattr(result, "mesh", None)
    n_devices = int(mesh.devices.size) if mesh is not None else 1
    record = AttributionRecord(
        flops_per_step=flops,
        bytes_accessed_per_step=bytes_accessed,
        peak_hbm_bytes=peak_hbm,
        collective_bytes=coll_per_step,
        predicted_comm_s=comm_s,
        predicted_comm_total_s=sum(comm_s.values()),
        predicted_compute_s=(flops / peak_flops if peak_flops > 0
                             else 0.0),
        peak_flops_per_s=peak_flops,
        hbm_budget_bytes=budget,
        n_devices=n_devices,
        source=source,
        capture_seconds=time.monotonic() - t0,
        step_scopes=scope_table,
    )
    if emit:
        emit_event(
            EventKind.ATTRIBUTION_CAPTURED,
            flops_per_step=record.flops_per_step,
            bytes_accessed_per_step=record.bytes_accessed_per_step,
            arithmetic_intensity=round(record.arithmetic_intensity, 4),
            peak_hbm_mb=round(record.peak_hbm_bytes / _MB, 3),
            predicted_comm_total_s=round(
                record.predicted_comm_total_s, 6),
            predicted_compute_s=round(record.predicted_compute_s, 9),
            peak_flops_per_s=record.peak_flops_per_s,
            n_devices=record.n_devices,
            source=record.source,
            capture_seconds=round(record.capture_seconds, 3),
        )
    logger.info(
        "attribution captured: %.3g flops/step, %.3g bytes, peak HBM "
        "%.1f MB, comm %s (%.2fs, source=%s)",
        record.flops_per_step, record.bytes_accessed_per_step,
        record.peak_hbm_bytes / _MB,
        {n: f"{b / 1e6:.2f}MB" for n, b in coll_per_step.items()},
        record.capture_seconds, source,
    )
    return record


# -- measured overlap: jax.profiler trace -> device-time buckets --------------

# op-name patterns per category; first match wins. Collectives before
# compute: a fused op named "fusion.all-reduce..." is traffic.
_CATEGORY_PATTERNS: Tuple[Tuple[str, re.Pattern], ...] = (
    ("collective", re.compile(
        r"all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute|collective_permute|send\b|recv\b|"
        r"cross_replica", re.IGNORECASE)),
    ("infeed", re.compile(r"infeed|outfeed|host-to-device|"
                          r"device-to-host|transfer", re.IGNORECASE)),
    ("compute", re.compile(
        r"fusion|dot|conv|matmul|gemm|scatter|gather|reduce|"
        r"select|iota|broadcast|transpose|copy|sort|rng|custom-call",
        re.IGNORECASE)),
)


def categorize_op(name: str) -> str:
    """Trace-event op name -> device-time category
    (collective / infeed / compute / other)."""
    for category, pat in _CATEGORY_PATTERNS:
        if pat.search(name or ""):
            return category
    return "other"


def load_trace(path: str) -> List[Dict]:
    """Read a Chrome trace-event file (``.json`` or ``.json.gz``,
    either a bare event list or the ``{"traceEvents": [...]}``
    envelope) — the format ``jax.profiler`` dumps as
    ``*.trace.json.gz`` under a profile directory."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        data = json.load(fh)
    if isinstance(data, dict):
        data = data.get("traceEvents", [])
    return [e for e in data if isinstance(e, dict)]


def find_trace_files(profile_dir: str) -> List[str]:
    """Every ``*.trace.json[.gz]`` under a profiler dump directory."""
    out: List[str] = []
    for root, _dirs, files in os.walk(profile_dir):
        for name in files:
            if name.endswith((".trace.json", ".trace.json.gz")):
                out.append(os.path.join(root, name))
    return sorted(out)


def scopes_by_instruction(table: Dict[str, Any]) -> Dict[str, Tuple]:
    """``step_scope_table``'s groups (a ``step_scopes`` event's
    ``instructions``) turned around: instruction -> (phase, innermost
    scope, '' where it has none)."""
    out = {}
    for key, names in (table.get("instructions") or {}).items():
        phase, _, path = key.partition("|")
        for name in names:
            out[name] = (phase, path.rpartition("/")[2])
    return out


def parse_trace_events(records: List[Dict],
                       step_scopes: Optional[Dict[str, Any]] = None,
                       ) -> Dict[str, Any]:
    """Partition a trace's complete ('ph' == 'X') events into
    per-category seconds. Real profiler dumps hold MANY lanes (device
    cores, host threads) whose events overlap in time, so the sums are
    lane-aware:

      * category seconds (``collective_s`` …) sum over every lane;
      * ``busy_s`` is the busiest single (pid, tid) lane's busy time —
        the device cannot be busier than its busiest lane, and a host
        TraceMe lane must not double-count the wall;
      * ``idle_s`` is the wall envelope minus that busiest lane;
      * ``measured_comm_frac`` is collective over the CATEGORIZED
        device-op time (collective + compute + infeed) — uncategorized
        host-side lanes cannot dilute the communication share this
        exists to measure (the *measured* counterpart of the derived
        exposed-comm upper bound).

    With ``step_scopes`` (the ``step_scopes`` event of the program the
    window ran: a dump names its device events by instruction) the
    events that table names are also summed into ``by_phase`` and
    ``by_scope`` (the innermost scope, ``""`` for none), seconds over
    every lane like the categories."""
    where = scopes_by_instruction(step_scopes) if step_scopes else {}
    by_phase: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    per_cat: Dict[str, float] = {}
    per_track: Dict[Tuple, float] = {}
    t_min = float("inf")
    t_max = float("-inf")
    n_events = 0
    for e in records:
        if e.get("ph") != "X":
            continue
        try:
            start = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        if dur <= 0:
            continue
        n_events += 1
        cat = categorize_op(str(e.get("name", "")))
        per_cat[cat] = per_cat.get(cat, 0.0) + dur
        # a dump names a device event by its instruction, or by the
        # instruction's whole text (``%fusion.4 = bf16[...] fusion(``)
        instruction = str(e.get("name", "")).split(" = ", 1)[0].lstrip("%")
        if instruction in where:
            phase, scope = where[instruction]
            by_phase[phase] = by_phase.get(phase, 0.0) + dur
            by_scope[scope] = by_scope.get(scope, 0.0) + dur
        track = (e.get("pid"), e.get("tid"))
        per_track[track] = per_track.get(track, 0.0) + dur
        t_min = min(t_min, start)
        t_max = max(t_max, start + dur)
    # trace timestamps are microseconds
    wall = max(0.0, (t_max - t_min)) / 1e6 if n_events else 0.0
    seconds = {cat: v / 1e6 for cat, v in per_cat.items()}
    busy_s = max(per_track.values()) / 1e6 if per_track else 0.0
    collective_s = seconds.get("collective", 0.0)
    categorized_s = (collective_s + seconds.get("compute", 0.0)
                     + seconds.get("infeed", 0.0))
    split = {
        "by_phase": {k: round(v / 1e6, 6) for k, v in by_phase.items()},
        "by_scope": {k: round(v / 1e6, 6) for k, v in by_scope.items()},
    } if step_scopes else {}
    return {
        **split,
        "events": n_events,
        "wall_s": round(wall, 6),
        "busy_s": round(busy_s, 6),
        "idle_s": round(max(0.0, wall - busy_s), 6),
        "collective_s": round(collective_s, 6),
        "compute_s": round(seconds.get("compute", 0.0), 6),
        "infeed_s": round(seconds.get("infeed", 0.0), 6),
        "other_s": round(seconds.get("other", 0.0), 6),
        "measured_comm_frac": round(
            collective_s / categorized_s, 4
        ) if categorized_s > 0 else 0.0,
    }


def parse_trace_path(path: str,
                     step_scopes: Optional[Dict[str, Any]] = None,
                     ) -> Dict[str, Any]:
    """``parse_trace_events`` over one file or every trace under a
    profiler dump directory (events merge into one bucket set)."""
    if os.path.isdir(path):
        files = find_trace_files(path)
        if not files:
            raise FileNotFoundError(
                f"no *.trace.json[.gz] under {path}")
        records: List[Dict] = []
        for f in files:
            records.extend(load_trace(f))
        report = parse_trace_events(records, step_scopes)
        report["source_files"] = len(files)
        return report
    return parse_trace_events(load_trace(path), step_scopes)
