"""SPMD graph lint: check the program XLA will run against the plan the
planner priced.

The pass reuses the ``accelerate()`` build + ``lower()``/``compile()``
path of ``parallel.aot`` — the same artifacts the AOT fit-proof reads —
and checks invariants on three layers:

  StableHLO (pre-partitioning)   G102 host callbacks, G104 dtype drift
  lowering metadata              G103 weak-type (recompile-hazard) inputs
  optimized per-device HLO       G101 unintended full-parameter
                                 all-gathers / silently replicated
                                 params, G105 donation actually applied,
                                 G106 planner-vs-HLO collective byte
                                 audit

Rule ids:

  G101 sharded-strategy, replicated reality (or a hoisted full gather)
  G102 host callback inside the jitted step
  G103 weak-type python-scalar argument (recompiles on every new value)
  G104 dtype drift: f32 matmuls on a bf16 compute path
  G105 donation not applied to the train state
  G106 actual HLO collective bytes vs ``planner.predicted_collective_bytes``
  G107 compiled peak HBM above the configured per-device budget
  G108 serialized large collective: result consumed with no independent
       compute scheduled between issue and use (an overlap opportunity)

Every check is a pure function over lowered/compiled text so the AOT CLI
(``parallel.aot --lint``) and golden-fixture tests reuse them without
rebuilding models.
"""

from __future__ import annotations

import re
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from dlrover_tpu.analysis.findings import Finding
from dlrover_tpu.common.log import get_logger

logger = get_logger("analysis.graph")

ALL_GRAPH_RULES = ("G101", "G102", "G103", "G104", "G105", "G106",
                   "G107", "G108", "G109", "G110")

GRAPH_RULE_DOCS: Dict[str, str] = {
    "G101": "params the strategy shards are replicated in the compiled "
            "program, or one all-gather re-materializes the full "
            "parameter set",
    "G102": "host callback (pure_callback/io_callback/debug.print) "
            "inside the jitted train step",
    "G103": "weak-type python-scalar argument — recompiles on every "
            "distinct value",
    "G104": "f32 dot_generals dominate a bf16 compute path (dtype drift)",
    "G105": "buffer donation not applied to the train state",
    "G106": "compiled HLO collective bytes diverge from the planner's "
            "predicted collective bytes beyond tolerance",
    "G107": "compiled peak HBM residency exceeds the configured "
            "per-device budget",
    "G108": "a large collective's result is consumed with no "
            "independent compute between issue and use — the network "
            "sits on the critical path (overlap opportunity)",
    "G109": "a quantized program's output drifts from its bf16 twin "
            "beyond the ratcheted per-model baseline (numerics "
            "regression)",
    "G110": "a gather on the KV read path of a compiled serving "
            "program (decode/prefill/page-copy must read the pool "
            "with slices, never a gather over pages)",
}

# G108: collectives below this output size are not worth overlapping
# (latency-bound, not bandwidth-bound) — and the CPU-mesh test fixtures
# all sit far below it, so the rule stays clean on HEAD while firing on
# real serial exchanges (the committed fixture is sized above it).
G108_MIN_BYTES = 1 << 20

# ops that count as INDEPENDENT work the scheduler could have run under
# an in-flight collective: fused compute, bare dots/convs, kernels
# (custom-call), and counted loops (which contain compute)
_G108_COMPUTE_OPS = ("fusion", "dot", "convolution", "custom-call",
                     "while")

# Default G106 tolerance (ratio, symmetric in log space). Chosen as one
# power of two above the worst measured-vs-predicted ratio observed on
# the HEAD fixtures (~16.7x for the einsum capacity dispatch, whose
# [T,E,C] one-hot movement GSPMD realizes as per-layer all-gathers the
# cost model prices as compute) — so the audit tolerates GSPMD's
# discretion and per-device-vs-per-link accounting slop, while a
# dropped, double-counted or mis-scaled cost term (the regression tests
# perturb terms 100-10000x) fails loudly. See docs/static_analysis.md.
DEFAULT_AUDIT_TOL = 32.0

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute")

_CALLBACK_TARGETS = re.compile(
    r"custom_call\s*@(\w*callback\w*|xla_ffi_python\w*)", re.IGNORECASE
)


def _balanced_block(text: str, marker: str) -> str:
    """The brace-balanced block opened by ``marker`` ('' if absent) —
    alias maps nest braces (``{0}: (0, {1}, may-alias)``), so a lazy
    regex would stop at the first ``}``."""
    start = text.find(marker)
    if start < 0:
        return ""
    i = start + len(marker)
    depth = 1
    while i < len(text) and depth:
        if text[i] == "{":
            depth += 1
        elif text[i] == "}":
            depth -= 1
        i += 1
    return text[start + len(marker):i - 1]


def _shapes_bytes(fragment: str) -> int:
    """Total bytes of every ``dtype[dims]`` shape in an HLO fragment."""
    total = 0
    for m in re.finditer(r"\b(\w+)\[([\d,]*)\]", fragment):
        dt = _DTYPE_BYTES.get(m.group(1))
        if dt is None:
            continue
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * dt
    return total


def _max_shape_bytes(fragment: str) -> int:
    """Bytes of the LARGEST single ``dtype[dims]`` shape in an HLO
    fragment — the payload estimate for async ``-start`` ops, whose
    tuple shape carries BOTH the operand and result buffers (summing
    the members would double-count the traffic)."""
    best = 0
    for m in re.finditer(r"\b(\w+)\[([\d,]*)\]", fragment):
        dt = _DTYPE_BYTES.get(m.group(1))
        if dt is None:
            continue
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        best = max(best, n * dt)
    return best


def _computations(optimized_hlo: str) -> Dict[str, str]:
    """HLO computation name -> body text. Headers sit at column 0
    (``%region_1.22 (...) -> ... {`` / ``ENTRY %main (...) -> ... {``),
    bodies are indented, ``}`` at column 0 closes."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in optimized_hlo.splitlines():
        if (not line.startswith((" ", "}")) and "{" in line
                and "(" in line and "->" in line):
            name = line.split(" (", 1)[0]
            if name.startswith("ENTRY "):
                name = name[len("ENTRY "):]
            cur = name.strip()
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return {k: "\n".join(v) for k, v in comps.items()}


_WHILE_BODY_RE = re.compile(r"\bbody=(%[\w.\-]+)")
_TRIP_COUNT_RE = re.compile(r'known_trip_count\\?":\{\\?"n\\?":\\?"(\d+)')


def _loop_multipliers(comps: Dict[str, str]) -> Dict[str, int]:
    """Execution multiplier per computation: a while body's ops run
    trip-count times (nested loops multiply). XLA annotates counted
    loops — every ``lax.scan``, in particular the scan-over-layers every
    production model here uses — with ``known_trip_count`` on the while
    op; an unannotated while conservatively counts once (today's
    behavior for genuinely dynamic loops)."""
    parent: Dict[str, Tuple[str, int]] = {}  # body -> (enclosing, trip)
    for name, text in comps.items():
        for line in text.splitlines():
            if " while(" not in line:
                continue
            body = _WHILE_BODY_RE.search(line)
            if not body:
                continue
            trip = _TRIP_COUNT_RE.search(line)
            parent[body.group(1)] = (
                name, int(trip.group(1)) if trip else 1
            )

    mult: Dict[str, int] = {}

    def resolve(name: str, seen=()) -> int:
        if name in mult:
            return mult[name]
        if name not in parent or name in seen:
            return 1
        enclosing, trip = parent[name]
        mult[name] = trip * resolve(enclosing, seen + (name,))
        return mult[name]

    return {name: resolve(name) for name in comps}


def collective_bytes_by_kind(optimized_hlo: str) -> Dict[str, int]:
    """Per-device bytes moved by each collective kind in one step.

    Parses the optimized (post-SPMD-partitioning) HLO: each op line's
    *output* shape is what this device receives, weighted by the
    enclosing while-loops' trip counts (``_loop_multipliers``) — a TP
    allreduce inside the 32-layer scan body moves 32x its textual
    bytes, which is what the planner's per-layer terms price. ``-done``
    halves of async pairs are skipped so starts aren't double-counted.
    """
    out: Dict[str, int] = {}
    # shape is non-greedy .+?: the TPU backend emits TUPLE-shaped
    # collectives — "(f32[..]{..:T(8,128)}, f32[..]) all-reduce(" — whose
    # shape list contains spaces; _shapes_bytes then sums every member
    pat = re.compile(
        r"^\s*%?\S+ = (.+?) ("
        + "|".join(_COLLECTIVE_KINDS)
        + r")(-start)?\(", re.MULTILINE
    )
    comps = _computations(optimized_hlo)
    mult = _loop_multipliers(comps)
    for name, text in comps.items():
        for m in pat.finditer(text):
            out[m.group(2)] = (
                out.get(m.group(2), 0)
                + _shapes_bytes(m.group(1)) * mult.get(name, 1)
            )
    return out


def max_allgather_bytes(optimized_hlo: str) -> int:
    """Largest single all-gather output (bytes) in the step."""
    best = 0
    pat = re.compile(r"^\s*%?\S+ = (.+?) all-gather(-start)?\(",
                     re.MULTILINE)
    for m in pat.finditer(optimized_hlo):
        best = max(best, _shapes_bytes(m.group(1)))
    return best


# -- individual checks (pure functions over artifacts) ----------------------


def check_host_callbacks(stablehlo: str,
                         path: str = "<train_step>") -> List[Finding]:
    findings = []
    targets = sorted({m.group(1) for m in
                      _CALLBACK_TARGETS.finditer(stablehlo)})
    for t in targets:
        findings.append(Finding(
            rule_id="G102", path=path, line=0,
            message=f"host callback `{t}` lowered inside the jitted "
                    f"step: every invocation synchronizes device->host, "
                    f"serializing the step and deadlocking under SPMD "
                    f"if any peer skips it",
            fixit="move the callback out of the step (metrics ride the "
                  "step outputs), or gate debug prints behind a "
                  "config flag that stays off in production",
        ))
    return findings


def check_weak_type_inputs(args_info: Any,
                           path: str = "<train_step>") -> List[Finding]:
    """``lowered.args_info`` -> findings for weak-typed scalar args."""
    import jax

    findings = []
    for leaf in jax.tree.leaves(args_info,
                                is_leaf=lambda x: hasattr(x, "_aval")
                                or hasattr(x, "aval")):
        aval = getattr(leaf, "aval", None) or getattr(leaf, "_aval", None)
        if aval is not None and getattr(aval, "weak_type", False):
            findings.append(Finding(
                rule_id="G103", path=path, line=0,
                message=f"argument traced from a python scalar "
                        f"(weak-type {aval}): jit re-compiles for every "
                        f"distinct value — the classic per-step "
                        f"learning-rate recompile",
                fixit="wrap host scalars in jnp.asarray(...) (strong "
                      "dtype) before passing them into the step",
            ))
    return findings


def check_dtype_drift(stablehlo: str, compute_dtype: str,
                      path: str = "<train_step>",
                      max_f32_frac: float = 0.5) -> List[Finding]:
    """On a bf16 compute path, most dots must be bf16.

    A tolerated f32 minority covers the blessed exceptions (f32 logits /
    loss reductions, optimizer math); crossing ``max_f32_frac`` means
    params or activations are being silently upcast — the full matmul
    cost of the precision you thought you were saving.
    """
    if compute_dtype not in ("bfloat16", "bf16", "float16", "f16"):
        return []
    dots = re.findall(
        r"stablehlo\.dot_general.*?->\s*tensor<[^>]*x(\w+)>", stablehlo
    )
    if not dots:
        dots = re.findall(r"dot_general[^\n]*\btensor<[^>]*x(\w+)>",
                          stablehlo)
    if not dots:
        return []
    f32 = sum(1 for d in dots if d in ("f32", "f64"))
    frac = f32 / len(dots)
    if frac > max_f32_frac:
        return [Finding(
            rule_id="G104", path=path, line=0,
            message=f"{f32}/{len(dots)} dot_generals compute in f32 on a "
                    f"{compute_dtype} path ({frac:.0%} > "
                    f"{max_f32_frac:.0%}): activations or params are "
                    f"being silently upcast",
            fixit="check model compute_dtype plumbing and optimizer "
                  "dtype casts; only the logits/loss tail should be f32",
        )]
    return []


def check_donation(optimized_hlo: str, n_state_leaves: int,
                   path: str = "<train_step>",
                   min_frac: float = 0.5) -> List[Finding]:
    """Donated state must actually alias: each aliased pair reuses an
    input buffer for an output, halving peak param+optimizer residency.
    XLA silently DROPS donation on dtype/shape/layout mismatch (it only
    warns), so absence here is a real memory regression, not a style
    issue."""
    block = _balanced_block(optimized_hlo, "input_output_alias={")
    aliased = len(re.findall(r"\(\s*\d+\s*,", block))
    need = max(1, int(n_state_leaves * min_frac))
    if aliased < need:
        return [Finding(
            rule_id="G105", path=path, line=0,
            message=f"donation not applied: {aliased} aliased buffers "
                    f"for a train state of {n_state_leaves} leaves "
                    f"(expected >= {need}) — peak memory pays params + "
                    f"optimizer state twice",
            fixit="jit the step with donate_argnums=(0,) and keep "
                  "input/output state dtypes+shapes identical so XLA "
                  "can alias them",
        )]
    return []


def check_param_shardings(state_sharding: Any, abstract_state: Any,
                          mesh_plan: Any,
                          path: str = "<train_step>",
                          rel_frac: float = 1 / 64) -> List[Finding]:
    """A strategy with model axes >1 must actually shard its big params.

    Catches sharding-rule/param-tree mismatches: ``tree_shardings``
    falls back to replicated when no rule matches a path, which
    silently costs fsdp-times the param memory and a full-parameter
    gather per step. "Big" is RELATIVE — bytes >= ``rel_frac`` of the
    total parameter bytes — because every sane rule set deliberately
    replicates the small per-layer tensors (norm scales, biases), and
    an absolute element threshold misfires on them the moment layers
    are stacked (a 32-layer llama's norm scales are 131k elems and
    0.004% of the params)."""
    import jax

    sizes = dict(mesh_plan.axis_sizes()) if hasattr(
        mesh_plan, "axis_sizes") else {}
    model_par = max(sizes.get("fsdp", 1), 1) * max(
        sizes.get("tensor", 1), 1) * max(sizes.get("pipe", 1), 1)
    if model_par <= 1:
        return []
    findings = []
    leaves = list(zip(
        jax.tree_util.tree_leaves_with_path(state_sharding.params),
        jax.tree.leaves(abstract_state.params),
    ))
    total_bytes = sum(a.size * a.dtype.itemsize for _, a in leaves)
    min_bytes = max(total_bytes * rel_frac, 1024)
    for (keypath, sharding), aval in leaves:
        if aval.size * aval.dtype.itemsize < min_bytes:
            continue
        if getattr(sharding, "is_fully_replicated", False):
            name = jax.tree_util.keystr(keypath)
            findings.append(Finding(
                rule_id="G101", path=path, line=0,
                message=f"param {name} ({aval.shape}, {aval.size} elems) "
                        f"is fully replicated although the strategy "
                        f"declares model-parallel degree {model_par}: "
                        f"no sharding rule matched this path",
                fixit="add a rule for this param path to the strategy's "
                      "rule set (parallel/sharding_rules.py)",
            ))
    return findings[:8]


def check_full_param_gather(optimized_hlo: str, total_param_bytes: int,
                            path: str = "<train_step>",
                            frac: float = 0.6) -> List[Finding]:
    """One all-gather whose output is ~the whole parameter set = XLA
    hoisted the fsdp gather out of the layer loop. Bounded above as well:
    a single *param* gather can produce at most total_param_bytes, so a
    bigger gather is activation movement (e.g. the capacity-MoE one-hot
    tensors) priced elsewhere — G106's business, not G101's."""
    biggest = max_allgather_bytes(optimized_hlo)
    if (total_param_bytes > 0
            and total_param_bytes * frac <= biggest
            <= total_param_bytes * 1.25):
        return [Finding(
            rule_id="G101", path=path, line=0,
            message=f"one all-gather re-materializes "
                    f"{biggest / 1e6:.1f} MB (> {frac:.0%} of the "
                    f"{total_param_bytes / 1e6:.1f} MB parameter set) on "
                    f"every device: XLA hoisted a full-parameter gather "
                    f"out of the layer loop",
            fixit="check donation + sharding specs; a scan-over-layers "
                  "model should gather at most one layer's params at "
                  "a time",
        )]
    return []


def collective_audit(measured_total: float, predicted_total: float,
                     tol: float = DEFAULT_AUDIT_TOL,
                     path: str = "<train_step>",
                     detail: str = "") -> List[Finding]:
    """G106: the compiled program's collective bytes must be within a
    (log-symmetric) factor ``tol`` of what the planner priced.

    Too-high means XLA inserted traffic the cost model does not price
    (plan/graph divergence — the planner is ranking meshes on fiction);
    too-low means the model overprices and will veto good plans. Skipped
    when the prediction is below 1 KiB (single-chip / degenerate mesh:
    scalar-reduction noise would dominate the ratio).
    """
    if predicted_total < 1024:
        return []
    measured_total = max(measured_total, 1.0)
    ratio = measured_total / predicted_total
    if 1.0 / tol <= ratio <= tol:
        return []
    direction = (
        "collectives the cost model does not price (plan/graph "
        "divergence)" if ratio > tol else
        "far less traffic than priced (the cost model overprices this "
        "mesh and will veto good plans)"
    )
    return [Finding(
        rule_id="G106", path=path, line=0,
        message=f"compiled HLO moves {measured_total / 1e6:.2f} MB of "
                f"collectives vs {predicted_total / 1e6:.2f} MB "
                f"predicted (ratio {ratio:.1f}x, tolerance {tol:g}x): "
                f"{direction}" + (f" [{detail}]" if detail else ""),
        fixit="re-derive the planner term for this mesh "
              "(parallel/planner.py predicted_collective_bytes) or fix "
              "the sharding rules producing the extra movement",
    )]


def check_serialized_collectives(
    optimized_hlo: str,
    path: str = "<train_step>",
    min_bytes: int = G108_MIN_BYTES,
    max_findings: int = 4,
) -> List[Finding]:
    """G108: a large collective whose result is consumed with NO
    independent compute between issue and first use — the op-order
    rendering of "the network sits on the critical path". The compiled
    HLO's textual op order follows the schedule (def before use), so
    zero compute ops between a collective (or its ``-start``) and the
    first line referencing its result means the scheduler had nothing
    to hide the exchange under: a chunked/double-buffered formulation
    (``ops.moe`` dispatch_chunks, the FSDP layer prefetch) is the fix
    this tree ships. Collectives under ``min_bytes`` are skipped —
    latency-bound traffic isn't worth restructuring, and the tolerance
    keeps the rule clean on the CPU-mesh fixtures."""
    findings: List[Finding] = []
    op_re = re.compile(r"^\s*(%?[\w.\-]+) = (.+?) ([\w\-]+)\(")
    for comp_name, body in _computations(optimized_hlo).items():
        lines = body.splitlines()
        parsed = [op_re.match(ln) for ln in lines]
        for i, m in enumerate(parsed):
            if m is None:
                continue
            name, shape, opcode = m.group(1), m.group(2), m.group(3)
            is_start = opcode.endswith("-start")
            base = opcode[:-len("-start")] if is_start else opcode
            if base not in _COLLECTIVE_KINDS or opcode.endswith("-done"):
                continue
            # a -start op's tuple shape holds operand AND result
            # buffers: size by the largest member, not the sum
            nbytes = (_max_shape_bytes(shape) if is_start
                      else _shapes_bytes(shape))
            if nbytes < min_bytes:
                continue
            token = re.compile(re.escape(name) + r"\b")
            independent = 0
            use_line = None
            for j in range(i + 1, len(lines)):
                if token.search(lines[j]):
                    use_line = j
                    break
                pj = parsed[j]
                if pj is not None and pj.group(3) in _G108_COMPUTE_OPS:
                    independent += 1
            if use_line is None or independent > 0:
                continue
            findings.append(Finding(
                rule_id="G108", path=path, line=0,
                message=f"{base} ({nbytes / 1e6:.1f} MB, {name} in "
                        f"{comp_name}) is consumed immediately — no "
                        f"independent compute between issue and use, "
                        f"so the exchange sits fully exposed on the "
                        f"critical path",
                fixit="restructure for overlap: chunk the exchange and "
                      "double-buffer it under compute (ops/moe.py "
                      "dispatch_chunks, the ops/ring.py ppermute ring) "
                      "or prefetch the gather a layer ahead "
                      "(fsdp_prefetch)",
            ))
            if len(findings) >= max_findings:
                return findings
    return findings


# G109: how far above its committed baseline a model's quantization
# drift may grow before the lint fires. The baseline is the drift
# MEASURED at commit time (quant_baseline.json, per model label) — the
# ratchet mirrors the AST baseline's discipline: today's numerics are
# the contract, and a change that doubles the drift is a regression to
# explain, not to absorb silently. 4x leaves room for routing jitter
# across probe batches; an fp8 path gone wrong (scale bug, double
# quantization, a dequant in the wrong dtype) moves drift by orders of
# magnitude, not fractions.
G109_DRIFT_RATIO = 4.0
# the absolute floor under which drift differences are noise (f32
# accumulation order), and the default tolerance when a model has no
# committed baseline entry yet
G109_DRIFT_FLOOR = 1e-5
G109_DEFAULT_TOL = 0.02


def check_quantization_drift(measured_drift: float,
                             baseline_drift: Optional[float],
                             ratio: float = G109_DRIFT_RATIO,
                             path: str = "<train_step>",
                             detail: str = "") -> List[Finding]:
    """G109: the relative output drift of a quantized program against
    its bf16 twin (same params, same probe batch) must stay within the
    ratcheted per-model baseline — ``baseline * ratio``, floored so a
    near-zero committed baseline cannot make reassociation noise fire.
    ``baseline_drift=None`` (no committed entry) falls back to the
    absolute default tolerance. The G104 extension the low-precision
    paths needed: G104 catches dtype drift in the PROGRAM (f32 dots on
    a bf16 path); G109 catches drift in the NUMBERS (a quantization
    regression the graph text cannot show)."""
    if baseline_drift is None:
        tol = G109_DEFAULT_TOL
        basis = f"default tolerance {G109_DEFAULT_TOL:g} (no baseline)"
    else:
        tol = max(float(baseline_drift) * ratio, G109_DRIFT_FLOOR)
        basis = (f"baseline {baseline_drift:.3g} x {ratio:g} "
                 f"(floor {G109_DRIFT_FLOOR:g})")
    if measured_drift <= tol:
        return []
    return [Finding(
        rule_id="G109", path=path, line=0,
        message=f"quantized program drifts {measured_drift:.3g} "
                f"(relative) from its bf16 twin on the fixed probe "
                f"batch, above {basis}: the low-precision path's "
                f"numerics regressed"
                + (f" [{detail}]" if detail else ""),
        fixit="bisect the quantization path (ops/quantize.py encode, "
              "ops/grouped_matmul.py dequant-in-kernel, ops/moe.py "
              "wire boundary); if the drift increase is understood and "
              "acceptable, re-ratchet the model's entry in "
              "dlrover_tpu/analysis/quant_baseline.json",
    )]


def quantization_drift_baseline_path() -> str:
    import os

    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "quant_baseline.json")


def _probe_batch(config, global_batch: int, seed: int = 0):
    """The fixed, seeded probe batch every drift family shares."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed)
    seq = config.max_seq_len
    ids = rng.randint(0, config.vocab_size, size=(global_batch, seq + 1))
    return {
        "input_ids": jnp.asarray(ids[:, :-1]),
        "labels": jnp.asarray(ids[:, 1:]),
    }


def _measure_fsdp_drift(config, precision: str, global_batch: int):
    """The "fsdp" family probe: one forward loss of the dense llama
    with the quantized per-layer gather wire vs its bf16 twin. The
    wire transform is elementwise over the stacked params (quantize
    commutes with the per-layer slice), so the drift is pure weight-
    qdq rounding and mesh-independent — the probe runs unsharded."""
    import dataclasses

    import jax

    from dlrover_tpu.models import llama

    if config is None:
        config = llama.llama_tiny(num_layers=4)
    batch = _probe_batch(config, global_batch)
    params = llama.init(jax.random.PRNGKey(0), config)

    def loss_at(prec: str) -> float:
        cfg = dataclasses.replace(config, fsdp_precision=prec)
        out = jax.jit(llama.make_loss_fn(cfg))(
            params, batch, jax.random.PRNGKey(1))
        loss = out[0] if isinstance(out, tuple) else out
        return float(jax.device_get(loss))

    loss_q = loss_at(precision)
    loss_b = loss_at("bf16")
    drift = abs(loss_q - loss_b) / max(abs(loss_b), 1e-12)
    label = f"llama_tiny[fsdp,{precision}]@{jax.default_backend()}"
    return drift, label


def _measure_grad_drift(config, precision: str, global_batch: int,
                        steps: int = 4, lr: float = 1e-2):
    """The "grad" family probe: a few deterministic SGD steps with the
    error-feedback quantized gradient path vs the exact bf16 twin,
    judged on the final loss. Single-program (no mesh): the transform
    is elementwise over the gradient tree, so the drift does not
    depend on how the grads were sharded."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import _apply_grad_wire

    if config is None:
        config = llama.llama_tiny(num_layers=2)
    batch = _probe_batch(config, global_batch)
    loss_fn = llama.make_loss_fn(_dc.replace(config))
    grad_fn = jax.value_and_grad(
        lambda p, b, r: loss_fn(p, b, r)[0])

    def step(params, residual, quantized):
        loss, grads = grad_fn(params, batch, jax.random.PRNGKey(1))
        new_residual = residual
        if quantized:
            # the probed mode must be the LABELED mode — "fp8_nofb"
            # measures the no-feedback control, not the EF path
            grads, new_residual = _apply_grad_wire(
                grads, residual, precision)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, new_residual, loss

    def run(quantized: bool) -> float:
        params = llama.init(jax.random.PRNGKey(0), config)
        residual = jax.tree.map(jnp.zeros_like, params)
        loss = None
        fn = jax.jit(lambda p, r: step(p, r, quantized))
        for _ in range(steps):
            params, residual, loss = fn(params, residual)
        return float(jax.device_get(loss))

    loss_q = run(True)
    loss_b = run(False)
    drift = abs(loss_q - loss_b) / max(abs(loss_b), 1e-12)
    label = f"llama_tiny[grad,{precision}]@{jax.default_backend()}"
    return drift, label


def _measure_kv_drift(config, precision: str, global_batch: int,
                      prompt_len: int = 12, decode_steps: int = 6):
    """The "kv" family probe: teacher-forced prefill+decode over the
    serving KV cache with quantized (int8) page storage vs the f32
    pool, judged on the mean next-token cross entropy of the decode
    steps. Single-slot, unsharded: the page encode/decode is
    elementwise per token vector, so the drift does not depend on how
    the pool was sharded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import llama
    from dlrover_tpu.serving.kv_cache import (
        KVCacheSpec,
        init_kv_cache,
        resolve_kv_precision,
    )

    if resolve_kv_precision(precision) != precision:
        # the probe-fallback would silently run TWO f32 programs and
        # measure drift 0 against the ratchet — the fp8 families'
        # contract is to RAISE on an incapable host so the lint runner
        # skips the family with a warning instead of recording a
        # fiction (and the both-ways ratchet firing "improved")
        raise RuntimeError(
            f"kv drift probe: backend cannot run {precision!r} "
            "(capability probe failed)")
    if config is None:
        config = llama.llama_tiny()
    rng = np.random.RandomState(0)
    seq = rng.randint(0, config.vocab_size,
                      size=(prompt_len + decode_steps + 1,))

    def run(kvp: str) -> float:
        spec = KVCacheSpec.from_model(
            config, num_slots=2,
            max_seq=prompt_len + decode_steps + 1, page_size=8,
            precision=kvp)
        params = llama.init(jax.random.PRNGKey(0), config)
        cache = init_kv_cache(spec)
        cache, logits = llama.prefill_chunk(
            params, cache, jnp.asarray(seq[:prompt_len], jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(prompt_len),
            config, spec)
        active = jnp.asarray([True, False])
        total = -jax.nn.log_softmax(logits)[seq[prompt_len]]
        for j in range(decode_steps):
            tokens = jnp.asarray([seq[prompt_len + j], 0], jnp.int32)
            _nt, step_logits, cache = llama.decode_step(
                params, cache, tokens, active, config, spec)
            total = total - jax.nn.log_softmax(
                step_logits[0])[seq[prompt_len + j + 1]]
        return float(jax.device_get(total)) / (decode_steps + 1)

    loss_q = run(precision)
    loss_b = run("f32")
    drift = abs(loss_q - loss_b) / max(abs(loss_b), 1e-12)
    label = f"llama_tiny[kv,{precision}]@{jax.default_backend()}"
    return drift, label


def measure_quantization_drift(config=None, precision: str = "fp8",
                               global_batch: int = 4,
                               family: str = "moe"):
    """(drift, label): the relative loss difference between the
    quantized program and its bf16-wire twin on a FIXED probe batch —
    same params, same routing seed, only the wire precision differs.
    Deterministic per backend (the probe is seeded and single-process),
    which is what lets the baseline ratchet instead of tolerance-guess.

    ``family`` selects which quantized boundary is probed; each knob
    family ratchets its OWN ``quant_baseline.json`` entry (fire/clean
    per family): "moe" (the grouped_ep row-exchange wire — the default
    and the PR 11 behavior), "fsdp" (the dense per-layer param-gather
    wire, ``_measure_fsdp_drift``) and "grad" (the error-feedback
    gradient path, ``_measure_grad_drift``).

    The "moe" model: the tiny grouped_ep MoE llama over an explicit
    4-way (data x fsdp) expert submesh — every quantized boundary
    (row quantize, exchange, dequant-in-kernel, return wire) executes.
    Runs on the HOST backend's devices (the probe needs to EXECUTE,
    unlike the deviceless byte audits)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import llama

    if family == "fsdp":
        return _measure_fsdp_drift(config, precision, global_batch)
    if family == "grad":
        return _measure_grad_drift(config, precision, global_batch)
    if family == "kv":
        return _measure_kv_drift(config, precision, global_batch)
    if family != "moe":
        raise ValueError(f"unknown drift family {family!r}")
    if config is None:
        # chunks pinned to 1: the probe must not resolve an ambient
        # Context chunk knob (drift is C-invariant — per-row outputs
        # are exact at any C — but the baseline label should name ONE
        # program shape)
        config = llama.llama_tiny(
            num_experts=8, moe_dispatch="grouped_ep", moe_top_k=2,
            moe_dispatch_chunks=1,
        )
    # 4-way when the host has it, else 2-way — never an odd count the
    # (n//2, 2) mesh reshape cannot tile (a 3-device host must probe
    # on 2, not crash)
    n = 4 if len(jax.devices()) >= 4 else 2
    if len(jax.devices()) < 2:
        raise RuntimeError(
            "quantization drift probe needs >= 2 devices for the "
            "expert submesh"
        )
    from jax.sharding import Mesh

    mesh = Mesh(
        np.array(jax.devices()[:n]).reshape(n // 2, 2),
        ("data", "fsdp"),
    )
    rng = np.random.RandomState(0)
    seq = config.max_seq_len
    ids = rng.randint(0, config.vocab_size, size=(global_batch, seq + 1))
    batch = {
        "input_ids": jnp.asarray(ids[:, :-1]),
        "labels": jnp.asarray(ids[:, 1:]),
    }
    params = llama.init(jax.random.PRNGKey(0), config)

    def loss_at(prec: str) -> float:
        cfg = dataclasses.replace(config, mesh=mesh, moe_precision=prec)
        loss_fn = llama.make_loss_fn(cfg)
        out = jax.jit(loss_fn)(params, batch, jax.random.PRNGKey(1))
        loss = out[0] if isinstance(out, tuple) else out
        return float(jax.device_get(loss))

    loss_q = loss_at(precision)
    loss_b = loss_at("bf16")
    drift = abs(loss_q - loss_b) / max(abs(loss_b), 1e-12)
    # the label carries the EXECUTING backend: drift is a property of
    # the kernels that ran (interpret-mode on cpu, Mosaic on tpu —
    # different accumulation/fusion orders), so a baseline ratcheted
    # on one backend must not judge another; a backend without an
    # entry falls back to the absolute default tolerance
    label = (f"llama_tiny_moe[grouped_ep,{precision}]"
             f"@{jax.default_backend()}")
    return drift, label


def quantization_drift_audit(config=None, precision: str = "fp8",
                             baseline_path: str = "",
                             ratio: float = G109_DRIFT_RATIO,
                             family: str = "moe",
                             ) -> GraphLintReport:
    """The G109 acceptance audit: run the quantized-vs-bf16 probe for
    one knob ``family`` ("moe" | "fsdp" | "grad") and judge the drift
    against the committed per-model, per-family baseline
    (``dlrover_tpu/analysis/quant_baseline.json``) — numerics
    regressions fail ``tpulint`` / ``aot --lint`` the way byte
    regressions (G106) already do."""
    import json
    import os

    t0 = time.time()
    drift, label = measure_quantization_drift(config, precision,
                                              family=family)
    path = baseline_path or quantization_drift_baseline_path()
    baseline_drift = None
    if os.path.exists(path):
        with open(path) as fh:
            entries = json.load(fh).get("entries", {})
        entry = entries.get(label)
        if entry is not None:
            baseline_drift = float(entry.get("drift", 0.0))
    report = GraphLintReport(label=label)
    report.findings = check_quantization_drift(
        drift, baseline_drift, ratio=ratio, path=label,
        detail=f"measured drift {drift:.3g}",
    )
    report.build_seconds = time.time() - t0
    logger.info(
        "quantization drift audit %s: drift %.3g vs baseline %s, "
        "%d findings, %.1fs", label, drift, baseline_drift,
        len(report.findings), report.build_seconds,
    )
    return report


def check_memory_budget(peak_hbm_bytes: float, hbm_budget_bytes: float,
                        path: str = "<train_step>") -> List[Finding]:
    """G107: the compiled program's peak HBM (``memory_analysis``:
    args + temps + outputs - donated aliases, per device) must fit the
    configured budget — the static-analysis face of the runtime
    optimizer's memory-feasibility gate, so an over-budget program
    fails ``aot.py --lint`` BEFORE a chip is allocated. Skipped when
    either side is unknown (<= 0)."""
    if peak_hbm_bytes <= 0 or hbm_budget_bytes <= 0:
        return []
    if peak_hbm_bytes <= hbm_budget_bytes:
        return []
    return [Finding(
        rule_id="G107", path=path, line=0,
        message=f"compiled peak HBM {peak_hbm_bytes / 1e9:.2f} GB "
                f"exceeds the per-device budget "
                f"{hbm_budget_bytes / 1e9:.2f} GB "
                f"({peak_hbm_bytes / hbm_budget_bytes:.2f}x): this "
                f"program OOMs the devices it claims to target",
        fixit="shard more (fsdp/tensor), raise remat, shrink the "
              "per-chip batch, or raise "
              "DLROVER_TPU_DEVICE_HBM_BUDGET_BYTES if the budget is "
              "deliberately conservative",
    )]


# -- drivers ----------------------------------------------------------------


@dataclass
class GraphLintReport:
    label: str
    findings: List[Finding] = field(default_factory=list)
    measured_bytes: Dict[str, int] = field(default_factory=dict)
    predicted_bytes: Dict[str, float] = field(default_factory=dict)
    build_seconds: float = 0.0

    @property
    def measured_total(self) -> int:
        return sum(self.measured_bytes.values())

    @property
    def predicted_total(self) -> float:
        return sum(self.predicted_bytes.values())


def lint_artifacts(
    *,
    stablehlo: str,
    optimized_hlo: str = "",
    args_info: Any = None,
    state_sharding: Any = None,
    abstract_state: Any = None,
    mesh_plan: Any = None,
    model_spec: Any = None,
    device_spec: Any = None,
    compute_dtype: str = "",
    total_param_bytes: int = 0,
    n_state_leaves: int = 0,
    rules: Optional[Set[str]] = None,
    audit_tol: float = DEFAULT_AUDIT_TOL,
    pipe_virtual: int = 1,
    peak_hbm_bytes: float = 0.0,
    hbm_budget_bytes: float = 0.0,
    label: str = "<train_step>",
) -> GraphLintReport:
    """Run every enabled graph rule over already-built artifacts (the
    shared entry for ``lint_train_step`` and ``parallel.aot --lint``).
    ``pipe_virtual`` must match what the caller's ``estimate()`` priced —
    the circular schedule multiplies the pipe handoff bytes by V.
    ``peak_hbm_bytes``/``hbm_budget_bytes``: the compiled per-device
    residency and its budget for G107 (0 = skip the check)."""
    from dlrover_tpu.parallel import planner

    on = set(rules) if rules is not None else set(ALL_GRAPH_RULES)
    report = GraphLintReport(label=label)
    f = report.findings
    if "G102" in on:
        f.extend(check_host_callbacks(stablehlo, path=label))
    if "G103" in on and args_info is not None:
        f.extend(check_weak_type_inputs(args_info, path=label))
    if "G104" in on and compute_dtype:
        f.extend(check_dtype_drift(stablehlo, compute_dtype, path=label))
    if optimized_hlo:
        report.measured_bytes = collective_bytes_by_kind(optimized_hlo)
        if "G105" in on and n_state_leaves:
            f.extend(check_donation(optimized_hlo, n_state_leaves,
                                    path=label))
        if "G101" in on and total_param_bytes:
            f.extend(check_full_param_gather(
                optimized_hlo, total_param_bytes, path=label))
    if "G101" in on and state_sharding is not None and mesh_plan is not None:
        f.extend(check_param_shardings(
            state_sharding, abstract_state, mesh_plan, path=label))
    if ("G106" in on and optimized_hlo and mesh_plan is not None
            and model_spec is not None):
        report.predicted_bytes = planner.predicted_collective_bytes(
            mesh_plan, model_spec,
            device_spec or planner.TPU_SPECS["v5e"],
            pipe_virtual=pipe_virtual,
        )
        detail = ", ".join(
            f"{k}={v / 1e6:.2f}MB"
            for k, v in sorted(report.measured_bytes.items())
        )
        f.extend(collective_audit(
            report.measured_total, report.predicted_total,
            tol=audit_tol, path=label, detail=detail,
        ))
    if "G107" in on:
        f.extend(check_memory_budget(peak_hbm_bytes, hbm_budget_bytes,
                                     path=label))
    if "G108" in on and optimized_hlo:
        f.extend(check_serialized_collectives(optimized_hlo, path=label))
    return report


def lint_train_step(
    config=None,
    *,
    strategy=None,
    global_batch: int = 8,
    rules: Optional[Set[str]] = None,
    audit_tol: float = DEFAULT_AUDIT_TOL,
    devices=None,
    tpu_gen: str = "v5e",
    hbm_budget_bytes: float = 0.0,
    label: str = "",
) -> GraphLintReport:
    """Build (model, strategy) through ``accelerate``, lower + compile on
    the available devices, and lint the artifacts.

    Defaults to the bf16 ``llama_tiny`` on a data=2 x fsdp=2 x tensor=2
    mesh — small enough that the whole pass (build, lower, compile,
    checks) stays in single-digit seconds on a CPU host, while still
    exercising every collective family the planner prices.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel import planner
    from dlrover_tpu.parallel.accelerate import accelerate
    from dlrover_tpu.parallel.mesh import MeshPlan
    from dlrover_tpu.parallel.strategy import Strategy

    t0 = time.time()
    if config is None:
        config = llama.llama_tiny(
            param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16
        )
    if strategy is None:
        n = len(devices) if devices is not None else len(jax.devices())
        if n >= 8:
            plan = MeshPlan(data=2, fsdp=2, tensor=2)
        elif n > 1:
            plan = MeshPlan(data=1, fsdp=n)
        else:
            plan = MeshPlan(data=1)
        rule = "moe_ep" if (config.num_experts > 0
                            and config.moe_dispatch == "grouped_ep") else (
            "moe" if config.num_experts > 0 else "llama")
        strategy = Strategy(mesh=plan, rule_set=rule)

    rng = np.random.RandomState(0)
    seq = config.max_seq_len
    ids = rng.randint(0, config.vocab_size, size=(global_batch, seq + 1))
    batch = {
        "input_ids": jnp.asarray(ids[:, :-1]),
        "labels": jnp.asarray(ids[:, 1:]),
    }
    result = accelerate(
        llama.make_init_fn(config),
        llama.make_loss_fn(config),
        optax.adafactor(1e-3),
        batch,
        strategy=strategy,
        devices=devices,
    )
    abstract_state = jax.eval_shape(result.init_fn, jax.random.PRNGKey(0))
    abstract_batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    lowered = result.train_step.lower(abstract_state, abstract_batch, key)
    compiled = lowered.compile()

    model_spec = planner.model_spec_from_llama(config, global_batch)
    param_bytes = sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves(abstract_state.params)
    )
    name = label or (
        f"llama_tiny[{config.moe_dispatch}]" if config.num_experts > 0
        else "llama_tiny"
    )
    # G107 inputs: compiled residency via the shared memory shim, the
    # budget from the caller > Context knob > the device spec capacity
    from dlrover_tpu.common.config import get_context
    from dlrover_tpu.utils.prof import compiled_peak_bytes

    budget = (
        hbm_budget_bytes
        or float(getattr(get_context(), "device_hbm_budget_bytes", 0.0))
        or float(planner.TPU_SPECS[tpu_gen].hbm_bytes)
    )
    report = lint_artifacts(
        stablehlo=lowered.as_text(),
        optimized_hlo=compiled.as_text(),
        args_info=getattr(lowered, "args_info", None),
        state_sharding=result.state_sharding,
        abstract_state=abstract_state,
        mesh_plan=strategy.mesh.resolve(
            len(devices) if devices is not None else len(jax.devices())
        ),
        model_spec=model_spec,
        device_spec=planner.TPU_SPECS[tpu_gen],
        compute_dtype=jnp.dtype(config.compute_dtype).name,
        total_param_bytes=param_bytes,
        n_state_leaves=len(jax.tree.leaves(abstract_state)),
        rules=rules,
        audit_tol=audit_tol,
        peak_hbm_bytes=float(compiled_peak_bytes(compiled)),
        hbm_budget_bytes=budget,
        label=name,
    )
    report.build_seconds = time.time() - t0
    logger.info(
        "graph lint %s: %d findings, %.2f MB measured vs %.2f MB "
        "predicted collectives, %.1fs",
        name, len(report.findings), report.measured_total / 1e6,
        report.predicted_total / 1e6, report.build_seconds,
    )
    return report


def moe_dispatch_audit(
    dispatches=("gather", "einsum", "grouped", "grouped_ep"),
    num_experts: int = 4,
    audit_tol: float = DEFAULT_AUDIT_TOL,
    rules: Optional[Set[str]] = None,
) -> List[GraphLintReport]:
    """The acceptance audit: compile tiny MoE models for every dispatch
    and check each compiled program's collective bytes against the
    planner terms (``moe_disp_*`` et al.) — cost-model drift on ANY
    dispatch fails the lint.

    The "einsum" REFERENCE ORACLE is exempt from G108: its one-hot
    [T,E,C] capacity movement is serialized by construction (GSPMD
    all-gathers consumed straight into the dispatch einsums) and the
    planner already prices it as quadratic COMPUTE, not comm — it
    exists to test against, never to run. G108's job is keeping the
    production paths (grouped_ep's chunked exchange, the fsdp
    gathers) overlapped; those stay fully covered."""
    from dlrover_tpu.models import llama

    reports = []
    for dispatch in dispatches:
        config = llama.llama_tiny(
            num_experts=num_experts, moe_dispatch=dispatch
        )
        dispatch_rules = rules
        if dispatch == "einsum":
            dispatch_rules = (
                set(rules) if rules is not None else set(
                    ALL_GRAPH_RULES)
            ) - {"G108"}
        reports.append(lint_train_step(
            config,
            rules=dispatch_rules,
            audit_tol=audit_tol,
            label=f"llama_tiny_moe[{dispatch}]",
        ))
    return reports


# -- G110: the serving-program audit ----------------------------------------

_HLO_DEF_RE = re.compile(r"%([\w.\-]+)\s*=\s*\(?\s*(\w+)\[([\d,]*)\]")


def check_kv_read_gather(optimized_hlo: str,
                         path: str = "<serve>",
                         min_rank: int = 4) -> List[Finding]:
    """No ``gather`` whose operand is a KV pool tensor (rank >=
    ``min_rank``) may survive compilation of a serving program.

    The slot-major pool exists so decode reads K/V with contiguous
    (dynamic-)slices; a gather over pages re-materializes the page
    table indirection on device — per-token random access at HBM
    latency on the hottest serving loop. Rank separates the pool
    (``[L, S, T, KV, HD]`` and its scale leaves, rank 4-5) from the
    benign rank-2 table gathers every program legitimately contains
    (token embeddings ``[V, D]``, rotary tables): firing on those
    would make the rule all-noise. The scan covers every computation
    body, so gathers fused into fusion computations are seen too."""
    # name -> rank, from every instruction definition in the module
    ranks: Dict[str, int] = {}
    for m in _HLO_DEF_RE.finditer(optimized_hlo):
        dims = m.group(3)
        ranks[m.group(1)] = len(dims.split(",")) if dims else 0
    findings: List[Finding] = []
    # first operand, either inline-typed (`gather(f32[2,8,..]{..} %x,`)
    # or bare (`gather(%x,`); the lookbehind keeps `all-gather(` — a
    # *collective*, not an indexed read — out of scope
    gather_re = re.compile(
        r"(?<![\w-])gather\(\s*(?:(\w+)\[([\d,]*)\]\S*\s+)?%([\w.\-]+)")
    for line in optimized_hlo.splitlines():
        gm = gather_re.search(line)
        if gm is None:
            continue
        operand = gm.group(3)
        if gm.group(1) is not None:
            # operand written inline with a shape: count its dims
            rank = len(gm.group(2).split(",")) if gm.group(2) else 0
        else:
            rank = ranks.get(operand, 0)
        if rank >= min_rank:
            findings.append(Finding(
                rule_id="G110", path=path, line=0,
                message=f"compiled program gathers from rank-{rank} "
                        f"operand `%{operand}`: a gather over the KV "
                        f"pool puts per-token random access on the "
                        f"decode hot path (the slot-major layout "
                        f"exists so reads are contiguous slices)",
                fixit="index pages with lax.dynamic_slice / "
                      "dynamic_update_slice keyed by slot+position; "
                      "keep page indirection on the host (the router "
                      "picks the slot, the program slices it)",
            ))
    return findings


def serving_program_audit(
    rules: Optional[Set[str]] = None,
    num_slots: int = 4,
    max_seq: int = 64,
    prefill_chunk: int = 16,
    spec_draft_len: int = 4,
) -> List[GraphLintReport]:
    """Compile the five serving programs exactly as ``ServeEngine.
    _compile`` does — ``decode_step`` / ``prefill_chunk`` (with the
    on-device first-token argmax) / speculative ``verify_step`` with
    the cache donated, the prefix page copies with their destination
    donated — and lint each: the gather-free KV read invariant (G110:
    for ``verify_step`` this covers the masked multi-token KV append,
    whose ``mode="drop"`` scatter rows must not reintroduce a pool
    gather), donation actually applied (G105: losing it doubles pool
    residency per dispatch), and weak-type scalar args (G103: a
    python-int slot id would recompile per slot). No mesh/shardings
    needed: the invariants are layout properties of the single-device
    program, and GSPMD only partitions the same op stream."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import llama
    from dlrover_tpu.serving.kv_cache import (
        KVCacheSpec,
        copy_page_to_slot,
        copy_page_to_pool,
        init_kv_cache,
        init_prefix_pool,
    )

    config = llama.llama_tiny(param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16)
    spec = KVCacheSpec.from_model(
        config, num_slots=num_slots, max_seq=max_seq,
        prefix_pool_pages=4)
    params_abs = jax.eval_shape(
        lambda r: llama.init(r, config), jax.random.PRNGKey(0))
    cache_abs = jax.eval_shape(lambda: init_kv_cache(spec))
    pool_abs = jax.eval_shape(lambda: init_prefix_pool(spec))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

    def decode_fn(params, cache, tokens, active):
        return llama.decode_step(params, cache, tokens, active,
                                 config, spec)

    def prefill_fn(params, cache, tokens, slot, start, n_valid):
        cache, last_logits = llama.prefill_chunk(
            params, cache, tokens, slot, start, n_valid, config, spec)
        first = jnp.argmax(last_logits).astype(jnp.int32)
        return cache, last_logits, first

    def verify_fn(params, cache, tokens, active, n_draft):
        return llama.verify_step(params, cache, tokens, active,
                                 n_draft, config, spec)

    def admit_fn(cache, pool, slot, dst_start, src_page):
        return copy_page_to_slot(cache, pool, slot, dst_start,
                                 src_page, spec)

    def publish_fn(pool, cache, slot, src_start, dst_page):
        return copy_page_to_pool(pool, cache, slot, src_start,
                                 dst_page, spec)

    programs = [
        ("serve_decode",
         jax.jit(decode_fn, donate_argnums=(1,)),
         (params_abs, cache_abs, i32(num_slots),
          jax.ShapeDtypeStruct((num_slots,), jnp.bool_)),
         len(jax.tree.leaves(cache_abs))),
        ("serve_prefill",
         jax.jit(prefill_fn, donate_argnums=(1,)),
         (params_abs, cache_abs, i32(prefill_chunk), i32(), i32(),
          i32()),
         len(jax.tree.leaves(cache_abs))),
        ("serve_verify",
         jax.jit(verify_fn, donate_argnums=(1,)),
         (params_abs, cache_abs, i32(num_slots, spec_draft_len + 1),
          jax.ShapeDtypeStruct((num_slots,), jnp.bool_),
          i32(num_slots)),
         len(jax.tree.leaves(cache_abs))),
        ("serve_admit_copy",
         jax.jit(admit_fn, donate_argnums=(0,)),
         (cache_abs, pool_abs, i32(), i32(), i32()),
         len(jax.tree.leaves(cache_abs))),
        ("serve_publish_copy",
         jax.jit(publish_fn, donate_argnums=(0,)),
         (pool_abs, cache_abs, i32(), i32(), i32()),
         len(jax.tree.leaves(pool_abs))),
    ]
    on = set(rules) if rules is not None else set(ALL_GRAPH_RULES)
    reports = []
    for label, fn, abstract_args, n_donated in programs:
        t0 = time.time()
        lowered = fn.lower(*abstract_args)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        report = GraphLintReport(label=label)
        if "G110" in on:
            report.findings.extend(
                check_kv_read_gather(hlo, path=label))
        if "G105" in on:
            report.findings.extend(check_donation(
                hlo, n_donated, path=label))
        if "G103" in on:
            report.findings.extend(check_weak_type_inputs(
                getattr(lowered, "args_info", None), path=label))
        report.build_seconds = time.time() - t0
        logger.info("serving audit %s: %d findings, %.1fs",
                    label, len(report.findings), report.build_seconds)
        reports.append(report)
    return reports
