"""Cost-model mesh planner: pick the parallelism layout analytically.

Role parity: ``atorch/auto/opt_lib/shard_planners/`` —
``mip_tp_planner.py:29`` (mixed-integer-programming TP planner over an op
DAG with a comm/compute cost model), ``base_stage_planner.py:125``
(pipeline stage split), ``topology.py`` (device topology). The TPU search
space is small enough (factorizations of the device count over five mesh
axes) that exhaustive scoring under an analytic cost model replaces the
MIP solver; the cost model mirrors the roofline terms of the public
scaling playbook: MXU FLOPs, HBM bytes, ICI collective bytes.

The dryrun search (``parallel.search``) measures; this planner *predicts*
— useful before any compile (initial plan, elasticity re-planning) and as
the candidate-ordering prior for the measured search.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import MeshPlan, candidate_plans

logger = get_logger("parallel.planner")


@dataclass(frozen=True)
class DeviceSpec:
    """Per-chip capability (reference: topology.py DeviceTopology).
    Defaults are TPU v5e; override per generation."""

    flops_per_s: float = 197e12  # bf16
    hbm_bytes: float = 16e9
    hbm_bw: float = 8.2e11  # bytes/s
    ici_bw: float = 4.5e10  # bytes/s per link, one direction
    dcn_bw: float = 2.5e9  # bytes/s per host


TPU_SPECS = {
    "v4": DeviceSpec(275e12, 32e9, 1.2e12, 4.5e10),
    "v5e": DeviceSpec(197e12, 16e9, 8.2e11, 4.5e10),
    "v5p": DeviceSpec(459e12, 95e9, 2.8e12, 9.0e10),
    "v6e": DeviceSpec(918e12, 32e9, 1.6e12, 9.0e10),
}


@dataclass
class ModelSpec:
    """What the planner needs to know about the workload (derivable from
    a model config or ``utils.meta_init.param_stats``)."""

    param_count: int
    num_layers: int
    hidden_size: int
    seq_len: int
    global_batch: int  # rows per step
    vocab_size: int = 32000
    param_bytes: int = 2  # bf16 storage
    optim_bytes_per_param: int = 8  # adam moments in f32... adafactor ~1
    dtype_bytes: int = 2
    ffn_mult: float = 2.7  # intermediate/hidden ratio (llama ~2.69)
    # GQA shape: kv_heads/num_heads sets the ring-attention ICI bytes
    # (0 = MHA, kv bytes == activation bytes)
    num_heads: int = 0
    kv_heads: int = 0
    # switch-MoE shape (0 experts = dense). The dispatch choice changes
    # the cost STRUCTURE, not just a constant: see _moe_dispatch_terms.
    num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    # "gather" | "einsum" | "grouped" | "grouped_ep" (ops.moe dispatches)
    moe_dispatch: str = "gather"
    # grouped_ep chunked double-buffered dispatch (ops.moe
    # dispatch_chunks): C > 1 splits the row exchange into C
    # ppermute-ring chunks so the grouped GEMM overlaps the in-flight
    # exchange. The BYTES on the wire are invariant in C (the audit
    # contract); what changes is how much of them is EXPOSED — see
    # ``estimate``'s overlap-aware moe_disp_comm_s.
    moe_dispatch_chunks: int = 1
    # FSDP layer prefetch (models/llama.py fsdp_prefetch): gather layer
    # l+1's params under layer l's compute, exposing only the
    # non-overlappable remainder of the fsdp gather bytes.
    fsdp_prefetch: bool = False
    # grouped_ep wire precision (ops.moe precision / ops.quantize):
    # "fp8" ships the row exchanges as block-scaled e4m3 values plus
    # f32 per-block scales — the BYTES change (wire_bytes_per_elem
    # below), which is exactly what the G106 audit must see; the
    # schedule does not. "fp8_qdq" (the reference oracle) prices as
    # bf16: its wire IS full precision.
    moe_precision: str = "bf16"
    # dense FSDP wire precision (models/llama.py fsdp_precision):
    # "fp8" ships the per-layer param GATHERS of the scan-over-layers
    # as block-scaled e4m3 + f32 scales (``fsdp_wire_bytes_per_elem``
    # — ~1/4 of an f32 gather); the gradient reduce-scatter direction
    # stays at the param dtype — under GSPMD the cotangent reduction
    # ships the compute dtype regardless of the gradient-path
    # quantization (``grad_precision``), whose error-feedback qdq is a
    # numerics contract, not a transport change. "fp8_qdq" (the
    # dequant-exact oracle) prices at the full-precision wire it
    # actually ships.
    fsdp_precision: str = "bf16"

    def moe_wire_bytes_per_elem(self) -> float:
        """Wire bytes per exchanged row element, scale side-band
        INCLUDED: the quantized wire ships 1-byte e4m3 values plus one
        f32 scale per quantization block (ops.quantize layout), so a
        bf16 exchange drops to 1 + 4/32 = 1.125 bytes/elem (~0.56x).
        The ONE formula the pricing, the audit, and the tests'
        wire-bytes ratio all read. The "fp8_qdq" reference oracle
        prices at the f32 wire its implementation actually ships
        (``dequantize_block_scaled`` decodes to f32 before the
        exchange) — never at the bytes it does not save."""
        if self.moe_precision == "fp8":
            from dlrover_tpu.ops.quantize import resolve_quant_block

            block = resolve_quant_block(max(1, int(self.hidden_size)))
            return 1.0 + 4.0 / block
        if self.moe_precision == "fp8_qdq":
            return 4.0
        return float(self.dtype_bytes)

    def fsdp_wire_bytes_per_elem(self) -> float:
        """Wire bytes per gathered PARAM element on the dense FSDP
        gather legs, scale side-band included — the fsdp analog of
        ``moe_wire_bytes_per_elem`` and likewise the ONE formula the
        pricing, the G106 audit comparison and the tests' wire-bytes
        ratio read. "fp8" ships e4m3 values + one f32 scale per
        quantization block (blocks along each kernel's last dim;
        hidden_size is the representative channel count). "fp8_qdq"
        decodes BEFORE the wire, so it prices at the param bytes it
        actually ships (never winning on bytes it does not save)."""
        if self.fsdp_precision == "fp8":
            from dlrover_tpu.ops.quantize import resolve_quant_block

            block = resolve_quant_block(max(1, int(self.hidden_size)))
            return 1.0 + 4.0 / block
        return float(self.param_bytes)

    def fsdp_byte_split(self, fsdp: int, tensor: int = 1,
                        pipe: int = 1) -> Tuple[float, float]:
        """(gather_bytes, scatter_bytes) of the per-step dense FSDP
        traffic for one chip — the two DIRECTIONS of the wire, split
        so each can be priced at the dtype it actually ships:

          gather  : 2 traversals of the sharded params (the forward
                    per-layer all-gather + the backward re-gather the
                    remat replay pays) at ``fsdp_wire_bytes_per_elem``
                    — the legs the fsdp_precision knob compresses;
          scatter : 1 traversal (the gradient reduce-scatter) at the
                    param dtype — under GSPMD the cotangent reduction
                    ships the compute dtype regardless of
                    ``grad_precision`` (see docs/parallelism.md).

        At precision "bf16" the sum reproduces the historical
        ``3 * shard_bytes * (fsdp-1)/fsdp`` exactly."""
        if fsdp <= 1:
            return 0.0, 0.0
        shard_elems = self.param_count / (tensor * pipe)
        frac = (fsdp - 1) / fsdp
        gather = 2.0 * shard_elems * self.fsdp_wire_bytes_per_elem() * frac
        scatter = shard_elems * self.param_bytes * frac
        return gather, scatter


# Recompute multiplier on executed FLOPs per remat policy: "full" re-runs
# the forward in the backward (8N vs 6N per token), dots_saveable saves
# the matmul outputs and re-runs roughly half of the forward.
REMAT_RECOMPUTE = {
    "": 1.0,
    "none": 1.0,
    "dots_saveable": 7.0 / 6.0,
    "dots_and_attn_saveable": 7.0 / 6.0,
    "attn_saveable": 7.5 / 6.0,  # full minus the attention-fwd re-run
    "full": 8.0 / 6.0,
    "nothing_saveable": 8.0 / 6.0,  # jax alias for save-nothing
}

# No predicted step may claim better than this fraction of peak: keeps
# every prediction physical (MFU < 1) even with zero modeled comm.
MAX_EFFICIENCY = 0.9

# Host-side overhead per compiled-step dispatch (the Python step loop,
# runtime enqueue, rng split, lagged-ring bookkeeping). The number is an
# order of magnitude read off a CPU run of ``llama_tiny`` (round 6); no
# chip produced it. The ledger's ``dispatch_ms`` on a v5e reads 2.9 to
# 10.4 ms a step (PR 30, three steady cells), 8 to 30 times this, under
# steps of 0.9 to 1.2 s. The executor's in-flight window overlaps it
# with device work, so it enters the step time as a FLOOR (max), not an
# additive term: big models never see it, while tiny/fast steps are
# host-dispatch-bound.
HOST_DISPATCH_OVERHEAD_S = 350e-6


@dataclass(frozen=True)
class CalibrationAnchor:
    """One measured (model, chip) -> step-time point used to fit the
    compute-efficiency term (reference: the MIP planner's cost model is
    likewise fitted to profiled kernels, ``mip_tp_planner.py:29``)."""

    name: str
    model: ModelSpec
    device_gen: str
    remat_policy: str
    measured_step_s: float
    measured_mfu: float


# Single-chip anchors (llama_pretrain_mfu on one v5e) from chip runs
# of rounds 1-3, made before the chip that builders have now; their
# record files are gone (git history has them) and PERF_LEDGER.jsonl
# holds no line for them yet — recalibrate from the ledger (ROADMAP D6).
MEASURED_ANCHORS = (
    CalibrationAnchor(
        name="bench_r01_940m",  # round 1, a 940M preset
        model=ModelSpec(
            param_count=940_640_256, num_layers=16, hidden_size=2048,
            seq_len=2048, global_batch=4, vocab_size=32000,
            optim_bytes_per_param=1, ffn_mult=5504 / 2048,
            num_heads=16, kv_heads=16,
        ),
        device_gen="v5e",
        remat_policy="dots_saveable",
        measured_step_s=0.443,
        measured_mfu=0.5676,
    ),
    CalibrationAnchor(
        name="bench_r02_2p7b",  # round 2, the 2.7B preset
        model=ModelSpec(
            param_count=2_701_560_320, num_layers=32, hidden_size=2560,
            seq_len=2048, global_batch=2, vocab_size=32000,
            optim_bytes_per_param=1, ffn_mult=6912 / 2560,
            num_heads=20, kv_heads=20,
        ),
        device_gen="v5e",
        remat_policy="full",
        measured_step_s=0.701,
        measured_mfu=0.5106,
    ),
    CalibrationAnchor(
        name="bench_r03_2p7b_tuned",  # round-3 shape-sweep winner
        model=ModelSpec(
            param_count=2_701_560_320, num_layers=32, hidden_size=2560,
            seq_len=1024, global_batch=16, vocab_size=32000,
            optim_bytes_per_param=1, ffn_mult=6912 / 2560,
            num_heads=20, kv_heads=20,
        ),
        device_gen="v5e",
        remat_policy="full",
        measured_step_s=2.4624,
        measured_mfu=0.5645,
    ),
)


_DEFAULT_EFFICIENCY: Optional[float] = None


def calibrated_efficiency(anchors: Tuple = MEASURED_ANCHORS) -> float:
    """Executed-FLOP throughput / peak, geomean-fitted to the measured
    anchors (~0.67 on v5e), clamped to MAX_EFFICIENCY."""
    global _DEFAULT_EFFICIENCY
    if anchors is MEASURED_ANCHORS and _DEFAULT_EFFICIENCY is not None:
        return _DEFAULT_EFFICIENCY
    effs = []
    for a in anchors:
        exec_flops = _flops_per_step(a.model) * REMAT_RECOMPUTE.get(
            a.remat_policy, 1.0
        )
        dev = TPU_SPECS[a.device_gen]
        effs.append(exec_flops / (dev.flops_per_s * a.measured_step_s))
    out = float(min(math.exp(
        sum(math.log(e) for e in effs) / len(effs)
    ), MAX_EFFICIENCY))
    if anchors is MEASURED_ANCHORS:
        _DEFAULT_EFFICIENCY = out
    return out


@dataclass
class PlanScore:
    plan: MeshPlan
    step_time_s: float
    memory_bytes: float
    fits: bool
    breakdown: Dict[str, float]
    predicted_mfu: float = 0.0


# breakdown keys that are ICI/DCN collective seconds — the "comm" term
# of combine_step_time (and the term the runtime calibrator scales as
# one family; see master/optimizer/calibration.py)
COMM_BREAKDOWN_KEYS = (
    "tp_comm_s", "fsdp_comm_s", "dp_comm_s", "seq_comm_s",
    "pipe_comm_s", "moe_disp_comm_s",
)


def overlap_exposed_comm(comm_s: float, overlappable_compute_s: float,
                         chunks: int) -> float:
    """EXPOSED seconds of a chunked, double-buffered exchange — the
    overlap-aware pricing both overlapped paths share (chunked expert
    dispatch, FSDP layer prefetch), so the planner stops summing comm
    and compute serially where the program actually interleaves them.

    The C-chunk schedule is: exchange chunk 0; then for each next chunk
    its exchange runs UNDER the previous chunk's compute; the last
    chunk's compute runs alone. With per-chunk exchange e = comm/C and
    per-chunk compute g = overlappable/C the exposed comm is
    e + (C-1)*max(e - g, 0), which simplifies to

        max(comm_s / C,  comm_s - (C-1)/C * overlappable_compute_s)

    — at C=1 this is the serial comm_s; it is non-increasing in C for
    fixed bytes (both tests pin both directions), and it can never go
    below comm_s/C (the un-overlappable head of the pipeline)."""
    c = max(1, int(chunks))
    if comm_s <= 0:
        return 0.0
    if c <= 1:
        return comm_s
    return max(comm_s / c,
               comm_s - overlappable_compute_s * (c - 1) / c)


def combine_step_time(compute_s: float, comm_s: float,
                      dispatch_s: float,
                      overlapped: bool = True) -> float:
    """The ONE formula turning cost terms into a predicted step time —
    used by ``estimate`` and by the runtime optimizer's calibrated
    re-pricing (``master/optimizer/calibration.py``), so the two can
    never drift apart.

    Comm overlaps compute imperfectly: charge the max plus a quarter of
    the smaller (conservative). The host dispatch cost enters as a
    FLOOR when the executor's in-flight window overlaps it with device
    work (``overlapped=True``, the production default); a synchronous
    loop (``train_window=0``) pays it additively. Dispatch-bound plans
    keep a 1% residual of their device time so the ranking still
    prefers the faster compiled program instead of collapsing every
    tiny-model mesh into a tie."""
    step_s = max(compute_s, comm_s) + 0.25 * min(compute_s, comm_s)
    if not overlapped:
        return step_s + dispatch_s
    if dispatch_s > step_s:
        step_s = dispatch_s + 0.01 * step_s
    return step_s


def _flops_per_step(m: ModelSpec) -> float:
    tokens = m.global_batch * m.seq_len
    attn = 12 * m.num_layers * m.hidden_size * m.seq_len * 0.5
    return (6.0 * m.param_count + attn) * tokens


def ring_kv_repeat(kv_heads: int, num_heads: int,
                   tensor: int) -> Optional[int]:
    """The minimal KV-head repeat ``ops.ring_attention`` applies when the
    kv heads don't divide the tensor axis — planner-visible so the seq
    comm term prices the extra ICI bytes instead of hiding them.

    Returns None when NO legal repeat exists — the same inputs make the
    runtime legalizer (``ops.flash_attention.minimal_kv_repeat``) raise,
    so the planner must demote the mesh as infeasible rather than price
    a program that cannot be built."""
    if kv_heads <= 0 or tensor <= 1 or kv_heads % tensor == 0:
        return 1
    num_heads = max(num_heads, kv_heads)
    for rep in range(1, num_heads // kv_heads + 1):
        if (kv_heads * rep) % tensor == 0 and num_heads % (kv_heads * rep) == 0:
            return rep
    return None


def _moe_dispatch_terms(
    model: ModelSpec,
    device: DeviceSpec,
    eff: float,
    tokens_per_chip: float,
    ep: int,
) -> Tuple[float, float]:
    """(extra compute seconds, extra ICI *bytes*) the MoE DISPATCH adds
    per step — the term that ranks ``grouped_ep`` against the capacity
    paths honestly (the expert GEMMs themselves ride the 6N model-FLOPs
    compute term like every other matmul).

    Cost structure per layer (t = tokens/chip, k = top_k, cf =
    capacity_factor, D = hidden, P = expert-parallel degree):

      einsum, and gather when experts shard over the EP submesh (P>1):
        the one-hot [T,E,C] dispatch/combine einsums — the gather
        path's data-dependent scatters are opaque to GSPMD across the
        expert axis, so the EP-sharded lowering falls back to exactly
        this capacity-shaped movement. 2 einsums x 2TECD FLOPs x 3
        (fwd+bwd) with E*C = cf*k*t  =>  12*cf*k*t^2*D — QUADRATIC in
        tokens.
      gather / grouped per-shard (P==1): slot-map gathers, O(t*D) HBM
        bytes — linear and tiny.
      grouped_ep: two all_to_alls fwd + their transposes bwd moving the
        static dropless row buffer [P, t*k, D] => 4*P*t*k*D *
        wire_bytes_per_elem bytes on ICI — LINEAR in tokens, and
        DTYPE-AWARE: the fp8 wire ships 1-byte values + the f32
        per-block scale side-band (``ModelSpec.moe_wire_bytes_per_elem``
        — ~0.56x of bf16), which is what the G106 audit of a quantized
        program must be compared against. (The buffer is the
        static-shape worst case the implementation actually exchanges;
        see ``ops.moe._moe_compute_grouped_ep``.)

    The quadratic-vs-linear structure crosses over: below ~12k
    tokens/chip (v5e numbers) the capacity fallback wins, above it
    ``grouped_ep`` does — ``tests/test_planner.py`` pins the flip.
    """
    if model.num_experts <= 0:
        return 0.0, 0.0
    t = tokens_per_chip
    d = model.hidden_size
    k = max(1, model.moe_top_k)
    cf = model.moe_capacity_factor
    layers = model.num_layers
    dispatch = model.moe_dispatch
    if dispatch == "einsum" or (dispatch == "gather" and ep > 1):
        flops = 12.0 * cf * k * t * t * d * layers
        return flops / (device.flops_per_s * eff), 0.0
    if dispatch == "grouped_ep" and ep > 1:
        ici_bytes = (4.0 * ep * t * k * d * layers
                     * model.moe_wire_bytes_per_elem())
        return 0.0, ici_bytes
    if dispatch == "grouped" and ep > 1:
        # the kernel is opaque to GSPMD: EP-sharded expert weights get
        # all-gathered to every chip each layer (fwd + the grad
        # reduce-scatter bwd) — price that honestly so the planner
        # steers EP meshes to grouped_ep/gather instead
        w_bytes = (2.0 * model.num_experts * d * (model.ffn_mult * d)
                   * model.dtype_bytes)
        ici_bytes = 3.0 * w_bytes * (ep - 1) / ep * layers
        return 0.0, ici_bytes
    # per-shard gather/grouped (and grouped_ep degraded to P==1):
    # slot-gather/sort data movement, a few passes over the token rows
    hbm_bytes = 4.0 * cf * k * t * d * model.dtype_bytes * layers
    return hbm_bytes / device.hbm_bw, 0.0


def predicted_collective_bytes(
    plan: MeshPlan,
    model: ModelSpec,
    device: DeviceSpec = DeviceSpec(),
    efficiency: Optional[float] = None,
    pipe_virtual: int = 1,
) -> Dict[str, float]:
    """Per-step collective traffic (bytes, per link/chip) the cost model
    prices for one mesh — the SAME formulas ``estimate`` divides by link
    bandwidth, exposed so the graph lint (``dlrover_tpu.analysis``) can
    audit the compiled HLO's actual collective bytes against the plan the
    planner scored. If the two drift by more than the audit tolerance,
    either XLA is executing a different program than the one we priced
    (plan/graph divergence) or the cost model has rotted — both must fail
    loudly (ISSUE 2 / ElasWave's silent-divergence failure class).

    Keys: ``tp`` (activation allreduces), ``fsdp`` (param gather + grad
    scatter), ``dp`` (grad allreduce), ``seq`` (ring-attention KV
    rotation), ``pipe`` (stage-boundary activation handoff — DCN, not
    ICI), ``moe_dispatch`` (all-to-all / weight-gather bytes of the MoE
    dispatch; 0 for the capacity paths, whose overhead is compute-shaped).
    """
    pipe = max(getattr(plan, "pipe", 1), 1)
    data = max(getattr(plan, "data", 1), 1)
    fsdp = max(getattr(plan, "fsdp", 1), 1)
    seq = max(getattr(plan, "seq", 1), 1)
    tensor = max(getattr(plan, "tensor", 1), 1)

    rows = model.global_batch / max(data * fsdp, 1)
    act_elems = rows * (model.seq_len / seq) * model.hidden_size

    out = {"tp": 0.0, "fsdp": 0.0, "dp": 0.0, "seq": 0.0, "pipe": 0.0,
           "moe_dispatch": 0.0}
    if tensor > 1:
        bytes_per_ar = 2 * (tensor - 1) / tensor * (
            act_elems * model.dtype_bytes
        )
        out["tp"] = 4 * model.num_layers * bytes_per_ar
    if fsdp > 1:
        # dtype-aware split (ModelSpec.fsdp_byte_split): the 2 gather
        # traversals at the wire precision + the reduce-scatter at the
        # param dtype — at "bf16" this IS the historical
        # 3 * shard_bytes * (fsdp-1)/fsdp
        gather_b, scatter_b = model.fsdp_byte_split(fsdp, tensor, pipe)
        out["fsdp"] = gather_b + scatter_b
    if data > 1:
        grad_bytes = model.param_count * model.param_bytes / (
            tensor * pipe * fsdp
        )
        out["dp"] = 2 * grad_bytes * (data - 1) / data
    if pipe > 1:
        out["pipe"] = (
            2 * max(pipe_virtual, 1) * act_elems * model.dtype_bytes
        )
    if seq > 1:
        kv_frac = 1.0
        if model.kv_heads and model.num_heads:
            rep = ring_kv_repeat(model.kv_heads, model.num_heads, tensor)
            # rep None = infeasible heads (estimate marks the plan
            # unbuildable); keep the rep=1 bytes so the breakdown stays
            # finite and comparable
            kv_frac = model.kv_heads * (rep or 1) / model.num_heads
        kv_bytes = 2 * act_elems * model.dtype_bytes * kv_frac
        out["seq"] = model.num_layers * (seq - 1) * kv_bytes
    eff = min(
        efficiency if efficiency is not None else calibrated_efficiency(),
        MAX_EFFICIENCY,
    )
    _, moe_bytes = _moe_dispatch_terms(
        model, device, eff, rows * (model.seq_len / seq), data * fsdp
    )
    out["moe_dispatch"] = moe_bytes
    return out


def estimate(
    plan: MeshPlan,
    model: ModelSpec,
    device: DeviceSpec = DeviceSpec(),
    remat_policy: str = "",
    efficiency: Optional[float] = None,
    pipe_microbatches: int = 0,
    pipe_virtual: int = 1,
    stage_depths=None,
    stage_remat: Optional[bool] = None,
) -> PlanScore:
    """Analytic step-time + memory estimate for one mesh factorization.

    Terms:
      compute  : *executed* FLOPs (model FLOPs x remat recompute) over
                 chips x peak x a compute efficiency **calibrated to the
                 measured BENCH anchors** (``calibrated_efficiency``, ~0.67
                 on v5e). Efficiency is clamped to MAX_EFFICIENCY, so the
                 predicted step time is always >= executed FLOPs /
                 (0.9 * peak) — no prediction can be unphysical (MFU >= 1).
                 Pipeline adds the GPipe bubble factor.
      tp comm  : 2 allreduces of activations per layer over the tensor
                 axis (Megatron fwd+bwd), ICI bandwidth.
      fsdp comm: params all-gathered + grads reduce-scattered per step
                 over the fsdp axis.
      dp comm  : gradient allreduce over the data axis.
      seq comm : ring-attention KV rotation — only the (possibly
                 repeated, ``ring_kv_repeat``) kv heads travel.
      moe disp : MoE dispatch overhead per ``model.moe_dispatch`` —
                 quadratic one-hot einsums for the capacity paths under
                 EP, linear all-to-all bytes for "grouped_ep"
                 (``_moe_dispatch_terms``; ep degree = data x fsdp, the
                 expert submesh of the canonical rule sets). With
                 ``moe_dispatch_chunks`` > 1 (and with
                 ``fsdp_prefetch`` for the fsdp gathers) only the
                 EXPOSED remainder enters the step time
                 (``overlap_exposed_comm``); bytes stay invariant.
      memory   : params+optimizer sharded over (fsdp x tensor x pipe),
                 activations for one microbatch per layer (remat floor).

    ``stage_remat``: whether the model ACTUALLY applies stage-boundary
    remat when pipelined (``apply_pipelined`` derives it from the MODEL
    config's remat_policy, not the strategy's) — pass it from aot/
    callers that know; None falls back to inferring from
    ``remat_policy``.
    """
    pipe = max(getattr(plan, "pipe", 1), 1)
    data = max(getattr(plan, "data", 1), 1)
    fsdp = max(getattr(plan, "fsdp", 1), 1)
    seq = max(getattr(plan, "seq", 1), 1)
    tensor = max(getattr(plan, "tensor", 1), 1)
    n_chips = pipe * data * fsdp * seq * tensor

    # ---- compute (executed flops at calibrated efficiency)
    flops = _flops_per_step(model)
    from dlrover_tpu.ops.remat import remat_enabled

    recompute = REMAT_RECOMPUTE.get(remat_policy or "", 1.0)
    stage_remat_on = (stage_remat if stage_remat is not None
                      else remat_enabled(remat_policy))
    if pipe > 1 and stage_remat_on:
        # pipelined stages run under STAGE-BOUNDARY remat (the tick
        # scan stores only one state per tick; dispatch_pipeline's
        # remat_stage): the backward replays each stage's forward, so
        # executed FLOPs are at least the save-nothing factor (8/6 =
        # fwd + fwd-replay + bwd over fwd + bwd) regardless of how
        # much the inner per-layer policy saves during the replay.
        # The models key remat_stage off the MODEL config's policy, so
        # callers that know it pass stage_remat explicitly — the
        # strategy-level string may be empty while the model remats
        # (examples/train_llama.py), or vice versa.
        recompute = max(recompute, REMAT_RECOMPUTE["full"])
    eff = min(
        efficiency if efficiency is not None else calibrated_efficiency(),
        MAX_EFFICIENCY,
    )
    exec_flops = flops * recompute
    compute_s = exec_flops / (n_chips * device.flops_per_s * eff)
    if pipe > 1:
        # circular interleaved bubble (P-1)/(V*M+P-1); V=1 reduces to
        # the GPipe factor (M+P-1)/M this branch always modeled
        microbatches = pipe_microbatches or max(2 * pipe, 4)
        v = max(pipe_virtual, 1)
        compute_s *= 1.0 + (pipe - 1) / (v * microbatches)
        if stage_depths:
            # uneven split: every tick runs max(depths) padded layer
            # slots per chunk — the slots beyond L/(V*P) are idle-time
            # overhead on the light stages (pipeline.stack_stages_uneven)
            d = tuple(stage_depths)
            compute_s *= (v * pipe * max(d)) / max(1, sum(d))

    # ---- per-chip batch rows (data-ish axes shard the batch)
    rows = model.global_batch / max(data * fsdp, 1)
    act_elems = rows * (model.seq_len / seq) * model.hidden_size

    # ---- collective traffic: all byte quantities come from
    # predicted_collective_bytes — the ONE set of formulas the graph
    # lint's HLO audit also reads, so the seconds priced here and the
    # bytes audited there cannot drift apart.
    #   tp   : 2 allreduces of activations per layer fwd + 2 bwd (ICI)
    #   fsdp : param all-gather + grad reduce-scatter per step (ICI)
    #   dp   : plain gradient allreduce (ICI)
    #   seq  : ring-attention KV rotation, GQA- and repeat-aware (ICI)
    #   pipe : stage-boundary activation handoff, per-link; pipe is the
    #          outermost axis so on multi-slice topologies it rides DCN
    #          (V>1: the circular schedule wraps each microbatch around
    #          the ring V times)
    comm_bytes = predicted_collective_bytes(
        plan, model, device, efficiency=eff, pipe_virtual=pipe_virtual
    )
    tp_comm_s = comm_bytes["tp"] / device.ici_bw
    fsdp_comm_s = comm_bytes["fsdp"] / device.ici_bw
    dp_comm_s = comm_bytes["dp"] / device.ici_bw
    seq_comm_s = comm_bytes["seq"] / device.ici_bw
    pipe_comm_s = comm_bytes["pipe"] / device.dcn_bw

    # feasibility: the runtime head-shard legalizer raises when no legal
    # KV repeat exists for this head/tensor combination; any mesh relying
    # on it must never win the ranking
    heads_shardable = True
    if model.kv_heads and model.num_heads and ring_kv_repeat(
            model.kv_heads, model.num_heads, tensor) is None:
        heads_shardable = False

    # ---- MoE dispatch overhead (quadratic capacity einsums vs linear
    # all-to-all bytes): ep degree = data x fsdp, the expert submesh of
    # the canonical rule sets (mesh.py: "expert" aliases data x fsdp)
    tokens_per_chip = rows * (model.seq_len / seq)
    moe_disp_comp_s, _moe_bytes = _moe_dispatch_terms(
        model, device, eff, tokens_per_chip, data * fsdp
    )
    moe_disp_comm_s = comm_bytes["moe_dispatch"] / device.ici_bw
    compute_s += moe_disp_comp_s

    # ---- overlap-aware exposure: on the overlapped paths the planner
    # must not sum comm and compute serially. The BYTES stay invariant
    # (predicted_collective_bytes — the G106 audit side); what the
    # chunk schedule changes is how many of their seconds are EXPOSED.
    moe_disp_comm_serial_s = moe_disp_comm_s
    # the bf16 TWIN: what the same exchange would cost at the compute
    # dtype's wire — held beside the (possibly quantized) actual
    # pricing so `tpurun plan` shows what the precision knob buys, and
    # so the monotonicity pin (quantized <= bf16, both directions) has
    # an in-breakdown anchor. At precision "bf16" the twins are equal.
    moe_disp_comm_bf16_serial_s = moe_disp_comm_serial_s
    if (model.num_experts > 0 and model.moe_dispatch == "grouped_ep"
            and model.moe_precision != "bf16"):
        import dataclasses as _dc

        _, bf16_bytes = _moe_dispatch_terms(
            _dc.replace(model, moe_precision="bf16"), device, eff,
            tokens_per_chip, data * fsdp,
        )
        moe_disp_comm_bf16_serial_s = bf16_bytes / device.ici_bw
    moe_disp_comm_bf16_s = moe_disp_comm_bf16_serial_s
    chunks = max(1, int(getattr(model, "moe_dispatch_chunks", 1)))
    if (model.num_experts > 0 and model.moe_dispatch == "grouped_ep"
            and moe_disp_comm_s > 0):
        # what the row exchange hides under: the expert FFN's own
        # grouped GEMMs (up+down, fwd+bwd) on this chip's rows —
        # per-chunk exchange c+1 runs beneath chunk c's GEMMs
        f_dim = model.ffn_mult * model.hidden_size
        gemm_flops = (
            12.0 * tokens_per_chip * max(1, model.moe_top_k)
            * model.hidden_size * f_dim * model.num_layers
        )
        moe_gemm_s = gemm_flops / (device.flops_per_s * eff)
        moe_disp_comm_s = overlap_exposed_comm(
            moe_disp_comm_serial_s, moe_gemm_s, chunks)
        moe_disp_comm_bf16_s = overlap_exposed_comm(
            moe_disp_comm_bf16_serial_s, moe_gemm_s, chunks)

    # dense-wire split twins: gather legs (dtype-aware — what the
    # fsdp_precision knob compresses) vs the grad reduce-scatter (the
    # param dtype GSPMD actually ships); the bf16 twins hold the
    # unquantized pricing beside them so `tpurun plan` shows what the
    # precision knob buys and the monotonicity pin (quantized <= bf16,
    # both directions) has an in-breakdown anchor
    gather_b, scatter_b = model.fsdp_byte_split(fsdp, tensor, pipe)
    fsdp_gather_serial_s = gather_b / device.ici_bw
    fsdp_scatter_s = scatter_b / device.ici_bw
    fsdp_gather_s = fsdp_gather_serial_s
    fsdp_comm_serial_s = fsdp_gather_serial_s + fsdp_scatter_s
    bf16_gather_serial_s = fsdp_gather_serial_s
    if fsdp > 1 and model.fsdp_precision != "bf16":
        import dataclasses as _dc

        bf16_gather_b, _ = _dc.replace(
            model, fsdp_precision="bf16"
        ).fsdp_byte_split(fsdp, tensor, pipe)
        bf16_gather_serial_s = bf16_gather_b / device.ici_bw
    fsdp_comm_bf16_serial_s = bf16_gather_serial_s + fsdp_scatter_s
    bf16_gather_s = bf16_gather_serial_s
    if model.fsdp_prefetch and fsdp > 1 and fsdp_comm_s > 0:
        # layer prefetch hides the GATHER legs (forward all-gather +
        # the backward re-gather) under the neighboring layers'
        # compute — a chunk schedule with one chunk per layer; the
        # grad reduce-scatter has nothing later to hide under
        fsdp_gather_s = overlap_exposed_comm(
            fsdp_gather_serial_s, compute_s, max(1, model.num_layers))
        bf16_gather_s = overlap_exposed_comm(
            bf16_gather_serial_s, compute_s, max(1, model.num_layers))
    fsdp_comm_s = fsdp_gather_s + fsdp_scatter_s
    fsdp_comm_bf16_s = bf16_gather_s + fsdp_scatter_s

    # comm + dispatch fold into the step time through the shared
    # combiner (overlap max + dispatch floor; see combine_step_time)
    comm_s = (tp_comm_s + fsdp_comm_s + dp_comm_s + seq_comm_s
              + pipe_comm_s + moe_disp_comm_s)
    dispatch_s = HOST_DISPATCH_OVERHEAD_S
    step_s = combine_step_time(compute_s, comm_s, dispatch_s)

    # ---- memory (modeled on the production path: flash attention, so
    # no S^2 tile; dots_saveable-style per-layer saves). Terms validated
    # against XLA memory_analysis of 7B AOT compiles: 28.87 GB/chip at
    # data=2 x fsdp=4 x tensor=2 (reproduced by tests/test_aot.py's slow
    # cross-check) and 27.39 GB at data=8 x tensor=2 (AOT_7B.json).
    param_shard = model.param_count * (
        model.param_bytes + model.optim_bytes_per_param
    ) / (fsdp * tensor * pipe)
    # gradient AND optimizer-update trees materialize in f32 during the
    # step (donation reuses the state buffers, not these); both are
    # sharded over the model axes only, replicated across data
    grad_temp = 2 * model.param_count * 4 / (fsdp * tensor * pipe)
    # fsdp all-gather working set: at least 2 layers' worth of gathered
    # bf16 params live at once (current + prefetch). The rules keep the
    # stacked layer axis off fsdp, so no stack is ever gathered: the
    # compiled four-chip step at Mistral-7B widths gathers one layer's
    # kernel at a time (tests/test_tpu_compile.py); the 0.8 fit
    # threshold below is headroom for XLA's other temporaries
    gather_buf = 0.0
    if fsdp > 1:
        per_layer = model.param_count * model.param_bytes / max(
            model.num_layers, 1
        ) / (tensor * pipe)
        gather_buf = 2 * per_layer
    # activations: the remat floor persists ~2 residual-stream saves per
    # layer; recomputation additionally holds ONE layer's full working
    # set (attention projections + MLP gate/up, tensor-sharded) at a
    # time during the backward sweep
    # residual stream (unsharded) + attention projections and MLP
    # gate/up, both tensor-sharded
    layer_working = act_elems * model.dtype_bytes * (
        1.0 + (2.0 + 2.0 * model.ffn_mult) / tensor
    )
    act_bytes = (
        model.num_layers / pipe
    ) * act_elems * model.dtype_bytes * 2 + layer_working
    # vocab logits in f32, forward value + backward cotangent
    logits_bytes = (
        rows * (model.seq_len / seq) * model.vocab_size / tensor * 4 * 2
    )
    memory = (
        param_shard + grad_temp + gather_buf + act_bytes + logits_bytes
    )
    # 0.8: headroom for allocator fragmentation, collective buffers, and
    # the hoisted-gather case the model undercounts (measured 28.87 vs
    # modeled ~22.7 GB on the 7B AOT point => ~1.3x, inside the margin)
    fits = memory < device.hbm_bytes * 0.8
    if not heads_shardable:
        # the attention program cannot be built for this head/tensor
        # combination — never feasible, and never the least-bad fallback
        fits = False
        step_s = float("inf")

    # predicted MFU convention: MODEL flops (6N+attn), not recompute
    # flops; bounded < 1 by construction (step_s >= exec/(n*peak*0.9))
    predicted_mfu = (
        flops / (n_chips * device.flops_per_s * step_s)
        if step_s != float("inf") else 0.0
    )

    return PlanScore(
        plan=plan,
        step_time_s=step_s,
        memory_bytes=memory,
        fits=fits,
        predicted_mfu=predicted_mfu,
        breakdown={
            "compute_s": compute_s,
            "dispatch_s": dispatch_s,
            "tp_comm_s": tp_comm_s,
            # the EXPOSED seconds (post-overlap) — what enters the
            # step time; the *_serial_s twins keep the pre-overlap
            # figure visible so `tpurun plan` can show what the chunk
            # schedule bought
            "fsdp_comm_s": fsdp_comm_s,
            "fsdp_comm_serial_s": fsdp_comm_serial_s,
            # the dense-wire split: gather legs (dtype-aware, the
            # fsdp_precision knob's lever, overlappable by
            # fsdp_prefetch) vs the grad reduce-scatter (param-dtype,
            # never hidden) — plus the bf16 twins (equal to the pair
            # above at precision "bf16"), the quantized-vs-bf16 delta
            # `tpurun plan` surfaces
            "fsdp_gather_s": fsdp_gather_s,
            "fsdp_gather_serial_s": fsdp_gather_serial_s,
            "fsdp_scatter_s": fsdp_scatter_s,
            "fsdp_comm_bf16_s": fsdp_comm_bf16_s,
            "fsdp_comm_bf16_serial_s": fsdp_comm_bf16_serial_s,
            "dp_comm_s": dp_comm_s,
            "seq_comm_s": seq_comm_s,
            "pipe_comm_s": pipe_comm_s,
            "moe_disp_comp_s": moe_disp_comp_s,
            "moe_disp_comm_s": moe_disp_comm_s,
            "moe_disp_comm_serial_s": moe_disp_comm_serial_s,
            # the bf16 twins (what the wire would cost unquantized;
            # equal to the pair above at precision "bf16") — the
            # quantized-vs-bf16 delta `tpurun plan` surfaces
            "moe_disp_comm_bf16_s": moe_disp_comm_bf16_s,
            "moe_disp_comm_bf16_serial_s": moe_disp_comm_bf16_serial_s,
            "moe_dispatch_chunks": float(chunks),
            # predicted analog of the attribution plane's measured
            # exposed-comm bound (1 - compute/step): what `tpurun
            # plan`/`attribution` print beside the measured gauge
            "exposed_comm_frac": (
                min(max(1.0 - compute_s / step_s, 0.0), 1.0)
                if step_s not in (0.0, float("inf")) else 0.0
            ),
            "param_shard_bytes": param_shard,
            "grad_temp_bytes": grad_temp,
            "gather_buf_bytes": gather_buf,
            "act_bytes": act_bytes,
            "exec_flops": exec_flops,
            "efficiency": eff,
        },
    )


def plan_mesh(
    model: ModelSpec,
    n_devices: int,
    device: DeviceSpec = DeviceSpec(),
    candidates: Optional[List[MeshPlan]] = None,
    top_k: int = 1,
    remat_policy: str = "",
) -> List[PlanScore]:
    """Score every factorization; return the ``top_k`` feasible plans,
    fastest first (the MIP planner's argmin under constraints)."""
    plans = candidates if candidates is not None else candidate_plans(
        n_devices
    )
    scored = [estimate(p, model, device, remat_policy=remat_policy)
              for p in plans]
    feasible = [s for s in scored if s.fits]
    pool = feasible if feasible else scored  # degrade gracefully
    pool.sort(key=lambda s: s.step_time_s)
    if not feasible:
        logger.warning(
            "no mesh plan fits in HBM for %d devices; returning least-bad",
            n_devices,
        )
    return pool[:top_k]


def plan_stages(
    layer_costs: List[float], num_stages: int
) -> List[Tuple[int, int]]:
    """Split layers into contiguous stages minimizing the max stage cost
    (reference base_stage_planner.py:125). Returns [start, end) spans.

    Dynamic programming over prefix sums — optimal, O(L^2 * P)."""
    layers = len(layer_costs)
    if num_stages <= 0 or layers < num_stages:
        raise ValueError(
            f"cannot split {layers} layers into {num_stages} stages"
        )
    prefix = [0.0]
    for cost in layer_costs:
        prefix.append(prefix[-1] + cost)

    def span_cost(i, j):
        return prefix[j] - prefix[i]

    inf = float("inf")
    # best[p][j]: minimal max-stage-cost splitting first j layers into p
    best = [[inf] * (layers + 1) for _ in range(num_stages + 1)]
    cut = [[0] * (layers + 1) for _ in range(num_stages + 1)]
    best[0][0] = 0.0
    for p in range(1, num_stages + 1):
        for j in range(p, layers + 1):
            for i in range(p - 1, j):
                c = max(best[p - 1][i], span_cost(i, j))
                if c < best[p][j]:
                    best[p][j] = c
                    cut[p][j] = i
    spans = []
    j = layers
    for p in range(num_stages, 0, -1):
        i = cut[p][j]
        spans.append((i, j))
        j = i
    return list(reversed(spans))


def plan_stage_depths(
    layer_costs: List[float], num_stages: int, num_virtual: int = 1
) -> Tuple[int, ...]:
    """Per-stage-chunk layer counts for ``Strategy.stage_depths``.

    Runs the ``plan_stages`` DP over V*P contiguous chunks (visit
    order), minimizing the max chunk cost — the quantity a lockstep
    tick pays. With uniform layer costs this is the balanced
    ceil/floor split of L % (V*P) != 0; with heterogeneous costs
    (e.g. a future mixed dense/MoE stack) it shifts layer counts off
    the expensive chunks. Feed the result to
    ``Strategy(stage_depths=...)`` / ``apply_pipelined``.
    """
    spans = plan_stages(layer_costs, num_stages * num_virtual)
    return tuple(j - i for i, j in spans)


# -- the serving decode term --------------------------------------------------
#
# Decode is the MEMORY-BOUND regime: each step reads every live KV page
# plus (its share of) the weights once, per generated token — so the
# bytes term is KV reads + weight reads over HBM bandwidth, and the
# FLOPs term almost never binds. The slot width multiplies tokens/step
# for nearly-flat step time (the weight read amortizes across slots;
# the KV read scales with slots), which is exactly why continuous
# batching wins and why ``serve_slots`` is an optimizer knob — until
# the pool no longer fits, which is the HBM feasibility gate's job.


def kv_bytes_per_elem(kv_precision: str, channels: int = 0) -> float:
    """Stored bytes per KV element: int8 = values + the f32 per-block
    scale side-band (the ``ops.quantize`` block geometry, resolved
    against the channel/head dim when known); ONE formula for pricing,
    the feasibility gate and ``KVCacheSpec.bytes_per_slot`` — they
    cannot drift."""
    if kv_precision == "int8":
        from dlrover_tpu.ops.quantize import (
            QUANT_BLOCK,
            resolve_quant_block,
        )

        block = (resolve_quant_block(channels) if channels
                 else QUANT_BLOCK)
        return 1.0 + 4.0 / block
    if kv_precision == "bf16":
        return 2.0
    return 4.0


def serve_cache_bytes(m: ModelSpec, serve_slots: int, max_seq: int,
                      kv_precision: str = "f32") -> float:
    """Whole-pool KV residency (K and V, every slot at full depth —
    preallocated, so this is what must FIT, not an average)."""
    kv_heads = m.kv_heads or m.num_heads or 1
    heads = max(1, m.num_heads or 1)
    head_dim = m.hidden_size // heads
    elems = (m.num_layers * serve_slots * max_seq
             * max(1, kv_heads) * head_dim)
    return 2.0 * elems * kv_bytes_per_elem(kv_precision, head_dim)


def serve_prefix_pool_bytes(m: ModelSpec, pool_pages: int,
                            page_size: int,
                            kv_precision: str = "f32") -> float:
    """Device residency of the shared prefix pool (K and V for every
    layer, ``pool_pages`` pages of ``page_size`` tokens) — the SAME
    byte formula as ``serve_cache_bytes``/``KVCacheSpec``. The pool
    REPLICATES across the data axes (any slot may admit any page), so
    the per-device HBM charge is this number UNDIVIDED."""
    kv_heads = m.kv_heads or m.num_heads or 1
    heads = max(1, m.num_heads or 1)
    head_dim = m.hidden_size // heads
    elems = (m.num_layers * max(0, int(pool_pages))
             * max(1, int(page_size)) * max(1, kv_heads) * head_dim)
    return 2.0 * elems * kv_bytes_per_elem(kv_precision, head_dim)


def decode_kv_read_bytes(m: ModelSpec, serve_slots: int, seq_fill: int,
                         kv_precision: str = "f32") -> float:
    """Bytes of KV pages one decode step reads: every live token's K
    and V, every layer, every slot (``seq_fill`` = the depth actually
    filled — callers price at max_seq/2 as the steady-state average)."""
    kv_heads = m.kv_heads or m.num_heads or 1
    heads = max(1, m.num_heads or 1)
    head_dim = m.hidden_size // heads
    elems = (m.num_layers * serve_slots * seq_fill
             * max(1, kv_heads) * head_dim)
    return 2.0 * elems * kv_bytes_per_elem(kv_precision, head_dim)


def estimate_decode(m: ModelSpec, num_devices: int, serve_slots: int,
                    prefill_chunk: int, max_seq: int,
                    kv_precision: str = "f32",
                    prefix_pool_pages: int = 0,
                    page_size: int = 16,
                    prefix_hit_rate: float = 0.0,
                    spec_draft_len: int = 0,
                    spec_accept_rate: float = -1.0,
                    device: Optional[DeviceSpec] = None) -> Dict:
    """Price one serving config: predicted decode-step seconds and
    tokens/second, with the breakdown the decision trail shows.

    Terms (per device, ``num_devices`` shards the batch and weights):
      kv_read_s      KV pages at half fill over HBM bandwidth
      weight_read_s  2 bytes/param/step over HBM bandwidth (decode
                     re-reads the weights once per step; batch-
                     amortized across slots by construction)
      flops_s        2*params*slots/peak — the check that the regime
                     really is memory-bound
      dispatch_s     the PR 3 host floor, one dispatch per step
      prefill amortization: a bigger chunk admits a prompt in fewer
                     interleaved steps but each chunk stalls one
                     decode step longer — priced as chunk_steps
                     spread over the chunk's tokens. A nonzero prefix
                     pool discounts it by the expected hit rate
                     (matched tokens are page COPIES, priced as one
                     dispatch per page instead of a chunk prefill).
      speculative decode (``spec_draft_len`` K > 0): a verify step
                     emits 1 + rate*K expected tokens but computes
                     K+1 positions — the FLOPs term scales by K+1
                     while the memory terms stay per-step, so the
                     trade is real, not assumed. Priced ONLY from an
                     observed ``spec_accept_rate`` in [0, 1]: with no
                     evidence (rate < 0) the estimate is EXACTLY the
                     K=0 estimate — 1.0x, no speculative speedup
                     assumed (the prefix-discount discipline).

    Returns {"step_s", "tokens_per_s", "cache_bytes",
    "cache_bytes_per_device", "breakdown"}. ``tokens_per_s`` is
    monotone-increasing in ``serve_slots`` until the HBM gate refuses
    the pool — which is the caller's check (``serve_cache_bytes`` plus
    the UNDIVIDED ``serve_prefix_pool_bytes`` against the device
    budget), not this function's.
    """
    dev = device or DeviceSpec()
    n = max(1, int(num_devices))
    slots = max(1, int(serve_slots))
    chunk = max(1, int(prefill_chunk))
    pool_pages = max(0, int(prefix_pool_pages))
    hit_rate = min(1.0, max(0.0, float(prefix_hit_rate))) \
        if pool_pages else 0.0
    cache_bytes = serve_cache_bytes(m, slots, max_seq, kv_precision)
    pool_bytes = serve_prefix_pool_bytes(
        m, pool_pages, page_size, kv_precision)
    kv_read = decode_kv_read_bytes(
        m, slots, max(1, max_seq // 2), kv_precision) / n
    kv_read_s = kv_read / dev.hbm_bw
    weight_read_s = (m.param_count * 2.0 / n) / dev.hbm_bw
    flops_s = (2.0 * m.param_count * slots / n) / (
        dev.flops_per_s * MAX_EFFICIENCY)
    dispatch_s = HOST_DISPATCH_OVERHEAD_S
    # a prompt of L tokens takes ceil(L/chunk) interleaved prefill
    # calls; each call costs ~one dispatch + the chunk's weight read.
    # Amortized per generated token (assuming ~one admission per slot
    # drain), this prefers bigger chunks until the chunk itself
    # dominates a decode step — the trade the optimizer enumerates.
    avg_prompt = max(1.0, max_seq / 4.0)
    prefill_calls = math.ceil(avg_prompt / chunk)
    prefill_s_per_req = prefill_calls * (
        dispatch_s + weight_read_s + chunk * kv_read_s / max(1, max_seq // 2) / slots)
    # prefix reuse: an expected-hit admission replaces its matched
    # prefill with per-page admit copies (one dispatch each; the page
    # bytes move at HBM bandwidth, negligible beside the dispatch).
    # The pool can only ever hold hit tokens it has pages for, so the
    # discount is additionally capped by the pool's token capacity
    # against the average prompt.
    if pool_pages:
        pool_tokens = pool_pages * max(1, int(page_size))
        coverage = min(1.0, pool_tokens / avg_prompt)
        discount = hit_rate * coverage
        copy_pages = avg_prompt / max(1, int(page_size))
        copy_s_per_req = discount * copy_pages * dispatch_s
        prefill_s_per_req = ((1.0 - discount) * prefill_s_per_req
                             + copy_s_per_req)
    avg_new = max(1.0, max_seq / 4.0)
    prefill_amort_s = prefill_s_per_req / avg_new / slots
    # speculative decode: evidence-gated. k stays 0 unless BOTH the
    # knob is on and an acceptance rate was observed, so the no-spec /
    # no-evidence estimate below is byte-identical to today's — the
    # "zero evidence prices at exactly 1.0x" contract the optimizer
    # and its tests pin.
    k = max(0, int(spec_draft_len))
    rate = float(spec_accept_rate)
    if k > 0 and 0.0 <= rate <= 1.0:
        # expected emitted tokens per verify step (greedy acceptance
        # of an i.i.d.-approximated draft stream: 1 + rate*K is the
        # linear lower bound of the geometric sum — conservative)
        expected_tokens = 1.0 + rate * k
        # the verify step runs K+1 positions: FLOPs scale, the KV and
        # weight reads stay one pass per step (slot-major pool reads
        # the same pages; weights are read once per step regardless)
        spec_flops_s = flops_s * (k + 1)
        step_s = max(kv_read_s + weight_read_s + prefill_amort_s,
                     spec_flops_s, dispatch_s)
        tokens_per_s = slots * expected_tokens / step_s
    else:
        expected_tokens = 1.0
        step_s = max(kv_read_s + weight_read_s + prefill_amort_s,
                     flops_s, dispatch_s)
        tokens_per_s = slots / step_s
    return {
        "step_s": step_s,
        "tokens_per_s": tokens_per_s,
        "cache_bytes": cache_bytes,
        "cache_bytes_per_device": cache_bytes / n + pool_bytes,
        "breakdown": {
            "kv_read_s": kv_read_s,
            "weight_read_s": weight_read_s,
            "flops_s": flops_s,
            "dispatch_s": dispatch_s,
            "prefill_amort_s": prefill_amort_s,
            "prefix_pool_bytes": pool_bytes,
            "prefix_hit_rate": hit_rate,
            "spec_draft_len": k,
            "spec_accept_rate": (rate if 0.0 <= rate <= 1.0
                                 else -1.0),
            "spec_expected_tokens_per_step": expected_tokens,
            # channel-resolved, exactly as the terms above priced it —
            # the decision trail must show the number that was USED
            "kv_bytes_per_elem": kv_bytes_per_elem(
                kv_precision,
                m.hidden_size // max(1, m.num_heads or 1)),
        },
    }


def model_spec_from_llama(config, global_batch: int) -> ModelSpec:
    """Convenience: derive a ModelSpec from a LlamaConfig."""
    import numpy as np

    from dlrover_tpu.common.config import get_context
    from dlrover_tpu.models import llama

    return ModelSpec(
        param_count=llama.param_count(config),
        num_layers=config.num_layers,
        hidden_size=config.hidden_size,
        seq_len=config.max_seq_len,
        global_batch=global_batch,
        vocab_size=config.vocab_size,
        param_bytes=np.dtype(config.param_dtype).itemsize,
        ffn_mult=config.intermediate_size / config.hidden_size,
        num_heads=config.num_heads,
        kv_heads=config.num_kv_heads,
        num_experts=config.num_experts,
        moe_top_k=config.moe_top_k,
        moe_capacity_factor=config.moe_capacity_factor,
        moe_dispatch=config.moe_dispatch,
        # 0 = the Context knob, exactly how ops.moe resolves it at
        # trace time — the spec must price the program that will build
        moe_dispatch_chunks=(
            config.moe_dispatch_chunks
            or int(getattr(get_context(), "dispatch_chunks", 1))
        ),
        fsdp_prefetch=(
            bool(config.fsdp_prefetch)
            if config.fsdp_prefetch is not None
            else bool(getattr(get_context(), "fsdp_prefetch", False))
        ),
        # "" = the Context knob, exactly how ops.moe resolves it at
        # trace time — the spec must price the wire the program ships
        moe_precision=(
            config.moe_precision
            or str(getattr(get_context(), "moe_precision", "bf16")
                   or "bf16")
        ),
        # "" = the Context knob, exactly how models/llama resolves the
        # dense wire at trace time (resolve_fsdp_precision)
        fsdp_precision=(
            getattr(config, "fsdp_precision", "")
            or str(getattr(get_context(), "fsdp_precision", "bf16")
                   or "bf16")
        ),
    )
