"""AOT compile-and-fit proof on virtual TPU topologies.

Role parity: atorch's dryrun/analyse stage (``atorch/atorch/auto/
accelerate.py:563-614``, ``dry_runner.py:12``) profiles a candidate
strategy on live GPUs before committing to it. The TPU-native superpower
is doing this with *no hardware at all*: XLA's TPU compiler is
hermetic, so we AOT-compile the full jitted train step against a
deviceless ``TopologyDescription`` (e.g. a v5p 2x2x4 slice = v5p-32)
and read compiled memory/cost analysis — proving a model FITS and
measuring its per-step FLOPs before a single chip is allocated.

This is the BASELINE "Llama-2-7B on v5p-32" viability proof: run

    python -m dlrover_tpu.parallel.aot --model llama2_7b \
        --topology v5:2x2x4 --gen v5p --batch 16

and it prints one JSON line with the chosen mesh, per-device HBM usage
vs capacity, and the analytic MFU the planner predicts at that step's
measured FLOP count.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, Optional

from dlrover_tpu.common.log import get_logger

logger = get_logger("parallel.aot")

# TensorCore-count naming (v5p-32 = 16 chips) -> topology strings
KNOWN_TOPOLOGIES = {
    "v5p-16": "v5:2x2x2",
    "v5p-32": "v5:2x2x4",
    "v5p-64": "v5:2x4x4",
    "v5p-128": "v5:4x4x4",
}


@dataclass
class AotReport:
    model: str
    topology: str
    n_devices: int
    mesh: Dict[str, int]
    params: int
    global_batch: int
    seq_len: int
    fits: bool
    hbm_per_device_bytes: float
    hbm_capacity_bytes: float
    flops_per_step: float
    predicted_step_time_s: float
    predicted_mfu: float
    compile_time_s: float
    # graph-lint findings (dlrover_tpu.analysis) when the caller asked
    # for the lint pass; None = pass not run
    lint_findings: Optional[list] = None

    def to_json(self) -> str:
        d = dict(self.__dict__)
        if d.get("lint_findings") is None:
            d.pop("lint_findings", None)
        else:
            d["lint_findings"] = [
                {"rule": f.rule_id, "message": f.message}
                for f in d["lint_findings"]
            ]
        d["hbm_per_device_gb"] = round(d.pop("hbm_per_device_bytes") / 1e9, 2)
        d["hbm_capacity_gb"] = round(d.pop("hbm_capacity_bytes") / 1e9, 2)
        d["flops_per_step"] = float(f"{d['flops_per_step']:.4g}")
        d["predicted_step_time_s"] = round(d["predicted_step_time_s"], 4)
        d["predicted_mfu"] = round(d["predicted_mfu"], 4)
        d["compile_time_s"] = round(d["compile_time_s"], 1)
        return json.dumps(d)


_LIBTPU_LOCKFILE = "/tmp/libtpu_lockfile"


def _get_topology_desc_serialized(topologies, topology: str,
                                  wait_budget_s: float = 1800.0,
                                  poll_s: float = 15.0):
    """``get_topology_desc`` with libtpu single-host serialization.

    libtpu holds ``/tmp/libtpu_lockfile`` for the LIFETIME of the
    process that initialized it; a second initialization on the same
    host aborts ("Internal error when accessing libtpu multi-process
    lockfile"), and a SIGKILLed holder leaves the file behind so even
    the next solo run aborts. Distinguish the two with a non-blocking
    flock probe: acquirable means the holder is gone (stale file —
    unlink it while STILL holding the lock, and only if the path's
    inode is the one we locked, so a sibling's freshly created live
    lockfile is never deleted out from under it); unacquirable means a
    live sibling compile, so wait. The wait is a TIME budget, not an
    attempt count — queued compiles on one host each hold the lock for
    their full compile (minutes), and several can be ahead of us.
    """
    import time

    # monotonic: an NTP step or VM resume must not stretch or chop
    # the wait budget
    deadline = time.monotonic() + wait_budget_s
    while True:
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name=topology
            )
        except Exception as e:  # noqa: BLE001 — only the lockfile retries
            if "libtpu" not in str(e) or "lockfile" not in str(e):
                raise
            if time.monotonic() >= deadline:
                raise
            try:
                import fcntl
                import os as _os

                # NB: holding LOCK_EX here (even briefly, for the
                # inode-checked unlink below) can make a CONCURRENT
                # libtpu init abort instead of block — libtpu errors
                # rather than waits on a held lock. Acceptable: the
                # sibling lands back in this same retry loop and
                # re-inits within the budget; the alternative (probe
                # with LOCK_SH first) still needs the exclusive window
                # for the unlink, so it only narrows the race, not
                # closes it.
                with open(_LIBTPU_LOCKFILE) as fh:
                    try:
                        fcntl.flock(fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    except OSError:
                        # a live sibling holds it: wait within budget
                        logger.info(
                            "libtpu lockfile held by a live process; "
                            "polling (%.0fs of budget left)",
                            deadline - time.monotonic(),
                        )
                        time.sleep(
                            max(0.0, min(poll_s,
                                         deadline - time.monotonic()))
                        )
                        continue
                    try:
                        # we hold the flock on OUR opened inode; only
                        # unlink if the path still names that inode — a
                        # sibling may have recreated the file (its live
                        # lock is on a NEW inode our flock says nothing
                        # about)
                        if (_os.fstat(fh.fileno()).st_ino
                                == _os.stat(_LIBTPU_LOCKFILE).st_ino):
                            logger.warning(
                                "removing stale %s (no live holder; a "
                                "killed jax process left it)",
                                _LIBTPU_LOCKFILE,
                            )
                            _os.remove(_LIBTPU_LOCKFILE)
                    except OSError:
                        pass
                    finally:
                        fcntl.flock(fh, fcntl.LOCK_UN)
            except OSError:
                pass  # file vanished: retry immediately
            # brief pause so a pathologically recreating-and-failing
            # init cannot busy-spin the loop until the deadline
            time.sleep(max(0.0, min(0.2, poll_s)))
            continue


def aot_compile_train_step(
    config,
    topology: str = "v5:2x2x4",
    tpu_gen: str = "v5p",
    global_batch: int = 16,
    mesh_plan=None,
    rule_set: str = "llama",
    remat_policy: str = "",
    model_name: str = "llama",
    ring: bool = False,
    head_chunk: int = 0,
    packed_doc_len: int = 0,
    pipeline: Optional[dict] = None,
    graph_lint: bool = False,
) -> AotReport:
    """Compile the full accelerate() train step for ``config`` against a
    deviceless TPU topology; assert HBM fit via memory_analysis.

    ``mesh_plan``: explicit MeshPlan; default = the roofline planner's
    top choice for this model/topology (``planner.plan_mesh``).

    ``ring``: run ring attention over the plan's "seq" axis (requires an
    explicit ``mesh_plan`` with seq > 1) — proves the flash-fused
    long-context multi-chip path lowers and fits at scale, hermetically.

    ``pipeline``: {"num_stages", "num_microbatches", "num_virtual"?,
    "stage_depths"?} — run the decoder through ``apply_pipelined``
    (GPipe / circular interleaved, optionally uneven per-chunk layer
    counts) instead of the plain forward; pair with the "llama_pp"
    rule set and a mesh_plan with pipe > 1.

    ``graph_lint``: run the SPMD graph lint (``dlrover_tpu.analysis``)
    over the winning plan's lowered/compiled artifacts — host callbacks,
    dtype drift, dropped donation, replicated params, and the
    planner-vs-HLO collective byte audit; findings land on
    ``report.lint_findings``.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel import planner
    from dlrover_tpu.parallel.accelerate import accelerate
    from dlrover_tpu.parallel.strategy import Strategy

    topology = KNOWN_TOPOLOGIES.get(topology, topology)
    topo = _get_topology_desc_serialized(topologies, topology)
    devices = list(topo.devices)
    n = len(devices)
    device_spec = planner.TPU_SPECS[tpu_gen]

    model = planner.model_spec_from_llama(config, global_batch)
    effective_remat = remat_policy or getattr(config, "remat_policy", "")
    fallback_plans: list = []
    if mesh_plan is not None:
        # a CLI/user plan may carry an unresolved data=-1 axis; resolve
        # it against the topology NOW — estimate() and the report's
        # mesh dict must see the same chip count accelerate() compiles
        # for, or the artifact's predicted numbers are for a different
        # (smaller) machine
        mesh_plan = mesh_plan.resolve(n)
    if mesh_plan is None:
        scores = planner.plan_mesh(model, n, device_spec,
                                   remat_policy=effective_remat, top_k=3)
        if not scores:
            raise ValueError(f"no mesh plan for {n} devices")
        mesh_plan = scores[0].plan
        # planner proposes, XLA disposes: if the compiled memory analysis
        # contradicts the analytic fit, fall back to the next-ranked plan
        # (the dryrun-loop shape of the reference's search, executed
        # against the hermetic compiler instead of live chips)
        fallback_plans = [s.plan for s in scores[1:]]
        logger.info(
            "planner chose %s (predicted %.3fs/step)",
            mesh_plan, scores[0].step_time_s,
        )

    if ring:
        from dataclasses import replace as _replace

        seq_size = dict(mesh_plan.axis_sizes()).get("seq", 1)
        if seq_size <= 1:
            raise ValueError(
                "ring=True needs an explicit mesh_plan with seq > 1"
            )
        # the exact mesh accelerate() will build — same plan, same
        # device order — so the ring's shard_map axis resolves
        config = _replace(
            config, seq_axis="seq", mesh=mesh_plan.build(devices)
        )

    rng_np = np.random.RandomState(0)
    seq = config.max_seq_len
    ids = rng_np.randint(
        0, config.vocab_size, size=(global_batch, seq + 1)
    )
    batch = {
        "input_ids": jnp.asarray(ids[:, :-1]),
        "labels": jnp.asarray(ids[:, 1:]),
    }
    if packed_doc_len:
        # packed documents (segment ids + cross-document masking in the
        # kernel tiles), the production long-context batch shape
        doc = max(1, min(packed_doc_len, seq))
        seg = (np.arange(seq) // doc).astype(np.int32)
        seg = np.broadcast_to(seg, (global_batch, seq)).copy()
        same_next = np.concatenate(
            [seg[:, :-1] == seg[:, 1:],
             np.zeros((global_batch, 1), bool)], axis=1)
        batch["segment_ids"] = jnp.asarray(seg)
        batch["labels"] = jnp.asarray(
            np.where(same_next, ids[:, 1:], -100))
    if pipeline:
        from dlrover_tpu.models.losses import masked_lm_loss

        def loss_fn(params, batch, rng):
            logits, moe_aux = llama.apply_pipelined(
                params, batch["input_ids"], config,
                num_stages=pipeline["num_stages"],
                num_microbatches=pipeline["num_microbatches"],
                rng=rng,
                num_virtual=pipeline.get("num_virtual", 1),
                stage_depths=pipeline.get("stage_depths"),
            )
            loss = masked_lm_loss(logits, batch["labels"])
            if config.num_experts > 0:
                # apply_pipelined sums the (token-count-invariant)
                # load-balance aux over MICROBATCHES as well as layers;
                # divide by both so the regularizer weight matches the
                # unpipelined make_loss_fn path exactly
                loss = loss + config.moe_aux_weight * moe_aux / (
                    max(1, config.num_layers)
                    * pipeline["num_microbatches"]
                )
            return loss, {}
    else:
        loss_fn = llama.make_loss_fn(config, head_chunk=head_chunk)

    def compile_plan(plan):
        result = accelerate(
            llama.make_init_fn(config),
            loss_fn,
            optax.adafactor(1e-3),
            batch,
            strategy=Strategy(
                mesh=plan, rule_set=rule_set, remat_policy=remat_policy
            ),
            devices=devices,
        )
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        abstract_state = jax.eval_shape(
            result.init_fn, jax.random.PRNGKey(0)
        )
        abstract_batch = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch
        )
        t0 = time.time()
        lowered = result.train_step.lower(
            abstract_state, abstract_batch, key
        )
        compiled = lowered.compile()
        return compiled, time.time() - t0, lowered, result, abstract_state

    best = None  # (per_device, compiled, compile_time, plan, artifacts)
    last_exc: Optional[Exception] = None
    for plan in [mesh_plan] + fallback_plans:
        try:
            (compiled_i, compile_time_i, lowered_i, result_i,
             abstract_state_i) = compile_plan(plan)
        except Exception as e:  # noqa: BLE001 — plan infeasible for XLA
            last_exc = e
            logger.warning(
                "plan %s failed to compile (%s); trying next-ranked",
                plan, f"{type(e).__name__}: {e}"[:160],
            )
            continue
        # per-device residency: arguments (the sharded state + batch)
        # plus transient temps; donated (alias) bytes not double-counted
        # (the shared shim in utils/prof — one accounting everywhere)
        from dlrover_tpu.utils.prof import compiled_peak_bytes

        per_device_i = compiled_peak_bytes(compiled_i)
        if best is None or per_device_i < best[0]:
            # the lowering artifacts (full StableHLO + traced closures)
            # are only worth keeping alive past the loop when the lint
            # pass will read them
            best = (per_device_i, compiled_i, compile_time_i, plan,
                    (lowered_i, result_i, abstract_state_i)
                    if graph_lint else None)
        if per_device_i <= device_spec.hbm_bytes:
            break
        logger.warning(
            "plan %s compiled but needs %.1f GB > %.0f GB HBM; trying "
            "next-ranked", plan, per_device_i / 1e9,
            device_spec.hbm_bytes / 1e9,
        )
    if best is None:
        # nothing compiled at all — surface the last compiler error
        raise last_exc if last_exc is not None else RuntimeError(
            "no plan compiled"
        )
    per_device, compiled, compile_time, mesh_plan, artifacts = best
    fits = per_device <= device_spec.hbm_bytes

    # XLA cost_analysis does not multiply FLOPs by loop trip counts, so
    # a scan-over-layers model reads ~1/num_layers of the truth; report
    # the max of compiled and analytic executed counts. The *prediction*
    # comes from the calibrated planner roofline (anchored to measured
    # BENCH points, efficiency clamped < 1, so predicted_mfu is always
    # physical — the round-2 artifact claimed 1.31 from an uncalibrated
    # compute term).
    costs = compiled.cost_analysis()
    pipe_kwargs = {}
    if pipeline:
        from dlrover_tpu.ops.remat import remat_enabled

        pipe_kwargs = dict(
            pipe_microbatches=pipeline["num_microbatches"],
            pipe_virtual=pipeline.get("num_virtual", 1),
            stage_depths=pipeline.get("stage_depths"),
            # whether the compiled program ACTUALLY replays each
            # stage's forward: apply_pipelined keys remat_stage off the
            # MODEL config's policy, not the strategy-level string the
            # estimate would otherwise infer from (ADVICE r5 #4 — a
            # blank strategy policy with model-internal remat on used
            # to drop the replay factor from the prediction)
            stage_remat=remat_enabled(
                getattr(config, "remat_policy", "") or ""
            ),
        )
    score = planner.estimate(mesh_plan, model, device_spec,
                             remat_policy=effective_remat,
                             **pipe_kwargs)
    flops = max(float(costs.get("flops", 0.0)) * n,
                score.breakdown["exec_flops"])
    step_time = score.step_time_s
    # MFU convention: MODEL flops (6N+attn), not recompute flops
    predicted_mfu = score.predicted_mfu
    if not 0.0 < predicted_mfu < 1.0:
        raise AssertionError(
            f"cost model produced unphysical MFU {predicted_mfu:.3f} "
            f"(step {step_time:.4f}s, mesh {mesh_plan})"
        )

    report = AotReport(
        model=model_name,
        topology=topology,
        n_devices=n,
        mesh={
            k: v for k, v in mesh_plan.axis_sizes().items() if v > 1
        } if hasattr(mesh_plan, "axis_sizes") else str(mesh_plan),
        params=model.param_count,
        global_batch=global_batch,
        seq_len=seq,
        fits=bool(fits),
        hbm_per_device_bytes=float(per_device),
        hbm_capacity_bytes=float(device_spec.hbm_bytes),
        flops_per_step=flops,
        predicted_step_time_s=float(step_time),
        predicted_mfu=float(predicted_mfu),
        compile_time_s=compile_time,
    )
    if graph_lint:
        from dlrover_tpu.analysis import graph_lint as gl

        lowered, result, abstract_state = artifacts
        param_bytes = sum(
            a.size * a.dtype.itemsize
            for a in jax.tree.leaves(abstract_state.params)
        )
        from dlrover_tpu.common.config import get_context

        lint = gl.lint_artifacts(
            stablehlo=lowered.as_text(),
            optimized_hlo=compiled.as_text(),
            args_info=getattr(lowered, "args_info", None),
            state_sharding=result.state_sharding,
            abstract_state=abstract_state,
            mesh_plan=mesh_plan,
            model_spec=model,
            device_spec=device_spec,
            compute_dtype=jnp.dtype(config.compute_dtype).name,
            total_param_bytes=param_bytes,
            n_state_leaves=len(jax.tree.leaves(abstract_state)),
            pipe_virtual=(pipeline or {}).get("num_virtual", 1),
            # G107: the artifact's own measured residency against the
            # operator budget (default: the generation's HBM capacity)
            peak_hbm_bytes=float(per_device),
            hbm_budget_bytes=(
                float(getattr(get_context(),
                              "device_hbm_budget_bytes", 0.0))
                or float(device_spec.hbm_bytes)
            ),
            label=f"{model_name}@{topology}",
        )
        report.lint_findings = lint.findings
        # G109: the quantization-drift probe must EXECUTE the program,
        # which a deviceless topology cannot — it runs the same model
        # family on the HOST backend's devices instead (the numerics
        # of the quantized wire do not depend on which backend carries
        # it; the bitwise wire tests pin that)
        if (getattr(config, "num_experts", 0) > 0
                and getattr(config, "moe_dispatch", "") == "grouped_ep"):
            try:
                # resolve INSIDE the guard: a malformed precision
                # string (a typo'd env override) must also skip the
                # probe, not kill the fit-proof
                from dlrover_tpu.ops.moe import resolve_moe_precision
                from dlrover_tpu.ops.moe import MoEConfig as _MC

                resolved = resolve_moe_precision(_MC(
                    num_experts=config.num_experts,
                    precision=getattr(config, "moe_precision", ""),
                ))
                if resolved != "bf16":
                    drift_rep = gl.quantization_drift_audit(
                        precision=resolved)
                    report.lint_findings = (
                        list(report.lint_findings)
                        + list(drift_rep.findings))
            except Exception:  # noqa: BLE001 — a host backend without
                # enough devices (or an unresolvable precision knob)
                # skips the probe, it does not kill the fit-proof
                logger.warning(
                    "quantization drift probe skipped", exc_info=True)
        # dense-wire families: the fsdp gather wire (trace-time knob,
        # models/llama.resolve_fsdp_precision) and the error-feedback
        # gradient path (build-time knob, accelerate) each ratchet
        # their own G109 entry when resolved quantized
        try:
            from dlrover_tpu.models.llama import resolve_fsdp_precision

            if resolve_fsdp_precision(config) != "bf16":
                drift_rep = gl.quantization_drift_audit(family="fsdp")
                report.lint_findings = (list(report.lint_findings)
                                        + list(drift_rep.findings))
        except Exception:  # noqa: BLE001 — same contract as the moe
            # probe: skip, never kill the fit-proof
            logger.warning(
                "fsdp drift probe skipped", exc_info=True)
        try:
            from dlrover_tpu.parallel.accelerate import (
                resolve_grad_precision,
            )

            if resolve_grad_precision() != "bf16":
                drift_rep = gl.quantization_drift_audit(family="grad")
                report.lint_findings = (list(report.lint_findings)
                                        + list(drift_rep.findings))
        except Exception:  # noqa: BLE001
            logger.warning(
                "grad drift probe skipped", exc_info=True)
        # the concurrency pass rides the same flag: the artifact this
        # proof blesses is deployed by the very control plane DLR009-011
        # guard, and the whole-package pass costs ~1s next to the
        # compiles above. Findings are baseline-filtered like tpulint's.
        try:
            import os as _os

            import dlrover_tpu as _pkg
            from dlrover_tpu.analysis import concurrency as _conc
            from dlrover_tpu.analysis import findings as _fmod

            _pkg_dir = _os.path.dirname(_os.path.abspath(_pkg.__file__))
            _base = _fmod.Baseline.load(_os.path.join(
                _pkg_dir, "analysis", "baseline.json"))
            _new, _ = _base.filter(_conc.lint_paths_concurrency(
                [_pkg_dir], root=_os.path.dirname(_pkg_dir)))
            report.lint_findings = list(report.lint_findings) + _new
        except Exception:  # noqa: BLE001 — same contract as the
            # drift probes: skip, never kill the fit-proof
            logger.warning("concurrency lint skipped", exc_info=True)
        for f in report.lint_findings:
            logger.warning("graph lint: %s", f.render())
    logger.info("AOT report: %s", report.to_json())
    return report


def main(argv: Optional[list] = None) -> int:
    import argparse

    import jax

    from dlrover_tpu.models import llama

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="llama2_7b",
                   choices=["llama2_7b", "llama2_13b", "llama3_8b",
                            "llama3_70b", "llama_tiny"])
    p.add_argument("--topology", default="v5p-32",
                   help="v5p-N alias or raw topology (v5:2x2x4)")
    p.add_argument("--gen", default="v5p", choices=["v4", "v5e", "v5p",
                                                    "v6e"])
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=4096)
    p.add_argument("--remat", default="dots_saveable")
    p.add_argument("--flash", dest="flash", action="store_true",
                   default=None,
                   help="force the Pallas kernel path")
    p.add_argument("--no-flash", dest="flash", action="store_false",
                   help="lower the XLA reference attention instead of "
                        "the Pallas kernel")
    p.add_argument("--mesh", default="",
                   help="override the planner, e.g. data=2,fsdp=4,tensor=2")
    p.add_argument("--ring", action="store_true",
                   help="run ring attention over the mesh's seq axis "
                        "(long-context path; requires --mesh with seq>1)")
    p.add_argument("--head-chunk", type=int, default=0,
                   help="fused chunked lm-head loss chunk size (0=off; "
                        "required at long seq x large vocab, where full "
                        "[B,S,V] f32 logits alone exceed HBM)")
    p.add_argument("--experts", type=int, default=0,
                   help="switch-MoE with N experts (rule_set=moe: "
                        "expert parallelism over the data x fsdp "
                        "submesh)")
    p.add_argument("--packed-doc-len", type=int, default=0,
                   help="pack N-token documents per row (segmented "
                        "fused-mask kernel; composes with --ring)")
    p.add_argument("--pipe-stages", type=int, default=0,
                   help="run the decoder as a pipeline with N stages "
                        "(rule_set=llama_pp; requires --mesh with "
                        "pipe=N)")
    p.add_argument("--pipe-microbatches", type=int, default=0,
                   help="microbatches for the pipeline schedule "
                        "(default: 2*stages)")
    p.add_argument("--pipe-virtual", type=int, default=1,
                   help="virtual stages per physical stage (V>1 = the "
                        "circular interleaved schedule)")
    p.add_argument("--pipe-depths", default="",
                   help="comma-separated per-chunk layer counts in "
                        "visit order (uneven stage split; default "
                        "even)")
    p.add_argument("--lint", action="store_true",
                   help="run the SPMD graph lint (dlrover_tpu.analysis) "
                        "over the compiled artifact, plus the "
                        "concurrency pass (DLR009-011) over the control "
                        "plane; findings print and flip the exit code")
    args = p.parse_args(argv)

    jax.config.update("jax_platforms", "cpu")  # AOT needs no devices

    import jax.numpy as jnp

    factory = getattr(llama, args.model)
    overrides = dict(
        max_seq_len=args.seq,
        param_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16,
        remat_policy=args.remat,
        # tracing happens on a CPU host but the compile targets the TPU
        # topology: force the real Mosaic kernel, never the interpreter
        # emulation the backend-sniffing default would pick
        flash_interpret=False,
    )
    if args.experts:
        overrides["num_experts"] = args.experts
    if args.flash is not None:
        # only override the factory's use_flash when the user asked
        # (llama_tiny deliberately defaults to the XLA reference path)
        overrides["use_flash"] = args.flash
    elif args.model != "llama_tiny":
        # every production-scale model proves the production path: the
        # hermetic TPU compiler lowers Pallas/Mosaic with no devices, so
        # no S^2 tile exists and dots_saveable fits where the XLA
        # reference path OOMs (llama_tiny deliberately stays on the
        # reference path)
        overrides["use_flash"] = True
    config = factory(**overrides)
    mesh_plan = None
    if args.mesh:
        from dlrover_tpu.parallel.mesh import MeshPlan

        mesh_plan = MeshPlan(**{
            k: int(v) for k, v in
            (kv.split("=") for kv in args.mesh.split(","))
        })
    if args.ring and mesh_plan is None:
        p.error("--ring requires --mesh with a seq>1 axis")
    pipeline = None
    if args.pipe_stages:
        if mesh_plan is None:
            p.error("--pipe-stages requires --mesh with a pipe axis "
                    "matching the stage count")
        pipe_size = dict(mesh_plan.axis_sizes()).get("pipe", 1)
        if pipe_size != args.pipe_stages:
            # a mismatched (or absent) pipe axis would silently compile
            # an artifact whose stage dim never lands on "pipe" — the
            # same hard validation the ring path applies to "seq"
            p.error(f"--pipe-stages {args.pipe_stages} needs --mesh "
                    f"with pipe={args.pipe_stages} (got pipe="
                    f"{pipe_size})")
        if args.packed_doc_len:
            p.error("--packed-doc-len does not compose with "
                    "--pipe-stages: apply_pipelined has no segment_ids "
                    "path (packed batches ride the unpipelined apply)")
        if args.head_chunk:
            p.error("--head-chunk does not compose with --pipe-stages: "
                    "the pipelined loss materializes full logits "
                    "(pipe-sharded over the batch dim instead)")
        pipeline = {
            "num_stages": args.pipe_stages,
            "num_microbatches": (args.pipe_microbatches
                                 or 2 * args.pipe_stages),
            "num_virtual": args.pipe_virtual,
        }
        if args.pipe_depths:
            pipeline["stage_depths"] = tuple(
                int(d) for d in args.pipe_depths.split(",")
            )
    # llama_pp carries BOTH the pipe-leading layer rules and the expert
    # submesh rules, so MoE+PP must resolve to it — "moe" has no pipe
    # entry and would compile stage params off the pipe axis silently
    rule_set = ("llama_pp" if pipeline
                else ("moe" if args.experts else "llama"))
    report = aot_compile_train_step(
        config,
        topology=args.topology,
        tpu_gen=args.gen,
        global_batch=args.batch,
        mesh_plan=mesh_plan,
        model_name=args.model + (f"+moe{args.experts}" if args.experts
                                 else "") + ("+pp" if pipeline else ""),
        rule_set=rule_set,
        ring=args.ring,
        head_chunk=args.head_chunk,
        packed_doc_len=args.packed_doc_len,
        pipeline=pipeline,
        graph_lint=args.lint,
    )
    print(report.to_json())
    if report.lint_findings:
        for f in report.lint_findings:
            print(f.render())
        return 1
    return 0 if report.fits else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
