"""``tpurun`` — the elastic launcher CLI.

Role parity: ``dlrover-run`` (``dlrover/trainer/torch/elastic_run.py``):
torchrun-flavoured flags, ``--standalone`` boots a local master subprocess,
and if no master is reachable the launcher degrades to running the script
directly (the reference falls back to vanilla torchrun).

One process drives all of a host's chips (``--nproc_per_node`` 1, the
default); more than one is for CPU hosts and is refused on a TPU host.

Usage:
    tpurun --standalone train.py --lr 3e-4
    tpurun --nnodes 2:4 --node_unit 2 --network-check train.py
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import select
import subprocess
import sys
import time
from typing import List, Optional, Tuple

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.monitor.resource import ResourceMonitor
from dlrover_tpu.agent.training_agent import (
    AgentConfig,
    ElasticTrainingAgent,
)
from dlrover_tpu.agent.worker_group import WorkerSpec
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.rpc.server import addr_connectable

logger = get_logger("trainer.run")


def parse_nnodes(value: str) -> Tuple[int, int]:
    """"2" -> (2,2); "1:4" -> (1,4)."""
    if ":" in value:
        lo, hi = value.split(":", 1)
        return int(lo), int(hi)
    n = int(value)
    return n, n


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tpurun", description="dlrover_tpu elastic launcher"
    )
    p.add_argument("--nnodes", default="1",
                   help="node count or MIN:MAX for elasticity")
    p.add_argument("--nproc_per_node", default="auto",
                   help="JAX processes per host ('auto' = 1). On a TPU "
                        "host only 1 works: one process drives all of "
                        "the host's chips")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get(NodeEnv.NODE_RANK, "0")))
    p.add_argument("--node_unit", type=int, default=1,
                   help="hosts per TPU slice; worlds stay a multiple")
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("--standalone", action="store_true",
                   help="boot a local master subprocess")
    p.add_argument("--master_addr",
                   default=os.environ.get(NodeEnv.MASTER_ADDR, ""))
    p.add_argument("--network-check", dest="network_check",
                   action="store_true",
                   help="run the paired allgather probe before training")
    p.add_argument("--probe_platform", default="",
                   help="jax platform for the chip probe (tests: cpu)")
    p.add_argument("--rdzv_waiting_timeout", type=float, default=30.0)
    p.add_argument("--monitor_interval", type=float, default=2.0)
    p.add_argument("--relaunch_on_hang", "--relaunch-on-hang",
                   dest="relaunch_on_hang",
                   type=float, default=0.0, metavar="SECONDS",
                   help="restart workers when no heartbeat lands for this "
                        "many seconds (0 = off); parity with the "
                        "reference's --relaunch_on_hanging mode")
    p.add_argument("--log_dir", default="",
                   help="redirect per-worker stdout/err to this directory")
    p.add_argument("--train_window", type=int, default=None,
                   help="in-flight dispatch window of the async train "
                        "loop (0 = synchronous; workers see it as "
                        "DLROVER_TPU_TRAIN_WINDOW)")
    p.add_argument("--dispatch_chunks", type=int, default=None,
                   help="chunked grouped_ep MoE dispatch: split the "
                        "row exchange into this many double-buffered "
                        "ppermute-ring chunks (1 = serial one-shot "
                        "all_to_all; workers see it as "
                        "DLROVER_TPU_DISPATCH_CHUNKS; the runtime "
                        "optimizer retunes it live)")
    p.add_argument("--moe_precision", default=None,
                   choices=["bf16", "fp8", "fp8_qdq"],
                   help="grouped_ep MoE wire precision: fp8 quantizes "
                        "the row exchanges to block-scaled e4m3 "
                        "(values + f32 scales, ~half the wire bytes; "
                        "bf16 fallback when the backend fails the fp8 "
                        "probe); workers see it as "
                        "DLROVER_TPU_MOE_PRECISION and the runtime "
                        "optimizer retunes it live")
    p.add_argument("--fsdp_precision", default=None,
                   choices=["bf16", "fp8", "fp8_qdq"],
                   help="dense FSDP wire precision: fp8 quantizes the "
                        "per-layer param gathers of the scan-over-"
                        "layers to block-scaled e4m3 (values + f32 "
                        "scales, ~1/4 of an f32 gather; dequant-exact, "
                        "gradients untouched; bf16 fallback when the "
                        "backend fails the fp8 probe); workers see it "
                        "as DLROVER_TPU_FSDP_PRECISION and the runtime "
                        "optimizer retunes it live")
    p.add_argument("--grad_precision", default=None,
                   choices=["bf16", "fp8"],
                   help="gradient-path precision: fp8 quantizes the "
                        "per-shard gradient tree with an error-"
                        "feedback residual carried in TrainState "
                        "(bounded drift, G109-ratcheted); a BUILD-time "
                        "knob — workers see it as "
                        "DLROVER_TPU_GRAD_PRECISION; never retuned "
                        "live")
    p.add_argument("--snapshot_replicas", type=int, default=None,
                   help="peer-redundant host snapshots: keep this many "
                        "in-DRAM replicas of each node's snapshot "
                        "regions on master-chosen peers (0 = off; the "
                        "budget admission can degrade below it), "
                        "enabling the checkpoint-free peer-rebuild "
                        "recovery rung (docs/elasticity.md); workers "
                        "and the master see it as "
                        "DLROVER_TPU_SNAPSHOT_REPLICAS")
    p.add_argument("--replica_cadence_steps", type=int, default=None,
                   help="materialized steps between snapshot "
                        "replication pushes (wall-time floored by "
                        "replica_min_interval_secs)")
    p.add_argument("--live_recovery", "--live-recovery",
                   dest="live_recovery", action="store_true",
                   help="absorb survivable membership changes with an "
                        "in-process snapshot -> reshard -> resume "
                        "instead of restarting workers; the agent only "
                        "falls back to a restart after a grace window "
                        "(docs/operations.md)")
    p.add_argument("--metrics_port", type=int, default=None,
                   help="serve Prometheus /metrics from the agent on "
                        "this port (also DLROVER_TPU_METRICS_PORT; "
                        "0/unset = off)")
    p.add_argument("--events_file", default=None,
                   help="append the structured event timeline (JSONL) "
                        "here; workers inherit it via "
                        "DLROVER_TPU_EVENTS_FILE so one file holds "
                        "the whole job")
    p.add_argument("entrypoint", help="training script or executable")
    p.add_argument("args", nargs=argparse.REMAINDER)
    return p


def _launch_local_master(timeout: float = 30.0) -> Tuple[subprocess.Popen, str]:
    """Spawn ``python -m dlrover_tpu.master.main`` and scrape its addr."""
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "dlrover_tpu.master.main",
         "--platform", "local"],
        stdout=subprocess.PIPE, stderr=None, text=True,
    )
    deadline = time.time() + timeout
    addr = ""
    while time.time() < deadline:
        ready, _, _ = select.select([proc.stdout], [], [], 0.5)
        if not ready:
            if proc.poll() is not None:
                raise RuntimeError("local master exited during startup")
            continue
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise RuntimeError("local master exited during startup")
            time.sleep(0.1)
            continue
        m = re.match(r"DLROVER_TPU_MASTER_ADDR=(\S+)", line)
        if m:
            addr = m.group(1)
            break
    if not addr:
        proc.terminate()
        raise RuntimeError("local master did not report its address")
    logger.info("standalone master at %s", addr)
    return proc, addr


def _on_tpu_host() -> bool:
    """Whether this host's workers will run on TPU chips — found
    WITHOUT importing JAX (the launcher must stay off the chip): an
    explicit ``JAX_PLATFORMS`` pin decides (tests and rehearsals pin
    ``cpu``); unpinned, the device nodes a TPU VM exposes do."""
    pinned = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if pinned:
        return pinned.lower() == "tpu"
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def _run_without_master(args, script_args: List[str]) -> int:
    """Degraded mode: exec the entrypoint directly (reference falls back to
    torchrun when no master is reachable, ``elastic_run.py:154-171``)."""
    logger.warning("no master reachable; running entrypoint directly")
    cmd = (
        [sys.executable, "-u", args.entrypoint]
        if args.entrypoint.endswith(".py") else [args.entrypoint]
    )
    return subprocess.call(cmd + script_args)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "lint":
        # `tpurun lint [...]` — the pre-submit static-analysis gate
        # (framework AST lint + SPMD graph lint); see
        # docs/static_analysis.md
        from dlrover_tpu.analysis.cli import main as lint_main

        return lint_main(argv[1:])
    if argv and argv[0] in ("serve", "requests"):
        # `tpurun serve --addr ...` runs one continuous-batching serve
        # worker; `tpurun requests` renders the router ledger (live
        # --addr / forensic --events) — see docs/serving.md
        from dlrover_tpu.serving.cli import main as serving_main

        return serving_main(argv)
    if argv and argv[0] in ("metrics", "mttr", "goodput", "diagnose",
                            "plan", "attribution", "data", "readiness",
                            "events", "trace", "cache"):
        # `tpurun metrics [--addr host:port]` / `tpurun mttr ...` /
        # `tpurun goodput` / `tpurun diagnose` / `tpurun plan` /
        # `tpurun attribution` / `tpurun data` / `tpurun readiness` /
        # `tpurun cache` — the observability CLI
        # (docs/observability.md)
        from dlrover_tpu.telemetry.cli import main as telemetry_main

        return telemetry_main(argv)
    args = build_parser().parse_args(argv)
    script_args = list(args.args)
    if script_args and script_args[0] == "--":
        script_args = script_args[1:]  # strip only the leading separator
    # dispatch-pipeline knobs ride the worker environment: the Context
    # singleton reads DLROVER_TPU_* overrides at import, so every
    # executor/trainer the entrypoint builds picks them up without code
    # changes (and the degraded no-master path inherits them too)
    if args.train_window is not None:
        os.environ["DLROVER_TPU_TRAIN_WINDOW"] = str(args.train_window)
    if args.dispatch_chunks is not None:
        os.environ["DLROVER_TPU_DISPATCH_CHUNKS"] = str(
            args.dispatch_chunks)
    if args.moe_precision is not None:
        os.environ["DLROVER_TPU_MOE_PRECISION"] = args.moe_precision
    if args.fsdp_precision is not None:
        os.environ["DLROVER_TPU_FSDP_PRECISION"] = args.fsdp_precision
    if args.grad_precision is not None:
        os.environ["DLROVER_TPU_GRAD_PRECISION"] = args.grad_precision
    if args.snapshot_replicas is not None:
        # the MASTER prices the replica plan off this knob and the
        # workers gate their replicator/peer-restore on it, so it must
        # land in the shared environment before either initializes
        os.environ["DLROVER_TPU_SNAPSHOT_REPLICAS"] = str(
            args.snapshot_replicas)
    if args.replica_cadence_steps is not None:
        os.environ["DLROVER_TPU_REPLICA_CADENCE_STEPS"] = str(
            args.replica_cadence_steps)
    if args.live_recovery:
        # workers' executors route survivable changes to the in-process
        # reshard path (Context.live_recovery reads this at import)
        os.environ["DLROVER_TPU_LIVE_RECOVERY"] = "1"
    if args.events_file is not None:
        # workers inherit os.environ (worker_group), so the agent's and
        # every worker's lifecycle edges land in ONE timeline file
        os.environ["DLROVER_TPU_EVENTS_FILE"] = args.events_file
    exporter = None
    if args.metrics_port is not None and args.metrics_port > 0:
        from dlrover_tpu.telemetry.exporter import maybe_start_exporter

        exporter = maybe_start_exporter(port=args.metrics_port)
    min_nodes, max_nodes = parse_nnodes(args.nnodes)
    nproc = 1 if args.nproc_per_node == "auto" else int(args.nproc_per_node)
    if nproc < 1:
        print("tpurun: --nproc_per_node must be >= 1", file=sys.stderr)
        return 2
    if nproc > 1 and _on_tpu_host():
        # established on a v5e (PR 22): every worker is handed the whole
        # host (no per-process chip bounds are set), a chip belongs to
        # one process at a time, and the second process dies at backend
        # init ("Internal error when accessing libtpu multi-process
        # lockfile")
        print(f"tpurun: --nproc_per_node {nproc} cannot work on a TPU "
              "host: each worker process would claim all of the host's "
              "chips, and a chip belongs to one process at a time. One "
              "process drives all of a host's chips: use "
              "--nproc_per_node 1 (the default).", file=sys.stderr)
        return 2

    master_proc = None
    addr = args.master_addr
    try:
        if args.standalone:
            master_proc, addr = _launch_local_master()
        if not addr or not addr_connectable(addr):
            return _run_without_master(args, script_args)

        os.environ[NodeEnv.MASTER_ADDR] = addr
        client = MasterClient(addr, node_id=args.node_rank)
        config = AgentConfig(
            node_rank=args.node_rank,
            node_id=args.node_rank,
            nproc_per_node=nproc,
            min_nodes=min_nodes,
            max_nodes=max_nodes,
            node_unit=args.node_unit,
            max_restarts=args.max_restarts,
            monitor_interval=args.monitor_interval,
            rdzv_waiting_timeout=args.rdzv_waiting_timeout,
            network_check=args.network_check,
            probe_platform=args.probe_platform,
            hang_timeout=args.relaunch_on_hang,
            live_recovery=args.live_recovery,
        )
        spec = WorkerSpec(
            entrypoint=args.entrypoint,
            args=tuple(script_args),
            nproc_per_node=nproc,
            redirect_output=args.log_dir or None,
        )
        monitor = ResourceMonitor(client)
        monitor.start()
        agent = ElasticTrainingAgent(config, spec, client)
        rc = agent.run()
        if args.standalone and args.node_rank == 0:
            client.report_job_exit(success=(rc == 0))
        monitor.stop()
        return rc
    finally:
        if exporter is not None:
            exporter.stop()
        if master_proc is not None:
            time.sleep(0.2)
            master_proc.terminate()


if __name__ == "__main__":
    sys.exit(main())
