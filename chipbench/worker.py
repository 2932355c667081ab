"""The benchmark's worker: the process that holds the chip.

``tpurun --standalone`` starts it (and starts it again after a kill).
It builds the job of a configuration file under ``configs/`` through
the ``job`` module of the file's ``family`` (``families/<family>/``),
trains it through the program's normal path (``init_worker`` ->
``ElasticTrainer`` -> ``TrainExecutor.train_and_evaluate``) as the
traffic file under ``traffic/`` says, and writes one JSON object a line
on stdout for ``run.py``: ``worker`` (the device facts), ``reference``
(the check against the family's plain reference), ``start``, ``step``
(once the step's loss has reached the host), ``trace``. Under
``--profile_dir`` it arms the executor's profiling window, which
``run.py`` opens with a signal and hears of through ``events.jsonl``.

Everything of one configuration, one architecture or one traffic mix is
in those files; nothing here names a cell or a model.
"""

import argparse
import importlib
import json
import os
import sys
import time

_T_BOOT = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from chipbench import arithmetic  # noqa: E402
from dlrover_tpu.checkpoint import CheckpointInterval  # noqa: E402
from dlrover_tpu.common.constants import NodeEnv  # noqa: E402
from dlrover_tpu.trainer.bootstrap import init_worker  # noqa: E402
from dlrover_tpu.trainer.conf import build_configuration  # noqa: E402
from dlrover_tpu.trainer.elastic import ElasticTrainer  # noqa: E402
from dlrover_tpu.trainer.executor import (  # noqa: E402
    TrainExecutor,
    TrainHook,
)
from dlrover_tpu.utils.compile_cache import cache_traffic  # noqa: E402

def emit(event, **fields):
    print(json.dumps({"event": event, "t": time.time(), **fields}),
          flush=True)


def load(path):
    with open(path) as f:
        return json.load(f)


def build_job(model):
    """The ``Job`` of a configuration file's dictionary, from the
    ``job`` module of its ``family``."""
    return importlib.import_module(
        f"chipbench.families.{model['family']}.job").build(model)


def build_optimizer(spec):
    """``assumed.optimizer``: the name of an optax optimizer and its
    arguments."""
    args = {k: v for k, v in spec.items() if k not in ("name", "why")}
    return getattr(optax, spec["name"])(**args)


def batch_for(seed, k, vocab_size, batch, seq_len):
    """Batch ``k`` of the stream of ``seed``: a function of (seed, k)
    alone, so a restarted worker continues the stream where the
    restored step left it."""
    ids = np.random.default_rng([seed, k]).integers(
        0, vocab_size, size=(batch, seq_len + 1), dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


class Batches(TrainHook):
    """The executor's ``train_iter_fn``: batch ``k`` feeds step ``k+1``,
    starting at the step the state holds. The stream ends (and with it
    the job, cleanly) when ``stop_file`` appears."""

    def __init__(self, seed, job, batch, stop_file):
        self._args = (seed, job.vocab_size, batch, job.seq_len)
        self._stop_file = stop_file
        self._executor = None

    def begin(self, executor):
        self._executor = executor

    def example(self):
        return batch_for(self._args[0], 0, *self._args[1:])

    def __call__(self):
        k = int(self._executor.state.step)
        while not os.path.exists(self._stop_file):
            with jax.profiler.TraceAnnotation("chipbench:input"):
                batch = batch_for(self._args[0], k, *self._args[1:])
            yield batch
            k += 1


class StepLines(TrainHook):
    """One line a step, written once the step's metrics have reached the
    host, i.e. after the device finished it."""

    def __init__(self, devices):
        self._devices = devices

    def begin(self, executor):
        self._last = time.time()
        emit("start", resumed_step=int(executor.state.step),
             boot_seconds=self._last - _T_BOOT)

    def after_step(self, step, metrics):
        with jax.profiler.TraceAnnotation("chipbench:step_line"):
            loss = float(metrics["loss"])
            now = time.time()
            cache = cache_traffic()
            peak = max(((d.memory_stats() or {}).get(
                "peak_bytes_in_use") or 0) for d in self._devices)
            emit("step", step=int(step), loss=loss,
                 seconds=now - self._last, peak_bytes_in_use=peak,
                 cache_hits=cache["hits"], cache_misses=cache["misses"])
            self._last = now


class ReferenceCheck(TrainHook):
    """Set-up, first round only: the system's loss (the program's own
    compiled ``eval_step`` under its mesh) on one seeded row at the
    initial weights against the family's plain reference. The row is
    repeated over the batch so that the mesh's batch axes divide it;
    the mean of equal rows is the row's own loss."""

    def __init__(self, job, trainer, seed, batch):
        self._job, self._trainer = job, trainer
        self._seed, self._batch = seed, batch

    def begin(self, executor):
        t0 = time.time()
        job = self._job
        state, program = executor.state, self._trainer.accelerated
        row = batch_for(self._seed, 2 ** 31 - 1, job.vocab_size, 1,
                        job.seq_len)
        tiled = {k: np.repeat(v, self._batch, axis=0)
                 for k, v in row.items()}
        system = float(program.eval_step(
            state, program.shard_batch(tiled))["loss"])
        t1 = time.time()
        ref = job.reference_loss(state.params, row["input_ids"][0],
                                 row["labels"][0])
        emit("reference", system_loss=system, reference_loss=ref,
             abs_diff=abs(system - ref), tolerance=job.reference_tol,
             ok=bool(abs(system - ref) <= job.reference_tol),
             tokens=int(job.seq_len), system_seconds=t1 - t0,
             seconds=time.time() - t0)


class TraceWindow(TrainHook):
    """Trace ``steps`` steps of device work into ``trace_dir``, starting
    when the step after the round's ``warmup`` steps has reached the
    host. Only this process can trace the chip it holds."""

    def __init__(self, trace_dir, warmup, steps):
        self._dir, self._warmup, self._steps = trace_dir, warmup, steps
        self._first = self._last = self._t0 = None

    def begin(self, executor):
        self._first = int(executor.state.step) + self._warmup + 1
        self._last = self._first + self._steps

    def after_step(self, step, metrics):
        if step == self._first and self._t0 is None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # spans, not every call
            options.host_tracer_level = 2
            jax.profiler.start_trace(self._dir, profiler_options=options)
            self._t0 = time.time()
        elif step == self._last and self._t0 is not None:
            t1 = time.time()
            jax.profiler.stop_trace()
            emit("trace", dir=self._dir, after_step=self._first,
                 until_step=self._last, t0=self._t0, t1=t1,
                 stop_seconds=time.time() - t1)
            self._t0 = None

    def end(self, executor):
        if self._t0 is not None:
            jax.profiler.stop_trace()


def require_devices(chips, rehearsal):
    """The first ``chips`` devices, or an ``error`` line and exit 3: a
    measurement never falls back to the CPU."""
    devices = jax.devices()
    kind, platform = devices[0].device_kind, devices[0].platform
    problem = ""
    if len(devices) < chips:
        problem = f"the cell needs {chips} chip(s), JAX found {len(devices)}"
    elif not rehearsal:
        if platform != "tpu" or len(devices) != chips:
            problem = (f"need exactly {chips} TPU chip(s), JAX found "
                       f"{len(devices)} x {platform} ({kind})")
        else:
            try:
                arithmetic.peaks(kind)
            except arithmetic.UnknownDevice as e:
                problem = str(e)
    if problem:
        emit("error", error=problem)
        sys.exit(3)
    return devices[:chips]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--stop_file", required=True)
    p.add_argument("--ckpt_dir", default="")
    p.add_argument("--trace_dir", default="",
                   help="--trace 1: trace the steps after the warm-up "
                        "through TraceWindow")
    p.add_argument("--profile_dir", default="",
                   help="--trace 2: arm the executor's own profiling "
                        "window (SIGUSR2 opens it) on this directory")
    p.add_argument("--rehearsal", action="store_true",
                   help="allow a platform that is not a TPU (tests)")
    args = p.parse_args(argv)
    model, traffic = load(args.config), load(args.traffic)

    worker = init_worker()
    devices = require_devices(model["chips"], args.rehearsal)
    job = build_job(model)
    batch = model["assumed"]["batch"]
    emit("worker", pid=os.getpid(), t_boot=_T_BOOT,
         restart_round=worker.restart_round,
         master_addr=os.environ.get(NodeEnv.MASTER_ADDR, ""),
         platform=devices[0].platform, device_kind=devices[0].device_kind,
         device_count=len(devices),
         bytes_limit=(devices[0].memory_stats() or {}).get("bytes_limit"),
         params=job.param_count, layers=job.layers,
         batch=batch, seq_len=job.seq_len)

    seed = args.seed % (2 ** 32)
    restarted = worker.restart_round > 0
    # a mix that kills does so in set-up, so its window is measured on
    # the restarted worker; any other mix's on the first
    window_round = restarted == (traffic.get("kill", "never") != "never")
    every = traffic.get("save_every_steps", 0) if args.ckpt_dir else 0
    interval = None
    if every:
        interval = CheckpointInterval(steps=every)
        # the job's first save is where the mix puts it; a restored
        # trainer counts its cadence from the restored step itself
        interval.mark_saved(traffic["first_save_step"] - every)
    batches = Batches(seed, job, batch, args.stop_file)
    trainer = ElasticTrainer(
        job.init_fn, job.loss_fn,
        build_optimizer(model["assumed"]["optimizer"]), batches.example(),
        strategy=job.strategy, ckpt_dir=args.ckpt_dir if every else "",
        ckpt_interval=interval, master_client=worker.master_client,
        devices=devices,
    )
    # the weights come from --seed: ElasticTrainer takes no seed of its
    # own, and a seed closed over by init_fn would be a constant of the
    # compiled program (a new program, and a compile, for every seed)
    trainer._rng = jax.random.PRNGKey(seed)
    hooks = [batches]
    if not restarted:  # no part of the time to resume
        hooks.append(ReferenceCheck(job, trainer, seed, batch))
    hooks.append(StepLines(devices))
    if args.trace_dir and window_round:
        hooks.append(TraceWindow(args.trace_dir, traffic["warmup_steps"],
                                 traffic["trace_steps"]))
    conf = {"train_steps": 0, "log_every_steps": 1000}
    if args.profile_dir:
        # nothing is scheduled and nothing of the profiler starts
        # until run.py sends the signal, after its window has closed
        conf.update(profile_signal="USR2", trace_dir=args.profile_dir,
                    trace_start_step=-1,
                    trace_num_steps=traffic["trace_steps"])
    executor = TrainExecutor(
        trainer, train_iter_fn=batches, hooks=hooks,
        conf=build_configuration(conf),
        master_client=worker.master_client,
    )
    out = executor.train_and_evaluate()
    emit("finished", step=int(out["step"]))


if __name__ == "__main__":
    main()
