"""Elastic model checkpointing over Orbax.

Role parity: ``atorch/atorch/utils/fsdp_save_util.py:97-549`` — the
reference saves per-rank FSDP flat params + meta and hand-reshards them on
load to a different world size. On TPU none of that machinery is needed:
GSPMD + Orbax make resharding native. Saving writes the *global* logical
arrays (each host contributing its shards); restoring materializes them
directly into whatever ``NamedSharding``s the *new* mesh wants. A job that
went from 32 to 16 hosts restores the same checkpoint unchanged.

Also the parity point for the reference's async-save design goal
(``docs/blogs/stabilize_llm_training_cn.md:215``: 10 min → 1 min saves):
``enable_async_checkpointing`` stages device arrays to host DRAM and
writes in a background thread. The staging itself (a device-to-host
copy of the whole state, seconds on a chip) is kept off the training
thread too: a save takes a device-side snapshot of the state and a
saver thread stages that, behind the next steps (``save``).

Data-shard state rides along: the master's shard checkpoint string
(``task_manager.get_shard_checkpoint``) is saved next to the model state so
a restored job resumes mid-epoch without re-reading consumed data.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import math
import os
import shutil
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry import (
    EventKind,
    SpanName,
    emit_event,
    get_registry,
    names as tm,
    span,
)

logger = get_logger("checkpoint.manager")

# ``_every_process_has_room``: the agreements this process has entered
# (every process of a job counts the same ones, in the same order), and
# how long one waits for a process that does not come
_AGREEMENTS = itertools.count()
_AGREE_MS = 300_000


@dataclass
class CheckpointInterval:
    """Cadence helper (reference: ``trainer/torch/elastic.py:170``).

    ``steps`` and ``secs`` compose with OR: save when either elapses.
    """

    steps: int = 0
    secs: float = 0.0
    _last_step: int = 0
    _last_time: float = 0.0

    def __post_init__(self):
        self._last_time = time.time()

    def should_save(self, step: int) -> bool:
        due = False
        if self.steps and step - self._last_step >= self.steps:
            due = True
        if self.secs and time.time() - self._last_time >= self.secs:
            due = True
        return due

    def mark_saved(self, step: int):
        """Count ``step`` as saved; returns the cadence as it was, for
        ``unmark`` should the save write nothing after all."""
        was = (self._last_step, self._last_time)
        self._last_step = step
        self._last_time = time.time()
        return was

    def unmark(self, step: int, was):
        """Undo ``mark_saved(step)``, unless a later save was marked."""
        if self._last_step == step:
            self._last_step, self._last_time = was


def abstract_like(state: Any, sharding_tree: Any = None) -> Any:
    """Build the abstract (shape/dtype/sharding) target for a restore.

    Pass the sharding tree of the *current* mesh — this is where cross-
    world-size resharding happens: the checkpoint holds global arrays, and
    Orbax lays them out into these shardings on load.
    """
    if sharding_tree is None:
        return jax.eval_shape(lambda x: x, state)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(lambda x: x, state),
        sharding_tree,
    )


@dataclass
class HostSnapshot:
    """An in-process, host-DRAM copy of a TrainState — the live-recovery
    analogue of the staging mirror, with the storage round-trip removed.

    Where the mirror layer copies a *committed Orbax step* into tmpfs so
    a restarted process restores from DRAM, ``HostSnapshot`` keeps the
    *live* state in this process's own heap so a surviving process never
    restores at all: the executor drains its in-flight window, takes one
    snapshot (a single ``device_get``), rebuilds the mesh for the new
    world, and ``device_put``s the snapshot against the new shardings —
    GSPMD lays the global arrays out for the survivor topology exactly
    as an Orbax reshard-on-load would, minus serialization, storage, and
    process boot. Leaves are host numpy arrays: donation-safe (XLA never
    owned them) and immune to peer/device loss.
    """

    step: int
    tree: Any
    meta: Dict[str, Any]

    @classmethod
    def take(cls, state: Any, **meta) -> "HostSnapshot":
        """One device sync: pull every leaf to host DRAM. Callers drain
        in-flight work first so this waits only on the last step.

        On the CPU backend ``device_get`` can return numpy views that
        ALIAS the live XLA buffers (host memory IS device memory there
        — the same zero-copy family as the Orbax adjacency hang): a
        donated train step dispatched after ``take()`` would then
        scribble over the "snapshot". One host-side copy per leaf makes
        the snapshot genuinely immune to later donation; accelerator
        backends skip it (their device_get is a real D2H copy)."""
        reg = get_registry()
        t0 = time.monotonic()
        with span(SpanName.STATE_SNAPSHOT):
            tree = jax.device_get(state)
            if _on_cpu_backend(state):
                import numpy as _np

                tree = jax.tree.map(
                    lambda x: _np.array(x, copy=True)
                    if isinstance(x, _np.ndarray) else x,
                    tree,
                )
        snap_s = time.monotonic() - t0
        reg.histogram(
            tm.SNAPSHOT_TIME,
            help="host-DRAM TrainState snapshot (device_get) seconds",
        ).observe(snap_s)
        step = int(tree.step) if hasattr(tree, "step") else -1
        emit_event(EventKind.STATE_SNAPSHOT, step=step,
                   snapshot_seconds=round(snap_s, 3))
        return cls(step=step, tree=tree, meta=dict(meta))

    def restore(self, sharding_tree: Any) -> Any:
        """Materialize the snapshot into ``sharding_tree`` — the new
        mesh's NamedShardings. ``device_put`` against them IS the
        reshard: XLA scatters each host array into the survivor
        topology's layout (the in-memory twin of Orbax's
        reshard-on-load)."""
        return jax.device_put(self.tree, sharding_tree)

    def nbytes(self) -> int:
        """Host bytes this snapshot holds: the replica-budget admission
        prices plans off this number."""
        return _tree_nbytes(self.tree)


def _tree_nbytes(tree: Any) -> int:
    """Bytes of a tree's arrays (each whole, whatever its sharding).
    Non-numpy leaves (python scalars, 0-d device remnants) are sized
    through ``np.asarray`` instead of silently counting 0."""
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(tree):
        n = getattr(leaf, "nbytes", None)
        if n is None:
            try:
                n = np.asarray(leaf).nbytes
            except (TypeError, ValueError):
                n = 0
        total += int(n)
    return total


def _on_cpu_backend(state: Any) -> bool:
    """True when the state's device arrays live on the CPU backend (the
    zero-copy-aliasing platform the donation-safety copies exist for)."""
    leaves = [x for x in jax.tree.leaves(state) if isinstance(x, jax.Array)]
    if not leaves:
        return False
    try:
        return {d.platform for d in leaves[0].devices()} == {"cpu"}
    except Exception as e:  # noqa: BLE001 — conservative: copy when unsure
        logger.debug("could not read device platform (%s: %s); assuming "
                     "cpu for the donation-safety copy",
                     type(e).__name__, e)
        return True


def _rematerialize(state: Any) -> Any:
    """Copy restored arrays into fresh XLA-owned buffers.

    Orbax materializes restored ``jax.Array``s over buffers that (on the
    CPU backend) can alias tensorstore-owned host memory. The train step
    is compiled with ``donate_argnums``, so the first step after a
    restore would DONATE those aliased buffers — XLA then writes into /
    frees memory it does not own. Observed as a segfault or a wedged
    dispatch once another Orbax manager has touched the process (the
    tests/test_checkpoint_trainer.py + tests/test_executor.py adjacency
    hang). One cheap copy per restore makes every restored leaf
    donation-safe; sharding is preserved. The whole tree goes through
    ONE jitted program (not a per-leaf ``jnp.copy`` — that would
    compile hundreds of trivial executables on a large model's first
    restore, a real MTTR tax)."""
    return _copy_tree(state)


@jax.jit
def _copy_tree(tree: Any) -> Any:
    import jax.numpy as jnp

    with jax.named_scope("ckpt_state_copy"):
        return jax.tree.map(jnp.copy, tree)


def _decouple_from_donation(state: Any) -> Any:
    """The WRITE-side twin of ``_rematerialize``: one device-side copy
    of the state (one jitted program, enqueued behind the step that
    produced the state), so that what is saved is buffers nothing ever
    donates. The training loop's next step DONATES the live buffers,
    and a save reads its tree after ``save`` has returned: the saver
    thread stages it to the host behind the next steps on every
    backend, and on the CPU backend Orbax's background write
    zero-copy-references it besides (host memory IS device memory
    there; a NaN landing one step after a save used to poison the
    freshly "committed" checkpoint that way). A tree with no device
    array is returned as it is."""
    if not any(isinstance(x, jax.Array) for x in jax.tree.leaves(state)):
        return state
    return _copy_tree(state)


def _bytes_by_device(tree: Any) -> Dict[Any, int]:
    """The bytes each local device holds of ``tree``'s device arrays,
    from shapes and shardings alone."""
    held: Dict[Any, int] = {}
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array):
            shard = x.dtype.itemsize * math.prod(
                x.sharding.shard_shape(x.shape))
            for device in x.sharding.addressable_devices:
                held[device] = held.get(device, 0) + shard
    return held


class ElasticCheckpointManager:
    """Save/restore TrainState + metadata, async by default.

    The directory layout is Orbax-standard (one numbered subdir per step),
    so checkpoints written at one world size restore at any other.
    """

    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        async_save: Optional[bool] = None,
        save_interval: Optional[CheckpointInterval] = None,
        staging_dir: Optional[str] = None,
        run_identity: str = "",
    ):
        import orbax.checkpoint as ocp

        from dlrover_tpu.common.config import get_context

        # staging provenance token. A path-local uuid file alone cannot
        # survive the very outage staging exists for (primary root wiped
        # => the uuid is gone => a fresh uuid rejects the good mirror and
        # the job silently restarts from scratch). A caller-stable run
        # identity survives primary loss while still fencing out another
        # run reusing the path. RUN_ID (job name + launch epoch, set by
        # the scalers) is preferred over the bare JOB_NAME: a brand-new
        # job reusing the same name and checkpoint path — the common
        # rerun pattern — must NOT adopt the previous run's staged
        # weights, which a name-only token would allow.
        self._run_identity = (
            run_identity
            or os.environ.get(NodeEnv.RUN_ID, "")
            or os.environ.get(NodeEnv.JOB_NAME, "")
        )

        self._ocp = ocp
        ctx = get_context()
        if async_save is None:
            async_save = ctx.ckpt_async
        self._async = bool(async_save)
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            enable_async_checkpointing=async_save,
        )
        self._manager = ocp.CheckpointManager(self.directory, options=options)
        self.interval = save_interval or CheckpointInterval()
        # Host-DRAM staging (reference: Flash Checkpoint / the <90 s
        # restore budget, stabilize_llm_training_cn.md:209-216): after a
        # save commits, the step dir is mirrored into tmpfs so a restart
        # on the same host restores from DRAM instead of (remote) storage.
        self._staging_root: Optional[str] = None
        if staging_dir is None and ctx.ckpt_host_staging:
            shm = "/dev/shm"
            if (
                os.path.isdir(shm)
                and os.access(shm, os.W_OK)
                and not self.directory.startswith(shm)
            ):
                staging_dir = os.path.join(
                    shm, "dlrover_tpu_ckpt",
                    hashlib.md5(self.directory.encode()).hexdigest()[:12],
                )
        if staging_dir:
            self._staging_root = os.path.abspath(staging_dir)
            os.makedirs(self._staging_root, exist_ok=True)
        reg = get_registry()
        self._c_saves = reg.counter(
            tm.CKPT_SAVES, help="checkpoint saves queued")
        self._c_mode = {
            "snapshot": reg.counter(
                tm.CKPT_SNAPSHOT_SAVES,
                help="saves staged by the saver thread from a device-"
                     "side snapshot, behind the next steps"),
            "blocking": reg.counter(
                tm.CKPT_BLOCKING_SAVES,
                help="saves staged by the calling thread from the live "
                     "state (no room for a snapshot, or a synchronous "
                     "manager)"),
        }
        self._c_dropped = reg.counter(
            tm.CKPT_DROPPED_SAVES,
            help="snapshot saves counted as begun whose state the saver "
                 "thread then found not finite: nothing was written")
        self._h_save = reg.histogram(
            tm.CKPT_SAVE_TIME,
            help="seconds save() held its caller, the training thread: "
                 "a snapshot save's device copy enqueued (and the wait "
                 "for an earlier snapshot still staging), a blocking "
                 "save's device->host copy")
        self._h_mirror = reg.histogram(
            tm.CKPT_MIRROR_TIME, help="host-DRAM staging mirror copy time")
        self._c_mirror_timeouts = reg.counter(
            tm.CKPT_MIRROR_TIMEOUTS,
            help="staging mirrors still uncommitted at a wait() deadline")
        self._h_restore = reg.histogram(
            tm.CKPT_RESTORE_TIME, help="restore wall time")
        self._c_restores = reg.counter(
            tm.CKPT_RESTORES, help="successful restores")
        self._mirror_lock = threading.Lock()
        self._mirror_threads: list = []
        # mirror THREAD OBJECTS that already consumed a full join
        # timeout (wait() only polls these afterwards). Keyed by object,
        # never by ident: idents are recycled after a thread exits, and
        # a fresh healthy mirror inheriting a stale flag would get a
        # 0-second join on the preemption exit path
        self._mirror_timed_out: set = set()
        # Orbax's manager is not thread-safe. One saver thread at a
        # time calls into it (``_run_saver``), and every other way in
        # joins that thread first (``_drain``): calls are serialised,
        # saves stay in step order, at most one snapshot is alive.
        self._saver: Optional[threading.Thread] = None
        self._saver_error: Optional[BaseException] = None

    # -- save ----------------------------------------------------------------

    def save(
        self,
        step: int,
        state: Any,
        metadata: Optional[Dict] = None,
        shard_checkpoint: str = "",
        force: bool = False,
        finite: Any = None,
    ) -> bool:
        """Begin a checkpoint of ``state``; returns True if one was begun.

        ``finite`` is the save step's flag, a device value, or None: a
        state that is not finite is never written, it would poison
        the rollback and restore target.

        One algorithm, its staging (Orbax's device-to-host copy of
        every leaf, seconds for a few GB) on one of two threads, chosen
        from what the devices report (``_room_for_snapshot``):

        - ``snapshot``: the caller enqueues one device-side copy of the
          state behind the step that produced it and returns without
          reading a device value, so with True and ``ckpt_save`` for a
          save it cannot yet know to be finite. A saver thread waits for
          the flag, drops the snapshot if it is not finite (the warning,
          ``ckpt_save_dropped`` and its counter; the cadence is handed
          back), else stages the snapshot behind the caller's next
          steps and deletes it as soon as it is on the host. A save
          that comes due while the previous snapshot is still staging
          waits for it here.
        - ``blocking``: some device, of this process or of another
          (``_every_process_has_room``), has no room for a second copy
          of its part of the state, or the manager is synchronous
          (``async_save=False``: returns when the step is on disk). The
          caller waits for the flag and stages the live state itself;
          the next step, which donates those buffers, waits.

        Either way the write follows in Orbax's threads (async), commit
        means the step directory exists, and ``wait`` blocks for it.
        ``ckpt_save.stage_seconds`` is what this call held its caller
        (less a blocking save's wait for the steps in flight, which is
        no staging), ``ckpt_save_staged.copy_seconds`` the staging
        itself, on whichever thread.
        """
        if not force and not self.interval.should_save(step):
            return False
        t0 = time.monotonic()
        self._drain()
        meta = dict(metadata or {})
        meta["save_wall_time"] = time.time()
        held = _bytes_by_device(state)
        if self._async and self._every_process_has_room(
                self._room_for_snapshot(held)):
            mode = "snapshot"
            snapshot = _decouple_from_donation(state)
            cadence = self.interval.mark_saved(step)
            # not a daemon: an exiting process joins it, so no thread
            # is inside JAX or Orbax while the interpreter is torn
            # down. Only ``wait`` commits: Orbax can begin no save once
            # the exit has begun (asyncio's pool is closed by then), so
            # a failing run flushes first (``TrainExecutor``)
            self._saver = threading.Thread(
                target=self._run_saver, name=f"ckpt-saver-{step}",
                args=(step, snapshot, finite, meta, shard_checkpoint,
                      sum(held.values()), cadence),
                daemon=False)
            self._saver.start()
        else:
            mode = "blocking"
            t1 = time.monotonic()
            ok = self._finite(finite, step)
            t0 += time.monotonic() - t1  # the steps in flight: no staging
            if not (ok and self._stage(step, state, meta, shard_checkpoint,
                                       snapshot_bytes=0)):
                return False
            self.interval.mark_saved(step)
        stage_s = time.monotonic() - t0
        self._c_saves.inc()
        self._c_mode[mode].inc()
        self._h_save.observe(stage_s)
        emit_event(EventKind.CKPT_SAVE, step=step,
                   stage_seconds=round(stage_s, 6), forced=force, mode=mode)
        return True

    def _room_for_snapshot(self, held: Dict[Any, int]) -> bool:
        """Memory decides which thread stages: True when every local
        device has room, now and with the steps in flight, for a second
        copy of what it holds of the state: ``bytes_limit`` less
        ``bytes_in_use`` and ``bytes_reserved`` of its
        ``memory_stats()``. (``bytes_reserved`` is the scratch of the
        loaded step program, which ``bytes_in_use`` and its peak leave
        out: 5.6 of 16.9 GB beside 4.1 in use at Mistral-7B widths and
        depth 8 on a v5e.) A backend without the stat (the CPU: host
        memory) always has room."""
        for device, nbytes in held.items():
            stats = device.memory_stats() or {}
            limit = stats.get("bytes_limit")
            if limit is not None and nbytes > (
                    limit - stats.get("bytes_in_use", 0)
                    - stats.get("bytes_reserved", 0)):
                return False
        return True

    def _every_process_has_room(self, room: bool) -> bool:
        """The same answer on every process of a multi-process job:
        all take the snapshot path or none does, so that every process
        launches the same programs in the same order (the device copy
        is one) and no training thread sits in Orbax's barrier waiting
        for another process's saver thread. Each process posts what its
        own devices say to the coordinator's key-value store and reads
        the others': host-side, so no step in flight is waited for
        beyond the skew between the processes' training threads. Keys
        are numbered by the process's saves that came this far, which
        every process counts alike."""
        if jax.process_count() == 1:
            return room
        from jax._src import distributed

        client = distributed.global_state.client
        key = f"dlrover_tpu/ckpt_room/{next(_AGREEMENTS)}/"
        client.key_value_set(key + str(jax.process_index()), str(int(room)))
        return all(
            client.blocking_key_value_get(key + str(p), _AGREE_MS) == "1"
            for p in range(jax.process_count()))

    def _finite(self, finite: Any, step: int) -> bool:
        """Read the save step's flag: the one device value a save waits
        for, on the thread that stages."""
        import numpy as np

        if finite is None or bool(np.all(finite)):
            return True
        logger.warning(
            "skipping checkpoint at step %d: non-finite state", step)
        return False

    def _run_saver(self, step, snapshot, finite, meta, shard_checkpoint,
                   snapshot_bytes, cadence):
        """The saver thread of a snapshot save."""
        try:
            try:
                is_finite = self._finite(finite, step)
                begun = is_finite and self._stage(
                    step, snapshot, meta, shard_checkpoint, snapshot_bytes)
            finally:
                # on the host, or dropped: free the device memory now,
                # not when the last reference goes. Not on the CPU
                # backend, where Orbax's write may still be reading
                # these very buffers; and a tree with no device array
                # is the caller's own.
                if snapshot_bytes and not _on_cpu_backend(snapshot):
                    for x in jax.tree.leaves(snapshot):
                        if isinstance(x, jax.Array):
                            x.delete()
            if not begun:
                # ``save`` has counted and announced this save as begun
                # (it reads no device value): say that nothing was
                # written, and let the next step try again
                self.interval.unmark(step, cadence)
                self._c_dropped.inc()
                emit_event(EventKind.CKPT_SAVE_DROPPED, step=step,
                           reason="refused" if is_finite else "non_finite")
        except BaseException as e:  # noqa: BLE001 — raised by _drain
            logger.exception("staging checkpoint %d failed", step)
            self._saver_error = e

    def _drain(self):
        """Join the saver thread; a failure of its save is raised here,
        on the thread that next comes for the manager."""
        saver, self._saver = self._saver, None
        if saver is not None:
            saver.join()
        error, self._saver_error = self._saver_error, None
        if error is not None:
            raise error

    def _stage(self, step: int, tree: Any, meta: Dict,
               shard_checkpoint: str, snapshot_bytes: int) -> bool:
        """The Orbax save of ``tree``: async, it returns when every leaf
        is on the host. Runs on the saver thread (a snapshot) or on the
        caller's (the live state)."""
        ocp = self._ocp
        args = {"state": ocp.args.StandardSave(tree),
                "meta": ocp.args.JsonSave(meta)}
        if shard_checkpoint:
            args["data_shards"] = ocp.args.JsonSave(
                {"checkpoint": shard_checkpoint}
            )
        t0 = time.monotonic()
        with span(SpanName.CKPT_SAVE_STAGE, step=step):
            saved = self._manager.save(
                step, args=ocp.args.Composite(**args))
        if not saved:
            return False
        emit_event(EventKind.CKPT_SAVE_STAGED, step=step,
                   copy_seconds=round(time.monotonic() - t0, 6),
                   snapshot_bytes=snapshot_bytes)
        logger.info("checkpoint %d queued to %s", step, self.directory)
        if self._staging_root is not None:
            # mirror once the async write commits, off the hot path
            thread = threading.Thread(
                target=self._wait_and_mirror, args=(step,), daemon=True
            )
            self._mirror_threads = [
                t for t in self._mirror_threads if t.is_alive()
            ] + [thread]
            thread.start()
        return True

    def wait(self, mirror_timeout: float = 120.0) -> bool:
        """Block until queued async saves hit disk (and their staging
        mirrors complete).

        Returns ``timed_out``: True when a staging-mirror thread was
        still alive after ``mirror_timeout`` — the host-DRAM mirror for
        some step never committed, so a storage-outage restore would
        fall back to an OLDER staged step. Callers on exit paths (the
        preemption drain, ``finalize``) surface this instead of
        silently proceeding; the primary (Orbax) copy is unaffected
        either way."""
        self._drain()
        self._manager.wait_until_finished()
        timed_out = False
        pending: list = []
        for thread in self._mirror_threads:
            if thread.is_alive():
                # a thread that already burned one full timeout is only
                # POLLED afterwards: repeated wait() calls (e.g. the
                # preemption drain's latest_checkpoint_step + finalize
                # back-to-back) must not stack 120s stalls inside the
                # bounded grace window
                already_flagged = thread in self._mirror_timed_out
                thread.join(timeout=0.0 if already_flagged
                            else mirror_timeout)
            if thread.is_alive():
                timed_out = True
                pending.append(thread)
                if thread not in self._mirror_timed_out:
                    self._mirror_timed_out.add(thread)
                    self._c_mirror_timeouts.inc()
                    emit_event(EventKind.CKPT_MIRROR_TIMEOUT,
                               error_code="CKPT_MIRROR_TIMEOUT",
                               timeout_seconds=mirror_timeout)
                    logger.error(
                        "[CKPT_MIRROR_TIMEOUT] staging mirror thread %s "
                        "still running after %.0fs: the host-DRAM mirror "
                        "for its step never committed (primary "
                        "checkpoint unaffected)",
                        thread.name, mirror_timeout,
                    )
            else:
                self._mirror_timed_out.discard(thread)
        # keep only the still-alive threads: a later wait() can still
        # observe them instead of forgetting the in-flight mirror
        self._mirror_threads = pending
        self._mirror_timed_out &= set(pending)
        return timed_out

    # -- host-DRAM staging ----------------------------------------------------

    def _step_dir(self, root: str, step: int) -> str:
        return os.path.join(root, str(step))

    def _newer_step_committed(self, step: int) -> bool:
        """Filesystem-only (the mirror thread must never touch the
        non-thread-safe Orbax manager): a committed step dir numbered
        above ``step``."""
        try:
            return any(
                name.isdigit() and int(name) > step
                for name in os.listdir(self.directory)
            )
        except OSError:
            return False

    def _wait_and_mirror(self, step: int, deadline_s: float = 600.0):
        """Mirror once the step commits. Orbax's CheckpointManager is not
        thread-safe, so this thread never touches it: on posix the atomic
        rename of the tmp dir to ``<root>/<step>`` IS the commit marker —
        poll for that instead of wait_until_finished()."""
        import time as _time

        step_dir = self._step_dir(self.directory, step)
        deadline = _time.monotonic() + deadline_s
        try:
            while not os.path.isdir(step_dir):
                if _time.monotonic() > deadline:
                    logger.warning(
                        "step %d never committed; skipping staging", step
                    )
                    return
                if self._newer_step_committed(step):
                    # commits are ordered, so a NEWER numbered dir with
                    # this one absent means max_to_keep already deleted
                    # it (or will): stop polling instead of spinning to
                    # the deadline and stalling wait() — the newer
                    # step's own mirror supersedes this one anyway
                    logger.info(
                        "step %d superseded before mirroring; skipping",
                        step,
                    )
                    return
                _time.sleep(0.5)
            self._mirror_to_staging(step)
        except Exception:  # noqa: BLE001 — staging is best-effort
            logger.exception("staging mirror for step %d failed", step)

    def _mirror_to_staging(self, step: int):
        src = self._step_dir(self.directory, step)
        if not os.path.isdir(src):
            return
        with self._mirror_lock:  # serialize: mirrors must not interleave
            # reclaim tmp dirs orphaned by a crash mid-copy (the exact
            # preemption staging exists for): the keep-newest cleanup
            # below only understands numbered step dirs, so without this
            # every crashed mirror permanently leaks tmpfs until the
            # free-space gate silently disables staging altogether
            try:
                for name in os.listdir(self._staging_root):
                    if name.startswith(".tmp_"):
                        shutil.rmtree(
                            os.path.join(self._staging_root, name),
                            ignore_errors=True,
                        )
            except OSError:
                pass
            newest = self.staged_step()
            if newest is not None and not self._staging_provenance_valid():
                # leftovers from a previous job at this checkpoint path:
                # clear them so staging works from this job's first save
                logger.info("clearing stale staging mirror (provenance "
                            "mismatch)")
                self._clear_staging()
                newest = None
            if newest is not None and (
                newest > step
                or (newest == step and self._staged_digest_valid(step))
            ):
                return  # an equal-or-newer valid step is already staged
            # size gate: a checkpoint bigger than (half the) free tmpfs
            # would just burn read bandwidth and fail with ENOSPC
            try:
                ckpt_bytes = sum(
                    os.path.getsize(os.path.join(r, f))
                    for r, _d, files in os.walk(src) for f in files
                )
                free = shutil.disk_usage(self._staging_root).free
            except OSError:
                ckpt_bytes, free = 0, 0
            if ckpt_bytes and ckpt_bytes * 2 > free:
                logger.warning(
                    "skipping host-DRAM staging: checkpoint %.1f GB vs "
                    "%.1f GB free tmpfs", ckpt_bytes / 1e9, free / 1e9,
                )
                return
            tmp = os.path.join(self._staging_root, f".tmp_{step}")
            dst = self._step_dir(self._staging_root, step)
            shutil.rmtree(tmp, ignore_errors=True)
            t0 = time.monotonic()
            try:
                with span(SpanName.CKPT_MIRROR, step=step):
                    digest = self._dir_digest(src)
                    shutil.copytree(src, tmp)
                    shutil.rmtree(dst, ignore_errors=True)
                    os.rename(tmp, dst)
                with open(dst + ".digest", "w") as f:
                    f.write(digest)
                self._write_provenance()
                # keep only the newest staged step: DRAM is precious
                for name in os.listdir(self._staging_root):
                    base = name.split(".")[0]
                    if base.isdigit() and int(base) < step:
                        path = os.path.join(self._staging_root, name)
                        if os.path.isdir(path):
                            shutil.rmtree(path, ignore_errors=True)
                        else:
                            try:
                                os.remove(path)
                            except OSError:
                                pass
                mirror_s = time.monotonic() - t0
                self._h_mirror.observe(mirror_s)
                emit_event(EventKind.CKPT_MIRROR, step=step,
                           mirror_seconds=round(mirror_s, 3))
                logger.info("checkpoint %d staged to %s", step,
                            self._staging_root)
            except OSError as e:  # tmpfs full, races — never fail the job
                logger.warning("host-DRAM staging failed: %s", e)
                shutil.rmtree(tmp, ignore_errors=True)
                shutil.rmtree(dst, ignore_errors=True)

    def _primary_identity(self) -> str:
        """Identity token used for staging provenance. With a run
        identity (job name), the token is stable across loss of the
        primary root — the storage-outage case staging exists for.
        Otherwise: a uuid file created once per root; it survives a
        same-host restart, but a wiped-and-recreated root gets a new
        uuid (so an anonymous fresh job can never inherit a previous
        job's weights — at the cost of the outage fallback)."""
        if self._run_identity:
            return f"job:{self._run_identity}"
        marker = os.path.join(self.directory, ".dlrover_ckpt_id")
        try:
            with open(marker) as f:
                return f.read().strip()
        except OSError:
            pass
        import uuid

        ident = uuid.uuid4().hex
        try:
            tmp = f"{marker}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(ident)
            os.rename(tmp, marker)
            with open(marker) as f:  # racing writers: reread the winner
                return f.read().strip()
        except OSError:
            return ""

    def _write_provenance(self):
        ident = self._primary_identity()
        if not ident:
            return
        try:
            with open(os.path.join(self._staging_root, "PROVENANCE"),
                      "w") as f:
                f.write(ident)
        except OSError:
            pass

    def _staging_provenance_valid(self) -> bool:
        try:
            with open(os.path.join(self._staging_root, "PROVENANCE")) as f:
                recorded = f.read().strip()
        except OSError:
            return False
        ident = self._primary_identity()
        return bool(ident) and ident == recorded

    def _clear_staging(self):
        try:
            for name in os.listdir(self._staging_root):
                path = os.path.join(self._staging_root, name)
                if os.path.isdir(path):
                    shutil.rmtree(path, ignore_errors=True)
                else:
                    try:
                        os.remove(path)
                    except OSError:
                        pass
        except OSError:
            pass

    @staticmethod
    def _dir_digest(path: str) -> str:
        """Cheap content-identity fingerprint of a step dir: every file's
        relpath, size, and mtime. Guards staged restores against a stale
        mirror left by a PREVIOUS job at the same checkpoint path."""
        entries = []
        for root, _dirs, files in os.walk(path):
            for name in sorted(files):
                full = os.path.join(root, name)
                try:
                    st = os.stat(full)
                except OSError:
                    continue
                entries.append(
                    f"{os.path.relpath(full, path)}:{st.st_size}:"
                    f"{st.st_mtime_ns}"
                )
        return hashlib.sha256("\n".join(sorted(entries)).encode()).hexdigest()

    def _staged_digest_valid(self, step: int) -> bool:
        """The staged copy is trustworthy iff its recorded digest matches
        the primary step dir as it is NOW — or the primary step dir is
        gone entirely (the storage-outage fast-restart case)."""
        dst = self._step_dir(self._staging_root, step)
        try:
            with open(dst + ".digest") as f:
                recorded = f.read().strip()
        except OSError:
            return False
        src = self._step_dir(self.directory, step)
        if not os.path.isdir(src):
            if not os.path.isdir(self.directory):
                # the primary ROOT vanished after construction (the
                # constructor makedirs it, so a fresh job always has
                # one): storage outage — the mirror is the survivor
                logger.warning(
                    "adopting staged checkpoint step=%d: primary root "
                    "%s is GONE (storage outage path). If this is a "
                    "fresh run, these are a previous run's weights — "
                    "clear %s to start from scratch.",
                    step, self.directory, self._staging_root,
                )
                return True
            # root present but step missing: trust the mirror only for
            # the SAME run identity (a fresh job recreating the path
            # must not inherit the previous job's weights)
            ok = self._staging_provenance_valid()
            if ok:
                logger.warning(
                    "adopting staged checkpoint step=%d under identity "
                    "'%s' with an EMPTY primary %s. A same-named fresh "
                    "run inherits the previous run's weights here — set "
                    "%s (or pass run_identity) to fence runs apart.",
                    step, self._primary_identity(), self.directory,
                    NodeEnv.RUN_ID,
                )
            return ok
        return self._dir_digest(src) == recorded

    def staged_step(self) -> Optional[int]:
        """Newest step available in the host-DRAM staging mirror."""
        if self._staging_root is None or not os.path.isdir(
            self._staging_root
        ):
            return None
        steps = [
            int(n) for n in os.listdir(self._staging_root) if n.isdigit()
        ]
        return max(steps) if steps else None

    # -- restore -------------------------------------------------------------

    def latest_step(self) -> Optional[int]:
        self._drain()
        return self._manager.latest_step()

    def restore_from_staging(
        self, abstract_state: Any
    ) -> Optional[Dict[str, Any]]:
        """Warm-restart fast path: restore the newest staged step from
        the host-DRAM mirror WITHOUT touching the primary directory.

        ``restore()`` consults the primary's step listing first; on a
        remote/flaky store that round-trip alone can dominate a restart
        budget. A same-host process restart (the agent's default
        recovery for a survivable failure when no process survived) can
        skip it: the mirror holds the newest step this host committed,
        digest/provenance-validated like any staged restore. Returns
        None when there is nothing staged or validation fails — callers
        fall back to ``restore()``.
        """
        if self._staging_root is None:
            return None
        step = self.staged_step()
        if step is None or not self._staged_digest_valid(step):
            return None
        t0 = time.monotonic()
        try:
            with span(SpanName.CKPT_RESTORE, source="staging"):
                out = self._restore_from(self._staging_root, step,
                                         abstract_state)
        except Exception:  # noqa: BLE001 — callers fall back to restore()
            logger.exception(
                "staging fast-path restore of step %d failed", step)
            return None
        restore_s = time.monotonic() - t0
        self._h_restore.observe(restore_s)
        self._c_restores.inc()
        emit_event(EventKind.CKPT_RESTORE, step=step,
                   restore_seconds=round(restore_s, 3), source="staging",
                   bytes=_tree_nbytes(out["state"]))
        logger.info("restored step %d from host-DRAM staging (no "
                    "primary round-trip)", step)
        return out

    def restore(
        self,
        abstract_state: Any,
        step: Optional[int] = None,
    ) -> Optional[Dict[str, Any]]:
        """Restore into the shardings carried by ``abstract_state``.

        Prefers the host-DRAM staged copy when it holds the requested
        step (no storage round-trip). Returns {"state": ..., "meta":
        {...}, "shard_checkpoint": str}, or None if no checkpoint exists.
        """
        # a save still staging or being written is the newest step:
        # Orbax lists it at once and can read it only when committed
        self._drain()
        self._manager.wait_until_finished()
        t0 = time.monotonic()
        # what was read, and why the mirror was passed over where it
        # was: ``_restore_any`` fills it in
        read: Dict[str, str] = {}
        with span(SpanName.CKPT_RESTORE):
            out = self._restore_any(abstract_state, step, read)
        if out is not None:
            restore_s = time.monotonic() - t0
            self._h_restore.observe(restore_s)
            self._c_restores.inc()
            emit_event(EventKind.CKPT_RESTORE, step=out.get("step"),
                       restore_seconds=round(restore_s, 3),
                       bytes=_tree_nbytes(out["state"]), **read)
        return out

    def _restore_any(
        self,
        abstract_state: Any,
        step: Optional[int],
        read: Dict[str, str],
    ) -> Optional[Dict[str, Any]]:
        """``read`` is filled with ``source`` (``staging`` or
        ``directory``) and, where a mirror is kept and was not what was
        read first, ``mirror_skipped``: ``absent`` (it holds no step),
        ``step_mismatch`` (not this one), ``digest`` (its copy is not
        the primary's as it is now) or ``unreadable``."""
        staging_only = False
        explicit_step = step is not None
        if step is None:
            try:
                step = self.latest_step()
            except Exception:  # noqa: BLE001 — primary storage gone
                step = None
            if step is None and self._staging_root is not None:
                # primary storage lost entirely: the host-DRAM mirror is
                # the restore source of last resort (digest/provenance
                # checked below like any other staged restore). The
                # primary has no such step, so there is no fallback:
                # failed validation means "no checkpoint", not a crash.
                step = self.staged_step()
                staging_only = step is not None
        if step is None:
            return None
        staged_already_failed = False
        read["source"] = "directory"
        staged = None
        if self._staging_root is not None:
            staged = self.staged_step()
            read["mirror_skipped"] = (
                "absent" if staged is None
                else "step_mismatch" if staged != step else "digest")
        if staged == step and self._staged_digest_valid(step):
            try:
                out = self._restore_from(self._staging_root, step,
                                         abstract_state)
                logger.info(
                    "restored checkpoint step=%d from host-DRAM staging",
                    step,
                )
                read["source"] = "staging"
                del read["mirror_skipped"]
                return out
            except Exception:  # noqa: BLE001 — fall back to the real dir
                staged_already_failed = True
                read["mirror_skipped"] = "unreadable"
                logger.exception(
                    "staged restore failed; falling back to %s",
                    self.directory,
                )
        if staging_only:
            # the step exists ONLY in staging and wasn't restorable
            # (stale provenance or a failed read): a fresh job must
            # start from scratch, not crash on a primary that never
            # held this step
            logger.warning(
                "staged step %d not restorable and absent from the "
                "primary; treating as no checkpoint", step,
            )
            return None
        try:
            out = self._restore_from(self.directory, step, abstract_state)
        except Exception:  # noqa: BLE001 — torn/corrupt latest step
            if explicit_step:
                raise
            # before dropping to an older step: the host-DRAM mirror may
            # hold a readable copy of EXACTLY this step (the digest gate
            # above compares against the now-corrupt primary, so it
            # rejected the mirror for the wrong reason). Provenance still
            # must match — a stale mirror from another job must not win.
            if (
                not staged_already_failed
                and self._staging_root is not None
                and self.staged_step() == step
                and self._staging_provenance_valid()
            ):
                try:
                    out = self._restore_from(self._staging_root, step,
                                             abstract_state)
                    logger.warning(
                        "primary step %d unreadable; restored the SAME "
                        "step from host-DRAM staging", step,
                    )
                    self._quarantine_step(step)
                    read["source"] = "staging"
                    return out
                except Exception:  # noqa: BLE001 — mirror also bad
                    logger.exception(
                        "staged copy of step %d also unreadable", step)
            # auto-selected latest failed (partial write, bit corruption):
            # a recovering job must come back from the newest GOOD step,
            # not crash on the bad one
            older = sorted(
                (s for s in self._manager.all_steps() if s < step),
                reverse=True,
            )
            logger.exception(
                "restore of latest step %d failed; trying older steps %s",
                step, older,
            )
            for s in older:
                try:
                    out = self._restore_from(self.directory, s,
                                             abstract_state)
                    logger.warning(
                        "restored OLDER checkpoint step=%d (latest %d "
                        "unreadable)", s, step,
                    )
                    self._quarantine_step(step)
                    return out
                except Exception:  # noqa: BLE001 — keep walking back
                    logger.exception("restore of step %d also failed", s)
            raise
        logger.info("restored checkpoint step=%d from %s", step,
                    self.directory)
        return out

    def _quarantine_step(self, step: int) -> None:
        """Move an unreadable step dir aside after a successful fallback.

        Left in place, the corrupt dir keeps winning latest_step() (every
        restart repeats the failed walk) and — worse — Orbax refuses to
        save any step <= the existing latest, so the resumed job's re-save
        at that step number would be silently dropped and progress past
        the fallback step repeatedly lost."""
        src = self._step_dir(self.directory, step)
        dst = os.path.join(self.directory,
                           f"corrupt-{step}-{int(time.time())}")
        try:
            os.replace(src, dst)
            logger.warning("quarantined unreadable step %d -> %s", step, dst)
        except OSError:
            logger.exception("could not quarantine step %d", step)
            return
        try:
            self._manager.reload()  # drop the cached step listing
        except Exception:  # noqa: BLE001 — cache refresh is best-effort
            logger.exception("orbax reload after quarantine failed")

    def _restore_from(
        self, root: str, step: int, abstract_state: Any
    ) -> Dict[str, Any]:
        ocp = self._ocp
        if os.path.abspath(root) == self.directory:
            manager = self._manager
        else:
            manager = ocp.CheckpointManager(
                root,
                options=ocp.CheckpointManagerOptions(
                    enable_async_checkpointing=False, read_only=True,
                ),
            )
        try:
            items = manager.item_metadata(step)
            args = {"state": ocp.args.StandardRestore(abstract_state),
                    "meta": ocp.args.JsonRestore()}
            try:
                has_shards = (
                    items is not None and "data_shards" in items.keys()
                )
            except (AttributeError, TypeError):
                has_shards = False
            if has_shards:
                args["data_shards"] = ocp.args.JsonRestore()
            restored = manager.restore(step, args=ocp.args.Composite(**args))
            out = {
                "state": _rematerialize(restored["state"]),
                "meta": restored["meta"] or {},
                "shard_checkpoint": "",
                "step": step,
            }
            if has_shards and restored.get("data_shards"):
                out["shard_checkpoint"] = restored["data_shards"].get(
                    "checkpoint", ""
                )
            return out
        finally:
            if manager is not self._manager:
                manager.close()

    def close(self):
        self._drain()
        self._manager.close()
