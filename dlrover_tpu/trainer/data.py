"""Elastic data input: resumable sampler + runtime-adjustable loader.

Role parity: ``dlrover/trainer/torch/elastic_sampler.py:25``
(``ElasticDistributedSampler`` — resumable, world-size-change-aware) and
``elastic_dataloader.py:19`` (``ElasticDataLoader`` — batch size changed
at runtime from a config push).

TPU-first: each *host* feeds its local slice of the global batch; the
sampler partitions the index space by (num_shards, shard_rank) just like
per-host ``tf.data`` sharding, and resuming after a world change re-
partitions the *remaining* indices over the new world. When a master is
present, the dynamic sharding client (``IndexShardingClient``) replaces
static partitioning entirely — faster hosts pull more shards.
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable, Iterator, List, Optional, Sequence

import numpy as np

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry import get_registry, names as tm

logger = get_logger("trainer.data")


class ElasticDistributedSampler:
    """Deterministic, resumable index sampler over ``dataset_size``.

    ``state_dict``/``load_state_dict`` carry ``completed_num`` so a restore
    (possibly at a different world size) skips consumed samples — the
    reference's semantics, minus torch.
    """

    def __init__(
        self,
        dataset_size: int,
        num_shards: int = 1,
        shard_rank: int = 0,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = False,
    ):
        if num_shards < 1 or not 0 <= shard_rank < num_shards:
            raise ValueError(
                f"bad shard spec rank={shard_rank} of {num_shards}"
            )
        self.dataset_size = dataset_size
        self.num_shards = num_shards
        self.shard_rank = shard_rank
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.epoch = 0
        self.completed_num = 0  # global count of consumed samples

    def set_epoch(self, epoch: int):
        self.epoch = epoch
        self.completed_num = 0

    def _global_indices(self) -> np.ndarray:
        idx = np.arange(self.dataset_size)
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self.epoch)
            rng.shuffle(idx)
        return idx

    def __iter__(self) -> Iterator[int]:
        indices = self._global_indices()[self.completed_num:]
        if self.drop_last:
            usable = (len(indices) // self.num_shards) * self.num_shards
            indices = indices[:usable]
        else:
            pad = (-len(indices)) % self.num_shards
            if pad and len(indices) > 0:
                # Tile until the pad is covered: near an epoch boundary the
                # remainder can be smaller than the pad, and every shard
                # must yield the same count or SPMD hosts desync.
                reps = -(-pad // len(indices))
                filler = np.tile(indices, reps)[:pad]
                indices = np.concatenate([indices, filler])
        for i in indices[self.shard_rank:: self.num_shards]:
            yield int(i)

    def __len__(self) -> int:
        remaining = self.dataset_size - self.completed_num
        if self.drop_last:
            return remaining // self.num_shards
        return math.ceil(remaining / self.num_shards)

    # -- elasticity ----------------------------------------------------------

    def record_batch(self, global_batch_size: int):
        """Advance the resume cursor by one global batch."""
        self.completed_num += global_batch_size

    def reshard(self, num_shards: int, shard_rank: int):
        """Adopt a new world; remaining indices re-partition cleanly."""
        logger.info(
            "sampler reshard: %d/%d -> %d/%d (completed=%d)",
            self.shard_rank, self.num_shards, shard_rank, num_shards,
            self.completed_num,
        )
        self.num_shards = num_shards
        self.shard_rank = shard_rank

    def state_dict(self) -> dict:
        return {
            "epoch": self.epoch,
            "completed_num": self.completed_num,
            "seed": self.seed,
        }

    def load_state_dict(self, state: dict):
        self.epoch = state.get("epoch", 0)
        self.completed_num = state.get("completed_num", 0)
        self.seed = state.get("seed", self.seed)


class ElasticDataLoader:
    """Batched host-side loader with a runtime-adjustable batch size.

    ``dataset`` is anything indexable; ``collate_fn`` stacks samples
    (default: numpy stack over tree leaves). ``set_batch_size`` takes
    effect at the next batch boundary — the reference reads a config file
    pushed by the master; here the agent calls it directly from the
    paral-config RPC.
    """

    def __init__(
        self,
        dataset: Sequence,
        batch_size: int,
        sampler: Optional[ElasticDistributedSampler] = None,
        collate_fn: Optional[Callable[[List[Any]], Any]] = None,
        sharding_client=None,
        drop_last: bool = True,
    ):
        self.dataset = dataset
        self._batch_size = batch_size
        self.sampler = sampler or ElasticDistributedSampler(
            len(dataset), shuffle=False
        )
        self._collate = collate_fn or _default_collate
        # When set, indices come from the master's dynamic sharding
        # service instead of the static sampler.
        self._sharding_client = sharding_client
        # Every emitted batch must have a fixed leading dim: a trailing
        # partial batch recompiles the jitted SPMD step, and with the
        # dynamic sharding client different hosts can see different
        # partial sizes and desync. drop_last=False pads the final batch
        # (wrapping samples) instead of dropping it.
        self._drop_last = drop_last

    @property
    def batch_size(self) -> int:
        return self._batch_size

    def set_batch_size(self, batch_size: int):
        if batch_size > 0 and batch_size != self._batch_size:
            logger.info("batch size %d -> %d", self._batch_size, batch_size)
            self._batch_size = batch_size

    def _index_stream(self) -> Iterator[int]:
        if self._sharding_client is not None:
            yield from self._sharding_client.record_indices()
        else:
            yield from self.sampler

    def __iter__(self) -> Iterator[Any]:
        buf: List[Any] = []
        for idx in self._index_stream():
            buf.append(self.dataset[idx])
            if len(buf) == self._batch_size:
                yield self._collate(buf)
                buf.clear()
        if buf and not self._drop_last:
            while len(buf) < self._batch_size:  # pad to the fixed shape
                buf.extend(buf[: self._batch_size - len(buf)])
            yield self._collate(buf[: self._batch_size])

    def __len__(self) -> int:
        n, bs = len(self.sampler), max(self._batch_size, 1)
        return n // bs if self._drop_last else -(-n // bs)


class DevicePreloader:
    """Overlap host→device transfer with compute — the ONE H2D
    prefetcher for both data paths (the in-process loader here and the
    shm coworker ring, which wraps it in background mode).

    Role parity: ``atorch/atorch/data/preloader.py:8`` (``GpuPreLoader``
    — a CUDA-stream H2D prefetcher). On TPU, ``jax.device_put`` is
    asynchronous: issuing the transfer for batch N+1 while batch N
    computes hides the PCIe/host time. ``sharding`` may be a
    NamedSharding (the accelerate batch spec) so the prefetch lands
    pre-sharded on the mesh.

    ``global_rows``: the GLOBAL batch row count (e.g.
    ``strategy.global_batch_size``). On a multi-host sharding each
    process feeds its PROCESS-LOCAL rows; with ``global_rows`` known,
    ``put_global_batch`` validates that loudly — a caller feeding the
    global batch on every host would otherwise silently assemble a
    process_count-times larger batch of duplicated rows. 0 skips the
    check (single-process shardings are unaffected either way).

    ``put_fn``: overrides the transfer entirely (the shm path's hook).
    ``background=True`` runs the puts on a daemon thread feeding a
    bounded queue (depth ``prefetch``) — the shm coworker mode, where
    ring reads must not serialize with the training loop.
    """

    def __init__(self, iterable, sharding=None, prefetch: int = 2,
                 global_rows: int = 0,
                 put_fn: Optional[Callable[[Any], Any]] = None,
                 background: bool = False):
        if prefetch < 1:
            raise ValueError("prefetch must be >= 1")
        self._iterable = iterable
        self._sharding = sharding
        self._prefetch = prefetch
        self._global_rows = int(global_rows)
        self._put_fn = put_fn
        self._background = background
        # data-plane instruments (null handles when telemetry is off).
        # Queue depth is the prefetcher's health gauge: pinned at 0 the
        # producer can't keep up (input-bound); pinned at `prefetch`
        # the consumer is the bottleneck (healthy). The wait histograms
        # split the same story by direction.
        reg = get_registry()
        self._g_depth = reg.gauge(
            tm.DATA_PREFETCH_QUEUE_DEPTH,
            help="ready batches in the H2D prefetch queue")
        self._h_producer_wait = reg.histogram(
            tm.DATA_PRODUCER_WAIT_TIME,
            help="producer-side wait per batch (foreground: host time "
                 "producing + issuing the next transfer; background: "
                 "time blocked handing a ready batch to a full queue)")
        self._h_consumer_wait = reg.histogram(
            tm.DATA_CONSUMER_WAIT_TIME,
            help="consumer time blocked on an empty prefetch queue "
                 "(the input-bound direction)")
        # background-mode pump state, created ONCE on first iteration:
        # re-entering __iter__ (the executor's restart path) must resume
        # draining the same queue — a second pump racing the first over
        # one shared source iterator would drop and interleave batches
        self._bg_queue = None
        self._bg_done = object()
        self._bg_error: List[BaseException] = []
        self._bg_exhausted = False

    def _put(self, batch):
        if self._put_fn is not None:
            return self._put_fn(batch)
        import jax

        if self._sharding is not None:
            # multi-host shardings assemble from PROCESS-LOCAL rows;
            # fully-addressable ones (incl. every single-process case,
            # any sharding type) stay on plain device_put
            from dlrover_tpu.parallel.accelerate import put_global_batch

            return put_global_batch(
                batch, self._sharding, self._global_rows)
        return jax.device_put(batch)

    def __iter__(self):
        if self._background:
            yield from self._background_iter()
            return
        import collections

        queue = collections.deque()
        it = iter(self._iterable)
        try:
            for _ in range(self._prefetch):
                queue.append(self._put(next(it)))
        except StopIteration:
            pass
        while queue:
            out = queue.popleft()
            t0 = time.monotonic()
            try:
                queue.append(self._put(next(it)))
            except StopIteration:
                pass
            # foreground mode serializes production with the consumer:
            # this IS the consumer's per-batch input cost (device_put
            # itself is async — the wait is host-side batch assembly)
            self._h_producer_wait.observe(time.monotonic() - t0)
            self._g_depth.set(len(queue))
            yield out

    def _background_iter(self):
        """Puts run on ONE daemon thread feeding a bounded queue:
        ``prefetch`` transfers stay in flight while the consumer
        computes (the shm path's DevicePrefetcher behavior, now
        shared). The pump starts on first iteration and is shared by
        every subsequent ``__iter__`` — re-entry resumes mid-stream."""
        import queue as _queue
        import threading

        if self._bg_queue is None:
            self._bg_queue = _queue.Queue(maxsize=self._prefetch)

            def pump():
                try:
                    for b in self._iterable:
                        item = self._put(b)
                        t0 = time.monotonic()
                        self._bg_queue.put(item)
                        # time blocked on a FULL queue: the consumer is
                        # slower than the pipeline — the healthy shape
                        self._h_producer_wait.observe(
                            time.monotonic() - t0)
                except BaseException as e:  # surface in the consumer
                    logger.warning(
                        "prefetch pump failed (%s); re-raising in the "
                        "consumer", type(e).__name__,
                    )
                    self._bg_error.append(e)
                finally:
                    self._bg_queue.put(self._bg_done)

            threading.Thread(target=pump, daemon=True).start()
        while not self._bg_exhausted:
            t0 = time.monotonic()
            item = self._bg_queue.get()
            # time blocked on an EMPTY queue: the producer is the
            # bottleneck — the input-bound direction
            self._h_consumer_wait.observe(time.monotonic() - t0)
            self._g_depth.set(self._bg_queue.qsize())
            if item is self._bg_done:
                self._bg_exhausted = True
                break
            yield item
        if self._bg_error:
            raise self._bg_error[0]


def _default_collate(samples: List[Any]):
    import jax

    return jax.tree.map(lambda *xs: np.stack(xs), *samples)
