"""ServeEngine + ServeExecutor — continuous-batching decode on the
training runtime.

``ServeEngine`` mirrors ``trainer.elastic.ElasticTrainer`` knob for
knob: compiled serve programs (decode step + prefill chunk) live in a
topology+knob program cache, ``prewarm`` standby-compiles a survivor
world or a candidate knob set (executing one dummy step — jit is lazy),
and ``live_resize`` is the PR 5 drain → host-DRAM snapshot → rebuild →
``device_put``-reshard path applied to ``{"params", "cache"}`` instead
of a TrainState. A previously-seen serving topology is ZERO recompiles.

``ServeExecutor`` is the PR 3 async-window skeleton re-aimed at decode:
a fixed-shape slot batch (``serve_slots``), per-step admit/evict slot
swaps through index ops (no recompiles as the active set churns),
prefill chunked INTO the decode stream so a long prompt cannot stall
the batch, and a bounded in-flight window of decode dispatches whose
token materialization lags — greedy sampling happens ON DEVICE, so
step k+1 never waits on step k's host sync.
"""

from __future__ import annotations

import collections
import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.serving.kv_cache import (
    KVCacheSpec,
    copy_page_to_pool,
    copy_page_to_slot,
    init_kv_cache,
    init_prefix_pool,
    migrate_slots_host,
    serve_shardings,
)
from dlrover_tpu.serving.prefix_index import PrefixIndex
from dlrover_tpu.serving.spec_decode import NgramProposer
from dlrover_tpu.telemetry import (
    EventKind,
    SpanName,
    emit_event,
    get_registry,
    names as tm,
    span,
)
from dlrover_tpu.telemetry.metrics import LATENCY_BUCKETS
from dlrover_tpu.telemetry.trace_context import trace_scope

logger = get_logger("serving.engine")


@dataclass
class ServeProgram:
    """One compiled serving world: the jitted decode/prefill programs
    plus everything needed to lay state out on its mesh."""

    decode: Callable
    prefill: Callable
    mesh: Any
    shardings: Dict[str, Any]  # {"params": ..., "cache"[, "prefix"]: ...}
    spec: KVCacheSpec
    config: Any
    strategy: Any
    prefill_chunk: int
    # prefix-pool page copies (None when the pool is off): ONE compiled
    # program each, reused for every hit length — the indices are
    # traced scalars, so an H-page hit is H calls, zero recompiles
    admit_copy: Optional[Callable] = None
    publish_copy: Optional[Callable] = None
    # speculative decode: the batched K-position verify program and
    # the draft length K it was compiled for (None/0 = spec off). K is
    # STATIC per program — mixed per-slot draft lengths ride the
    # n_draft valid mask, so steady state never recompiles.
    verify: Optional[Callable] = None
    spec_k: int = 0

    def compiled_cache_size(self) -> int:
        total = 0
        for fn in (self.decode, self.prefill, self.admit_copy,
                   self.publish_copy, self.verify):
            if fn is None:
                continue
            inner = getattr(fn, "__wrapped__", fn)
            size = getattr(inner, "_cache_size", None)
            if callable(size):
                total += int(size())
        return total


def _resolve_knob(value, name: str, default):
    if value is not None:
        return value
    from dlrover_tpu.common.config import get_context

    return getattr(get_context(), name, default)


def _fit_prefill_chunk(requested: int, pool_depth: int) -> int:
    """The largest divisor of the pool depth <= the requested chunk.

    Chunk cursors advance in whole chunks (the last chunk is the only
    partial one), so start positions are multiples of C — with C | T
    every padded write window [start, start+C) fits the pool. Without
    this, a window crossing the pool end would be CLAMPED by
    ``dynamic_update_slice`` (e.g. T=48, C=32, a 40-token prompt:
    chunk 2's start=32 clamps to 16), silently shifting the chunk onto
    — and destroying — earlier pages while the attention mask still
    uses the unclamped positions."""
    want = max(1, min(int(requested), int(pool_depth)))
    for cand in range(want, 0, -1):
        if pool_depth % cand == 0:
            return cand
    return 1


class ServeEngine:
    """Owns (config, compiled serve programs, params, cache) across
    world changes and knob retunes — the serving twin of
    ``ElasticTrainer``."""

    def __init__(self, config, strategy=None, serve_slots: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 kv_precision: Optional[str] = None,
                 max_seq: int = 0, page_size: int = 16,
                 prefix_pool_pages: Optional[int] = None,
                 spec_draft_len: Optional[int] = None,
                 devices=None):
        from dlrover_tpu.parallel.strategy import Strategy
        from dlrover_tpu.serving.kv_cache import resolve_kv_precision

        self._config = config
        self._base_strategy = strategy or Strategy(rule_set="llama")
        self.serve_slots = max(1, int(_resolve_knob(
            serve_slots, "serve_slots", 8)))
        self.kv_precision = resolve_kv_precision(kv_precision)
        self._max_seq = int(max_seq or config.max_seq_len)
        self._page_size = int(page_size)
        import math as _math

        self._pool_depth = self._page_size * max(
            1, _math.ceil(self._max_seq / self._page_size))
        self.prefill_chunk = _fit_prefill_chunk(
            int(_resolve_knob(prefill_chunk, "serve_prefill_chunk",
                              32)), self._pool_depth)
        self.prefix_pool_pages = max(0, int(_resolve_knob(
            prefix_pool_pages, "serve_prefix_pool_pages", 0)))
        # serve_spec_enabled is the master switch: when off, the draft
        # length is pinned to 0 no matter what the knob/optimizer says
        # (the optimizer also refuses to enumerate K under the same
        # gate, but the engine enforces it locally)
        self.spec_enabled = bool(_resolve_knob(
            None, "serve_spec_enabled", True))
        self.spec_draft_len = (max(0, int(_resolve_knob(
            spec_draft_len, "serve_spec_draft_len", 0)))
            if self.spec_enabled else 0)
        self._devices = list(devices) if devices is not None else None
        self._initial_devices: Optional[int] = None
        self._programs: "collections.OrderedDict[str, ServeProgram]" = (
            collections.OrderedDict()
        )
        self._program_cache_cap = 4
        self.compile_count = 0
        self.program: Optional[ServeProgram] = None
        self.params = None
        self.cache = None
        # shared prefix pool: device pages + the host radix index that
        # owns their meaning (None while the knob is 0)
        self.pool = None
        self.prefix_index: Optional[PrefixIndex] = None

    # -- program cache -------------------------------------------------------

    def _spec(self) -> KVCacheSpec:
        return KVCacheSpec.from_model(
            self._config, num_slots=self.serve_slots,
            max_seq=self._max_seq, page_size=self._page_size,
            precision=self.kv_precision,
            prefix_pool_pages=self.prefix_pool_pages,
        )

    def _resolved_strategy(self, num_devices: int):
        return self._base_strategy.adjust_to_world(
            num_devices, prev_num_devices=self._initial_devices)

    def _program_key(self, devices: list, strategy) -> str:
        from dlrover_tpu.parallel.mesh import mesh_axes_key, topology_key

        return (
            topology_key(devices)
            + f"|slots={self.serve_slots}"
            + f"|pc={self.prefill_chunk}"
            + f"|mesh={mesh_axes_key(strategy.mesh)}"
            + f"|kvp={self.kv_precision}"
            + f"|ppp={self.prefix_pool_pages}"
            + f"|spec={self.spec_draft_len}"
        )

    def _build(self, devices: Optional[list]) -> ServeProgram:
        import jax

        actual = list(devices) if devices else jax.devices()
        num = len(actual)
        if self._initial_devices is None:
            self._initial_devices = num
        strategy = self._resolved_strategy(num)
        key = self._program_key(actual, strategy)
        reg = get_registry()
        cached = self._programs.get(key)
        if cached is not None:
            self._programs.move_to_end(key)
            reg.counter(
                tm.PROGRAM_CACHE_HITS,
                help="rebuilds served from the compiled-program cache "
                     "(zero recompiles)").inc()
            logger.info("serve program cache hit for %d devices", num)
            return cached
        reg.counter(tm.PROGRAM_CACHE_MISSES,
                    help="rebuilds that had to compile").inc()
        program = self._compile(actual, strategy)
        self.compile_count += 1
        self._programs[key] = program
        while len(self._programs) > self._program_cache_cap:
            self._programs.popitem(last=False)
        return program

    def _compile(self, devices: list, strategy) -> ServeProgram:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from dlrover_tpu.models import llama

        config = self._config
        spec = self._spec()
        mesh = strategy.mesh.build(devices)
        params_abstract = jax.eval_shape(
            lambda r: llama.init(r, config), jax.random.PRNGKey(0))
        shardings = serve_shardings(
            mesh, spec, params_abstract,
            base_rule_set=strategy.rule_set)
        replicated = NamedSharding(mesh, PartitionSpec())

        def decode_fn(params, cache, tokens, active):
            return llama.decode_step(params, cache, tokens, active,
                                     config, spec)

        def prefill_fn(params, cache, tokens, slot, start, n_valid):
            cache, last_logits = llama.prefill_chunk(
                params, cache, tokens, slot, start, n_valid, config,
                spec)
            # the final chunk's first generated token comes out ON
            # DEVICE: the executor folds it straight into the decode
            # batch, so admission never pays a blocking host argmax
            # sync over the vocab-sized logits
            first = jnp.argmax(last_logits).astype(jnp.int32)
            return cache, last_logits, first

        decode = jax.jit(
            decode_fn,
            in_shardings=(shardings["params"], shardings["cache"],
                          replicated, replicated),
            out_shardings=(replicated, replicated, shardings["cache"]),
            donate_argnums=(1,),
        )
        prefill = jax.jit(
            prefill_fn,
            in_shardings=(shardings["params"], shardings["cache"],
                          replicated, replicated, replicated,
                          replicated),
            out_shardings=(shardings["cache"], replicated,
                           replicated),
            donate_argnums=(1,),
        )
        verify = None
        spec_k = int(self.spec_draft_len)
        if spec_k > 0:
            def verify_fn(params, cache, tokens, active, n_draft):
                return llama.verify_step(params, cache, tokens,
                                         active, n_draft, config,
                                         spec)

            verify = jax.jit(
                verify_fn,
                in_shardings=(shardings["params"],
                              shardings["cache"], replicated,
                              replicated, replicated),
                out_shardings=(replicated, replicated, replicated,
                               shardings["cache"]),
                donate_argnums=(1,),
            )
        admit_copy = publish_copy = None
        if spec.prefix_pool_pages > 0:
            def admit_fn(cache, pool, slot, dst_start, src_page):
                return copy_page_to_slot(cache, pool, slot, dst_start,
                                         src_page, spec)

            def publish_fn(pool, cache, slot, src_start, dst_page):
                return copy_page_to_pool(pool, cache, slot, src_start,
                                         dst_page, spec)

            admit_copy = jax.jit(
                admit_fn,
                in_shardings=(shardings["cache"], shardings["prefix"],
                              replicated, replicated, replicated),
                out_shardings=shardings["cache"],
                donate_argnums=(0,),
            )
            publish_copy = jax.jit(
                publish_fn,
                in_shardings=(shardings["prefix"], shardings["cache"],
                              replicated, replicated, replicated),
                out_shardings=shardings["prefix"],
                donate_argnums=(0,),
            )
        logger.info(
            "serve program compiled: %d devices, slots=%d chunk=%d "
            "kv=%s spec_k=%d mesh=%s", len(devices), spec.num_slots,
            self.prefill_chunk, spec.precision, spec_k,
            dict(zip(mesh.axis_names, mesh.devices.shape)),
        )
        return ServeProgram(
            decode=decode, prefill=prefill, mesh=mesh,
            shardings=shardings, spec=spec, config=config,
            strategy=strategy, prefill_chunk=self.prefill_chunk,
            admit_copy=admit_copy, publish_copy=publish_copy,
            verify=verify, spec_k=spec_k,
        )

    # -- lifecycle -----------------------------------------------------------

    def prepare(self, params) -> None:
        """Compile for the current world and lay ``params`` + a fresh
        pool out on its mesh. ``params`` may be host numpy, a live
        training tree, or a promoted checkpoint's params."""
        import jax

        self.program = self._build(self._devices)
        self.params = jax.device_put(
            params, self.program.shardings["params"])
        self.cache = jax.device_put(
            _host_zero_cache(self.program.spec),
            self.program.shardings["cache"])
        self.reset_prefix()
        jax.block_until_ready(self.params)

    def fresh_cache(self):
        import jax

        return jax.device_put(
            _host_zero_cache(self.program.spec),
            self.program.shardings["cache"])

    def reset_prefix(self):
        """(Re)build an EMPTY prefix pool + index for the active
        program — prepare, a pool-knob retune, and test legs that
        want identical cold-pool starting lines all land here."""
        import jax

        spec = self.program.spec
        if spec.prefix_pool_pages <= 0:
            self.pool = None
            self.prefix_index = None
            return
        self.pool = jax.device_put(
            _host_zero_pool(spec), self.program.shardings["prefix"])
        self.prefix_index = PrefixIndex(
            spec.page_size, spec.prefix_pool_pages)

    # -- promotion (checkpoint -> serving, no cold start) --------------------

    def load_from_snapshot(self, snapshot) -> None:
        """Promote a live trainer's ``HostSnapshot`` (or any TrainState-
        shaped host tree) into the serving shardings: the train+serve
        colocation path — one ``device_put``, no storage round-trip, no
        cold start."""
        import jax

        tree = getattr(snapshot, "tree", snapshot)
        params = getattr(tree, "params", tree)
        if self.program is None:
            self.prepare(params)
            return
        self.params = jax.device_put(
            params, self.program.shardings["params"])
        jax.block_until_ready(self.params)

    def load_from_checkpoint(self, ckpt_dir: str, init_fn, optimizer,
                             grad_precision: str = "bf16"):
        """Promote a TRAINING checkpoint into the serving tier: the
        TrainState restores against the SERVING param shardings
        directly (Orbax reshard-on-load — the Universal-Checkpointing
        move), so a differently-sharded serving world starts warm.
        Returns the restored step (None when no checkpoint exists)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from dlrover_tpu.checkpoint import (
            ElasticCheckpointManager,
            abstract_like,
        )
        from dlrover_tpu.parallel.accelerate import TrainState

        if self.program is None:
            self.program = self._build(self._devices)

        def make_state(r):
            params = init_fn(r)
            residual = (jax.tree.map(jnp.zeros_like, params)
                        if grad_precision != "bf16" else None)
            return TrainState(
                step=jnp.zeros((), jnp.int32), params=params,
                opt_state=optimizer.init(params),
                wire_residual=residual,
            )

        abstract = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        repl = NamedSharding(self.program.mesh, PartitionSpec())
        sharding_tree = TrainState(
            step=repl,
            params=self.program.shardings["params"],
            opt_state=jax.tree.map(lambda _: repl, abstract.opt_state),
            wire_residual=(
                jax.tree.map(lambda _: repl, abstract.wire_residual)
                if abstract.wire_residual is not None else None),
        )
        target = abstract_like(abstract, sharding_tree)
        mgr = ElasticCheckpointManager(ckpt_dir)
        try:
            out = mgr.restore(target)
        finally:
            mgr.close()
        if out is None:
            return None
        self.params = out["state"].params
        if self.cache is None:
            self.cache = self.fresh_cache()
        logger.info("promoted training checkpoint step %d into the "
                    "serving tier", out["step"])
        return out["step"]

    # -- elasticity ----------------------------------------------------------

    def prewarm(self, devices=None, serve_slots: Optional[int] = None,
                prefill_chunk: Optional[int] = None,
                prefix_pool_pages: Optional[int] = None,
                spec_draft_len: Optional[int] = None,
                execute: bool = True) -> bool:
        """Standby-compile the program for a topology or knob set we
        may swap to, executing one dummy decode step AND one dummy
        prefill chunk (plus one admit/publish page copy when the
        prefix pool is on — jit is lazy) — so the live resize / retune
        that follows pays ZERO recompiles. Does not switch the active
        program. Returns True when a compile happened."""
        import jax
        import jax.numpy as jnp

        prev_slots, prev_chunk = self.serve_slots, self.prefill_chunk
        prev_ppp = self.prefix_pool_pages
        prev_spec_k = self.spec_draft_len
        if serve_slots is not None:
            self.serve_slots = max(1, int(serve_slots))
        if prefill_chunk is not None:
            self.prefill_chunk = _fit_prefill_chunk(
                int(prefill_chunk), self._pool_depth)
        if prefix_pool_pages is not None:
            self.prefix_pool_pages = max(0, int(prefix_pool_pages))
        if spec_draft_len is not None:
            self.spec_draft_len = (max(0, int(spec_draft_len))
                                   if self.spec_enabled else 0)
        try:
            before = self.compile_count
            program = self._build(
                list(devices) if devices is not None else self._devices)
            compiled = self.compile_count > before
            if execute and compiled and self.params is not None:
                params = jax.device_put(
                    self.params, program.shardings["params"])
                cache = jax.device_put(
                    _host_zero_cache(program.spec),
                    program.shardings["cache"])
                s = program.spec.num_slots
                tokens = jnp.zeros((s,), jnp.int32)
                active = jnp.zeros((s,), bool)
                _nt, _lg, cache = program.decode(
                    params, cache, tokens, active)
                chunk = jnp.zeros((program.prefill_chunk,), jnp.int32)
                cache, _ll, _ft = program.prefill(
                    params, cache, chunk, jnp.int32(0), jnp.int32(0),
                    jnp.int32(1))
                if program.verify is not None:
                    draft = jnp.zeros((s, program.spec_k + 1),
                                      jnp.int32)
                    n_draft = jnp.zeros((s,), jnp.int32)
                    _g, _a, _nt, cache = program.verify(
                        params, cache, draft, active, n_draft)
                if program.admit_copy is not None:
                    pool = jax.device_put(
                        _host_zero_pool(program.spec),
                        program.shardings["prefix"])
                    cache = program.admit_copy(
                        cache, pool, jnp.int32(0), jnp.int32(0),
                        jnp.int32(0))
                    pool = program.publish_copy(
                        pool, cache, jnp.int32(0), jnp.int32(0),
                        jnp.int32(0))
                    jax.block_until_ready(pool)
                jax.block_until_ready(cache)
                logger.info("prewarmed standby serve program (%d "
                            "devices, slots=%d)", len(
                                program.mesh.devices.flatten()), s)
        finally:
            self.serve_slots = prev_slots
            self.prefill_chunk = prev_chunk
            self.prefix_pool_pages = prev_ppp
            self.spec_draft_len = prev_spec_k
        return compiled

    def snapshot(self):
        """Host-DRAM copy of ``{"params", "cache"}`` — the resize
        source. In-flight slots' KV pages ride it to the survivor
        world, which is what lets leased requests continue instead of
        restarting from their prompts."""
        from dlrover_tpu.checkpoint import HostSnapshot

        tree = {"params": self.params, "cache": self.cache}
        if self.pool is not None:
            # the prefix pool rides the resize with the slot pages: the
            # host index stays valid (it names pool page ids, and every
            # page's bytes survive the reshard), so pinned in-flight
            # hits and future matches carry straight across
            tree["prefix"] = self.pool
        return HostSnapshot.take(tree, kind="serving")

    def live_resize(self, devices=None, snapshot=None,
                    reason: str = "") -> int:
        """Drain (caller) → snapshot → rebuild (program cache; zero
        recompiles when prewarmed) → reshard params AND live KV pages
        onto the survivor world. Returns the number of programs
        compiled (0 = the prewarmed fast path)."""
        import jax

        old_n = (self.program.mesh.devices.size
                 if self.program is not None else 0)
        t0 = time.monotonic()
        emit_event(EventKind.SERVE_RESIZE_BEGIN, world_from=old_n,
                   reason=reason)
        with span(SpanName.LIVE_RESHARD, world_from=old_n):
            if snapshot is None:
                snapshot = self.snapshot()
            self._devices = list(devices) if devices is not None else None
            compiles_before = self.compile_count
            self.program = self._build(self._devices)
            targets = {
                "params": self.program.shardings["params"],
                "cache": self.program.shardings["cache"],
            }
            snap_tree = getattr(snapshot, "tree", None) or {}
            carry_pool = ("prefix" in snap_tree
                          and "prefix" in self.program.shardings)
            if carry_pool:
                targets["prefix"] = self.program.shardings["prefix"]
            state = snapshot.restore(targets)
            self.params, self.cache = state["params"], state["cache"]
            if carry_pool:
                self.pool = state["prefix"]
            elif self.program.spec.prefix_pool_pages > 0:
                # a snapshot without pool pages (e.g. taken before the
                # knob turned on) cannot carry the index: rebuild clean
                self.reset_prefix()
            else:
                self.pool = None
                self.prefix_index = None
            jax.block_until_ready(self.cache)
        n = self.program.mesh.devices.size
        recompiled = self.compile_count - compiles_before
        seconds = time.monotonic() - t0
        reg = get_registry()
        reg.counter(
            tm.SERVE_RESIZES,
            help="serving worlds resized live (no dropped requests)"
        ).inc()
        reg.histogram(
            tm.SERVE_RESIZE_TIME,
            help="drain -> snapshot -> reshard wall seconds (serving)",
        ).observe(seconds)
        emit_event(EventKind.SERVE_RESIZE_DONE, world_from=old_n,
                   world_to=int(n), reshard_seconds=round(seconds, 3),
                   recompiled=recompiled)
        logger.info("serve resize %d -> %d devices in %.2fs (%s)",
                    old_n, n, seconds,
                    "cache hit" if not recompiled else "recompiled")
        return recompiled

    def retune(self, serve_slots: Optional[int] = None,
               prefill_chunk: Optional[int] = None,
               prefix_pool_pages: Optional[int] = None,
               spec_draft_len: Optional[int] = None,
               slot_map: Optional[Dict[int, int]] = None) -> int:
        """Apply optimizer-chosen serve knobs on the current world
        through the program cache (drain first — the caller owns the
        window). A slot-count change repacks live slots host-side via
        ``slot_map`` (old -> new); prefill_chunk swaps are pure program
        swaps. Failure restores the previous knobs and re-raises.

        Prefix-pool discipline: a POOL-SIZE change rebuilds the pool
        empty (page ids mean nothing across capacities) and a
        PREFILL-CHUNK change flushes the index — published page bytes
        depend on the chunk windows that computed them, so pages
        published under the old grain would break the bitwise-
        continuation oracle under the new one. A slot-only retune
        carries pool and index untouched (the pool has no slot
        dimension). Flush/rebuild cannot dangle refcounts: in-flight
        handles hold the orphaned nodes and release into them."""
        import jax

        prev_slots, prev_chunk = self.serve_slots, self.prefill_chunk
        prev_ppp = self.prefix_pool_pages
        prev_spec_k = self.spec_draft_len
        prev_program = self.program
        old_spec = self.program.spec if self.program else None
        try:
            if serve_slots is not None:
                self.serve_slots = max(1, int(serve_slots))
            if prefill_chunk is not None:
                self.prefill_chunk = _fit_prefill_chunk(
                    int(prefill_chunk), self._pool_depth)
            if prefix_pool_pages is not None:
                self.prefix_pool_pages = max(0, int(prefix_pool_pages))
            if spec_draft_len is not None:
                # a K-only retune is the cheapest knob in the family:
                # K lives in the PROGRAM (tokens shape), not the
                # KVCacheSpec, so the pure-swap fast path below
                # applies — live params and pages stay put
                self.spec_draft_len = (max(0, int(spec_draft_len))
                                       if self.spec_enabled else 0)
            compiles_before = self.compile_count
            new_program = self._build(self._devices)
            chunk_changed = (prev_program is not None
                             and new_program.prefill_chunk
                             != prev_program.prefill_chunk)
            if old_spec is not None and new_program.spec == old_spec:
                # a pure PROGRAM swap (chunk-only retune): the pool
                # spec, shardings and devices are unchanged, so the
                # live params and KV pages are already laid out for
                # the new program — no host round-trip of the whole
                # state inside the serving drain
                self.program = new_program
                if chunk_changed and self.prefix_index is not None:
                    self.prefix_index.flush()
                return self.compile_count - compiles_before
            host = jax.device_get(
                {"params": self.params, "cache": self.cache})
            self.program = new_program
            cache_host = host["cache"]
            if old_spec is not None and \
                    old_spec.num_slots != self.program.spec.num_slots:
                cache_host = migrate_slots_host(
                    cache_host, old_spec, self.program.spec,
                    slot_map or {})
            self.params = jax.device_put(
                host["params"], self.program.shardings["params"])
            self.cache = jax.device_put(
                cache_host, self.program.shardings["cache"])
            jax.block_until_ready(self.cache)
            if self.prefix_pool_pages != prev_ppp:
                self.reset_prefix()
            elif chunk_changed and self.prefix_index is not None:
                self.prefix_index.flush()
            return self.compile_count - compiles_before
        except Exception:
            self.serve_slots = prev_slots
            self.prefill_chunk = prev_chunk
            self.prefix_pool_pages = prev_ppp
            self.spec_draft_len = prev_spec_k
            # the ACTIVE program too, not just the knobs: _build may
            # have swapped it before the device_put failed (OOM on a
            # wider pool) — leaving the new-spec program over the
            # old-shape cache would shape-mismatch every later call
            # and wipe the executor's slot bookkeeping at the next
            # _ensure_prepared
            self.program = prev_program
            raise

    # -- shared prefix pool (radix-indexed KV reuse, copy-on-admit) ----------

    def prefix_enabled(self) -> bool:
        return (self.program is not None
                and self.program.spec.prefix_pool_pages > 0
                and self.pool is not None
                and self.prefix_index is not None)

    def _prefix_align(self) -> int:
        """Matched prefixes round DOWN to this token grain —
        lcm(page_size, prefill_chunk) — so the unmatched tail's chunk
        windows start at the SAME multiples of the chunk a full
        prefill uses: the reused continuation is then the same
        compiled invocations over the same bytes, which is what makes
        it bitwise on f32/bf16 pools (and keeps every padded write
        window inside the pool — the dynamic_update_slice clamp
        hazard ``_fit_prefill_chunk`` documents cannot arise)."""
        import math as _math

        pg = self.program.spec.page_size
        c = self.program.prefill_chunk
        return pg * c // _math.gcd(pg, c)

    def prefix_match(self, prompt: List[int]):
        """Walk the index for the longest usable prefix of ``prompt``.
        Returns ``(matched_tokens, handle)`` with the matched chain
        PINNED, or ``(0, None)``. The match is capped strictly below
        ``len(prompt)`` — a final prefill chunk must always run (its
        last logits seed the first generated token)."""
        if not self.prefix_enabled():
            return 0, None
        align = self._prefix_align()
        pg = self.program.spec.page_size
        cap_tokens = ((len(prompt) - 1) // align) * align
        if cap_tokens <= 0:
            return 0, None
        handle = self.prefix_index.match(
            prompt, max_pages=cap_tokens // pg,
            align_pages=align // pg)
        if handle is None:
            return 0, None
        return handle.tokens, handle

    def prefix_admit(self, slot: int, handle) -> None:
        """Copy the matched pool pages into the slot's leading rows —
        H pages = H calls of ONE compiled copy program."""
        import jax.numpy as jnp

        program = self.program
        pg = program.spec.page_size
        for i, page_id in enumerate(handle.pages):
            self.cache = program.admit_copy(
                self.cache, self.pool, jnp.int32(slot),
                jnp.int32(i * pg), jnp.int32(page_id))

    def prefix_publish(self, slot: int, prompt: List[int]):
        """Index + copy the full pages of a COMPLETED prefill into the
        pool (pages already present are skipped; a full pool skips the
        rest — logged/counted, never raised). Returns
        ``(pages_published, pages_evicted)``."""
        import jax.numpy as jnp

        if not self.prefix_enabled():
            return 0, 0
        program = self.program
        pg = program.spec.page_size
        evict_before = self.prefix_index.evictions
        new_pages = self.prefix_index.publish(prompt)
        for idx, page_id in new_pages:
            self.pool = program.publish_copy(
                self.pool, self.cache, jnp.int32(slot),
                jnp.int32(idx * pg), jnp.int32(page_id))
        return (len(new_pages),
                self.prefix_index.evictions - evict_before)

    def prefix_release(self, handle) -> None:
        """Unpin a hit's pages (idempotent; survives flush/rebuild)."""
        if self.prefix_index is not None:
            self.prefix_index.release(handle)
        elif handle is not None:
            handle.released = True

    def prefix_stats(self) -> Dict[str, Any]:
        """Cumulative pool counters + current occupancy (empty when
        the pool is off) — the SERVE_END summary and the hit-rate the
        config report feeds the optimizer's pricing."""
        if self.prefix_index is None:
            return {}
        out = dict(self.prefix_index.stats())
        out["pool_bytes"] = self.program.spec.prefix_pool_bytes()
        out["used_bytes"] = (out["used_pages"]
                             * self.program.spec.prefix_page_bytes())
        return out


def _host_zero_cache(spec: KVCacheSpec):
    """Zero-filled host cache (numpy — no device allocation until the
    device_put lays it out shard by shard)."""
    import jax

    return jax.tree.map(
        lambda a: np.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: init_kv_cache(spec)),
    )


def _host_zero_pool(spec: KVCacheSpec):
    """Zero-filled host prefix pool (the ``_host_zero_cache`` twin)."""
    import jax

    return jax.tree.map(
        lambda a: np.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: init_prefix_pool(spec)),
    )


# -- the continuous-batching executor ----------------------------------------

# per-process executor sequence: SERVE_START/END events carry it so
# the forensic slot-ledger derivation can tell "one executor's
# cumulative ledger reported twice" from "two executors' ledgers"
_serve_seq = itertools.count(1)


@dataclass
class ServeRequestState:
    """Host-side bookkeeping for one leased request in a slot."""

    request_id: str
    prompt: List[int]
    max_new_tokens: int
    eos_id: int = -1
    cursor: int = 0            # prompt tokens prefilled so far
    generated: List[int] = field(default_factory=list)
    t_admit: float = 0.0
    t_first_token: Optional[float] = None
    # the per-request trace id minted at Router.submit (or locally for
    # router-less submissions): every lifecycle event this worker
    # emits for the request carries it
    trace_id: str = ""
    # local-queue submissions stamp their enqueue time so the worker
    # can report queue-wait without a router (local mode)
    t_submit: Optional[float] = None
    # prompt tokens whose KV pages came from the shared prefix pool
    # (copy-on-admit) instead of prefill, and the pin over those pages
    # — held admit -> completion, released idempotently
    prefix_hit_tokens: int = 0
    prefix_handle: Any = None
    # speculative decode: the per-request draft proposer (host-only
    # suffix index — it moves with the state object across slot
    # remaps) and the request's drafted/accepted ledger columns.
    # drafted - accepted = wasted by construction, checked end to end.
    draft_state: Any = None
    spec_drafted_tokens: int = 0
    spec_accepted_tokens: int = 0


@dataclass
class _InflightDecode:
    tokens: Any                       # device [S] next-token array
    owners: Dict[int, str]            # slot -> request_id at dispatch
    # slots whose FIRST token (the on-device prefill argmax) rides
    # this entry: materialization appends it, stamps TTFT and runs
    # finish detection — the host sync admission used to pay moved
    # behind the window
    firsts: Optional[Dict[int, str]] = None


class ServeExecutor:
    """Continuous batching over a fixed slot batch.

    One loop iteration: (boundary work: plans/resizes/admission) → at
    most one prefill chunk per admitting slot → ONE decode step for the
    whole batch → lagged materialization of the oldest in-flight decode
    (the PR 3 window, ``serve_window``). Greedy tokens feed back on
    device; the host only ever reads tokens that are already
    ``serve_window`` steps old, so Python/RPC overhead never drains the
    device queue.

    ``admission="static"`` is the comparison mode the tests pair
    against: a full batch admits together and the next
    batch waits for the LAST request of the current one — the classic
    static-batching tail every mixed-length workload pays.
    """

    def __init__(self, engine: ServeEngine, router_client=None,
                 admission: str = "continuous",
                 serve_window: Optional[int] = None,
                 eos_id: int = -1, max_new_default: int = 16,
                 plan_poll_secs: Optional[float] = None,
                 registry=None, report_hook=None,
                 spec_proposer: Optional[Callable] = None):
        from dlrover_tpu.common.config import get_context

        ctx = get_context()
        self._engine = engine
        self._client = router_client
        self._admission = admission
        self._window_cap = max(0, int(_resolve_knob(
            serve_window, "serve_window", 2)))
        self._eos_default = int(eos_id)
        self._max_new_default = int(max_new_default)
        self._plan_poll = float(
            plan_poll_secs if plan_poll_secs is not None
            else getattr(ctx, "plan_poll_secs", 30.0))
        self._last_plan_poll = 0.0
        self._seen_plan = ""
        self._last_touch = 0.0
        self._local_queue: "collections.deque" = collections.deque()
        self._window: "collections.deque[_InflightDecode]" = (
            collections.deque())
        self._slots: List[Optional[ServeRequestState]] = []
        self._active_host: List[bool] = []
        self._tokens = None
        self._active = None
        self._resize_devices = None
        self._resize_requested = False
        self._resize_trace_id = ""
        self._retune_request: Optional[Dict[str, Any]] = None
        self.completed: List[Dict[str, Any]] = []
        self.decode_steps = 0
        self._local_id_seq = 0
        # speculative decode: a factory producing one proposer PER
        # REQUEST (tests inject deterministic 0%/100%/alternating
        # proposers through it; default is the n-gram prompt-lookup
        # index). Worker-lifetime drafted/accepted totals feed the
        # acceptance-rate gauge and the config report's observed rate.
        self._spec_proposer_factory = spec_proposer
        self._spec_drafted_total = 0
        self._spec_accepted_total = 0
        self._serve_seq = next(_serve_seq)
        # slot-time ledger: every slot-second of the serve loop is
        # charged to exactly ONE class (decode / prefill /
        # admitted_idle / vacant / resize_frozen), so the classes sum
        # to slots x wall by construction — the serving analog of the
        # goodput partition. Accumulated host-side (plain float adds;
        # no registry on this path) and emitted on SERVE_END.
        self._ledger: Dict[str, float] = {
            k: 0.0 for k in ("decode", "prefill", "admitted_idle",
                             "vacant", "resize_frozen")}
        self._ledger_mark: Optional[float] = None
        self._slot_seconds = 0.0
        self._serve_wall = 0.0
        # the ledger is observability, so it pays inside the ≤5%
        # overhead gate: off with the rest of telemetry (resolved at
        # construction, the get_registry() discipline)
        self._ledger_enabled = bool(
            getattr(ctx, "telemetry_enabled", True))
        # a test may pass a private registry to simulate several serve
        # nodes in one process (the NodeRuntimeReportHook discipline)
        reg = registry if registry is not None else get_registry()
        self._c_tokens = reg.counter(
            tm.SERVE_TOKENS, help="tokens generated by this worker")
        self._c_decode = reg.counter(
            tm.SERVE_DECODE_STEPS, help="batched decode steps dispatched")
        self._c_prefill = reg.counter(
            tm.SERVE_PREFILL_CHUNKS, help="prefill chunks dispatched")
        self._c_admitted = reg.counter(
            tm.SERVE_ADMISSIONS, help="requests admitted into slots")
        self._g_occupancy = reg.gauge(
            tm.SERVE_SLOT_OCCUPANCY,
            help="slots holding a live request, after admission")
        self._h_step = reg.histogram(
            tm.SERVE_STEP_TIME, buckets=LATENCY_BUCKETS,
            help="per-decode-step wall seconds")
        self._h_prefill_e2e = reg.histogram(
            tm.SERVE_PREFILL_TIME, buckets=LATENCY_BUCKETS,
            help="admit -> prompt fully prefilled wall seconds")
        # shared prefix pool counters/gauges (flat at zero while the
        # pool knob is off — the registry costs nothing for them)
        self._c_phits = reg.counter(
            tm.SERVE_PREFIX_HITS,
            help="admissions whose leading pages came from the pool")
        self._c_pmisses = reg.counter(
            tm.SERVE_PREFIX_MISSES,
            help="admissions that walked the index and found nothing")
        self._c_pevict = reg.counter(
            tm.SERVE_PREFIX_EVICTIONS,
            help="pool pages LRU-evicted to make room for a publish")
        self._c_psaved = reg.counter(
            tm.SERVE_PREFIX_SAVED_TOKENS,
            help="prefill tokens skipped via copy-on-admit")
        self._g_pool_used = reg.gauge(
            tm.SERVE_PREFIX_POOL_USED_PAGES,
            help="prefix-pool pages currently indexed")
        self._g_pool_bytes = reg.gauge(
            tm.SERVE_PREFIX_POOL_BYTES,
            help="prefix-pool device residency (the HBM-gate charge)")
        # speculative-decode ledger counters (flat at zero while K=0):
        # drafted = accepted + wasted at every grain — per request,
        # per worker, per router job
        self._c_spec_steps = reg.counter(
            tm.SERVE_SPEC_VERIFY_STEPS,
            help="batched multi-token verify steps dispatched")
        self._c_spec_drafted = reg.counter(
            tm.SERVE_SPEC_DRAFTED,
            help="draft tokens proposed into verify steps")
        self._c_spec_accepted = reg.counter(
            tm.SERVE_SPEC_ACCEPTED,
            help="draft tokens accepted (matched the greedy argmax)")
        self._c_spec_wasted = reg.counter(
            tm.SERVE_SPEC_WASTED,
            help="draft tokens rejected by verify (computed, unused)")
        self._g_spec_rate = reg.gauge(
            tm.SERVE_SPEC_ACCEPT_RATE,
            help="accepted/drafted over this worker's lifetime "
                 "(-1 until the first draft)")
        self._g_spec_rate.set(-1.0)
        # SLO-plane node reporting: serve workers ride the SAME
        # NodeRuntimeReport path training workers do, so the master's
        # /metrics carries {node=} serving gauges and the straggler
        # detector judges slow decode workers. Auto-wired when the
        # client can carry it (the executor's NodeRuntimeReportHook
        # discipline); pass an explicit hook to control cadence.
        if report_hook is None and router_client is not None and \
                hasattr(router_client, "report_node_runtime"):
            from dlrover_tpu.serving.slo import ServeRuntimeReportHook

            report_hook = ServeRuntimeReportHook(
                router_client, registry=reg)
        self._report_hook = report_hook or None

    # -- local submission (router-less mode / tests) -------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 0,
               request_id: str = "", eos_id: Optional[int] = None):
        """Enqueue a request on the worker-local queue (no router)."""
        from dlrover_tpu.serving.router import new_request_trace_id

        # a monotonic sequence, never derived from queue/completed
        # lengths: those regress when a request is admitted-but-
        # unfinished, and a colliding id breaks the window's owner
        # guard (two live slots claiming one identity)
        self._local_id_seq += 1
        rid = request_id or f"local-{self._local_id_seq}"
        self._local_queue.append({
            "request_id": rid,
            "prompt": [int(t) for t in prompt],
            "max_new_tokens": int(max_new_tokens
                                  or self._max_new_default),
            "eos_id": (self._eos_default if eos_id is None
                       else int(eos_id)),
            "trace_id": new_request_trace_id(),
            "submit_ts": time.monotonic(),
        })
        return rid

    # -- elasticity hooks ----------------------------------------------------

    def request_resize(self, devices=None, trace_id: str = ""):
        """``trace_id`` threads the incident that caused the resize
        (an SLO scale proposal) onto the SERVE_RESIZE_* events."""
        self._resize_devices = (list(devices)
                                if devices is not None else None)
        self._resize_trace_id = str(trace_id or "")
        self._resize_requested = True

    def request_retune(self, serve_slots: Optional[int] = None,
                       prefill_chunk: Optional[int] = None,
                       prefix_pool_pages: Optional[int] = None,
                       spec_draft_len: Optional[int] = None,
                       plan_id: str = "", prewarm: bool = False):
        self._retune_request = {
            "serve_slots": serve_slots,
            "prefill_chunk": prefill_chunk,
            "prefix_pool_pages": prefix_pool_pages,
            "spec_draft_len": spec_draft_len,
            "plan_id": plan_id,
            "prewarm": bool(prewarm),
        }

    # -- loop ----------------------------------------------------------------

    def _ensure_prepared(self):
        import jax.numpy as jnp

        if self._engine.program is None:
            raise RuntimeError("engine.prepare(params) first")
        s = self._engine.program.spec.num_slots
        if len(self._slots) != s:
            if any(r is not None for r in self._slots):
                # the slot width changed UNDER live requests — a
                # direct engine.retune() between serve() calls.
                # Silently rebuilding would drop those requests (and
                # dangle their router leases); the supported path is
                # request_retune, which repacks them.
                raise RuntimeError(
                    "engine slot width changed with live requests; "
                    "use ServeExecutor.request_retune")
            self._slots = [None] * s
            self._active_host = [False] * s
        if self._tokens is None or int(self._tokens.shape[0]) != s:
            self._tokens = jnp.zeros((s,), jnp.int32)
            self._active = jnp.asarray(self._active_host)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is None]

    def _lease(self, n: int) -> List[Dict[str, Any]]:
        out = []
        while n > 0 and self._local_queue:
            out.append(self._local_queue.popleft())
            n -= 1
        if n > 0 and self._client is not None:
            try:
                out.extend(self._client.serve_lease(max_requests=n))
            except Exception:  # noqa: BLE001 — a dead master must not
                # kill serving; the worker drains its admitted slots
                logger.debug("serve lease failed", exc_info=True)
        return out

    def _admit(self):
        free = self._free_slots()
        if not free:
            return
        if self._admission == "static" and len(free) != len(self._slots):
            # static batching: the next batch waits for the WHOLE
            # current batch — the tail continuous batching removes
            return
        leases = self._lease(len(free))
        max_seq = self._engine.program.spec.max_seq
        for req in leases:
            slot = free.pop(0)
            state = ServeRequestState(
                request_id=str(req["request_id"]),
                prompt=[int(t) for t in req["prompt"]],
                max_new_tokens=int(req.get("max_new_tokens")
                                   or self._max_new_default),
                eos_id=int(req.get("eos_id", self._eos_default)),
                trace_id=str(req.get("trace_id", "") or ""),
                t_submit=req.get("submit_ts"),
                t_admit=time.monotonic(),
            )
            if len(state.prompt) + state.max_new_tokens > max_seq:
                # the pool cannot hold this request: evict loudly (a
                # failure-class edge — carries its error code) and
                # complete it as errored so the router never counts it
                # dropped-on-the-floor
                emit_event(
                    EventKind.SERVE_REQUEST_EVICTED,
                    error_code="SERVE_REQUEST_EVICTED",
                    trace_id=state.trace_id,
                    request_id=state.request_id,
                    prompt_tokens=len(state.prompt),
                    max_seq=max_seq,
                )
                self._complete(state, error_code="SERVE_REQUEST_EVICTED")
                continue
            matched, handle = self._engine.prefix_match(state.prompt)
            if handle is not None:
                # copy-on-admit: matched pages land in the slot's
                # leading rows NOW, so the prefill tick below starts at
                # the unmatched tail — same chunk windows a full
                # prefill would run from that cursor (bitwise)
                self._engine.prefix_admit(slot, handle)
                state.cursor = matched
                state.prefix_hit_tokens = matched
                state.prefix_handle = handle
                self._c_phits.inc()
                self._c_psaved.inc(matched)
                emit_event(
                    EventKind.SERVE_PREFIX_HIT,
                    trace_id=state.trace_id,
                    request_id=state.request_id, slot=slot,
                    hit_tokens=matched,
                    prompt_tokens=len(state.prompt),
                )
            elif self._engine.prefix_enabled():
                self._c_pmisses.inc()
            self._slots[slot] = state
            self._c_admitted.inc()
            if not free:
                break
        self._g_occupancy.set(
            sum(1 for r in self._slots if r is not None))
        if self._engine.prefix_enabled():
            stats = self._engine.prefix_stats()
            self._g_pool_used.set(stats.get("used_pages", 0))
            self._g_pool_bytes.set(stats.get("used_bytes", 0))

    def _prefill_tick(self):
        """Dispatch at most ONE chunk per admitting slot, so prefill
        interleaves with the decode stream instead of stalling it."""
        import jax.numpy as jnp

        program = self._engine.program
        c = program.prefill_chunk
        firsts: Dict[int, str] = {}
        for slot, state in enumerate(self._slots):
            if state is None or state.cursor >= len(state.prompt) \
                    or self._active_host[slot]:
                continue
            chunk = state.prompt[state.cursor:state.cursor + c]
            n_valid = len(chunk)
            padded = np.zeros((c,), np.int32)
            padded[:n_valid] = chunk
            with span(SpanName.SERVE_PREFILL, slot=slot):
                self._engine.cache, _last_logits, first_tok = (
                    program.prefill(
                        self._engine.params, self._engine.cache,
                        jnp.asarray(padded), jnp.int32(slot),
                        jnp.int32(state.cursor), jnp.int32(n_valid)))
            self._c_prefill.inc()
            state.cursor += n_valid
            emit_event(
                EventKind.SERVE_PREFILL_CHUNK,
                trace_id=state.trace_id, request_id=state.request_id,
                slot=slot, cursor=state.cursor,
                prompt_tokens=len(state.prompt),
            )
            if state.cursor >= len(state.prompt):
                # a completed prefill publishes its full pages into
                # the prefix pool BEFORE the decode stream can touch
                # the slot (decode appends rows past the prompt; the
                # published pages must be pure prefill output)
                published, evicted = self._engine.prefix_publish(
                    slot, state.prompt)
                if evicted:
                    self._c_pevict.inc(evicted)
                    emit_event(
                        EventKind.SERVE_PREFIX_EVICTED,
                        trace_id=state.trace_id,
                        request_id=state.request_id,
                        pages=evicted,
                    )
                # final chunk: the first token stays ON DEVICE — it
                # lands in the slot's decode-batch row and a firsts
                # window entry carries its identity, so admission no
                # longer blocks on a host argmax sync. TTFT/finish
                # detection happen at materialization (the same lag
                # eos detection already has in the decode stream).
                self._tokens = self._tokens.at[slot].set(first_tok)
                firsts[slot] = state.request_id
                self._active_host[slot] = True
                self._active = jnp.asarray(self._active_host)
        if firsts:
            self._window.append(_InflightDecode(
                tokens=self._tokens, owners={}, firsts=firsts))

    def _finished(self, state: ServeRequestState) -> bool:
        if len(state.generated) >= state.max_new_tokens:
            return True
        return (state.eos_id >= 0 and state.generated
                and state.generated[-1] == state.eos_id)

    def _complete(self, state: ServeRequestState, error_code: str = ""):
        now = time.monotonic()
        # the pin over the hit's pool pages ends with the request
        # (idempotent — a pool flush/rebuild in between is harmless)
        if state.prefix_handle is not None:
            self._engine.prefix_release(state.prefix_handle)
            state.prefix_handle = None
        record = {
            "request_id": state.request_id,
            "tokens": list(state.generated),
            "ttft_s": (round(state.t_first_token - state.t_admit, 6)
                       if state.t_first_token else None),
            "e2e_s": round(now - state.t_admit, 6),
            "error_code": error_code,
            "prefix_hit_tokens": int(state.prefix_hit_tokens),
            "spec_drafted_tokens": int(state.spec_drafted_tokens),
            "spec_accepted_tokens": int(state.spec_accepted_tokens),
        }
        emit_event(
            EventKind.SERVE_REQUEST_DONE,
            trace_id=state.trace_id, request_id=state.request_id,
            tokens=len(state.generated), ttft_s=record["ttft_s"],
            e2e_s=record["e2e_s"],
            done_error_code=error_code or None,
        )
        # local-queue submissions see their queue wait here (the
        # router measures its own at lease time)
        if state.t_submit is not None:
            record["queue_wait_s"] = round(
                state.t_admit - state.t_submit, 6)
        self.completed.append(record)
        self._c_tokens.inc(len(state.generated))
        if self._client is not None:
            wire = {k: v for k, v in record.items()
                    if k != "queue_wait_s"}
            try:
                # the request's trace id rides the gRPC metadata
                # channel, so the router's ingress-side events (the
                # completion record) join the request's lane
                if state.trace_id:
                    with trace_scope(state.trace_id):
                        self._client.serve_complete(**wire)
                else:
                    self._client.serve_complete(**wire)
            except Exception:  # noqa: BLE001 — the router re-leases on
                # lease timeout; a lost completion is re-served, never
                # silently dropped
                logger.warning("serve completion report failed",
                               exc_info=True)

    def _retire(self, slot: int):
        import jax.numpy as jnp

        state = self._slots[slot]
        self._slots[slot] = None
        self._active_host[slot] = False
        self._active = jnp.asarray(self._active_host)
        self._complete(state)

    # -- slot-time ledger ----------------------------------------------------

    def _classify(self) -> List[str]:
        """Per-slot ledger class under the CURRENT host state."""
        out = []
        for i, state in enumerate(self._slots):
            if state is None:
                out.append("vacant")
            elif self._active_host[i]:
                out.append("decode")
            elif state.cursor < len(state.prompt):
                out.append("prefill")
            else:
                # admitted, prompt prefilled, but not decoding — the
                # finish-detection lag / pre-activation gap
                out.append("admitted_idle")
        return out

    def _charge_slots(self, now: float, override: Optional[str] = None,
                      classes: Optional[List[str]] = None):
        """Charge the wall time since the previous mark to the ledger:
        ``dt`` per slot, each slot to exactly one class. ``override``
        charges every slot (the resize/retune freeze); ``classes`` is
        a pre-captured per-slot classification (the prefill interval
        classifies by the state that held DURING it, not the state the
        tick left behind). Classes sum to ∫slots·dt by construction."""
        if not self._ledger_enabled:
            return
        mark = self._ledger_mark
        self._ledger_mark = now
        if mark is None:
            return
        dt = now - mark
        if dt <= 0 or not self._slots:
            return
        self._slot_seconds += dt * len(self._slots)
        if override is not None:
            self._ledger[override] += dt * len(self._slots)
            return
        if classes is None or len(classes) != len(self._slots):
            classes = self._classify()
        for cls in classes:
            self._ledger[cls] += dt

    def slot_ledger(self) -> Dict[str, float]:
        """The accumulated slot-seconds partition plus its invariant
        total (``slot_seconds`` = ∫slots·dt charged so far; the sum of
        the classes, exactly) and the serve-loop wall it partitions."""
        out = {k: round(v, 6) for k, v in self._ledger.items()}
        out["slot_seconds"] = round(self._slot_seconds, 6)
        out["serve_wall_s"] = round(self._serve_wall, 6)
        return out

    def _materialize_oldest(self):
        import jax

        entry = self._window.popleft()
        host = np.asarray(jax.device_get(entry.tokens))
        for slot, rid in (entry.firsts or {}).items():
            state = self._slots[slot]
            if state is None or state.request_id != rid:
                continue
            state.generated.append(int(host[slot]))
            # TTFT means "first token host-visible": stamped here,
            # where a client could first read it, not at dispatch
            state.t_first_token = time.monotonic()
            self._h_prefill_e2e.observe(
                state.t_first_token - state.t_admit)
            emit_event(
                EventKind.SERVE_FIRST_TOKEN,
                trace_id=state.trace_id,
                request_id=state.request_id, slot=slot,
                ttft_s=round(state.t_first_token - state.t_admit, 6),
            )
            if self._finished(state):
                # later entries' tokens for this slot fail the owner
                # guard once retired — the decode step that ran past
                # a one-token request is discarded, never emitted
                self._retire(slot)
        for slot, rid in entry.owners.items():
            state = self._slots[slot]
            if state is None or state.request_id != rid:
                continue  # completed/reassigned meanwhile: stale token
            state.generated.append(int(host[slot]))
            if state.t_first_token is None:
                state.t_first_token = time.monotonic()
            if self._finished(state):
                self._retire(slot)

    def _drain_window(self):
        while self._window:
            self._materialize_oldest()

    # -- speculative decode (n-gram draft + batched verify) ------------------

    def _spec_step(self):
        """ONE verify step for the whole batch: propose up to K draft
        tokens per active slot from its own history (host n-gram
        index), run the compiled ``verify_step`` over K+1 positions,
        then commit the accepted prefix — emitted text is bitwise the
        plain-greedy stream at every acceptance pattern, and the one
        host sync this loop pays per step is amortized over up to K+1
        tokens (the window is firsts-only in spec mode; the caller
        drained it, so host history is current when proposing)."""
        import jax
        import jax.numpy as jnp

        program = self._engine.program
        k = program.spec_k
        s = program.spec.num_slots
        tokens_h = np.zeros((s, k + 1), np.int32)
        n_draft_h = np.zeros((s,), np.int32)
        owners: Dict[int, str] = {}
        for slot, state in enumerate(self._slots):
            if state is None or not self._active_host[slot]:
                continue
            owners[slot] = state.request_id
            tokens_h[slot, 0] = state.generated[-1]
            # the verify step emits up to n+1 tokens: cap the draft so
            # the commit can never run past max_new_tokens (eos inside
            # the accepted prefix truncates host-side below)
            budget = min(k, state.max_new_tokens
                         - len(state.generated) - 1)
            if budget <= 0:
                continue
            if state.draft_state is None:
                factory = self._spec_proposer_factory
                state.draft_state = (factory() if factory is not None
                                     else NgramProposer())
            draft = state.draft_state.propose(
                state.prompt + state.generated, budget)[:budget]
            if draft:
                n = len(draft)
                tokens_h[slot, 1:1 + n] = draft
                n_draft_h[slot] = n
        try:
            with span(SpanName.SERVE_DECODE, step=self.decode_steps):
                greedy_d, accepted_d, next_d, self._engine.cache = (
                    program.verify(
                        self._engine.params, self._engine.cache,
                        jnp.asarray(tokens_h), self._active,
                        jnp.asarray(n_draft_h)))
        except Exception:  # noqa: BLE001 — a failed verify step must
            # not kill serving OR charge the ledger: nothing was
            # committed (the raise happens before buffers are donated
            # to a successfully launched program), so the draft credit
            # is restored by simply not counting it, and the batch
            # falls back to ONE plain decode step — bitwise the same
            # stream, minus the speculation
            logger.warning("verify step failed; falling back to a "
                           "plain decode step", exc_info=True)
            next_tokens, _lg, self._engine.cache = (
                self._engine.program.decode(
                    self._engine.params, self._engine.cache,
                    self._tokens, self._active))
            self._tokens = next_tokens
            host = np.asarray(jax.device_get(next_tokens))
            for slot, rid in owners.items():
                state = self._slots[slot]
                if state is None or state.request_id != rid:
                    continue
                state.generated.append(int(host[slot]))
                if self._finished(state):
                    self._retire(slot)
            return
        self._tokens = next_d
        greedy_h, accepted_h = jax.device_get((greedy_d, accepted_d))
        greedy_h = np.asarray(greedy_h)
        accepted_h = np.asarray(accepted_h)
        self._c_spec_steps.inc()
        for slot, rid in owners.items():
            state = self._slots[slot]
            if state is None or state.request_id != rid:
                continue
            drafted = int(n_draft_h[slot])
            accepted = min(int(accepted_h[slot]), drafted)
            state.spec_drafted_tokens += drafted
            state.spec_accepted_tokens += accepted
            self._spec_drafted_total += drafted
            self._spec_accepted_total += accepted
            if drafted:
                self._c_spec_drafted.inc(drafted)
                self._c_spec_accepted.inc(accepted)
                self._c_spec_wasted.inc(drafted - accepted)
            # commit greedy[0..accepted] — exactly what plain greedy
            # would emit next — truncating at eos/max_new exactly
            # where the serial stream would have stopped
            for i in range(accepted + 1):
                state.generated.append(int(greedy_h[slot, i]))
                if self._finished(state):
                    self._retire(slot)
                    break
        if self._spec_drafted_total:
            self._g_spec_rate.set(self._spec_accepted_total
                                  / self._spec_drafted_total)

    def _apply_resize(self):
        self._resize_requested = False
        devices = self._resize_devices
        self._resize_devices = None
        trace_id = self._resize_trace_id
        self._resize_trace_id = ""
        import jax

        tokens_host = np.asarray(jax.device_get(self._tokens))
        active_host = list(self._active_host)
        if trace_id:
            # the SERVE_RESIZE_* events join the incident (SLO scale
            # proposal) that asked for the resize
            with trace_scope(trace_id):
                self._engine.live_resize(devices, reason="executor")
        else:
            self._engine.live_resize(devices, reason="executor")
        import jax.numpy as jnp

        self._tokens = jnp.asarray(tokens_host)
        self._active_host = active_host
        self._active = jnp.asarray(active_host)

    def _apply_retune(self):
        import jax
        import jax.numpy as jnp

        req = self._retune_request
        self._retune_request = None
        new_slots = req.get("serve_slots")
        new_chunk = req.get("prefill_chunk")
        new_ppp = req.get("prefix_pool_pages")
        new_spec_k = req.get("spec_draft_len")
        plan_id = req.get("plan_id", "")
        if new_chunk is not None:
            fitted = _fit_prefill_chunk(int(new_chunk),
                                        self._engine._pool_depth)
            if fitted != int(new_chunk):
                # the plan's chunk cannot be honored exactly (it does
                # not divide the pool depth): applying the fitted
                # variant while acking the plan would be the PR 11
                # phantom-apply loop — the master re-chooses the
                # unachievable tuple every cooldown window, each cycle
                # a futile drain. Negative-ack so it blacklists.
                logger.warning(
                    "serve plan %s wants prefill_chunk=%s but the "
                    "pool depth %d fits %d; negative-acking", plan_id,
                    new_chunk, self._engine._pool_depth, fitted)
                self._ack_plan(plan_id, apply_failed=True)
                return
            if int(new_chunk) != self._engine.prefill_chunk:
                # a chunk change invalidates IN-FLIGHT prefill
                # cursors: their start positions are multiples of the
                # OLD chunk, and a grown chunk's padded window could
                # cross the pool end (the dynamic_update_slice clamp
                # hazard _fit_prefill_chunk documents). Restart those
                # prompts from 0 — prefill rewrites its pages, so a
                # restart is always safe and bounded by one prompt.
                for slot, state in enumerate(self._slots):
                    if (state is not None
                            and not self._active_host[slot]
                            and state.cursor > 0):
                        state.cursor = 0
        live = [i for i, r in enumerate(self._slots) if r is not None]
        cur_slots = self._engine.program.spec.num_slots
        # host-side slot compaction happens ONLY when the slot width
        # actually changes (the engine migrates the KV pages under the
        # same condition — a chunk-only retune must leave both the
        # pages AND this bookkeeping exactly where they are, or they
        # diverge and every in-flight continuation is garbage)
        slots_changing = (new_slots is not None
                          and int(new_slots) != cur_slots)
        if slots_changing and len(live) > int(new_slots):
            logger.warning(
                "serve retune to %s slots declined: %d live requests",
                new_slots, len(live))
            self._ack_plan(plan_id, apply_failed=True)
            return
        slot_map = ({old: new for new, old in enumerate(live)}
                    if slots_changing else {i: i for i in live})
        tokens_host = np.asarray(jax.device_get(self._tokens))
        if req.get("prewarm"):
            # standby-compile the candidate program BEFORE the swap
            # (the training plan-apply discipline): the retune below
            # then hits the cache and the drained pause pays zero
            # compiles
            try:
                self._engine.prewarm(serve_slots=new_slots,
                                     prefill_chunk=new_chunk,
                                     prefix_pool_pages=new_ppp,
                                     spec_draft_len=new_spec_k)
            except Exception:  # noqa: BLE001 — prewarm is an
                # optimization; the retune still decides the outcome
                logger.warning("serve prewarm failed", exc_info=True)
        try:
            self._engine.retune(
                serve_slots=new_slots,
                prefill_chunk=req.get("prefill_chunk"),
                prefix_pool_pages=new_ppp,
                spec_draft_len=new_spec_k,
                slot_map=slot_map)
        except Exception:  # noqa: BLE001 — a bad plan must not kill
            # serving; the engine restored the previous knobs
            logger.exception("serve retune failed; continuing with the "
                             "previous config")
            self._ack_plan(plan_id, apply_failed=True)
            return
        if slots_changing:
            s = self._engine.program.spec.num_slots
            slots: List[Optional[ServeRequestState]] = [None] * s
            active = [False] * s
            tokens = np.zeros((s,), np.int32)
            for old, new in slot_map.items():
                slots[new] = self._slots[old]
                active[new] = self._active_host[old]
                tokens[new] = tokens_host[old]
            self._slots, self._active_host = slots, active
            self._tokens = jnp.asarray(tokens)
            self._active = jnp.asarray(active)
        self._ack_plan(plan_id)

    def _ack_plan(self, plan_id: str, apply_failed: bool = False):
        if not plan_id or self._client is None or not hasattr(
                self._client, "report_serve_config"):
            return
        try:
            self._report_config(plan_id=plan_id,
                                apply_failed=apply_failed)
        except Exception:  # noqa: BLE001
            logger.debug("serve plan ack failed", exc_info=True)

    def _report_config(self, plan_id: str = "",
                       apply_failed: bool = False):
        if self._client is None or not hasattr(
                self._client, "report_serve_config"):
            return
        program = self._engine.program
        stats = self._engine.prefix_stats()
        looked = stats.get("hits", 0) + stats.get("misses", 0)
        # -1 = "no observation yet": the optimizer then falls back to
        # the serve_prefix_expected_hit_rate prior instead of pricing
        # a cold pool as worthless forever
        hit_rate = (stats["hits"] / looked if stats and looked
                    else -1.0)
        try:
            self._client.report_serve_config(
                world=int(program.mesh.devices.size),
                serve_slots=int(program.spec.num_slots),
                prefill_chunk=int(program.prefill_chunk),
                kv_precision=str(program.spec.precision),
                max_seq=int(program.spec.max_seq),
                num_layers=int(program.spec.num_layers),
                kv_heads=int(program.spec.num_kv_heads),
                head_dim=int(program.spec.head_dim),
                prefix_pool_pages=int(program.spec.prefix_pool_pages),
                page_size=int(program.spec.page_size),
                prefix_hit_rate=float(hit_rate),
                spec_draft_len=int(program.spec_k),
                # -1 = "no draft observed yet": the optimizer prices
                # K>0 only from EVIDENCE (zero evidence = exactly 1.0x,
                # the prefix-discount discipline)
                spec_accept_rate=float(
                    self._spec_accepted_total / self._spec_drafted_total
                    if self._spec_drafted_total else -1.0),
                plan_id=plan_id, apply_failed=bool(apply_failed),
            )
        except Exception:  # noqa: BLE001 — a dead master must not
            # block serving
            logger.debug("serve config report failed", exc_info=True)

    def _poll_plan(self):
        if self._client is None or self._plan_poll <= 0 or not hasattr(
                self._client, "get_parallel_config"):
            return
        now = time.monotonic()
        if now - self._last_plan_poll < self._plan_poll:
            return
        self._last_plan_poll = now
        try:
            cfg = self._client.get_parallel_config()
        except Exception:  # noqa: BLE001 — master briefly away: retry
            # at the next poll cadence
            logger.debug("serve plan poll failed", exc_info=True)
            return
        plan_id = getattr(cfg, "plan_id", "") or ""
        slots = int(getattr(cfg, "serve_slots", 0) or 0)
        chunk = int(getattr(cfg, "serve_prefill_chunk", 0) or 0)
        # the pool and draft-length knobs' leave-unchanged sentinel is
        # -1 (0 is a real value: pool/spec off), unlike their
        # 0-sentinel siblings
        ppp = int(getattr(cfg, "serve_prefix_pool_pages", -1))
        sk = int(getattr(cfg, "serve_spec_draft_len", -1))
        if not plan_id or plan_id == self._seen_plan \
                or not (slots or chunk or ppp >= 0 or sk >= 0):
            return
        self._seen_plan = plan_id
        self.request_retune(serve_slots=slots or None,
                            prefill_chunk=chunk or None,
                            prefix_pool_pages=(ppp if ppp >= 0
                                               else None),
                            spec_draft_len=(sk if sk >= 0 else None),
                            plan_id=plan_id,
                            prewarm=bool(getattr(cfg, "prewarm", True)))

    def _touch(self):
        if self._client is None or not hasattr(self._client,
                                               "serve_touch"):
            return
        now = time.monotonic()
        if now - self._last_touch < 5.0:
            return
        self._last_touch = now
        try:
            self._client.serve_touch()
        except Exception:  # noqa: BLE001 — liveness is best-effort;
            # the lease-expiry scan is the backstop
            logger.debug("serve touch failed", exc_info=True)

    def serve(self, max_steps: int = 0, until_idle: bool = True):
        """Run the loop: admit → prefill tick → decode → lagged
        materialization, until the queue AND slots drain (or
        ``max_steps`` decode steps elapsed). Returns the completion
        records accumulated so far."""
        self._ensure_prepared()
        self._report_config()
        emit_event(EventKind.SERVE_START,
                   slots=self._engine.program.spec.num_slots,
                   prefill_chunk=self._engine.program.prefill_chunk,
                   kv_precision=self._engine.program.spec.precision,
                   spec_draft_len=self._engine.program.spec_k,
                   serve_seq=self._serve_seq)
        steps = 0
        idle_polls = 0
        loop_start = time.monotonic()
        self._ledger_mark = loop_start
        while True:
            # charge the elapsed interval to the ledger under the slot
            # states the PREVIOUS iteration left (the states that held
            # while its decode dispatch / materialization ran)
            self._charge_slots(time.monotonic())
            if self._resize_requested or self._retune_request is not None:
                self._drain_window()
                if self._resize_requested:
                    self._apply_resize()
                    self._report_config()
                if self._retune_request is not None:
                    self._apply_retune()
                # the drain + apply froze every slot: no decode or
                # prefill could run, whatever state the slots hold
                self._charge_slots(time.monotonic(),
                                   override="resize_frozen")
            self._poll_plan()
            self._admit()
            # the admission + prefill interval classifies by the state
            # that holds DURING it: a slot whose final chunk lands this
            # tick flips to decoding, and charging by the post-tick
            # state would fold every prefill second into decode
            pre_classes = (self._classify() if self._ledger_enabled
                           else None)
            self._prefill_tick()
            self._charge_slots(time.monotonic(), classes=pre_classes)
            self._touch()
            if not any(self._active_host):
                # nothing decoding: drain stragglers, then either a
                # fresh admission pass finds queued work or we are idle
                self._drain_window()
                if any(r is not None for r in self._slots):
                    continue  # admitted slots still prefilling
                if self._local_queue:
                    continue
                leased = self._lease(1)
                if leased:
                    self._local_queue.extend(leased)
                    continue
                idle_polls += 1
                if until_idle or (max_steps and steps >= max_steps) \
                        or idle_polls > 2:
                    break
                time.sleep(0.01)
                continue
            idle_polls = 0
            t0 = time.monotonic()
            if self._engine.program.verify is not None:
                # spec mode is SERIAL: the proposer needs current host
                # history before drafting, so the window (firsts-only
                # here) drains first and the verify step's host sync
                # is the price — amortized over up to K+1 tokens/slot
                self._drain_window()
                if not any(self._active_host):
                    continue  # the drain retired the last active slot
                self._spec_step()
            else:
                owners = {
                    i: r.request_id for i, r in enumerate(self._slots)
                    if r is not None and self._active_host[i]
                }
                with span(SpanName.SERVE_DECODE,
                          step=self.decode_steps):
                    next_tokens, _logits, self._engine.cache = (
                        self._engine.program.decode(
                            self._engine.params, self._engine.cache,
                            self._tokens, self._active))
                self._tokens = next_tokens
                self._window.append(
                    _InflightDecode(tokens=next_tokens, owners=owners))
                while len(self._window) > self._window_cap:
                    self._materialize_oldest()
            self._c_decode.inc()
            self.decode_steps += 1
            steps += 1
            self._h_step.observe(time.monotonic() - t0)
            if self._report_hook is not None:
                try:
                    self._report_hook.after_step(
                        self.decode_steps,
                        queue_len=len(self._local_queue),
                        slots=len(self._slots))
                except Exception:  # noqa: BLE001 — reporting must
                    # never take the decode loop down
                    logger.debug("serve runtime report hook failed",
                                 exc_info=True)
            if max_steps and steps >= max_steps:
                self._drain_window()
                break
        self._drain_window()
        now = time.monotonic()
        self._charge_slots(now)
        self._serve_wall += now - loop_start
        emit_event(EventKind.SERVE_END, decode_steps=self.decode_steps,
                   completed=len(self.completed),
                   slots=len(self._slots),
                   serve_seq=self._serve_seq,
                   slot_ledger={k: round(v, 6)
                                for k, v in self._ledger.items()},
                   slot_seconds=round(self._slot_seconds, 6),
                   serve_wall_s=round(self._serve_wall, 6),
                   prefix=self._engine.prefix_stats() or None,
                   spec=({"drafted": self._spec_drafted_total,
                          "accepted": self._spec_accepted_total,
                          "wasted": (self._spec_drafted_total
                                     - self._spec_accepted_total)}
                         if self._spec_drafted_total else None))
        if self._report_hook is not None:
            try:
                self._report_hook.flush(
                    queue_len=len(self._local_queue),
                    slots=len(self._slots))
            except Exception:  # noqa: BLE001 — best-effort final push
                logger.debug("serve runtime report flush failed",
                             exc_info=True)
        return list(self.completed)
