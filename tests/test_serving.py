"""The elastic serving tier (ISSUE 13): continuous-batching decode on
the training runtime.

Tier-1 core: router unit semantics (lease/complete/expiry,
conservation), KV-cache geometry + int8 storage + rule composition,
decode numerics (prefill+decode == the one-shot training forward —
EXACT for f32 pools on this backend; prefill_sequence bitwise),
checkpoint->serving promotion, the continuous-vs-static batching gate
(>= 1.3x fewer decode steps on the tiny-model wedge), and THE acceptance
wedge: a real router + two serve workers over RPC, a live 8->4 resize
under in-flight traffic -> zero dropped requests, held leases
complete, unaffected continuations bitwise-identical, zero recompiles
on the prewarmed survivor topology. The closed-loop serve replan
rides slow-marked."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common import comm
from dlrover_tpu.common.config import get_context
from dlrover_tpu.master.local_master import start_local_master
from dlrover_tpu.models import llama
from dlrover_tpu.parallel import planner
from dlrover_tpu.parallel.mesh import MeshPlan
from dlrover_tpu.parallel.strategy import Strategy
from dlrover_tpu.serving.engine import ServeEngine, ServeExecutor
from dlrover_tpu.serving.kv_cache import (
    KVCacheSpec,
    init_kv_cache,
    kv_cache_rules,
    migrate_slots_host,
    resolve_kv_precision,
)
from dlrover_tpu.serving.router import RequestRouter
from dlrover_tpu.telemetry import EventKind, read_events, recent_events
from dlrover_tpu.telemetry.events import clear_ring


@pytest.fixture(autouse=True)
def _telemetry_on():
    ctx = get_context()
    prev = ctx.telemetry_enabled
    ctx.telemetry_enabled = True
    yield
    ctx.telemetry_enabled = prev


TINY = llama.llama_tiny()


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def engine(tiny_params):
    eng = ServeEngine(
        TINY, strategy=Strategy(mesh=MeshPlan(data=-1),
                                rule_set="llama"),
        serve_slots=4, prefill_chunk=8, max_seq=48, page_size=8,
    )
    eng.prepare(tiny_params)
    return eng


def _prompt(n=6, seed=0):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, TINY.vocab_size, size=(n,))]


# -- the request router -------------------------------------------------------


class TestRequestRouter:
    def test_lease_complete_lifecycle_and_accounting(self):
        r = RequestRouter(lease_timeout_secs=120.0)
        rids = [r.submit([1, 2, 3], 4) for _ in range(3)]
        assert len(set(rids)) == 3
        leased = r.lease(node_id=0, max_requests=2)
        assert [q["request_id"] for q in leased] == rids[:2]
        assert r.complete(0, rids[0], [7, 8], ttft_s=0.1, e2e_s=0.2)
        rep = r.report()
        age = rep["requests"].pop("oldest_lease_age_s")
        assert age >= 0.0  # one lease still open
        assert rep["requests"] == {
            "queued": 1, "leased": 1, "done": 1, "submitted": 3,
            "completed": 1, "dropped": 0, "leases_expired": 0,
            "evicted": 0,
        }
        assert rep["latency"]["ttft_p50_s"] is not None
        assert rep["nodes"]["0"]["done"] == 1

    def test_resubmit_is_idempotent(self):
        r = RequestRouter()
        assert r.submit([1], 2, request_id="x") == "x"
        assert r.submit([9, 9], 5, request_id="x") == "x"
        assert r.report()["requests"]["submitted"] == 1

    def test_expired_lease_requeues_with_event_then_dedups_late_completion(
            self):
        clear_ring()
        r = RequestRouter(lease_timeout_secs=0.01)
        rid = r.submit([1, 2], 4)
        assert r.lease(0, 1)
        import time as _t

        _t.sleep(0.05)
        assert r.scan_expired_once() == [rid]
        evs = [e for e in recent_events()
               if e["kind"] == EventKind.SERVE_LEASE_EXPIRED]
        assert evs and evs[-1]["error_code"] == "SERVE_LEASE_EXPIRED"
        # the re-queued request leases to a LIVE worker...
        again = r.lease(1, 1)
        assert again and again[0]["request_id"] == rid
        # ...and the ORIGINAL worker's late completion is accepted
        # once, the twin's is a no-op: never a duplicate, never a drop
        assert r.complete(0, rid, [5])
        assert not r.complete(1, rid, [5])
        rep = r.report()["requests"]
        assert rep["completed"] == 1 and rep["dropped"] == 0
        assert rep["leases_expired"] == 1

    def test_completion_of_requeued_request_pulls_it_from_queue(self):
        r = RequestRouter(lease_timeout_secs=0.01)
        rid = r.submit([1], 4)
        r.lease(0, 1)
        import time as _t

        _t.sleep(0.05)
        r.scan_expired_once()
        # original worker finishes while the request sits re-queued
        assert r.complete(0, rid, [3])
        assert r.lease(1, 4) == []  # nothing left to hand out
        assert r.report()["requests"]["dropped"] == 0


# -- KV cache -----------------------------------------------------------------


class TestKVCache:
    def test_spec_geometry_page_aligned_and_one_byte_formula(self):
        spec = KVCacheSpec.from_model(TINY, num_slots=4, max_seq=30,
                                      page_size=8)
        assert spec.max_seq == 32  # rounded UP to whole pages
        assert spec.pages_per_slot == 4
        # bytes_per_slot and the planner's decode pricing share ONE
        # formula (kv_bytes_per_elem) — pinned so they cannot drift
        m = planner.model_spec_from_llama(TINY, global_batch=1)
        for precision in ("f32", "bf16", "int8"):
            s = KVCacheSpec.from_model(
                TINY, num_slots=4, max_seq=32, page_size=8,
                precision=precision)
            assert s.total_bytes() == pytest.approx(
                planner.serve_cache_bytes(m, 4, 32, precision))

    def test_int8_round_trip_bounded_by_block_scale(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(3, 2, 16).astype(np.float32))
        from dlrover_tpu.serving.kv_cache import decode_kv, encode_kv

        spec = KVCacheSpec.from_model(TINY, num_slots=1,
                                      precision="int8")
        v, s = encode_kv(x, spec)
        assert v.dtype == jnp.int8
        back = decode_kv(v, s, spec)
        # error bounded by half a quantization step of the BLOCK max
        err = np.abs(np.asarray(back) - np.asarray(x))
        assert err.max() <= float(np.abs(x).max()) / 127.0

    def test_precision_resolution_and_probe_fallback(self, monkeypatch):
        assert resolve_kv_precision("bf16") == "bf16"
        with pytest.raises(ValueError):
            resolve_kv_precision("fp4")
        import dlrover_tpu.serving.kv_cache as kvmod

        monkeypatch.setattr(kvmod, "int8_kv_supported", lambda: False)
        assert kvmod.resolve_kv_precision("int8") == "f32"

    def test_rules_compose_with_training_rules(self):
        rules = kv_cache_rules("llama")
        sizes = {"pipe": 1, "data": 2, "fsdp": 2, "seq": 1, "tensor": 2}
        # pool payload: slots on (data, fsdp), heads on tensor
        assert rules.spec_for("cache/k", (2, 4, 32, 2, 16), sizes) == \
            (None, ("data", "fsdp"), None, "tensor", None)
        assert rules.spec_for("cache/length", (4,), sizes) == \
            (("data", "fsdp"),)
        # params fall THROUGH to the unchanged training rules — what
        # makes promotion a pure device_put
        from dlrover_tpu.parallel.sharding_rules import llama_rules

        path = "params/layers/q_proj/kernel"
        shape = (2, 64, 64)
        assert rules.spec_for(path, shape, sizes) == \
            llama_rules().spec_for(path, shape, sizes)

    def test_migrate_slots_host_remaps_live_slots(self):
        spec4 = KVCacheSpec.from_model(TINY, num_slots=4, max_seq=16,
                                       page_size=8)
        spec2 = spec4.with_slots(2)
        host = {k: np.array(v)
                for k, v in init_kv_cache(spec4).items()}
        host["k"][:, 3] = 7.0
        host["length"][3] = 9
        out = migrate_slots_host(host, spec4, spec2, {3: 0})
        assert out["k"].shape[1] == 2
        assert (out["k"][:, 0] == 7.0).all()
        assert out["length"][0] == 9 and out["length"][1] == 0


# -- decode numerics ----------------------------------------------------------


class TestDecodeNumerics:
    def _reference(self, seq):
        logits, _aux = llama.apply(TINY, jnp.asarray(seq)[None], TINY) \
            if False else llama.apply(
                llama.init(jax.random.PRNGKey(0), TINY),
                jnp.asarray(seq)[None], TINY)
        return np.asarray(logits[0])

    def test_prefill_plus_decode_matches_one_shot_forward(
            self, tiny_params):
        """The decode-parity satellite: chunked prefill + teacher-
        forced single-token decode reproduces the one-shot training
        forward PER POSITION — exactly (f32 pool, this backend's
        kernels; the attention read mirrors mha_reference's f32
        logits/softmax conventions)."""
        p_len, new = 10, 5
        rng = np.random.RandomState(1)
        seq = rng.randint(0, TINY.vocab_size, size=(p_len + new,))
        ref, _ = llama.apply(tiny_params, jnp.asarray(seq)[None], TINY)
        ref = np.asarray(ref[0])
        spec = KVCacheSpec.from_model(TINY, num_slots=2, max_seq=32,
                                      page_size=8)
        cache = init_kv_cache(spec)
        c, start = 4, 0
        for i in range(math.ceil(p_len / c)):
            chunk = seq[:p_len][i * c:(i + 1) * c]
            padded = np.zeros((c,), np.int32)
            padded[:len(chunk)] = chunk
            cache, last = llama.prefill_chunk(
                tiny_params, cache, jnp.asarray(padded), jnp.int32(0),
                jnp.int32(start), jnp.int32(len(chunk)), TINY, spec)
            start += len(chunk)
        np.testing.assert_array_equal(np.asarray(last),
                                      ref[p_len - 1])
        active = jnp.asarray([True, False])
        dec = jax.jit(lambda cch, t: llama.decode_step(
            tiny_params, cch, t, active, TINY, spec))
        for j in range(new - 1):
            tokens = jnp.asarray([seq[p_len + j], 0], jnp.int32)
            _nt, logits, cache = dec(cache, tokens)
            np.testing.assert_array_equal(
                np.asarray(logits)[0], ref[p_len + j])

    def test_prefill_sequence_is_bitwise_the_training_forward(
            self, tiny_params):
        """``prefill_sequence`` routes the prompt through
        ``_attention_block`` itself (ring/flash included for big
        configs), so its last-token logits are BITWISE ``apply``'s —
        the first generated token of a promoted checkpoint is exactly
        what the trainer would predict."""
        seq = _prompt(9, seed=3)
        ref, _ = llama.apply(tiny_params, jnp.asarray(seq)[None], TINY)
        spec = KVCacheSpec.from_model(TINY, num_slots=2, max_seq=16,
                                      page_size=8)
        cache = init_kv_cache(spec)
        cache, last = llama.prefill_sequence(
            tiny_params, cache, jnp.asarray(seq), jnp.int32(1), TINY,
            spec)
        np.testing.assert_array_equal(np.asarray(last),
                                      np.asarray(ref[0, -1]))
        assert int(cache["length"][1]) == len(seq)

    def test_int8_pool_within_documented_tolerance(self, tiny_params):
        """int8 KV pages drift at the quantization level (the G109
        "kv" family ratchets the loss-level number; this pins the
        logit-level bound)."""
        p_len, new = 8, 4
        rng = np.random.RandomState(2)
        seq = rng.randint(0, TINY.vocab_size, size=(p_len + new,))
        ref, _ = llama.apply(tiny_params, jnp.asarray(seq)[None], TINY)
        ref = np.asarray(ref[0])
        spec = KVCacheSpec.from_model(TINY, num_slots=1, max_seq=16,
                                      page_size=8, precision="int8")
        cache = init_kv_cache(spec)
        cache, last = llama.prefill_chunk(
            tiny_params, cache, jnp.asarray(seq[:p_len], jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(p_len), TINY, spec)
        worst = np.abs(np.asarray(last) - ref[p_len - 1]).max()
        active = jnp.asarray([True])
        for j in range(new - 1):
            tokens = jnp.asarray([seq[p_len + j]], jnp.int32)
            _nt, logits, cache = llama.decode_step(
                tiny_params, cache, tokens, active, TINY, spec)
            worst = max(worst, np.abs(
                np.asarray(logits)[0] - ref[p_len + j]).max())
        assert worst < 0.25, worst  # documented: ~6e-2 observed


# -- promotion ----------------------------------------------------------------


class TestPromotion:
    def _trained_state(self, steps=3, lr=1e-2):
        from dlrover_tpu.parallel.accelerate import TrainState

        loss_fn = llama.make_loss_fn(TINY)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, TINY.vocab_size, size=(2, 17))
        batch = {"input_ids": jnp.asarray(ids[:, :-1]),
                 "labels": jnp.asarray(ids[:, 1:])}
        opt = optax.sgd(lr)
        params = llama.init(jax.random.PRNGKey(0), TINY)
        opt_state = opt.init(params)
        grad = jax.jit(jax.grad(
            lambda p: loss_fn(p, batch, jax.random.PRNGKey(1))[0]))
        for _ in range(steps):
            g = grad(params)
            updates, opt_state = opt.update(g, opt_state, params)
            params = optax.apply_updates(params, updates)
        return TrainState(step=jnp.asarray(steps, jnp.int32),
                          params=params, opt_state=opt_state), opt

    def test_snapshot_and_checkpoint_promote_with_exact_logits(
            self, engine, tmp_path):
        """Train a few steps -> promote (live HostSnapshot AND a saved
        training checkpoint restored against the SERVING shardings) ->
        the served first-token logits are bitwise a fresh forward's on
        the trained weights: no cold start, no numerics gap."""
        from dlrover_tpu.checkpoint import (
            ElasticCheckpointManager,
            HostSnapshot,
        )

        state, opt = self._trained_state()
        seq = _prompt(7, seed=5)
        ref, _ = llama.apply(state.params, jnp.asarray(seq)[None], TINY)
        ref_last = np.asarray(ref[0, -1])

        # live trainer -> serving (train+serve colocation)
        snap = HostSnapshot.take(state)
        engine.load_from_snapshot(snap)
        cache = engine.fresh_cache()
        cache, last = llama.prefill_sequence(
            engine.params, cache, jnp.asarray(seq), jnp.int32(0), TINY,
            engine.program.spec)
        np.testing.assert_array_equal(np.asarray(last), ref_last)

        # training checkpoint -> serving (restore against the serving
        # shardings directly)
        mgr = ElasticCheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(int(state.step), state, force=True)
        mgr.wait()
        mgr.close()
        engine.load_from_snapshot(
            HostSnapshot.take(jax.tree.map(np.zeros_like, state)))
        step = engine.load_from_checkpoint(
            str(tmp_path / "ckpt"),
            init_fn=llama.make_init_fn(TINY), optimizer=opt)
        assert step == int(state.step)
        cache = engine.fresh_cache()
        cache, last = llama.prefill_sequence(
            engine.params, cache, jnp.asarray(seq), jnp.int32(0), TINY,
            engine.program.spec)
        np.testing.assert_array_equal(np.asarray(last), ref_last)
        # leave the module engine with its canonical weights
        engine.load_from_snapshot(HostSnapshot.take(
            llama.init(jax.random.PRNGKey(0), TINY)))


# -- continuous batching ------------------------------------------------------


def _mixed_workload(requests):
    """Alternating 2- and 40-token generations: the shape where static
    batching pays its tail."""
    return [(_prompt(6, seed=i), 2 if i % 2 == 0 else 40)
            for i in range(requests)]


def _serve_leg(engine, admission, workload):
    """One serving leg on a fresh pool; the engine and its compiled
    programs are shared across legs."""
    engine.cache = engine.fresh_cache()
    # window=1: slot turnover is the variable under test, and a deeper
    # lag window delays finish detection by its depth in wasted decode
    # steps per short request (docs/serving.md)
    executor = ServeExecutor(engine, admission=admission, serve_window=1)
    for i, (prompt, max_new) in enumerate(workload):
        executor.submit(prompt, max_new_tokens=max_new,
                        request_id=f"{admission}-{i}")
    done = executor.serve()
    return {"completed": len(done), "decode_steps": executor.decode_steps}


class TestContinuousBatching:
    def test_beats_static_batching_on_mixed_lengths(self, engine):
        """The tier-1 gate: admission churn (slot reuse as short
        requests finish) must serve the same mixed-length workload in
        at most 1/1.3 of static batching's decode steps — the step
        count is the mechanism; a tokens/sec ratio would be a clock of
        the CPU mesh — and the whole paired run must not recompile
        anything."""
        workload = _mixed_workload(16)
        _serve_leg(engine, "continuous", _mixed_workload(2))
        _serve_leg(engine, "static", _mixed_workload(2))
        compiles = engine.compile_count
        cache_size = engine.program.compiled_cache_size()
        static = _serve_leg(engine, "static", workload)
        cont = _serve_leg(engine, "continuous", workload)
        assert static["completed"] == cont["completed"] == 16
        step_ratio = static["decode_steps"] / cont["decode_steps"]
        assert step_ratio >= 1.3, (static, cont)
        assert engine.compile_count == compiles
        assert engine.program.compiled_cache_size() == cache_size

    def test_prefill_chunk_fits_the_pool_and_long_prompts_survive(
            self, engine, tiny_params):
        """Regression: a requested chunk whose padded write window
        could cross the pool end (T=48, chunk 32, a 40-token prompt —
        ``dynamic_update_slice`` would CLAMP the start and silently
        destroy earlier pages) is normalized to the largest divisor of
        the pool depth, and the long prompt decodes identically to a
        small-chunk serve (the module engine, chunk 8)."""
        from dlrover_tpu.serving.engine import _fit_prefill_chunk

        assert _fit_prefill_chunk(32, 48) == 24
        assert _fit_prefill_chunk(8, 48) == 8
        assert _fit_prefill_chunk(500, 48) == 48

        prompt = _prompt(40, seed=9)
        engine.cache = engine.fresh_cache()
        ref = ServeExecutor(engine, serve_window=1)
        ref.submit(prompt, max_new_tokens=4, request_id="long")
        expect = {r["request_id"]: r["tokens"] for r in ref.serve()}

        eng_big = ServeEngine(
            TINY, strategy=Strategy(mesh=MeshPlan(data=-1),
                                    rule_set="llama"),
            serve_slots=2, prefill_chunk=32, max_seq=44, page_size=8)
        assert eng_big.prefill_chunk == 24  # normalized, pool depth 48
        eng_big.prepare(tiny_params)
        ex = ServeExecutor(eng_big, serve_window=1)
        ex.submit(prompt, max_new_tokens=4, request_id="long")
        got = {r["request_id"]: r["tokens"] for r in ex.serve()}
        assert got == expect
        assert len(got["long"]) == 4

    def test_oversized_request_evicts_with_error_code(self, engine):
        clear_ring()
        engine.cache = engine.fresh_cache()
        ex = ServeExecutor(engine, serve_window=1)
        ex.submit(_prompt(6), max_new_tokens=500, request_id="huge")
        ex.submit(_prompt(6, seed=7), max_new_tokens=3, request_id="ok")
        done = ex.serve()
        by = {r["request_id"]: r for r in done}
        assert by["huge"]["error_code"] == "SERVE_REQUEST_EVICTED"
        assert by["ok"]["error_code"] == ""
        assert len(by["ok"]["tokens"]) == 3
        evs = [e for e in recent_events()
               if e["kind"] == EventKind.SERVE_REQUEST_EVICTED]
        assert evs and evs[-1]["error_code"] == "SERVE_REQUEST_EVICTED"

    def test_retune_repacks_live_slots(self, engine, tiny_params):
        """An optimizer serve plan (slot-width change) applies at a
        drained boundary with live requests repacked host-side — no
        request lost, tokens unchanged."""
        engine.cache = engine.fresh_cache()
        baseline = ServeExecutor(engine, serve_window=1)
        for i in range(3):
            baseline.submit(_prompt(5, seed=10 + i), max_new_tokens=5,
                            request_id=f"b{i}")
        expect = {r["request_id"]: r["tokens"]
                  for r in baseline.serve()}
        engine.cache = engine.fresh_cache()
        ex = ServeExecutor(engine, serve_window=1)
        for i in range(3):
            ex.submit(_prompt(5, seed=10 + i), max_new_tokens=5,
                      request_id=f"b{i}")
        ex.serve(max_steps=2, until_idle=False)
        ex.request_retune(serve_slots=8)
        done = ex.serve()
        assert engine.program.spec.num_slots == 8
        got = {r["request_id"]: r["tokens"] for r in done}
        assert got == expect
        # restore the module engine's canonical knobs
        ex.request_retune(serve_slots=4)
        ex._drain_window()
        ex._apply_retune()
        assert engine.program.spec.num_slots == 4

    def test_chunk_only_retune_leaves_live_slots_in_place(self, engine):
        """A prefill_chunk-only plan swaps the program WITHOUT moving
        slots: the engine migrates no pages, so the executor must not
        compact its bookkeeping either — regression for the
        slot-map/page divergence that garbled every in-flight
        continuation."""
        engine.cache = engine.fresh_cache()
        baseline = ServeExecutor(engine, serve_window=1)
        for i in range(3):
            baseline.submit(_prompt(5, seed=30 + i), max_new_tokens=6,
                            request_id=f"c{i}")
        expect = {r["request_id"]: r["tokens"]
                  for r in baseline.serve()}
        engine.cache = engine.fresh_cache()
        ex = ServeExecutor(engine, serve_window=1)
        for i in range(3):
            ex.submit(_prompt(5, seed=30 + i), max_new_tokens=6,
                      request_id=f"c{i}")
        ex.serve(max_steps=2, until_idle=False)
        assert any(ex._active_host)
        ex.request_retune(prefill_chunk=4)
        done = ex.serve()
        assert engine.program.prefill_chunk == 4
        assert engine.program.spec.num_slots == 4  # unchanged
        got = {r["request_id"]: r["tokens"] for r in done}
        assert got == expect
        ex.request_retune(prefill_chunk=8)  # restore module knobs
        ex._drain_window()
        ex._apply_retune()
        assert engine.program.prefill_chunk == 8

    def test_chunk_retune_mid_prefill_restarts_the_prompt(self, engine):
        """Regression: a chunk change invalidates in-flight prefill
        cursors (old-chunk-multiple starts + a grown chunk = the
        window-clamp hazard) — those prompts restart from 0 and still
        decode correctly."""
        engine.cache = engine.fresh_cache()
        baseline = ServeExecutor(engine, serve_window=1)
        baseline.submit(_prompt(20, seed=33), max_new_tokens=4,
                        request_id="mid")
        expect = {r["request_id"]: r["tokens"]
                  for r in baseline.serve()}
        engine.cache = engine.fresh_cache()
        ex = ServeExecutor(engine, serve_window=1)
        ex.submit(_prompt(20, seed=33), max_new_tokens=4,
                  request_id="mid")
        ex._ensure_prepared()
        ex._admit()
        ex._prefill_tick()  # one 8-token chunk in: cursor=8, inactive
        state = next(s for s in ex._slots if s is not None)
        assert 0 < state.cursor < len(state.prompt)
        ex.request_retune(prefill_chunk=16)
        ex._apply_retune()
        assert state.cursor == 0  # restarted under the new chunk
        got = {r["request_id"]: r["tokens"] for r in ex.serve()}
        assert got == expect
        ex.request_retune(prefill_chunk=8)  # restore module knobs
        ex._apply_retune()

    def test_unachievable_chunk_plan_negative_acks(self, engine):
        """A plan whose chunk does not divide the pool depth (48) is
        negative-acked BEFORE any state change — the PR 11 phantom-
        apply guard — and the optimizer never enumerates such chunks
        in the first place."""
        class AckSpy:
            acks = []

            def report_serve_config(self, **kw):
                self.acks.append(kw)

            def get_parallel_config(self):  # plan-poll interface
                return comm.ParallelConfig()

        engine.cache = engine.fresh_cache()
        spy = AckSpy()
        ex = ServeExecutor(engine, router_client=spy,
                           serve_window=1, plan_poll_secs=0)
        ex._ensure_prepared()
        before = engine.prefill_chunk
        ex.request_retune(prefill_chunk=9, plan_id="bad-chunk")
        ex._apply_retune()
        assert engine.prefill_chunk == before  # nothing applied
        nack = [a for a in spy.acks if a.get("plan_id") == "bad-chunk"]
        assert nack and nack[-1]["apply_failed"] is True
        # master side: candidates are divisor-only
        opt = _optimizer()
        opts = opt._serve_candidates({
            "serve_slots": 4, "prefill_chunk": 8, "max_seq": 48,
            "kv_precision": "f32", "world": 8, "node_id": 0})
        assert all(48 % c["prefill_chunk"] == 0 for c in opts)


# -- THE acceptance wedge -----------------------------------------------------


class TestServeResizeWedge:
    def test_live_resize_under_traffic_zero_drops_bitwise_continuations(
            self, tmp_path, monkeypatch):
        """Real router + two serve workers over RPC; worker 0 resizes
        8 -> 4 LIVE with leased requests mid-decode. Pinned: zero
        dropped requests, zero expired leases (held, not dropped),
        every request completes, continuations bitwise-identical to a
        resize-free serve of the same workload, zero recompiles on the
        prewarmed survivor topology, and the mttr/goodput derivations
        see the serving_resize scenario. The workers run the PREFIX
        POOL (shared 16-token head across the workload) against a
        pool-FREE baseline — the bitwise gate then also pins reuse ==
        full prefill across the live resize, and the prefix columns
        must agree live-vs-forensic."""
        events_path = str(tmp_path / "events.jsonl")
        monkeypatch.setenv("DLROVER_TPU_EVENTS_FILE", events_path)
        shared = _prompt(16, seed=7)
        prompts = {f"r{i}": shared + _prompt(4, seed=20 + i)
                   for i in range(10)}

        def build_worker(pool_pages=8):
            eng = ServeEngine(
                TINY, strategy=Strategy(mesh=MeshPlan(data=-1),
                                        rule_set="llama"),
                serve_slots=4, prefill_chunk=4, max_seq=32,
                page_size=8, prefix_pool_pages=pool_pages,
            )
            eng.prepare(llama.init(jax.random.PRNGKey(0), TINY))
            return eng

        # resize-free pool-FREE baseline (local queue): ground truth
        base_eng = build_worker(pool_pages=0)
        base = ServeExecutor(base_eng, serve_window=1)
        for rid, p in prompts.items():
            base.submit(p, max_new_tokens=6, request_id=rid)
        expect = {r["request_id"]: r["tokens"] for r in base.serve()}

        master = start_local_master()
        try:
            sub = MasterClient(master.addr, node_id=99)
            for rid, p in prompts.items():
                assert sub.submit_serve_request(
                    p, max_new_tokens=6, request_id=rid) == rid

            eng_a = build_worker()
            worker_a = ServeExecutor(
                eng_a, router_client=MasterClient(master.addr,
                                                  node_id=0),
                serve_window=1, plan_poll_secs=0)
            eng_b = build_worker()
            worker_b = ServeExecutor(
                eng_b, router_client=MasterClient(master.addr,
                                                  node_id=1),
                serve_window=1, plan_poll_secs=0)

            # worker 0 leases a slot-batch and decodes PARTWAY —
            # in-flight traffic
            worker_a.serve(max_steps=3, until_idle=False)
            assert any(worker_a._active_host), "no in-flight traffic"
            # worker 1 serves a share of the queue over the same RPC
            # router (>= 2 real workers)
            worker_b.serve()
            assert worker_b.completed

            # live 8 -> 4 on the prewarmed survivor topology, leases
            # held across it
            survivors = jax.devices()[:4]
            eng_a.prewarm(devices=survivors)
            compiles = eng_a.compile_count
            worker_a.request_resize(survivors)
            worker_a.serve()
            assert eng_a.compile_count == compiles, \
                "resize recompiled on a prewarmed survivor topology"
            assert eng_a.program.mesh.devices.size == 4

            report = sub.get_serve_report()
            req = report["requests"]
            assert req["submitted"] == 10
            assert req["completed"] == 10, report
            assert req["dropped"] == 0
            assert req["leases_expired"] == 0  # held, never re-leased
            assert req["queued"] == 0 and req["leased"] == 0

            # continuations bitwise-identical to the resize-free serve
            got = {r["request_id"]: r["tokens"]
                   for r in worker_a.completed + worker_b.completed}
            assert set(got) == set(expect)
            for rid in expect:
                assert got[rid] == expect[rid], rid

            # both workers' rows in the ledger
            assert set(report["nodes"]) == {"0", "1"}

            # the CLI views agree (live vs forensic)
            import io
            import sys as _sys

            from dlrover_tpu.trainer.run import main as tpurun

            buf, prev = io.StringIO(), _sys.stdout
            _sys.stdout = buf
            try:
                rc = tpurun(["requests", "--addr", master.addr,
                             "--json"])
            finally:
                _sys.stdout = prev
            assert rc == 0
            live = json.loads(buf.getvalue())
            assert live["requests"]["completed"] == 10

            # the prefix columns: the shared head hits once each
            # worker's first completion publishes it, and the hit
            # totals survive the live resize
            live_prefix = live.get("prefix") or {}
            assert live_prefix.get("hits", 0) >= 1, live
            assert live_prefix["saved_prefill_tokens"] \
                == 16 * live_prefix["hits"]

            records = read_events(events_path)
            begun = [r for r in records
                     if r["kind"] == EventKind.SERVE_RESIZE_BEGIN]
            done_ev = [r for r in records
                       if r["kind"] == EventKind.SERVE_RESIZE_DONE]
            assert begun and done_ev
            assert done_ev[-1]["world_from"] == 8
            assert done_ev[-1]["world_to"] == 4
            assert done_ev[-1]["recompiled"] == 0

            buf, prev = io.StringIO(), _sys.stdout
            _sys.stdout = buf
            try:
                rc = tpurun(["requests", "--events", events_path,
                             "--json"])
            finally:
                _sys.stdout = prev
            assert rc == 0
            forensic = json.loads(buf.getvalue())
            assert forensic["resizes"][-1]["world_to"] == 4
            assert forensic["leases_expired"] == 0
            # live-vs-forensic agreement extends to the prefix
            # columns: router-ledger hits == worker HIT edges
            assert forensic["prefix"]["hits"] == live_prefix["hits"]
            assert forensic["prefix"]["saved_prefill_tokens"] \
                == live_prefix["saved_prefill_tokens"]

            # mttr derives the serving_resize scenario from the same
            # timeline; goodput books it as reshard-class downtime
            from dlrover_tpu.telemetry.goodput import derive_goodput
            from dlrover_tpu.telemetry.mttr import derive_incidents

            incidents = [i for i in derive_incidents(records)
                         if i["scenario"] == "serving_resize"]
            assert incidents
            assert incidents[-1]["recovery_seconds"] is not None
            ledger = derive_goodput(records)
            buckets = ledger["detail"]["buckets"]
            assert buckets.get("reshard", {}).get("seconds", 0.0) >= 0.0
        finally:
            master.stop()


# -- the serve knob family (runtime optimizer) --------------------------------


def _serve_report(**kw):
    base = dict(node_id=0, world=8, serve_slots=4, prefill_chunk=8,
                kv_precision="f32", max_seq=64)
    base.update(kw)
    return comm.ServeConfigReport(**base)


def _optimizer(publish=None):
    from dlrover_tpu.master.monitor.node_series import NodeRuntimeStore
    from dlrover_tpu.master.optimizer import RuntimeOptimizer

    return RuntimeOptimizer(NodeRuntimeStore(), publish=publish,
                            cooldown_secs=0.0)

class TestServeKnobFamily:
    def test_serve_config_triggers_replan_and_publishes_sentinels(self):
        published = []
        opt = _optimizer(publish=published.append)
        opt.update_model_info(comm.ModelInfo(
            num_params=10_000, hidden_size=64, num_layers=2,
            seq_len=128))
        opt.update_serving_config(_serve_report())
        serve_dec = [d for d in opt.decisions()
                     if d["trigger"].startswith("serve:")]
        assert serve_dec, opt.decisions()
        last = serve_dec[-1]
        assert last["outcome"] == "chosen"
        assert published
        cfg = published[-1]
        # more slots amortize the weight read: slots grow, chunk is a
        # tie broken toward NO change (sentinel 0)
        assert cfg.serve_slots > 4
        assert cfg.serve_prefill_chunk == 0
        assert cfg.plan_id == last["plan_id"]

    def test_hbm_gate_refuses_pools_that_cannot_fit(self, monkeypatch):
        monkeypatch.setattr(get_context(), "device_hbm_budget_bytes",
                            1.0)
        opt = _optimizer()
        opt.update_serving_config(_serve_report())
        last = [d for d in opt.decisions()
                if d["trigger"].startswith("serve:")][-1]
        assert last["outcome"] == "rejected"
        assert last["reason"] == "serve:no_feasible_candidate"
        assert last["memory_rejected"]
        worst = last["memory_rejected"][0]
        assert worst["predicted_hbm_bytes"] > worst["budget_bytes"]

    def test_failed_apply_blacklists_the_serve_knob_tuple(self):
        published = []
        opt = _optimizer(publish=published.append)
        opt.update_model_info(comm.ModelInfo(
            num_params=10_000, hidden_size=64, num_layers=2,
            seq_len=128))
        opt.update_serving_config(_serve_report())
        plan_id = published[-1].plan_id
        chosen_key = [d for d in opt.decisions()
                      if d.get("plan_id") == plan_id][-1]["chosen"]["key"]
        # negative ack: worker could not apply (e.g. live > new slots)
        opt.update_serving_config(_serve_report(
            plan_id=plan_id, apply_failed=True))
        assert chosen_key in opt._failed_keys
        # the same tuple is never re-chosen
        opt.replan_serving("again")
        latest = [d for d in opt.decisions()
                  if d["trigger"].startswith("serve:")][-1]
        assert (latest.get("chosen") or {}).get("key") != chosen_key

    def test_stale_laggard_report_neither_rewinds_nor_replans(self):
        """Two serve workers around an 8->4 resize: the survivor's
        world=4 report retriggers planning, but a laggard peer's
        queued PRE-resize report (world=8, no per-node change) must
        neither rewind the serving view to the dead world nor fire a
        replan priced for it — the update_running_config discipline."""
        opt = _optimizer()
        opt.update_serving_config(_serve_report(node_id=0, world=8))
        opt.update_serving_config(_serve_report(node_id=1, world=8))
        # node 0 resized: per-node change -> adopted
        opt.update_serving_config(_serve_report(node_id=0, world=4))
        assert opt.serving_config()["world"] == 4
        n = len(opt.decisions())
        # node 1's stale queued report: same world it last reported,
        # a minority view of a dead world — ignored entirely
        opt.update_serving_config(_serve_report(node_id=1, world=8))
        assert opt.serving_config()["world"] == 4
        assert len(opt.decisions()) == n

    def test_ack_marks_decision_applied_without_replan_chase(self):
        published = []
        opt = _optimizer(publish=published.append)
        opt.update_model_info(comm.ModelInfo(
            num_params=10_000, hidden_size=64, num_layers=2,
            seq_len=128))
        opt.update_serving_config(_serve_report())
        n_before = len(opt.decisions())
        plan = published[-1]
        # the worker applies and acks with its NEW config: the echo
        # must not trigger another serve replan (tail chasing)
        opt.update_serving_config(_serve_report(
            serve_slots=plan.serve_slots or 4,
            plan_id=plan.plan_id))
        assert len(opt.decisions()) == n_before
        applied = [d for d in opt.decisions()
                   if d.get("plan_id") == plan.plan_id][-1]
        assert applied["applied"] is True


class TestKvDriftFamily:
    @pytest.mark.slow  # the clean judgement ALSO runs tier-1 inside
    # test_lint_clean's full tpulint pass (which executes the kv
    # probe); this standalone copy rides slow
    def test_clean_against_the_committed_ratchet(self):
        """The G109 "kv" family: the teacher-forced prefill+decode
        probe reproduces the committed baseline (fire/clean judged
        like every other family)."""
        from dlrover_tpu.analysis import graph_lint

        report = graph_lint.quantization_drift_audit(
            family="kv", precision="int8")
        assert not report.findings, [f.message for f in report.findings]

    def test_fires_when_drift_regresses_past_the_ratchet(
            self, tmp_path, monkeypatch):
        from dlrover_tpu.analysis import graph_lint

        label = "llama_tiny[kv,int8]@cpu"
        baseline = tmp_path / "quant_baseline.json"
        baseline.write_text(json.dumps(
            {"version": 1, "entries": {label: {"drift": 1e-6}}}))
        monkeypatch.setattr(
            graph_lint, "measure_quantization_drift",
            lambda *a, **k: (1.0e-3, label))
        report = graph_lint.quantization_drift_audit(
            family="kv", precision="int8",
            baseline_path=str(baseline))
        assert report.findings
        assert report.findings[0].rule_id == "G109"


class TestPlannerDecodeTerm:
    def test_tokens_per_s_monotone_in_slots(self):
        m = planner.model_spec_from_llama(TINY, global_batch=1)
        prev = 0.0
        for slots in (1, 2, 4, 8, 16):
            est = planner.estimate_decode(m, 8, slots, 8, 64)
            assert est["tokens_per_s"] > prev
            prev = est["tokens_per_s"]

    def test_kv_precision_orders_bytes_and_step_time(self):
        m = planner.model_spec_from_llama(TINY, global_batch=1)
        by = {p: planner.estimate_decode(m, 8, 8, 8, 64, p)
              for p in ("f32", "bf16", "int8")}
        assert by["int8"]["cache_bytes"] < by["bf16"]["cache_bytes"] \
            < by["f32"]["cache_bytes"]
        assert by["int8"]["breakdown"]["kv_read_s"] \
            < by["f32"]["breakdown"]["kv_read_s"]

    def test_step_floors_at_host_dispatch(self):
        m = planner.model_spec_from_llama(TINY, global_batch=1)
        est = planner.estimate_decode(m, 8, 4, 8, 64)
        assert est["step_s"] >= planner.HOST_DISPATCH_OVERHEAD_S
        for key in ("kv_read_s", "weight_read_s", "flops_s",
                    "dispatch_s", "prefill_amort_s"):
            assert key in est["breakdown"]


# -- slow: the closed loop over RPC ------------------------------------------


@pytest.mark.slow
class TestServeReplanE2E:
    def test_closed_loop_retunes_serve_slots_live(self, tiny_params):
        """Serve config report -> optimizer prices the decode term ->
        publishes a serve plan -> the worker polls, retunes through
        the prewarmed program cache, and acks — the serving twin of
        the PR 7 replan wedge, over real RPC."""
        master = start_local_master()
        try:
            sub = MasterClient(master.addr, node_id=99)
            sub.report_model_info(comm.ModelInfo(
                num_params=100_000, hidden_size=64, num_layers=2,
                seq_len=128))
            for i in range(12):
                sub.submit_serve_request(_prompt(5, seed=40 + i),
                                         max_new_tokens=6,
                                         request_id=f"e{i}")
            eng = ServeEngine(
                TINY, strategy=Strategy(mesh=MeshPlan(data=-1),
                                        rule_set="llama"),
                serve_slots=4, prefill_chunk=4, max_seq=32,
                page_size=8)
            eng.prepare(tiny_params)
            ex = ServeExecutor(
                eng, router_client=MasterClient(master.addr,
                                                node_id=0),
                serve_window=1, plan_poll_secs=0.01)
            done = ex.serve()
            assert len(done) == 12
            # the optimizer chose a wider slot batch and the worker
            # applied it live, acking the plan
            assert eng.program.spec.num_slots > 4
            serve_dec = [
                d for d in master.servicer.runtime_optimizer.decisions()
                if d["trigger"].startswith("serve:")
                and d["outcome"] == "chosen"]
            assert serve_dec and serve_dec[0]["applied"]
        finally:
            master.stop()

# -- the shared prefix pool (ISSUE 16) ----------------------------------------


from dlrover_tpu.serving.prefix_index import PrefixIndex  # noqa: E402


class TestPrefixIndex:
    """Host-side radix-index semantics: exact-token matching, LRU
    eviction that never touches a pinned chain, full-pool degradation
    to miss-and-prefill, idempotent release across flush."""

    def test_match_is_exact_tokens_and_page_grain(self):
        ix = PrefixIndex(page_size=4, num_pages=8)
        ix.publish(list(range(12)))  # 3 pages
        assert ix.used_pages == 3
        # full-page exact match only: 11 tokens -> 2 whole pages
        h = ix.match(list(range(11)))
        assert h.tokens == 8 and len(h.pages) == 2
        ix.release(h)
        # one differing token inside the first page -> no hash
        # shortcut, the walk misses at the literal comparison
        assert ix.match([0, 1, 2, 99, 4, 5, 6, 7]) is None
        assert ix.misses == 1

    def test_match_caps_and_aligns_before_pinning(self):
        ix = PrefixIndex(page_size=4, num_pages=8)
        ix.publish(list(range(16)))  # 4 pages
        h = ix.match(list(range(16)), max_pages=3, align_pages=2)
        # capped to 3 then aligned DOWN to 2 pages; only those pinned
        assert len(h.pages) == 2
        assert all(n.refcount == 1 for n in h.nodes)
        unpinned = ix.match(list(range(16)))  # pins all 4
        assert [n.refcount for n in unpinned.nodes] == [2, 2, 1, 1]
        ix.release(h)
        ix.release(unpinned)

    def test_pinned_chains_never_evicted_lru_picks_oldest(self):
        ix = PrefixIndex(page_size=2, num_pages=2)
        ix.publish([1, 1])
        ix.publish([2, 2])
        pin = ix.match([1, 1])  # pins page for [1,1]
        # pool full; publishing a third chunk must evict [2,2] (the
        # only refcount-0 leaf), never the pinned [1,1]
        out = ix.publish([3, 3])
        assert len(out) == 1
        assert ix.evictions == 1
        assert ix.match([2, 2]) is None  # evicted -> exact miss
        got = ix.match([1, 1])
        assert got is not None  # pinned chain survived
        ix.release(pin)
        ix.release(got)

    def test_evicted_page_reuse_cannot_stale_match(self):
        """The page id freed by eviction is re-published under NEW
        tokens; a request for the OLD tokens misses (trie removal
        precedes reuse) and re-verifies by publishing afresh."""
        ix = PrefixIndex(page_size=2, num_pages=1)
        ix.publish([7, 7])
        assert ix.publish([8, 8])  # evicts [7,7], reuses its page
        assert ix.match([7, 7]) is None  # never a stale hit
        again = ix.publish([7, 7])  # the next miss re-publishes
        assert len(again) == 1
        assert ix.match([8, 8]) is None  # and [8,8] was the victim

    def test_full_pool_of_pinned_pages_degrades_never_raises(self):
        ix = PrefixIndex(page_size=2, num_pages=2)
        ix.publish([1, 1, 2, 2])
        pin = ix.match([1, 1, 2, 2])
        # every page pinned: publish skips, counted, no exception
        assert ix.publish([3, 3, 4, 4]) == []
        assert ix.publish_skipped == 1
        ix.release(pin)

    def test_interior_node_with_children_is_not_a_victim(self):
        ix = PrefixIndex(page_size=2, num_pages=2)
        ix.publish([1, 1, 2, 2])  # chain: [1,1] -> [2,2]
        # only the CHILDLESS tail is evictable — evicting the parent
        # would orphan the child and break "whole chain present"
        out = ix.publish([3, 3])
        assert len(out) == 1
        assert ix.match([1, 1]) is not None  # parent survived

    def test_release_is_idempotent_and_survives_flush(self):
        ix = PrefixIndex(page_size=2, num_pages=4)
        ix.publish([1, 1])
        h = ix.match([1, 1])
        ix.flush()
        assert ix.used_pages == 0
        ix.publish([9, 9])
        fresh = ix.match([9, 9])
        ix.release(h)  # orphaned nodes absorb it
        ix.release(h)  # idempotent
        assert fresh.nodes[0].refcount == 1  # fresh pin untouched
        ix.release(fresh)
        # stats survive the flush (they describe the process)
        assert ix.hits == 2 and ix.published == 2


@pytest.fixture(scope="module")
def prefix_engine(tiny_params):
    eng = ServeEngine(
        TINY, strategy=Strategy(mesh=MeshPlan(data=-1),
                                rule_set="llama"),
        serve_slots=4, prefill_chunk=8, max_seq=48, page_size=8,
        prefix_pool_pages=12,
    )
    eng.prepare(tiny_params)
    return eng


def _serve_locally(eng, jobs, tag):
    """Serve ``jobs`` ([(rid, prompt, max_new)]) on a fresh slot pool
    (the prefix pool is NOT reset — legs seed it deliberately)."""
    eng.cache = eng.fresh_cache()
    ex = ServeExecutor(eng, serve_window=1)
    for rid, prompt, max_new in jobs:
        ex.submit(prompt, max_new_tokens=max_new,
                  request_id=f"{tag}-{rid}")
    return {r["request_id"].split("-", 1)[1]: r for r in ex.serve()}


class TestPrefixReuseBitwise:
    """THE tentpole oracle: a prefix-reused continuation is BITWISE
    equal to the full prefill on the f32 pool, at every hit-length
    class — 0, partial-chunk, chunk-exact, and full-prompt (capped
    strictly below the prompt so the final chunk still seeds the
    first token)."""

    def test_bitwise_at_every_hit_length(self, engine, prefix_engine,
                                         tiny_params):
        seed_prompt = _prompt(40, seed=77)
        # hit-length cases against a pool seeded with seed_prompt:
        #  q0:  shares <1 page            -> hit 0
        #  q16: shares 20 tokens          -> partial page rounds DOWN to 16
        #  q24: shares 24 (3 exact pages) -> hit 24
        #  qfp: the seed prompt itself    -> hit 32 (cap < len(prompt))
        cases = {
            "q0": (seed_prompt[:4] + _prompt(8, seed=78), 0),
            "q16": (seed_prompt[:20] + _prompt(4, seed=79), 16),
            "q24": (seed_prompt[:24] + _prompt(8, seed=80), 24),
            "qfp": (list(seed_prompt), 32),
        }
        # seed the pool (published at the final prefill chunk)
        _serve_locally(prefix_engine, [("seed", seed_prompt, 2)], "s")
        assert prefix_engine.prefix_index.used_pages == 5

        jobs = [(rid, p, 4) for rid, (p, _) in cases.items()]
        on = _serve_locally(prefix_engine, jobs, "on")
        off = _serve_locally(engine, jobs, "off")
        for rid, (_, want_hit) in cases.items():
            assert on[rid]["prefix_hit_tokens"] == want_hit, rid
            assert on[rid]["tokens"] == off[rid]["tokens"], rid
        assert all(off[r]["prefix_hit_tokens"] == 0 for r in off)

    def test_int8_pool_reuse_token_identical_admission(self,
                                                      tiny_params):
        """int8 pools: the pool stores the QUANTIZED page bytes +
        scales the publishing slot computed, and admission copies them
        back verbatim — so the reused continuation sees bit-identical
        cache state to a same-engine full prefill. (Cross-engine
        logits may differ at quantization boundaries; the documented
        int8 caveat in docs/serving.md. Here both legs run one
        engine.)"""
        eng = ServeEngine(
            TINY, strategy=Strategy(mesh=MeshPlan(data=-1),
                                    rule_set="llama"),
            serve_slots=2, prefill_chunk=8, max_seq=48, page_size=8,
            kv_precision="int8", prefix_pool_pages=8,
        )
        eng.prepare(tiny_params)
        seed_prompt = _prompt(32, seed=81)
        # leg 1: pool empty -> full prefill (and it publishes)
        first = _serve_locally(eng, [("a", seed_prompt, 4)], "l1")
        assert first["a"]["prefix_hit_tokens"] == 0
        # leg 2: same prompt -> 24-token hit, quantized pages copied
        second = _serve_locally(eng, [("a", seed_prompt, 4)], "l2")
        assert second["a"]["prefix_hit_tokens"] == 24
        assert second["a"]["tokens"] == first["a"]["tokens"]


class TestPrefixPoolLifecycle:
    """Retune/resize discipline: slot-only retunes carry the pool,
    chunk changes flush the index (page bytes depend on the chunk
    windows), pool-width changes rebuild, and eviction pressure under
    a tiny pool stays a logged degradation."""

    def test_retune_carry_flush_rebuild(self, tiny_params):
        eng = ServeEngine(
            TINY, strategy=Strategy(mesh=MeshPlan(data=-1),
                                    rule_set="llama"),
            serve_slots=4, prefill_chunk=8, max_seq=48, page_size=8,
            prefix_pool_pages=8,
        )
        eng.prepare(tiny_params)
        p = _prompt(24, seed=90)
        _serve_locally(eng, [("seed", p, 2)], "s")
        assert eng.prefix_index.used_pages == 3

        # slot-only retune: pool and index carry (no slot dimension)
        eng.retune(serve_slots=6, slot_map={})
        got, h = eng.prefix_match(p + _prompt(8, seed=91))
        assert got == 24 and h is not None
        eng.prefix_release(h)

        # chunk change: index flushed (stats survive), pool pages
        # unreachable; a released pre-flush handle dangles nothing
        hits_before = eng.prefix_index.hits
        eng.retune(prefill_chunk=4)
        assert eng.prefix_index.used_pages == 0
        assert eng.prefix_index.hits == hits_before
        eng.prefix_release(h)  # idempotent, post-flush

        # pool-width change: rebuilt empty at the new capacity
        eng.retune(prefix_pool_pages=4)
        assert eng.prefix_index.capacity == 4
        assert eng.prefix_index.used_pages == 0
        # pool off: the engine reports disabled and matches miss
        eng.retune(prefix_pool_pages=0)
        assert not eng.prefix_enabled()
        assert eng.prefix_match(p) == (0, None)

    def test_eviction_pressure_end_to_end(self, tiny_params):
        """A pool smaller than the working set: victims are LRU,
        every re-use after eviction is a clean miss-and-prefill, and
        completions stay bitwise against a pool-free engine."""
        eng = ServeEngine(
            TINY, strategy=Strategy(mesh=MeshPlan(data=-1),
                                    rule_set="llama"),
            serve_slots=2, prefill_chunk=8, max_seq=48, page_size=8,
            prefix_pool_pages=3,
        )
        eng.prepare(tiny_params)
        off = ServeEngine(
            TINY, strategy=Strategy(mesh=MeshPlan(data=-1),
                                    rule_set="llama"),
            serve_slots=2, prefill_chunk=8, max_seq=48, page_size=8,
        )
        off.prepare(tiny_params)
        # three distinct 24-token prompts = 9 pages over a 3-page pool
        prompts = {f"p{i}": _prompt(24, seed=95 + i) for i in range(3)}
        jobs = [(rid, p, 3) for rid, p in prompts.items()]
        a = _serve_locally(eng, jobs, "w1")
        b = _serve_locally(eng, jobs, "w2")
        want = _serve_locally(off, jobs, "off")
        for rid in prompts:
            assert a[rid]["tokens"] == want[rid]["tokens"], rid
            assert b[rid]["tokens"] == want[rid]["tokens"], rid
        st = eng.prefix_stats()
        assert st["evictions"] > 0
        assert st["used_pages"] <= 3


class TestPrefixRouterAffinity:
    def test_soft_affinity_homes_without_starvation(self):
        r = RequestRouter(lease_timeout_secs=120.0)
        shared = list(range(100, 116))  # >= the 16-token prefix key
        a_ids = [r.submit(shared + [i], 4, request_id=f"a{i}")
                 for i in range(4)]
        b_ids = [r.submit(list(range(200, 216)) + [i], 4,
                          request_id=f"b{i}") for i in range(2)]
        # node 0 leases first: claims the shared-prefix home
        first = [q["request_id"] for q in r.lease(0, 2)]
        assert first == a_ids[:2]
        # node 1: pass 1 skips node-0-homed requests, claims the B
        # prefix; pass 2 fills spare capacity FIFO (no starvation)
        second = [q["request_id"] for q in r.lease(1, 3)]
        assert second[:2] == b_ids
        assert second[2] == a_ids[2]  # capacity steal, FIFO
        # node 0 returns: the remaining A request is homed here
        third = [q["request_id"] for q in r.lease(0, 4)]
        assert third == [a_ids[3]]
        summary = r.prefix_summary()
        assert summary["affinity_routed"] >= 1
        # hit accounting rides complete(); conservation holds
        for n, rid in [(0, a_ids[0]), (0, a_ids[1]), (1, b_ids[0]),
                       (1, b_ids[1]), (1, a_ids[2]), (0, a_ids[3])]:
            r.complete(n, rid, [1, 2], ttft_s=0.1, e2e_s=0.2,
                       prefix_hit_tokens=16 if rid[0] == "a" else 0)
        summary = r.prefix_summary()
        assert summary["hits"] == 4
        assert summary["saved_prefill_tokens"] == 64
        assert summary["hit_rate"] == pytest.approx(4 / 6, abs=1e-3)
        rep = r.report()["requests"]
        assert rep["completed"] == 6 and rep["leased"] == 0

    def test_affinity_disabled_keeps_pure_fifo(self, monkeypatch):
        monkeypatch.setattr(get_context(), "serve_prefix_affinity",
                            False)
        r = RequestRouter()
        shared = list(range(16))
        rids = [r.submit(shared + [i], 2) for i in range(3)]
        assert [q["request_id"] for q in r.lease(1, 1)] == rids[:1]
        assert [q["request_id"] for q in r.lease(0, 2)] == rids[1:]


class TestPrefixPlannerPricing:
    SPEC = planner.ModelSpec(
        param_count=7e9, num_layers=8, hidden_size=64, seq_len=128,
        global_batch=1, num_heads=4, kv_heads=2)

    def test_hit_rate_discount_raises_tokens_per_s(self):
        off = planner.estimate_decode(self.SPEC, 8, 16, 8, 64)
        on = planner.estimate_decode(
            self.SPEC, 8, 16, 8, 64, prefix_pool_pages=16,
            page_size=8, prefix_hit_rate=0.8)
        assert on["tokens_per_s"] > off["tokens_per_s"]
        assert on["breakdown"]["prefix_hit_rate"] == 0.8
        # zero observed/expected hits -> the pool is pure cost, the
        # throughput term must NOT move (the optimizer's churn
        # tie-break then keeps the knob off)
        cold = planner.estimate_decode(
            self.SPEC, 8, 16, 8, 64, prefix_pool_pages=16,
            page_size=8, prefix_hit_rate=0.0)
        assert cold["tokens_per_s"] == off["tokens_per_s"]

    def test_discount_capped_by_pool_token_coverage(self):
        small = planner.estimate_decode(
            self.SPEC, 8, 16, 8, 64, prefix_pool_pages=1,
            page_size=8, prefix_hit_rate=1.0)
        big = planner.estimate_decode(
            self.SPEC, 8, 16, 8, 64, prefix_pool_pages=16,
            page_size=8, prefix_hit_rate=1.0)
        assert big["tokens_per_s"] > small["tokens_per_s"]

    def test_pool_bytes_charged_undivided_per_device(self):
        est = planner.estimate_decode(
            self.SPEC, 8, 16, 8, 64, prefix_pool_pages=16,
            page_size=8, prefix_hit_rate=0.5)
        pool = planner.serve_prefix_pool_bytes(self.SPEC, 16, 8)
        assert pool > 0
        assert est["breakdown"]["prefix_pool_bytes"] == pool
        assert est["cache_bytes_per_device"] == pytest.approx(
            est["cache_bytes"] / 8 + pool)
        # the same byte formula as the device-side spec
        spec = KVCacheSpec(num_layers=8, num_kv_heads=2, head_dim=16,
                           num_slots=16, page_size=8,
                           prefix_pool_pages=16)
        assert pool == spec.prefix_pool_bytes()


class TestPrefixKnobFamily:
    def test_optimizer_chooses_pool_with_prior_and_geometry(
            self, monkeypatch):
        monkeypatch.setattr(get_context(),
                            "serve_prefix_expected_hit_rate", 0.8)
        published = []
        opt = _optimizer(publish=published.append)
        opt.update_model_info(comm.ModelInfo(
            num_params=7_000_000_000, hidden_size=64, num_layers=2,
            seq_len=128))
        opt.update_serving_config(_serve_report(
            num_layers=2, kv_heads=2, head_dim=16,
            prefix_pool_pages=0, page_size=8))
        assert published
        cfg = published[-1]
        assert cfg.serve_prefix_pool_pages > 0
        last = [d for d in opt.decisions()
                if d["trigger"].startswith("serve:")][-1]
        assert last["chosen"]["prefix_pool_pages"] \
            == cfg.serve_prefix_pool_pages
        assert "|ppp=" in last["chosen"]["key"]

    def test_without_evidence_pool_stays_off(self, monkeypatch):
        monkeypatch.setattr(get_context(),
                            "serve_prefix_expected_hit_rate", 0.0)
        published = []
        opt = _optimizer(publish=published.append)
        opt.update_model_info(comm.ModelInfo(
            num_params=7_000_000_000, hidden_size=64, num_layers=2,
            seq_len=128))
        opt.update_serving_config(_serve_report(
            num_layers=2, kv_heads=2, head_dim=16,
            prefix_pool_pages=0, page_size=8))
        # whatever else the plan tunes, the pool knob is the
        # leave-unchanged sentinel: no evidence, no pool
        assert all(p.serve_prefix_pool_pages == -1 for p in published)

    def test_observed_hit_rate_overrides_the_prior(self, monkeypatch):
        """A worker reporting hit_rate=0 beats an optimistic prior:
        with zero observed benefit every pool width ties and the churn
        tie-break refuses to GROW the pool — the plan leaves the knob
        at its unchanged sentinel."""
        monkeypatch.setattr(get_context(),
                            "serve_prefix_expected_hit_rate", 0.9)
        published = []
        opt = _optimizer(publish=published.append)
        opt.update_model_info(comm.ModelInfo(
            num_params=7_000_000_000, hidden_size=64, num_layers=2,
            seq_len=128))
        opt.update_serving_config(_serve_report(
            num_layers=2, kv_heads=2, head_dim=16,
            prefix_pool_pages=24, page_size=8, prefix_hit_rate=0.0))
        assert all(p.serve_prefix_pool_pages == -1 for p in published)

    def test_hbm_gate_charges_pool_undivided(self, monkeypatch):
        """A budget that fits every slot pool (divided by world) but
        not the UNDIVIDED prefix pool: pool candidates are memory-
        rejected with their page count on the decision trail."""
        spec = planner.ModelSpec(
            param_count=10_000, num_layers=2, hidden_size=64,
            seq_len=128, global_batch=1, num_heads=4, kv_heads=2)
        slot_worst = planner.serve_cache_bytes(spec, 16, 64) / 8
        budget = slot_worst * 1.5
        monkeypatch.setattr(get_context(), "device_hbm_budget_bytes",
                            budget)
        monkeypatch.setattr(get_context(),
                            "serve_prefix_expected_hit_rate", 0.8)
        opt = _optimizer()
        opt.update_model_info(comm.ModelInfo(
            num_params=10_000, hidden_size=64, num_layers=2,
            seq_len=128))
        opt.update_serving_config(_serve_report(
            num_layers=2, kv_heads=2, head_dim=16,
            prefix_pool_pages=0, page_size=8))
        last = [d for d in opt.decisions()
                if d["trigger"].startswith("serve:")][-1]
        rejected = last["memory_rejected"]
        assert any(r.get("prefix_pool_pages", 0) > 0
                   for r in rejected)
        # and anything chosen fits WITH its pool charge
        chosen = last.get("chosen")
        if chosen:
            pool = planner.serve_prefix_pool_bytes(
                spec, chosen["prefix_pool_pages"], 8)
            slot = planner.serve_cache_bytes(
                spec, chosen["serve_slots"], 64) / 8
            assert slot + pool <= budget
