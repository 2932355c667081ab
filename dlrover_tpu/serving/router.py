"""The request router — the master's serving control plane.

The PR 9 ``BatchDatasetManager`` dispatch ledger, generalized from
shards to requests: enqueue (todo) → lease (doing) → complete (done),
with the same invariants re-pointed at serving:

  * a leased request belongs to exactly one worker until it completes
    or its lease EXPIRES (the shard-timeout machinery: a request
    stranded on a dead/wedged worker re-queues to a live one — counted
    and evented, because the re-lease re-decodes the prompt);
  * accounting is conservation-checked: every submitted request is
    queued, leased, or done at all times — ``dropped_total`` counts
    conservation violations and the resize wedge pins it at ZERO;
  * per-request latency lands in master-side histograms (TTFT,
    per-token, end-to-end), the serving twin of the shard
    dispatch→complete latency histogram.

Leases survive a live resize by construction: the worker process never
dies (PR 5 in-process reshard), so its leases simply keep ticking —
the router HOLDS them, and only the expiry scan (a genuinely dead
worker) ever takes a request back.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.telemetry import (
    EventKind,
    SpanName,
    emit_event,
    get_registry,
    names as tm,
    span,
)
from dlrover_tpu.telemetry.metrics import COUNT_BUCKETS, LATENCY_BUCKETS
from dlrover_tpu.telemetry.trace_context import new_trace_id

logger = get_logger("serving.router")

_id_seq = itertools.count()


def new_request_trace_id() -> str:
    """A per-request trace id, minted at submission: every lifecycle
    event of the request (router AND worker pids) carries it, so
    ``tpurun trace --events`` stitches one lane per request."""
    return "req-" + new_trace_id()[len("inc-"):]


@dataclass
class ServeRequest:
    request_id: str
    prompt: List[int]
    max_new_tokens: int
    eos_id: int = -1
    state: str = "queued"  # queued | leased | done
    node_id: int = -1
    trace_id: str = ""
    enqueue_ts: float = 0.0
    lease_ts: float = 0.0
    # when an expiry re-queued the request: queue-wait of the NEXT
    # lease is measured from here, not from the original enqueue
    requeue_ts: float = 0.0
    first_lease_ts: float = 0.0
    done_ts: float = 0.0
    releases: int = 0
    tokens: List[int] = field(default_factory=list)
    ttft_s: Optional[float] = None
    e2e_s: Optional[float] = None
    error_code: str = ""
    # speculative-decode ledger columns, worker-reported at
    # completion: drafted - accepted = wasted per request
    spec_drafted_tokens: int = 0
    spec_accepted_tokens: int = 0

    def wire(self) -> Dict[str, Any]:
        return {
            "request_id": self.request_id,
            "prompt": list(self.prompt),
            "max_new_tokens": self.max_new_tokens,
            "eos_id": self.eos_id,
            "trace_id": self.trace_id,
        }


class RequestRouter:
    def __init__(self, lease_timeout_secs: Optional[float] = None):
        from dlrover_tpu.common.config import get_context

        self._lock = threading.Lock()
        self._timeout = float(
            lease_timeout_secs if lease_timeout_secs is not None
            else getattr(get_context(), "serve_lease_timeout_secs", 120.0))
        self._queue: "deque[ServeRequest]" = deque()
        self._requests: Dict[str, ServeRequest] = {}
        self._node_touch: Dict[int, float] = {}
        # instance-local totals: the registry counters below are
        # process-wide (shared across router instances in tests); the
        # ledger must report THIS router's ledger
        self._n_submitted = 0
        self._n_completed = 0
        self._n_dropped = 0
        self._n_expired = 0
        # completions that carried the eviction error code (the
        # worker could not fit the request): counted so the live
        # ledger and the forensic --events view agree on all four of
        # submitted/completed/evicted/expired
        self._n_evicted = 0
        # bounded done-ledger: a long-lived serving master must not
        # retain every completed request's prompt+tokens forever (the
        # decision-trail deque precedent) — completion order, oldest
        # pruned past the cap. Totals above keep counting; only the
        # per-request records age out.
        self._done_order: "deque[str]" = deque()
        self._done_retention_cap = 4096
        # incremental state counts, updated at every transition: the
        # gauges/ledger must not rescan every tracked request under
        # the lock on the serving hot path
        self._live_counts = {"queued": 0, "leased": 0, "done": 0}
        # prefix-hit ledger (worker-reported at completion) + the soft
        # session-affinity map: prefix key -> the node whose pool
        # first served it. SOFT: correctness never depends on routing
        # (a worker without the pages exact-misses and prefills), so
        # pass 2 of lease() fills spare capacity FIFO from anywhere —
        # affinity can never starve a request.
        self._n_prefix_hits = 0
        self._n_prefix_hit_tokens = 0
        self._n_affinity_routed = 0
        # speculative-decode ledger (worker-reported at completion):
        # drafted = accepted + wasted — wasted is DERIVED, never
        # accumulated separately, so the conservation identity holds
        # by construction at the job grain and the per-request columns
        # must sum to it (what the conservation test pins)
        self._n_spec_drafted = 0
        self._n_spec_accepted = 0
        self._prefix_home: Dict[tuple, int] = {}
        self._prefix_home_cap = 4096
        self._affinity = bool(getattr(
            get_context(), "serve_prefix_affinity", True))
        reg = get_registry()
        self._c_submitted = reg.counter(
            tm.SERVE_REQUESTS_SUBMITTED,
            help="requests enqueued on the router")
        self._c_completed = reg.counter(
            tm.SERVE_REQUESTS_COMPLETED,
            help="requests completed by workers")
        self._c_dropped = reg.counter(
            tm.SERVE_REQUESTS_DROPPED,
            help="requests lost without completion or re-lease "
                 "(conservation violations — must stay 0)")
        self._c_expired = reg.counter(
            tm.SERVE_LEASES_EXPIRED,
            help="leases expired on a silent worker and re-queued")
        self._g_queued = reg.gauge(
            tm.SERVE_REQUESTS_QUEUED, help="requests waiting for a lease")
        self._g_leased = reg.gauge(
            tm.SERVE_REQUESTS_LEASED, help="requests leased to workers")
        self._h_ttft = reg.histogram(
            tm.SERVE_TTFT_TIME, buckets=LATENCY_BUCKETS,
            help="admit -> first token wall seconds")
        self._h_e2e = reg.histogram(
            tm.SERVE_E2E_TIME, buckets=LATENCY_BUCKETS,
            help="admit -> completion wall seconds")
        self._h_queue_wait = reg.histogram(
            tm.SERVE_QUEUE_WAIT_TIME, buckets=LATENCY_BUCKETS,
            help="enqueue (or re-queue) -> lease wall seconds")
        self._h_tpot = reg.histogram(
            tm.SERVE_TPOT_TIME, buckets=LATENCY_BUCKETS,
            help="inter-token seconds: (e2e - ttft) / (tokens - 1)")
        self._h_tokens = reg.histogram(
            tm.SERVE_TOKENS_PER_REQUEST, buckets=COUNT_BUCKETS,
            help="tokens generated per completed request")
        self._c_affinity = reg.counter(
            tm.SERVE_PREFIX_AFFINITY_ROUTED,
            help="requests leased to the node already homing their "
                 "prefix pages")

    # -- the three verbs -----------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int,
               request_id: str = "", eos_id: int = -1) -> str:
        with self._lock:
            rid = request_id or f"req-{next(_id_seq)}"
            if rid in self._requests:
                # idempotent re-submit (a retried RPC): keep the first
                return rid
            req = ServeRequest(
                request_id=rid, prompt=[int(t) for t in prompt],
                max_new_tokens=int(max_new_tokens), eos_id=int(eos_id),
                trace_id=new_request_trace_id(),
                enqueue_ts=time.time(),
            )
            self._requests[rid] = req
            self._queue.append(req)
            self._live_counts["queued"] += 1
            self._n_submitted += 1
            self._c_submitted.inc()
            self._refresh_gauges()
            emit_event(
                EventKind.SERVE_REQUEST_SUBMITTED,
                trace_id=req.trace_id, request_id=rid,
                prompt_tokens=len(req.prompt),
                max_new_tokens=req.max_new_tokens,
            )
            return rid

    # prefix-key grain for session affinity: enough leading tokens to
    # separate system prompts, few enough that a shared header still
    # collides into ONE home. Routing is advisory — the worker's radix
    # index does the exact-token comparison that decides a hit.
    _PREFIX_KEY_TOKENS = 16

    @classmethod
    def _prefix_key(cls, prompt: List[int]) -> tuple:
        return tuple(int(t) for t in prompt[:cls._PREFIX_KEY_TOKENS])

    def _select_for_lease(self, node_id: int,
                          want: int) -> List[ServeRequest]:
        """Pop up to ``want`` queued requests for ``node_id``. Pass 1
        (affinity on): FIFO over requests homed on this node or not
        yet homed (claiming a home as it goes); pass 2 fills any spare
        capacity FIFO regardless of home, so affinity skews placement
        but can never starve the queue or idle a worker."""
        want = max(0, int(want))
        if not self._affinity:
            out = []
            while self._queue and len(out) < want:
                out.append(self._queue.popleft())
            return out
        selected: List[ServeRequest] = []
        rest: List[ServeRequest] = []
        for req in self._queue:
            if len(selected) < want:
                key = self._prefix_key(req.prompt)
                home = self._prefix_home.get(key)
                if home is None or home == int(node_id):
                    if home == int(node_id):
                        self._n_affinity_routed += 1
                        self._c_affinity.inc()
                    self._prefix_home[key] = int(node_id)
                    while len(self._prefix_home) > self._prefix_home_cap:
                        self._prefix_home.pop(
                            next(iter(self._prefix_home)))
                    selected.append(req)
                    continue
            rest.append(req)
        while rest and len(selected) < want:
            # spare capacity: take foreign-homed work FIFO (the home
            # map is NOT rewritten — a capacity steal must not flap
            # the affinity of a busy prefix)
            selected.append(rest.pop(0))
        self._queue = deque(rest)
        return selected

    def lease(self, node_id: int, max_requests: int) -> List[Dict]:
        self.scan_expired_once()
        out = []
        leased_meta = []
        with self._lock, span(SpanName.SERVE_LEASE, node=int(node_id)):
            now = time.time()
            self._node_touch[int(node_id)] = now
            for req in self._select_for_lease(node_id, max_requests):
                req.state = "leased"
                self._live_counts["queued"] -= 1
                self._live_counts["leased"] += 1
                req.node_id = int(node_id)
                req.lease_ts = now
                if not req.first_lease_ts:
                    req.first_lease_ts = now
                wait = max(0.0, now - (req.requeue_ts
                                       or req.enqueue_ts))
                self._h_queue_wait.observe(wait)
                leased_meta.append((req.trace_id, req.request_id,
                                    req.releases, wait))
                out.append(req.wire())
            if out:
                self._refresh_gauges()
        for tid, rid, releases, wait in leased_meta:
            emit_event(
                EventKind.SERVE_REQUEST_LEASED,
                trace_id=tid, request_id=rid, lease_node=int(node_id),
                queue_wait_s=round(wait, 6),
                releases=releases,
            )
        return out

    def complete(self, node_id: int, request_id: str,
                 tokens: List[int], ttft_s: Optional[float] = None,
                 e2e_s: Optional[float] = None,
                 error_code: str = "",
                 prefix_hit_tokens: int = 0,
                 spec_drafted_tokens: int = 0,
                 spec_accepted_tokens: int = 0) -> bool:
        with self._lock, span(SpanName.SERVE_COMPLETE,
                              node=int(node_id)):
            self._node_touch[int(node_id)] = time.time()
            req = self._requests.get(request_id)
            if req is None or req.state == "done":
                return False  # a re-leased twin already completed it
            if req.state == "queued":
                # completed by the ORIGINAL worker after an expiry
                # re-queued it: accept the result and pull it back out
                # of the queue (no duplicate decode)
                try:
                    self._queue.remove(req)
                except ValueError:
                    pass
                self._live_counts["queued"] -= 1
            else:
                self._live_counts["leased"] -= 1
            req.state = "done"
            self._live_counts["done"] += 1
            req.done_ts = time.time()
            req.tokens = [int(t) for t in tokens or []]
            req.ttft_s, req.e2e_s = ttft_s, e2e_s
            req.error_code = error_code or ""
            self._n_completed += 1
            if error_code == "SERVE_REQUEST_EVICTED":
                self._n_evicted += 1
            if prefix_hit_tokens and int(prefix_hit_tokens) > 0:
                self._n_prefix_hits += 1
                self._n_prefix_hit_tokens += int(prefix_hit_tokens)
            # spec columns accumulate INSIDE the done-guard, like the
            # counters above: a re-leased twin's duplicate completion
            # (the guard's False branch) must not double-charge the
            # ledger, and a worker whose verify step failed reported
            # ZERO drafted for those steps — its draft credit was
            # restored at the source, so conservation holds here too
            drafted = max(0, int(spec_drafted_tokens or 0))
            accepted = min(max(0, int(spec_accepted_tokens or 0)),
                           drafted)
            req.spec_drafted_tokens = drafted
            req.spec_accepted_tokens = accepted
            self._n_spec_drafted += drafted
            self._n_spec_accepted += accepted
            self._done_order.append(req.request_id)
            while len(self._done_order) > self._done_retention_cap:
                if self._requests.pop(self._done_order.popleft(),
                                      None) is not None:
                    self._live_counts["done"] -= 1
            self._c_completed.inc()
            tpot = None
            if ttft_s is not None:
                self._h_ttft.observe(float(ttft_s))
            if e2e_s is not None:
                self._h_e2e.observe(float(e2e_s))
                if ttft_s is not None and len(req.tokens) > 1:
                    # the decode-phase inter-token latency: the TTFT
                    # (queue + prefill + first token) is subtracted so
                    # TPOT judges ONLY the steady decode stream
                    tpot = max(0.0, (float(e2e_s) - float(ttft_s))
                               / (len(req.tokens) - 1))
                    self._h_tpot.observe(tpot)
            self._h_tokens.observe(float(len(req.tokens)))
            self._refresh_gauges()
            emit_event(
                EventKind.SERVE_REQUEST_COMPLETED,
                trace_id=req.trace_id, request_id=request_id,
                complete_node=int(node_id), tokens=len(req.tokens),
                ttft_s=ttft_s, e2e_s=e2e_s,
                tpot_s=round(tpot, 6) if tpot is not None else None,
                completed_error_code=error_code or None,
                spec_drafted=drafted or None,
                spec_accepted=accepted if drafted else None,
            )
            return True

    def touch(self, node_id: int):
        with self._lock:
            self._node_touch[int(node_id)] = time.time()

    # -- expiry (the shard-timeout machinery, re-pointed) --------------------

    def scan_expired_once(self, timeout_secs: Optional[float] = None
                          ) -> List[str]:
        """Re-queue leased requests whose worker has been silent past
        the lease timeout — the dead-worker re-lease path. The request
        re-decodes from its prompt on the next worker (counted and
        evented: duplicate work, never a drop)."""
        timeout = float(timeout_secs if timeout_secs is not None
                        else self._timeout)
        if timeout <= 0:
            return []
        requeued: List[str] = []
        with self._lock:
            now = time.time()
            for req in self._requests.values():
                if req.state != "leased":
                    continue
                last = max(req.lease_ts,
                           self._node_touch.get(req.node_id, 0.0))
                if now - last <= timeout:
                    continue
                req.state = "queued"
                self._live_counts["leased"] -= 1
                self._live_counts["queued"] += 1
                req.releases += 1
                req.requeue_ts = now
                stranded_node = req.node_id
                req.node_id = -1
                self._queue.append(req)
                requeued.append(req.request_id)
                self._n_expired += 1
                self._c_expired.inc()
                emit_event(
                    EventKind.SERVE_LEASE_EXPIRED,
                    error_code="SERVE_LEASE_EXPIRED",
                    trace_id=req.trace_id,
                    request_id=req.request_id,
                    stranded_node=stranded_node,
                    lease_age_s=round(now - last, 1),
                )
            if requeued:
                self._refresh_gauges()
                logger.warning("re-leased %d stranded requests: %s",
                               len(requeued), requeued[:8])
        return requeued

    # -- accounting ----------------------------------------------------------

    def _counts(self) -> Dict[str, int]:
        return dict(self._live_counts)

    def _refresh_gauges(self):
        c = self._counts()
        self._g_queued.set(c["queued"])
        self._g_leased.set(c["leased"])
        # conservation: every submitted request is in exactly one
        # state. TODAY this cannot fire (the three states are
        # exhaustive by construction) — it guards FUTURE code paths
        # that remove entries; the PRIMARY zero-drop check is the
        # completed-equals-submitted arithmetic the resize wedge
        # (tests/test_serving.py) pins, plus `oldest_lease_age_s` in the
        # report for leases a live-but-stuck worker never completes.
        lost = len(self._requests) - sum(c.values())
        if lost > 0:
            self._n_dropped += lost
            self._c_dropped.inc(lost)
            logger.error("request conservation violated: %d lost", lost)

    def dropped(self) -> int:
        with self._lock:
            return self._n_dropped

    def queue_depth(self) -> int:
        with self._lock:
            return self._live_counts["queued"]

    def slo_observations(self) -> Dict[str, Any]:
        """The SLO engine's per-evaluation snapshot: current queue
        depth plus the CUMULATIVE TTFT histogram counts (the engine
        diffs consecutive snapshots into rolling-window percentiles —
        the node-series discipline)."""
        with self._lock:
            counts = self._h_ttft.snapshot_counts()
            return {
                "queue_depth": self._live_counts["queued"],
                "leased": self._live_counts["leased"],
                "ttft_bounds": list(getattr(self._h_ttft, "bounds",
                                            ()) or ()),
                "ttft_counts": (list(counts)
                                if counts is not None else None),
            }

    def report(self) -> Dict[str, Any]:
        """The ``tpurun requests`` ledger."""
        from dlrover_tpu.telemetry.metrics import percentile_from_counts

        with self._lock:
            counts = self._counts()
            per_node: Dict[int, Dict[str, int]] = {}
            for r in self._requests.values():
                if r.node_id < 0:
                    continue
                row = per_node.setdefault(
                    r.node_id, {"leased": 0, "done": 0, "tokens": 0})
                if r.state == "leased":
                    row["leased"] += 1
                elif r.state == "done":
                    row["done"] += 1
                    row["tokens"] += len(r.tokens)

            def pct(h, q):
                b = getattr(h, "bounds", None)
                cts = h.snapshot_counts()
                if not b or cts is None:
                    return None
                return percentile_from_counts(b, cts, q)

            now = time.time()
            oldest_lease = max(
                (now - r.first_lease_ts
                 for r in self._requests.values()
                 if r.state == "leased" and r.first_lease_ts), default=0.0)
            return {
                "requests": {
                    **counts,
                    "submitted": self._n_submitted,
                    "completed": self._n_completed,
                    "dropped": self._n_dropped,
                    "leases_expired": self._n_expired,
                    "evicted": self._n_evicted,
                    # a live-but-stuck worker keeps touching, so its
                    # lease never expires: the age of the OLDEST open
                    # lease is the operator's visibility into that
                    # failure mode (expiry only catches SILENT workers)
                    "oldest_lease_age_s": round(oldest_lease, 1),
                },
                "latency": {
                    "ttft_p50_s": pct(self._h_ttft, 0.50),
                    "ttft_p95_s": pct(self._h_ttft, 0.95),
                    "e2e_p50_s": pct(self._h_e2e, 0.50),
                    "e2e_p95_s": pct(self._h_e2e, 0.95),
                    "queue_wait_p50_s": pct(self._h_queue_wait, 0.50),
                    "queue_wait_p95_s": pct(self._h_queue_wait, 0.95),
                    "tpot_p50_s": pct(self._h_tpot, 0.50),
                    "tpot_p95_s": pct(self._h_tpot, 0.95),
                },
                "nodes": {str(n): v
                          for n, v in sorted(per_node.items())},
                "prefix": self._prefix_summary_locked(),
                "spec": self._spec_summary_locked(),
            }

    def _prefix_summary_locked(self) -> Dict[str, Any]:
        done = max(0, self._n_completed - self._n_evicted)
        return {
            "hits": self._n_prefix_hits,
            "saved_prefill_tokens": self._n_prefix_hit_tokens,
            "hit_rate": (round(self._n_prefix_hits / done, 4)
                         if done else 0.0),
            "affinity_routed": self._n_affinity_routed,
        }

    def prefix_summary(self) -> Dict[str, Any]:
        """The prefix-hit ledger alone (the ``serve slo`` view rides
        it next to the SLO verdicts)."""
        with self._lock:
            return self._prefix_summary_locked()

    def _spec_summary_locked(self) -> Dict[str, Any]:
        drafted = self._n_spec_drafted
        accepted = self._n_spec_accepted
        return {
            "drafted_tokens": drafted,
            "accepted_tokens": accepted,
            # derived, so drafted = accepted + wasted by construction
            # at the job grain; the retained per-request columns must
            # sum to these totals (the conservation test's check)
            "wasted_tokens": drafted - accepted,
            "accept_rate": (round(accepted / drafted, 4)
                            if drafted else -1.0),
        }

    def spec_summary(self) -> Dict[str, Any]:
        """The speculative-decode ledger alone."""
        with self._lock:
            return self._spec_summary_locked()
