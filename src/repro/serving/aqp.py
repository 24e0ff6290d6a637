"""Microbatching AQP front: collect operator queries, flush one fused scan.

The serving-side counterpart of ``repro.aqp.batch``: concurrent dashboard
clients submit ``AggQuery``s; the service coalesces up to ``max_batch``
requests and executes them through ``BatchExecutor`` under the service-wide
``target_rel_error``, so the relation's sample batches are scanned once per
flush instead of once per request. Tickets resolve to ``QueryResult``s after
the flush — the classic serving microbatch pattern (cf. decode-step batching
in ``repro.serving.engine``) applied to query answering.

Concurrency: the queue and ticket bookkeeping mutate only under the service
lock, the engine itself is driven under a separate execution lock (pass
``engine_lock=`` to share it between services whose engines share learned
state — the multi-tenant front does), and every ticket carries an event so
``Ticket.result()`` from one thread waits correctly for a flush running on
another. Every ticket resolves exactly once (``Ticket.resolutions``).

Fault isolation (the serving half of the degraded-mode contract): one poison
query can no longer strand its microbatch. ``flush`` retries a failed fused
execution with bounded exponential backoff (transient faults — e.g. a
``max_fires``-limited injected fault — clear on retry), then BISECTS the
batch to isolate the poison query, which resolves as a typed
``FailedAnswer`` on its own ticket while every other ticket still gets its
real answer. A ``finally`` backstop guarantees no ticket ever hangs, even if
the isolation machinery itself dies. Per-query wall-clock ``deadline_s``
(threaded from ``ErrorBudget.deadline_s``) bounds response time: on expiry
the best-so-far answer returns with its honest wider CI.

Observability: cumulative counters (``counters()``: flushed queries, the
time tickets waited in the queue, prescreen lookups and the time they waited
for the engine lock, retries, bisects) and host spans on the profiler's
clock (``verdict.prescreen``, ``verdict.flush``, ``verdict.resolve``), which
record only while a ``jax.profiler`` session is active. Durations come from
the injected ``clock``, so tests can script them.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from repro.aqp.batch import BatchExecutor, BatchStats
from repro.aqp.queries import AggQuery
from repro.verdict.answer import FailedAnswer


@dataclasses.dataclass
class Ticket:
    """Handle for one submitted query; resolved by the owning flush.

    The result is stored on the ticket itself, so a long-lived service
    retains nothing once callers drop their tickets. ``resolutions`` counts
    resolve calls — the exactly-once contract the concurrency tests pin.
    ``req`` is the front's request id (joins the request's spans with the
    flush that answered it); ``_enqueued`` the service clock at enqueue.
    """

    _service: "AqpService"
    req: Optional[int] = None
    _enqueued: float = 0.0
    _result: object = None
    _done: bool = False
    resolutions: int = 0
    _event: threading.Event = dataclasses.field(
        default_factory=threading.Event)

    def result(self, timeout: Optional[float] = None):
        """The query's ``QueryResult`` (flushes the queue if still pending).

        Safe under concurrency: if another thread's flush owns this ticket's
        batch, the local ``flush()`` finds an empty queue and this call
        waits on the ticket's event instead of returning a premature None.
        """
        if not self._done:
            self._service.flush()
        if not self._event.wait(timeout):
            raise TimeoutError("ticket unresolved after "
                               f"{timeout}s (flush still in flight?)")
        return self._result

    def _resolve(self, result) -> None:
        self._result = result
        self.resolutions += 1
        self._done = True
        self._event.set()


class AqpService:
    """Thread-safe synchronous microbatcher over one ``VerdictEngine``.

    ``max_batch``: auto-flush threshold; ``target_rel_error`` /
    ``max_batches`` / ``stop_delta``: the error-budget contract applied to
    every flush (per the batched engine's per-query early stopping);
    ``mesh``: optional device mesh for the sharded scan path;
    ``tenant``: optional tenant label threaded into the workload-intel
    per-tenant counters; ``engine_lock``: pass one lock to every service
    sharing an engine (shared-store tenancy) so engine execution — and the
    intel prescreen that mutates shared cache state — serializes across
    them while isolated engines keep scanning in parallel; ``clock``: the
    monotonic time source of the wait counters (inject a fake to script
    them).
    """

    def __init__(self, engine, max_batch: int = 64,
                 target_rel_error: Optional[float] = None, mesh=None,
                 max_batches: Optional[int] = None,
                 stop_delta: Optional[float] = None,
                 result_wrapper=None,
                 deadline_s: Optional[float] = None,
                 max_retries: int = 2,
                 backoff_base_s: float = 0.01,
                 backoff_max_s: float = 0.5,
                 tenant: Optional[str] = None,
                 engine_lock: Optional[threading.Lock] = None,
                 clock=time.monotonic):
        # Accept either a raw VerdictEngine or a repro.verdict Session.
        self.engine = getattr(engine, "engine", engine)
        self.max_batch = int(max_batch)
        self.target_rel_error = target_rel_error
        self.max_batches = max_batches
        self.stop_delta = stop_delta
        # Per-query wall-clock deadline (ErrorBudget.deadline_s): expiry
        # returns the best-so-far answer, degraded + honest, never blocks.
        self.deadline_s = deadline_s
        # Slice retry budget + bounded exponential backoff between attempts
        # (the failed fused execution retries WHOLE first — transient faults
        # clear without bisecting — then bisection isolates persistence).
        self.max_retries = int(max_retries)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        # Applied to every QueryResult before it lands on a ticket —
        # Session.serve passes QueryAnswer.from_result so facade users get
        # the same typed answers session.execute returns.
        self.result_wrapper = result_wrapper
        self.tenant = tenant
        self.executor = BatchExecutor(self.engine, mesh=mesh)
        self._queue: List[tuple] = []  # (query, ticket) pairs
        # Queue/counter bookkeeping lock (never held across an engine call).
        self._lock = threading.Lock()
        # Engine execution lock: one flush (or prescreen) drives the engine
        # at a time; shared across services when their engines are shared.
        self._exec_lock = engine_lock if engine_lock is not None \
            else threading.Lock()
        self._clock = clock
        self.flushes = 0
        # Queries resolved at submit() by the workload-intelligence answer
        # cache (repro.intel) — they never entered a microbatch.
        self.prescreened = 0
        # Cumulative, under ``_lock``: tickets taken by flushes and their
        # summed enqueue -> queue-swap wait; prescreen lookups and their
        # summed wait for the engine lock; slice retries and bisections.
        self.flushed_queries = 0
        self.queue_wait_s = 0.0
        self.prescreen_lookups = 0
        self.prescreen_lock_wait_s = 0.0
        self.retries = 0
        self.bisects = 0
        self.last_stats: Optional[BatchStats] = None

    @property
    def pending(self) -> int:
        """Queries waiting for the next flush."""
        return len(self._queue)

    def submit(self, query: AggQuery, req: Optional[int] = None) -> Ticket:
        """Enqueue one query; auto-flushes when the microbatch is full.

        Accepts an ``AggQuery`` or anything with ``.build()`` (the facade's
        ``QueryBuilder``). Thread-safe: the append and the threshold check
        happen under one lock, so concurrent submitters can neither lose an
        entry nor double-flush the same batch. ``req``: the caller's request
        id, carried on the ticket and into the spans.
        """
        if not isinstance(query, AggQuery) and hasattr(query, "build"):
            query = query.build()
        ticket = Ticket(self, req=req)
        # Workload-intelligence pre-screen: a semantic-cache hit resolves
        # the ticket immediately — it never occupies a microbatch slot, so
        # repeated dashboard queries stop forcing flush cycles at all. The
        # lookup mutates shared LRU/counter state, so it runs under the
        # engine lock like every other engine-state access.
        intel = getattr(self.engine, "intel", None)
        if intel is not None:
            with TraceAnnotation("verdict.prescreen", req=req):
                t0 = self._clock()
                with self._exec_lock:
                    waited = self._clock() - t0
                    served = intel.lookup(
                        self.engine, query,
                        target_rel_error=self.target_rel_error,
                        stop_delta=self.stop_delta,
                        max_batches=self.max_batches, tenant=self.tenant)
            with self._lock:
                self.prescreen_lookups += 1
                self.prescreen_lock_wait_s += waited
            if served is not None:
                if self.result_wrapper is not None:
                    served = self.result_wrapper(served)
                with self._lock:
                    self.prescreened += 1
                ticket._resolve(served)
                return ticket
        with self._lock:
            ticket._enqueued = self._clock()
            self._queue.append((query, ticket))
            full = len(self._queue) >= self.max_batch
        if full:
            self.flush()
        return ticket

    def _execute_slice(self, queries: List[AggQuery]) -> List:
        return self.executor.execute_many(
            queries,
            target_rel_error=self.target_rel_error,
            max_batches=self.max_batches,
            stop_delta=self.stop_delta,
            deadline_s=self.deadline_s,
            tenant=self.tenant,
        )

    def _resolve(self, queries: List[AggQuery], idxs: List[int],
                 results: List, counts: Dict[int, int],
                 top: bool = True) -> None:
        """Fill ``results[i]`` for every ``i`` in ``idxs``: on failure retry
        the SAME slice with bounded exponential backoff first (a transient
        fault clears on re-run without costing the O(log n) bisect), then
        bisect to isolate the poison query, and give a terminal failure a
        typed ``FailedAnswer`` — never an exception.

        Bisected sub-slices skip the multi-query retry (the transient
        hypothesis was already spent at the top level); single queries
        always retry, so a poison query gets its full budget before the
        typed failure. ``counts`` tracks ACTUAL executions per query index —
        ``FailedAnswer.attempts`` reports exactly how many times the query
        ran, not a retry-loop upper bound.

        Re-running a slice after a mid-batch failure can re-record some
        queries' raw answers; recording is idempotent at the synopsis level
        (duplicate snippets refresh LRU stamps and keep the better answer),
        so isolation never corrupts learned state.
        """
        def run():
            for i in idxs:
                counts[i] = counts.get(i, 0) + 1
            return self._execute_slice([queries[i] for i in idxs])

        try:
            out = run()
        except BaseException as e:  # noqa: BLE001 — isolate, then type it
            out = None
            retries = self.max_retries if (top or len(idxs) == 1) else 0
            for attempt in range(retries):
                time.sleep(min(self.backoff_base_s * 2 ** attempt,
                               self.backoff_max_s))
                with self._lock:
                    self.retries += 1
                try:
                    out = run()
                    break
                except BaseException as retry_e:  # noqa: BLE001
                    e = retry_e
            if out is None:
                if len(idxs) > 1:
                    with self._lock:
                        self.bisects += 1
                    mid = len(idxs) // 2
                    self._resolve(queries, idxs[:mid], results, counts,
                                  top=False)
                    self._resolve(queries, idxs[mid:], results, counts,
                                  top=False)
                else:
                    results[idxs[0]] = FailedAnswer(
                        error=repr(e), error_type=type(e).__name__,
                        attempts=counts[idxs[0]])
                return
        for i, r in zip(idxs, out):
            results[i] = r

    def flush(self) -> List:
        """Execute all pending queries in one fused scan.

        Every ticket RESOLVES, unconditionally and exactly once: to its
        (possibly wrapped) ``QueryResult``, or to a typed ``FailedAnswer``
        if its query keeps failing after retries and bisect isolation. The
        happy path is one fused ``execute_many`` exactly as before;
        isolation only engages on failure. Concurrent flushes serialize on
        the engine lock; the queue swap is atomic, so two racing flushes
        split the pending work instead of double-executing it.
        """
        with self._exec_lock:
            with self._lock:
                batch, self._queue = self._queue, []
                if batch:
                    swap = self._clock()
                    self.flushed_queries += len(batch)
                    self.queue_wait_s += sum(swap - t._enqueued
                                             for _, t in batch)
            if not batch:
                return []
            # Space-separated: the profiler splits span arguments at commas.
            reqs = " ".join(str(t.req) for _, t in batch if t.req is not None)
            with TraceAnnotation("verdict.flush", flush=self.flushes + 1,
                                 queries=len(batch), reqs=reqs):
                out = self._flush_batch(batch)
        return out

    def _flush_batch(self, batch: List[tuple]) -> List:
        """Resolve every ticket of one swapped-out batch (engine lock held)."""
        queries = [q for q, _ in batch]
        results: List = [None] * len(batch)
        counts: Dict[int, int] = {}
        try:
            self._resolve(queries, list(range(len(batch))), results, counts)
        finally:
            # Backstop: no ticket may ever hang or silently carry None,
            # even if the isolation machinery itself raised.
            with TraceAnnotation("verdict.resolve"):
                out = []
                for (_, ticket), res in zip(batch, results):
                    if res is None:
                        res = FailedAnswer(
                            error="flush aborted before this query resolved",
                            error_type="RuntimeError", attempts=0)
                    elif (self.result_wrapper is not None
                          and not isinstance(res, FailedAnswer)):
                        res = self.result_wrapper(res)
                    ticket._resolve(res)
                    out.append(res)
                self.last_stats = self.executor.stats
                with self._lock:
                    self.flushes += 1
        return out

    def execute(self, queries: List[AggQuery]) -> List:
        """Convenience: submit a workload and return its results in order."""
        tickets = [self.submit(q) for q in queries]
        self.flush()
        return [t.result() for t in tickets]

    def drain(self):
        """Barrier over the engine's async synopsis ingest.

        Flushes never wait for learning — answers return while covariance
        builds catch up on the ingest threads (across every shard when the
        engine's store is sharded). Call this only at snapshot boundaries
        (checkpointing, refit, shutdown) where the fully-applied learned
        state is required.
        """
        self.engine.drain()

    def refit(self, **kw):
        """Offline learning boundary: drain pending ingest, then refit."""
        self.engine.refit(**kw)

    def heal(self, manager=None, step: Optional[int] = None) -> dict:
        """Heal quarantined synopses (optionally from a checkpoint's last
        good state) and rejoin them to serving; ``{state_key: healed}``."""
        return self.engine.heal(manager, step)

    def snapshot(self, manager, step: int):
        """Checkpoint the learned state (drains first; see repro.ft).

        Rides the store's structured-key, shard-tagged payload: a snapshot
        taken by a sharded service restores into a local one (and onto a
        different mesh shape) unchanged.
        """
        self.engine.save_synopses(manager, step)

    def counters(self) -> dict:
        """One consistent snapshot of the microbatching counters.

        ``flushes``; ``pending`` (queued now); ``prescreened`` (served by
        the answer cache at submit); ``flushed_queries`` (tickets taken by
        flushes) and ``queue_wait_s`` (their summed wait from enqueue to the
        queue swap); ``prescreen_lookups`` and ``prescreen_lock_wait_s``
        (their summed wait for the engine lock); ``retries`` and
        ``bisects`` of failed slices. All but ``pending`` are cumulative.
        """
        with self._lock:
            return {
                "flushes": self.flushes,
                "pending": len(self._queue),
                "prescreened": self.prescreened,
                "flushed_queries": self.flushed_queries,
                "queue_wait_s": self.queue_wait_s,
                "prescreen_lookups": self.prescreen_lookups,
                "prescreen_lock_wait_s": self.prescreen_lock_wait_s,
                "retries": self.retries,
                "bisects": self.bisects,
            }

    def stats(self) -> dict:
        """Operator snapshot: store placement/occupancy/back-pressure plus
        this service's microbatching counters and serving health."""
        from repro.ft import faults

        intel = getattr(self.engine, "intel", None)
        return {
            "store": self.engine.store.stats(),
            "tenant": self.tenant,
            **self.counters(),
            "health": {
                "quarantined": self.engine.store.quarantined(),
                "faults": faults.stats(),
            },
            "intel": (intel.stats() if intel is not None
                      else {"enabled": False}),
        }
