"""Batched multi-query execution with cross-query snippet dedup.

The paper's core observation — every query's answer "reveals some degree of
knowledge about the answer to another query" — also holds for the *scan*:
snippets from different queries in a workload overlap heavily (repeated
dashboards, shared group-by cells, popular predicate columns), so evaluating
each query's plan separately re-reads the same sample batches over and over.

``BatchExecutor`` fuses a whole workload into one scan. All the machinery
lives in the shared plan IR (``repro.aqp.plan``); this class just wires it
to one engine:

1. ``plan_workload`` decomposes every query into its ``LogicalPlan``
   (unsupported queries get their raw-only probe plan) and dedups identical
   snippets across queries into two fused ``SnippetBatch``es, keyed by the
   same content hash ``Synopsis`` uses (``snippet_key``);
2. two ``PhysicalPlan``s scan sample batches lazily, evaluating each batch
   EXACTLY ONCE for the union of snippets — supported queries through the
   executor's ``ScanPlacement`` (pure-jnp oracle, Pallas kernel, or the
   masked shape-agnostic sharded scan when a mesh is given), raw-only
   probes through pure ``eval_partials``;
3. ``replay_query`` replays queries in submission order against cumulative
   per-batch partials: improve via the synopsis, early-stop per query once
   its improved bound meets the target, and record raw answers — the same
   state transitions, in the same order, as query-at-a-time execution
   (which since the plan-IR refactor is literally a workload of one).

Learning is asynchronous and placement-aware: ``replay_query`` records raw
answers through the engine's ``SynopsisStore`` (``store.record``), which
enqueues them on each synopsis' background ingest thread — per shard when the
store is sharded — and ``execute_many`` returns without waiting for the
covariance builds. Each replayed ``store.improve_groups`` drains only the
involved synopses' pending batches (so the state transitions stay
deterministic and identical to the sequential engine); a full barrier
(``VerdictEngine.drain``) is only needed at snapshot/refit boundaries.

Because the scan path pads the snippet axis to fixed tiles
(``pad_snippets``), per-snippet partials are bitwise identical between the
fused scan and the single-query scan; the replay then performs the exact
per-query improvement/validation calls every path performs, so batched
answers equal sequential answers bit for bit while the number of
``eval_partials`` calls drops from sum(batches_used per query) to
max(batches_used over queries).
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

from jax.profiler import TraceAnnotation

from repro.aqp import queries as Q
from repro.aqp.executor import ScanPlacement, scan_placement
from repro.aqp.plan import (
    BatchStats,
    PhysicalPlan,
    QueryResult,
    plain_eval,
    plan_workload,
    replay_query,
)
from repro.core.types import SnippetBatch

__all__ = ["BatchExecutor", "BatchStats"]


class BatchExecutor:
    """Fused executor over one ``VerdictEngine`` (see module docstring).

    The scan routes through a ``ScanPlacement`` (``repro.aqp.executor``):
    pass ``placement=`` directly, or ``mesh=`` to build a
    ``ShardedScanPlacement`` over ``mesh_axis`` (shape-agnostic masked
    sharding — no divisibility precondition); with neither, the engine's
    own placement (local by default) is used. Stats of the latest call are
    kept in ``self.stats``.
    """

    def __init__(self, engine, mesh=None, mesh_axis: str = "data",
                 placement: ScanPlacement = None):
        self.engine = engine
        if placement is None:
            placement = (scan_placement(mesh, mesh_axis) if mesh is not None
                         else getattr(engine, "scan", None) or ScanPlacement())
        self.placement = placement
        self.mesh = placement.mesh  # back-compat aliases
        self.mesh_axis = placement.axis
        self.stats = BatchStats()

    # ---------------------------------------------------------------- scan
    def _eval(self, block, padded: SnippetBatch):
        return self.placement.eval_block(
            block, padded, local_eval=self.engine._eval_fn
        )

    # ------------------------------------------------------------- execute
    def execute_many(
        self,
        queries: Sequence[Q.AggQuery],
        target_rel_error: Optional[float] = None,
        max_batches: Optional[int] = None,
        stop_delta: Optional[float] = None,
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> List[QueryResult]:
        """``deadline_s``: per-query wall-clock budget, measured from each
        query's replay start (the shared scan amortizes across queries, so
        a query replaying over already-evaluated batches is nearly free; the
        deadline bounds the batches IT forces to be scanned). On expiry the
        best-so-far answer returns, ``degraded`` with a ``"deadline"``
        reason — every query resolves. ``tenant``: optional label threaded
        into the workload-intel per-tenant lookup/hit counters."""
        eng = self.engine
        max_batches = min(
            max_batches or eng.batches.n_batches, eng.batches.n_batches
        )
        results: List[Optional[QueryResult]] = [None] * len(queries)
        # Workload-intelligence pre-screen (repro.intel): queries served
        # from the semantic answer cache drop out of the fused batch BEFORE
        # planning/snippet dedup — they cost no probe, no scan, no improve
        # and no record. Miss queries flow through the unchanged lifecycle,
        # so their answers are bitwise-identical to a cache-disabled engine.
        intel = getattr(eng, "intel", None)
        live_idx = list(range(len(queries)))
        if intel is not None:
            live_idx = []
            for i, q in enumerate(queries):
                served = intel.lookup(
                    eng, q, target_rel_error=target_rel_error,
                    stop_delta=stop_delta, max_batches=max_batches,
                    tenant=tenant)
                if served is not None:
                    results[i] = served
                else:
                    live_idx.append(i)
        with TraceAnnotation("verdict.plan"):
            wp = plan_workload(eng, [queries[i] for i in live_idx])
            phys_main = PhysicalPlan(eng.batches, wp.fused, self._eval,
                                     stats=wp.stats)
            phys_raw = PhysicalPlan(eng.batches, wp.fused_raw, plain_eval,
                                    stats=wp.stats)
        self.stats = wp.stats
        for lp in wp.logical:
            deadline = (None if deadline_s is None
                        else time.monotonic() + float(deadline_s))
            q = live_idx[lp.index]
            with TraceAnnotation("verdict.replay", q=q):
                results[q] = replay_query(
                    eng, lp, phys_main if lp.supported else phys_raw,
                    target_rel_error=target_rel_error,
                    max_batches=max_batches, stop_delta=stop_delta,
                    deadline=deadline,
                )
        return results
