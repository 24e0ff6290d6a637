"""VerdictEngine: the end-to-end DBL query engine (paper Figure 2, Algorithm 2).

Workflow per query:
  1. support check (§2.2) — unsupported queries bypass inference entirely;
  2. decompose into snippets (§2.3), discovering group-by values from the
     first sample batch;
  3. online aggregation over sample batches; after each batch the raw
     answers are improved via the per-aggregate-function synopsis model and
     validated (Appendix B); stop early once the improved error bound meets
     the target — the source of the paper's speedups;
  4. insert the final raw answers into the synopsis (the model learns from
     *raw* answers, never from its own outputs).

The lifecycle itself lives in the shared plan IR (``repro.aqp.plan``), ALL
learned state lives behind the ``SynopsisStore`` protocol
(``repro.core.store``), and the scan routes through a ``ScanPlacement``
(``repro.aqp.executor``): ``execute(q)`` is literally
``execute_many([q])[0]``, so the engine holds only the store, the scan
placement, the engine-level config, and the sample-batch stream. Pass
``store=`` (an instance or a ``(schema, config) -> SynopsisStore``
factory) and/or ``scan=`` to choose placement per plane —
``LocalSynopsisStore`` + local ``ScanPlacement`` by default,
``ShardedSynopsisStore`` + ``ShardedScanPlacement`` for mesh placement
(``repro.verdict.connect`` wires both from its ``mesh=`` argument; the
sharded scan accepts any relation/mesh combination via masked tuple
padding).

``learning=False`` turns the engine into the NoLearn baseline of §8.1.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from repro.aqp import queries as Q
from repro.aqp.executor import ScanPlacement, eval_partials
from repro.aqp.plan import QueryResult  # noqa: F401 — canonical home is the plan IR
from repro.aqp.relation import Relation
from repro.aqp.sampler import SampleBatches, build_sample
from repro.core.store import (
    LocalSynopsisStore,
    SynopsisStore,
    agg_key,
    group_rows,
)
from repro.core.synopsis import MIN_FILL_BUCKET, MIN_Q_BUCKET, Synopsis
from repro.core.types import (
    ImprovedAnswer,
    RawAnswer,
    Schema,
    SnippetBatch,
    pad_snippets,
)


@dataclasses.dataclass
class EngineConfig:
    sample_rate: float = 0.1
    n_batches: int = 10
    capacity: int = 2000  # C_g
    n_max: int = 1000  # N^max
    delta_v: float = 0.99
    report_delta: float = 0.95
    learning: bool = True
    seed: int = 0
    use_kernels: bool = False  # route hot paths through the Pallas kernels
    async_ingest: bool = True  # learn on the background ingest thread
    ingest_max_pending: int = 64  # back-pressure bound on pending ingest batches
    # Smallest serve-path tiles (power-of-two ladder floors): fills/batches
    # below these share one compiled program. Per-deployment knobs — the
    # first step of the adaptive bucket policy (ROADMAP).
    min_fill_bucket: int = MIN_FILL_BUCKET
    min_q_bucket: int = MIN_Q_BUCKET


class VerdictEngine:
    def __init__(
        self,
        relation: Relation,
        config: Optional[EngineConfig] = None,
        store=None,
        scan: Optional[ScanPlacement] = None,
        intel=None,
    ):
        self.relation = relation
        self.schema: Schema = relation.schema
        self.config = config or EngineConfig()
        # Optional workload-intelligence plane (repro.intel.WorkloadIntel):
        # semantic answer cache + serve-path router. None (the default)
        # keeps every path bit-for-bit the historical engine; the plan
        # lifecycle and the batch executor consult it via getattr, so the
        # core never imports the intel package.
        self.intel = intel
        # The scan plane's placement seam (repro.aqp.executor.ScanPlacement):
        # every block evaluation routes through it, mirroring how all
        # learned state routes through `store`. Local by default;
        # `repro.verdict.connect(..., mesh=...)` passes a sharded one.
        self.scan: ScanPlacement = scan or ScanPlacement()
        self.batches: SampleBatches = build_sample(
            relation,
            rate=self.config.sample_rate,
            n_batches=self.config.n_batches,
            seed=self.config.seed,
        )
        if store is None:
            self.store: SynopsisStore = LocalSynopsisStore(
                self.schema, self.config)
        elif callable(store) and not isinstance(store, SynopsisStore):
            # a (schema, config) -> SynopsisStore factory
            self.store = store(self.schema, self.config)
        else:
            # an instance — SynopsisStore subclass or any duck-typed
            # implementation of the store protocol
            self.store = store
        self._eval_fn = eval_partials
        if self.config.use_kernels:
            # The fused masked-scan kernel: predicate compare, categorical
            # membership, validity masking and partials accumulation in one
            # VMEM pass — bitwise-equal to ``eval_partials`` in interpret
            # mode (the canonical ``masked_tile_fold`` reduction).
            from repro.kernels.fused_masked_scan import ops as fms_ops

            self._eval_fn = fms_ops.eval_partials_fused

    # ------------------------------------------------------------- synopses
    @property
    def synopses(self) -> Dict[tuple, Synopsis]:
        """Deprecated: the raw key → ``Synopsis`` mapping.

        The store is the only supported access path to learned state; this
        shim survives for external callers and returns the store's live
        mapping (reads and in-place synopsis mutation keep working).
        """
        warnings.warn(
            "VerdictEngine.synopses is deprecated; go through "
            "VerdictEngine.store (repro.core.store.SynopsisStore)",
            DeprecationWarning, stacklevel=2,
        )
        return self.store.synopses

    def synopsis_for(self, agg: int, measure: int) -> Synopsis:
        return self.store.for_key(agg_key(agg, measure))

    def drain(self):
        """Barrier over the store's async ingest (snapshot/refit boundary)."""
        self.store.drain()

    def refit(self, steps: int = 150, lr: float = 0.1, learn_sigma: bool = False):
        """Offline learning pass (paper Algorithm 1). Drains async ingest."""
        self.store.refit(steps=steps, lr=lr, learn_sigma=learn_sigma)

    def ingest_stats(self) -> Dict[str, dict]:
        """Per-synopsis async-ingest back-pressure telemetry (structured
        ``"agg<k>-measure<m>"`` keys; see ``repro.core.store.state_key``)."""
        return self.store.ingest_stats()

    def heal(self, manager=None, step: Optional[int] = None) -> Dict[str, bool]:
        """Heal every quarantined synopsis and rejoin it to serving.

        With a ``CheckpointManager``, quarantined keys restore from the
        last good committed checkpoint (``restore_blind``) and replay their
        parked batches on top; without one (or for keys absent from the
        checkpoint) they rebuild from their own row arrays. Returns
        ``{state_key: healed}`` for the keys that were quarantined.
        """
        states = None
        if manager is not None:
            try:
                states, _ = manager.restore_blind(step)
            except Exception as e:  # noqa: BLE001 — degrade to rebuild
                # No committed checkpoint (or none intact): heal from the
                # synopses' own row arrays instead of failing the heal.
                warnings.warn(
                    f"heal(): checkpoint restore unavailable ({e!r}); "
                    "rebuilding quarantined synopses from row arrays",
                    RuntimeWarning, stacklevel=2,
                )
                states = None
        return self.store.heal(states)

    # ------------------------------------------------------------ improve
    _group_rows = staticmethod(group_rows)  # back-compat alias

    def _improve(self, snippets: SnippetBatch, raw: RawAnswer) -> ImprovedAnswer:
        """Back-compat hook: the improvement lives in the store now."""
        return self.store.improve_groups(
            snippets, raw, use_kernels=self.config.use_kernels)

    def _record(self, snippets: SnippetBatch, raw: RawAnswer):
        """Back-compat hook: recording lives in the store now."""
        self.store.record(snippets, raw)

    # ------------------------------------------------------------- groups
    def _discover_groups(self, q: Q.AggQuery):
        return self._discover_groups_many([q])[0]

    def _discover_groups_many(self, queries: Sequence[Q.AggQuery]):
        """Group-by value discovery for a whole workload in ONE probe.

        Every query's COUNT-probe snippets are fused into a single padded
        batch and evaluated with one ``predicate_mask`` pass over the first
        sample batch, instead of one eval (and one ``relation.take``) per
        query. Mask columns are computed independently per snippet, so the
        per-query booleans — and hence the discovered groups — are identical
        to the one-probe-at-a-time path. The probe, with its (T, Q) mask
        pull to the host, is the ``verdict.discover`` span.
        """
        out: List[Optional[tuple]] = [None] * len(queries)
        need = []
        for i, q in enumerate(queries):
            if not q.groupby:
                out[i] = ((),)
            else:
                need.append(i)
        if not need:
            return out
        from repro.aqp.executor import predicate_mask

        with TraceAnnotation("verdict.discover"):
            first = self.batches.relation.take(self.batches.batch_rows[0])
            plans = [
                Q.decompose(
                    self.schema,
                    Q.AggQuery(aggs=(Q.AggSpec("COUNT"),),
                               predicates=queries[i].predicates),
                )
                for i in need
            ]
            fused = SnippetBatch.concat([p.snippets for p in plans])
            mask_all = np.asarray(predicate_mask(
                first.num_normalized, first.cat, pad_snippets(fused)
            )).astype(bool)
            cat_first = np.asarray(first.cat)
            off = 0
            for i, plan in zip(need, plans):
                q = queries[i]
                mask = mask_all[:, off]
                off += plan.snippets.n
                cats = cat_first[mask][:, list(q.groupby)]
                if cats.size == 0:
                    out[i] = tuple()
                    continue
                uniq = np.unique(cats, axis=0)
                out[i] = tuple(tuple(int(v) for v in row) for row in uniq)
        return out

    # ------------------------------------------------------------- execute
    def execute(
        self,
        q: Q.AggQuery,
        target_rel_error: Optional[float] = None,
        max_batches: Optional[int] = None,
    ) -> QueryResult:
        """One query is a workload of one: the entire lifecycle (plan, fused
        scan, improve, validate, early-stop, record) lives in
        ``repro.aqp.plan.replay_query`` — there is no second copy here."""
        return self.execute_many(
            [q], target_rel_error=target_rel_error, max_batches=max_batches
        )[0]

    def _tuples(self, used_batches: int) -> int:
        return int(sum(len(b) for b in self.batches.batch_rows[:used_batches]))

    def _execute_raw_only(self, q, reason, max_batches):
        """Forced raw-only execution: raw AQP answers over the supported
        subset probe, no learning, whatever ``q``'s own supportedness
        (paper §2.2). The lifecycle is the ``supported=False`` branch of the
        shared ``replay_query`` — no scan loop lives here.
        """
        from repro.aqp.plan import (LogicalPlan, PhysicalPlan,
                                    SnippetInterner, plain_eval, replay_query)

        probe = self.raw_only_probe(q)
        groups = self._discover_groups(probe)
        plan = Q.decompose(self.schema, probe, groups, n_max=self.config.n_max)
        interner = SnippetInterner(self.schema)
        rows = interner.intern(plan.snippets)
        lp = LogicalPlan(0, q, probe, reason or "forced raw-only", plan, rows)
        phys = PhysicalPlan(self.batches, interner.fused(), plain_eval)
        return replay_query(self, lp, phys, max_batches=max_batches)

    def raw_only_probe(self, q: Q.AggQuery) -> Q.AggQuery:
        """The supported-subset probe the raw-only path evaluates (§2.2)."""
        supported_aggs = tuple(
            a for a in q.aggs if a.kind in Q.SUPPORTED_KINDS
        ) or (Q.AggSpec("COUNT", None),)
        clean_preds = tuple(
            p for p in q.predicates
            if not isinstance(p, (Q.Disjunction, Q.TextLike))
        )
        return Q.AggQuery(aggs=supported_aggs, predicates=clean_preds,
                          groupby=q.groupby)

    # -------------------------------------------------------------- persist
    def synopses_state_dict(self) -> Dict[str, dict]:
        """Host snapshot of the store, keyed ``"agg<k>-measure<m>"`` with a
        ``shard`` tag per entry (see ``SynopsisStore.state_dict``)."""
        return self.store.state_dict()

    def load_synopses_state_dict(self, state: Dict[str, dict]):
        """Restore a store snapshot (accepts legacy ``"<agg>_<measure>"``
        keys from pre-store checkpoints; placement is re-derived by the
        current store's policy, so the snapshot re-places onto any mesh).

        A reserved ``"intel"`` payload (present when the saving engine had
        a workload-intelligence plane) restores the answer cache + learned
        router state when this engine has one too — AFTER the store, so
        cache-entry generations re-license against the restored synopses.
        """
        state = dict(state)
        intel_state = state.pop("intel", None)
        self.store.load_state_dict(state)
        if self.intel is not None and intel_state is not None:
            self.intel.load_state_dict(intel_state, self.store)

    def save_synopses(self, manager, step: int):
        """Checkpoint the learned synopses (plus, when a workload-
        intelligence plane is attached, its answer cache and learned router
        state under the reserved ``"intel"`` key) through a
        ``CheckpointManager`` — one payload, one CRC-verified commit."""
        payload = self.store.state_dict()
        if self.intel is not None:
            payload["intel"] = self.intel.state_dict(self.store)
        manager.save(step, payload, extra={"kind": "verdict-synopses"})

    def load_synopses(self, manager, step: Optional[int] = None):
        """Restore synopses from a ``CheckpointManager`` checkpoint.

        This is what makes the engine smarter across process restarts: a new
        process pays zero queries to recover everything past sessions learned
        — including re-placing a sharded checkpoint onto whatever devices
        this process' store spans, and (when both sides carry a workload-
        intelligence plane) the semantic answer cache and router state.
        """
        state, extra = manager.restore_blind(step)
        self.load_synopses_state_dict(state)
        return extra

    # -------------------------------------------------------------- batched
    def execute_many(
        self,
        queries,
        target_rel_error: Optional[float] = None,
        max_batches: Optional[int] = None,
        mesh=None,
        stop_delta: Optional[float] = None,
        deadline_s: Optional[float] = None,
    ) -> List[QueryResult]:
        """Execute a workload through the fused ``BatchExecutor`` path.

        Every sample batch is scanned exactly once for the whole workload
        (identical snippets deduped across queries); answers match ``execute``
        run query-by-query bit for bit. ``stop_delta`` overrides the
        confidence level of the early-stop check (default
        ``config.report_delta``); ``deadline_s`` bounds each query's wall
        clock — on expiry the best-so-far answer returns with its honest
        (wider) CI, flagged ``degraded``. See ``repro.aqp.batch``.
        """
        from repro.aqp.batch import BatchExecutor

        return BatchExecutor(self, mesh=mesh).execute_many(
            queries, target_rel_error=target_rel_error,
            max_batches=max_batches, stop_delta=stop_delta,
            deadline_s=deadline_s,
        )
