"""Workload intelligence: the fourth serving plane (`repro.intel`).

The engine already learns across queries at the *model* level (synopses);
this package learns across queries at the *workload* level, closing the
"database that becomes smarter every time" loop at serving:

- ``cache``: a plan-IR-keyed semantic answer cache with subsumption lookup
  and error-budget-aware staleness (generation counters threaded from
  ``Synopsis``);
- ``router``: a per-query serve-path router (cache / synopsis improve /
  full scan) with a deterministic online cost model, plus the learned
  bucket-ladder floors replacing the static ``EngineConfig`` minimums;
- ``telemetry``: the hit/miss/subsumption/staleness/route counters behind
  ``Session.stats()["intel"]`` and ``explain()``.

``WorkloadIntel`` bundles the three and is what
``repro.verdict.connect(cache=...)`` attaches to the engine
(``VerdictEngine.intel``). Everything here is strictly additive: with no
intel plane attached (the default) the engine behaves bit-for-bit as
before, and cache-miss answers are bitwise-identical to the cache-disabled
engine (pinned by ``tests/test_intel.py``).

Determinism (analysis rule A007): no wall-clock and no RNG anywhere in
cache-key or router-feature derivation — keys persist across processes and
route decisions replay deterministically.
"""
from __future__ import annotations

import dataclasses
import json
import threading
from typing import Optional, Tuple

import numpy as np

from repro.intel.cache import AnswerCache, CacheEntry, QuerySignature
from repro.intel.router import RouterConfig, ServeRouter
from repro.intel.telemetry import IntelTelemetry

__all__ = [
    "AnswerCache",
    "CacheEntry",
    "IntelConfig",
    "IntelTelemetry",
    "QuerySignature",
    "RouterConfig",
    "ServeRouter",
    "WorkloadIntel",
]


@dataclasses.dataclass
class IntelConfig:
    capacity: int = 256  # answer-cache entries (LRU beyond this)
    subsumption: bool = True
    router: RouterConfig = dataclasses.field(default_factory=RouterConfig)


class WorkloadIntel:
    """The workload-intelligence plane of one engine (cache+router+counters).

    Attach one instance per engine (``repro.verdict.connect(cache=True)``).
    Thread-safe: every public method takes ``lock``, the plane's own
    re-entrant lock, so a serving front's prescreen ``lookup`` runs beside a
    flush's ``choose_route``/``observe`` without holding the engine lock
    (``repro.serving.aqp``). Lock order: engine lock, then ``lock`` — a
    flush, ``explain`` or ``stream`` reaches here with the engine lock held,
    a prescreen takes only ``lock``, and nothing here takes the engine
    lock, so there is no cycle. ``lock`` is re-entrant so a caller can hold
    it around a call to time its wait (the microbatcher's prescreen).

    A lookup outside the engine lock is sound: the cache reads the store
    only through ``store.get`` and ``store.generation``, single attribute
    reads whose generations are bumped synchronously (ingest threads run
    beside lookups already). A lookup that races a flush between its
    ``store.record`` and its ``observe`` behaves as if it ran just before
    that flush: it misses, or refuses the entry as stale, and executes.
    """

    def __init__(self, config: Optional[IntelConfig] = None):
        self.config = config or IntelConfig()
        self.lock = threading.RLock()
        self.telemetry = IntelTelemetry()
        self.cache = AnswerCache(capacity=self.config.capacity,
                                 subsumption=self.config.subsumption)
        self.router = ServeRouter(self.config.router)

    # ------------------------------------------------------------- serving
    @staticmethod
    def _budget(engine, stop_delta, max_batches) -> Tuple[float, int]:
        delta = (engine.config.report_delta if stop_delta is None
                 else float(stop_delta))
        eff = min(max_batches or engine.batches.n_batches,
                  engine.batches.n_batches)
        return delta, eff

    def lookup(self, engine, query, target_rel_error: Optional[float] = None,
               stop_delta: Optional[float] = None,
               max_batches: Optional[int] = None,
               tenant: Optional[str] = None):
        """Serve ``query`` from the answer cache, or None (execute it).

        ``tenant``: optional label (the serving front's per-tenant
        namespace) — counted in ``telemetry.per_tenant`` so a shared cache
        still reports per-tenant hit rates."""
        sig = QuerySignature.from_query(engine.schema, query)
        with self.lock:
            if sig is None:
                self.telemetry.lookups += 1
                self.telemetry.misses += 1
                self.telemetry.uncacheable += 1
                if tenant is not None:
                    self.telemetry.record_tenant(tenant, hit=False)
                return None
            delta, eff = self._budget(engine, stop_delta, max_batches)
            res = self.cache.lookup(engine.store, sig, target_rel_error,
                                    delta, eff, telemetry=self.telemetry)
            if res is not None:
                self.telemetry.record_route("cache")
            if tenant is not None:
                self.telemetry.record_tenant(tenant, hit=res is not None)
            return res

    def peek(self, engine, query, target_rel_error: Optional[float] = None,
             stop_delta: Optional[float] = None,
             max_batches: Optional[int] = None,
             lp=None) -> Tuple[str, str]:
        """Read-only (status, route) prediction for ``explain()`` — no
        counters, no LRU movement, no probe-streak mutation."""
        sig = QuerySignature.from_query(engine.schema, query)
        if sig is None:
            return "uncacheable", "scan"
        delta, eff = self._budget(engine, stop_delta, max_batches)
        with self.lock:
            res = self.cache.lookup(engine.store, sig, target_rel_error,
                                    delta, eff, mutate=False)
            if res is not None:
                status = ("exact" if res.served_from == "cache:exact"
                          else "subsumed")
                return status, "cache"
            if lp is None or not lp.supported or lp.plan is None:
                return ("miss",
                        "scan" if target_rel_error is None else "improve")
            return "miss", self.router.predict_route(
                engine, lp, target_rel_error, eff)

    def choose_route(self, engine, lp, target_rel_error: Optional[float],
                     max_batches: int) -> str:
        with self.lock:
            return self.router.choose_route(engine, lp, target_rel_error,
                                            max_batches)

    def occupancy(self, engine, lp):
        """The store occupancy ``lp`` is served from (see
        ``ServeRouter.occupancy``); None for queries the router never
        observes."""
        if not lp.supported or lp.plan is None:
            return None
        with self.lock:
            return self.router.occupancy(engine, lp)

    def observe(self, engine, lp, res, target_rel_error: Optional[float],
                max_batches: int, route: str, occupancy):
        """Final-round bookkeeping for one executed query: route counters,
        router statistics (and the periodic ladder application), and the
        answer-cache insert. Runs right after ``store.record`` in the plan
        lifecycle, so the generation snapshot includes the answer's own
        ingest bump; ``occupancy`` (``self.occupancy``) was read before
        that record."""
        with self.lock:
            self.telemetry.record_route(route)
            if not lp.supported:
                return
            self.router.observe(engine, lp, res, target_rel_error, route,
                                occupancy)
            sig = QuerySignature.from_query(engine.schema, lp.query)
            if sig is not None:
                self.cache.insert(engine.store, sig, lp, res,
                                  target_rel_error, max_batches,
                                  telemetry=self.telemetry)

    # -------------------------------------------------------------- persist
    def state_dict(self, store) -> dict:
        """One ``"blob"`` uint8 array (canonical JSON) — rides the same
        np.savez + CRC checkpoint payload the synopses use."""
        with self.lock:
            payload = {
                "cache": self.cache.state_dict(store),
                "router": self.router.state_dict(),
                "telemetry": self.telemetry.state_dict(),
            }
        raw = json.dumps(payload, sort_keys=True).encode()
        return {"blob": np.frombuffer(raw, dtype=np.uint8)}

    def load_state_dict(self, state: dict, store):
        blob = np.asarray(state["blob"], dtype=np.uint8)
        payload = json.loads(bytes(blob).decode())
        with self.lock:
            self.cache.load_state_dict(payload.get("cache", {}), store)
            self.router.load_state_dict(payload.get("router", {}))
            self.telemetry.load_state_dict(payload.get("telemetry", {}))

    # ---------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self.lock:
            out = self.telemetry.as_dict()
            out["enabled"] = True
            out["entries"] = len(self.cache)
            out["capacity"] = self.cache.capacity
            out["router"] = self.router.stats()
        return out
