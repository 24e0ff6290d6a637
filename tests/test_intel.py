"""Workload-intelligence suite: semantic answer cache, subsumption serving,
staleness/quarantine licensing, the learned serve-path router, and the
checkpoint/chaos legs.

The binding contract is the bitwise oracle:

- a cache MISS is bitwise-identical to the cache-disabled engine (the miss
  path runs the unchanged plan lifecycle);
- an exact HIT is bitwise-identical to the originally recorded final answer;
- a SUBSUMED answer is exactly reproducible from the recorded cached cells
  (filter + project, no recomputation);
- router-chosen paths never violate the caller's ErrorBudget ("scan" serves
  the most refined full-budget answer, bitwise-equal to the always-improve
  engine when neither meets the target).
"""
import sys
import threading

import numpy as np
import pytest

import repro.verdict as vd
from repro.aqp import queries as Q
from repro.aqp.plan import plan_workload
from repro.aqp import workload as W
from repro.core.engine import EngineConfig
from repro.core.store import agg_key, state_key
from repro.core.types import AVG
from repro.ft import faults
from repro.ft.checkpoint import CheckpointManager
from repro.intel import IntelConfig, QuerySignature, RouterConfig
from repro.kernels import RANGE_EPS


@pytest.fixture(scope="module")
def relation():
    return W.make_relation(seed=0, n_rows=3_000, n_num=2, cat_sizes=(4,),
                           n_measures=1, lengthscale=0.4, noise=0.2)


def _cfg(**kw):
    base = dict(sample_rate=0.2, n_batches=4, capacity=128, seed=0)
    base.update(kw)
    return EngineConfig(**base)


def _cells(ans):
    return [c.to_dict() for c in ans.cells]


AVG_KEY = state_key(agg_key(AVG, 0))
B = vd.ErrorBudget(target_rel_error=0.5)


def _q_grouped(s):
    return (s.query().avg("v0").where(vd.between("x0", 2.0, 8.0))
            .group_by("c0").build())


def _q_plain(s):
    return s.query().avg("v0").where(vd.between("x1", 1.0, 6.0)).build()


# ------------------------------------------------------------- default off


def test_cache_off_by_default(relation):
    s = vd.connect(relation, _cfg())
    assert s.intel is None and s.engine.intel is None
    ans = s.execute(_q_grouped(s), B)
    assert ans.served_from is None
    assert s.stats()["intel"] == {"enabled": False}
    rep = s.explain(_q_grouped(s))
    assert rep.cache is None and rep.route is None
    assert "served from cache" not in str(rep)


# -------------------------------------------------- exact hits, miss parity


def test_exact_hit_bitwise_and_miss_parity(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    twin = vd.connect(relation, _cfg())  # cache-disabled oracle
    q1, q2 = _q_grouped(s), _q_plain(s)
    first = s.execute_many([q1, q2], B)
    want = twin.execute_many([q1, q2], B)
    # Miss path: bitwise-identical to the cache-disabled engine.
    for g, w in zip(first, want):
        assert g.served_from is None
        assert _cells(g) == _cells(w)
    # Repeat: exact hits, bitwise-identical to the recorded answers, and
    # the hit query drops out of the fused batch (no new scan work).
    q3 = s.query().count().where(vd.between("x0", 1.0, 9.0)).build()
    again = s.execute_many([q1, q3, q2], B)
    assert again[0].served_from == "cache:exact"
    assert again[2].served_from == "cache:exact"
    assert again[1].served_from is None  # the new query executed
    assert _cells(again[0]) == _cells(first[0])
    assert _cells(again[2]) == _cells(first[1])
    st = s.stats()["intel"]
    assert st["enabled"] and st["hits_exact"] == 2
    assert st["entries"] == 3 and st["insertions"] == 3
    assert st["routes"]["cache"] == 2


def test_full_accuracy_exact_hit_requires_full_budget(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    q = _q_plain(s)
    first = s.execute(q)  # no target: full budget, route "scan"
    again = s.execute(q)
    assert again.served_from == "cache:exact"
    assert _cells(again) == _cells(first)
    # A tighter batch budget is a different answer — never served from an
    # entry recorded under the full budget.
    capped = s.execute(q, vd.ErrorBudget(max_batches=2))
    assert capped.served_from is None
    assert capped.batches_used == 2


def test_uncacheable_query_counted_and_served_raw(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    bad = Q.AggQuery(aggs=(Q.AggSpec("AVG", 0),),
                     predicates=(Q.TextLike("x%"),))
    a1 = s.execute(bad)
    a2 = s.execute(bad)
    assert not a1.supported and not a2.supported
    assert a2.served_from is None
    assert s.stats()["intel"]["uncacheable"] == 2


# ------------------------------------------------------ staleness licensing


def test_ingest_invalidates_full_accuracy_then_refreshes(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    q_a, q_b = _q_grouped(s), _q_plain(s)
    s.execute(q_a)  # cached, full accuracy
    assert s.execute(q_a).served_from == "cache:exact"
    # q_b records through the same AVG synopsis: generation bumps at
    # enqueue, so q_a's entry is stale the moment the answer lands.
    s.execute(q_b)
    refreshed = s.execute(q_a)
    assert refreshed.served_from is None  # stale → refused → re-executed
    assert s.stats()["intel"]["stale_refused"] >= 1
    # The re-execution re-recorded a fresh entry: hits resume.
    assert s.execute(q_a).served_from == "cache:exact"


def test_stale_entry_serves_within_error_budget(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    q_a, q_b = _q_grouped(s), _q_plain(s)
    first = s.execute(q_a, B)
    s.execute(q_b, B)  # staleness-bump q_a's aggregate key
    served = s.execute(q_a, B)
    # The recorded CI still meets the caller's budget: bounded staleness
    # is licensed by the error budget, and the answer is exactly the
    # recorded one.
    assert served.served_from == "cache:exact"
    assert _cells(served) == _cells(first)
    assert served.max_rel_error(0.95) <= B.target_rel_error
    assert s.stats()["intel"]["stale_served"] >= 1


# ------------------------------------------------------------- subsumption


def test_subsumption_group_pin_and_subset(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    full = s.execute(_q_grouped(s), B)  # GROUP BY c0, all groups
    # Pin one group: served from the cached cells, bitwise.
    pin = (s.query().avg("v0").where(vd.between("x0", 2.0, 8.0))
           .where(vd.equals("c0", 1)).group_by("c0").build())
    got = s.execute(pin, B)
    assert got.served_from == "cache:subsumed"
    assert _cells(got) == [c for c in _cells(full) if c["group"] == (1,)]
    # Subset of groups: the cached cells filtered, original order kept.
    sub = (s.query().avg("v0").where(vd.between("x0", 2.0, 8.0))
           .where(vd.one_of("c0", [3, 0])).group_by("c0").build())
    got2 = s.execute(sub, B)
    assert got2.served_from == "cache:subsumed"
    assert _cells(got2) == [c for c in _cells(full)
                            if c["group"][0] in (0, 3)]
    # A dropped grouped dim must be pinned: an ungrouped spelling over the
    # full member set aggregates ACROSS groups — never servable from
    # per-group AVG cells.
    merged = (s.query().avg("v0")
              .where(vd.between("x0", 2.0, 8.0)).build())
    got3 = s.execute(merged, B)
    assert got3.served_from is None
    assert s.stats()["intel"]["hits_subsumed"] == 2


def test_subsumption_range_eps_boundary(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    aggs = (Q.AggSpec("AVG", 0),)
    base = Q.AggQuery(aggs=aggs, predicates=(Q.NumRange(0, 2.0, 8.0),),
                      groupby=(0,))
    first = s.execute(base, B)
    # Bounds within RANGE_EPS select the same tuples by construction of
    # predicate_mask: servable, and exactly the recorded cells.
    near = Q.AggQuery(aggs=aggs,
                      predicates=(Q.NumRange(0, 2.0 + RANGE_EPS / 2,
                                             8.0 - RANGE_EPS / 2),),
                      groupby=(0,))
    got = s.execute(near, B)
    assert got.served_from == "cache:subsumed"
    assert _cells(got) == _cells(first)
    # Past the epsilon the boxes differ semantically: a miss, executed.
    far = Q.AggQuery(aggs=aggs,
                     predicates=(Q.NumRange(0, 2.0 + 1e-6, 8.0),),
                     groupby=(0,))
    assert s.execute(far, B).served_from is None


def test_truncated_entry_never_subsumes(relation):
    # n_max=2 truncates the 4-value group-by: the cached cells are an
    # incomplete group set, unusable for subsumption (a pinned group may be
    # one of the dropped ones) — but an exact repeat still serves, with the
    # truncation surfaced.
    s = vd.connect(relation, _cfg(n_max=2), cache=True)
    q = _q_grouped(s)
    first = s.execute(q, B)
    assert first.truncated_groups > 0
    again = s.execute(q, B)
    assert again.served_from == "cache:exact"
    assert again.truncated_groups == first.truncated_groups
    pin = (s.query().avg("v0").where(vd.between("x0", 2.0, 8.0))
           .where(vd.equals("c0", 1)).group_by("c0").build())
    assert s.execute(pin, B).served_from is None


# -------------------------------------------- canonical keys (satellite 1)


def test_signature_canonicalization_matrix(relation):
    """Commutative/duplicated/reordered spellings of one query hash to one
    cache key AND intern to the same snippet rows (the NumEq-overwrite fix:
    canonical predicate boxes are order-independent)."""
    s = vd.connect(relation, _cfg())
    schema = s.schema
    aggs = (Q.AggSpec("AVG", 0),)
    spellings = [
        Q.AggQuery(aggs, (Q.NumRange(0, 2.0, 8.0), Q.CatIn(0, (1, 3, 2)))),
        Q.AggQuery(aggs, (Q.CatIn(0, (3, 2, 1)), Q.NumRange(0, 2.0, 8.0))),
        Q.AggQuery(aggs, (Q.NumRange(0, 2.0, 8.0), Q.NumRange(0, 2.0, 8.0),
                          Q.CatIn(0, (2, 1, 3, 1)))),
        Q.AggQuery(aggs, (Q.NumRange(0, 0.0, 8.0), Q.NumRange(0, 2.0, 10.0),
                          Q.CatIn(0, (1, 2, 3)))),
    ]
    digests = {QuerySignature.from_query(schema, q).digest()
               for q in spellings}
    assert len(digests) == 1
    wp = plan_workload(s.engine, spellings)
    for lp in wp.logical[1:]:
        np.testing.assert_array_equal(lp.rows, wp.logical[0].rows)
    # Full cross-query dedup: the fused set is one query's snippets.
    assert wp.stats.n_snippets_fused == wp.logical[0].plan.snippets.n
    # NumEq ∧ NumRange commutes (the pre-fix overwrite ordered it).
    eq_then_range = Q.AggQuery(
        aggs, (Q.NumEq(0, 5.0), Q.NumRange(0, 2.0, 8.0)))
    range_then_eq = Q.AggQuery(
        aggs, (Q.NumRange(0, 2.0, 8.0), Q.NumEq(0, 5.0)))
    assert (QuerySignature.from_query(schema, eq_then_range).digest()
            == QuerySignature.from_query(schema, range_then_eq).digest())
    boxes = [Q.predicates_to_arrays(schema, q.predicates)[0][0]
             for q in (eq_then_range, range_then_eq)]
    assert boxes[0] == boxes[1] == (5.0, 5.0)
    # Distinct semantics stay distinct.
    other = Q.AggQuery(aggs, (Q.NumRange(0, 2.0, 8.0),))
    assert QuerySignature.from_query(schema, other).digest() not in digests


# ------------------------------------------- quarantine / heal (satellite 2)


def test_quarantine_refuses_and_cache_survives_heal_bitwise(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    q_cached, q_poison = _q_grouped(s), _q_plain(s)
    s.execute(q_cached)
    assert s.execute(q_cached).served_from == "cache:exact"
    s.drain()  # quiesce: pending applies must not race the armed plan
    key = QuerySignature.from_query(s.schema, q_cached).digest()

    def entry_of(key):
        return next(e for e in s.intel.cache.state_dict(s.store)["entries"]
                    if e["key"] == key)

    before = entry_of(key)
    with faults.inject(faults.FaultSpec("ingest.apply", key=AVG_KEY,
                                        hits=(0,))):
        s.execute(q_poison)  # its record trips the poisoned async apply
        s.drain()  # barrier: the quarantine lands
        assert AVG_KEY in s.stats()["health"]["quarantined"]
        # A degraded key NEVER serves a pre-quarantine cached answer.
        during = s.execute(q_cached)
        assert during.served_from is None and during.degraded
        assert s.stats()["intel"]["quarantine_refused"] >= 1
    assert s.heal() == {AVG_KEY: True}
    # The entry itself survived the whole episode bitwise: degraded
    # answers are never inserted, refused lookups never mutate entries.
    assert entry_of(key) == before
    # Healed ≠ the state the entries saw: full-accuracy lookups refuse
    # (stale) and re-record; then hits resume against the healed store.
    refreshed = s.execute(q_cached)
    assert refreshed.served_from is None and not refreshed.degraded
    assert s.execute(q_cached).served_from == "cache:exact"


# ---------------------------------------------------- checkpoint round-trip


def test_cache_checkpoint_roundtrip(tmp_path, relation):
    s = vd.connect(relation, _cfg(), cache=True)
    q = _q_grouped(s)
    first = s.execute(q)
    s.drain()
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    s.save(mgr, step=1)
    # A fresh process: same relation, restored synopses + intel plane.
    s2 = vd.connect(relation, _cfg(), cache=True)
    s2.load(mgr, step=1)
    assert s2.stats()["intel"]["entries"] == 1
    got = s2.execute(q)
    assert got.served_from == "cache:exact"
    assert _cells(got) == _cells(first)
    # And a cache-less session restores the same payload untouched — the
    # reserved "intel" key never leaks into synopsis restore.
    s3 = vd.connect(relation, _cfg())
    s3.load(mgr, step=1)
    got_sd, want_sd = s3.engine.store.state_dict(), s.engine.store.state_dict()
    assert sorted(got_sd) == sorted(want_sd)
    for name in want_sd:
        for k in want_sd[name]:
            np.testing.assert_array_equal(got_sd[name][k], want_sd[name][k],
                                          err_msg=f"{name}/{k}")
    ans = s3.execute(q)
    assert ans.served_from is None and not ans.degraded


# ----------------------------------------------------------------- router


def test_router_learns_scan_route_bitwise(relation):
    tight = vd.ErrorBudget(target_rel_error=1e-9)  # never met: full budget
    cfg = IntelConfig(router=RouterConfig(probe_every=4, learn_ladder=False))
    s = vd.connect(relation, _cfg(), cache=cfg)
    twin = vd.connect(
        relation, _cfg(),
        cache=IntelConfig(router=RouterConfig(route_switching=False,
                                              learn_ladder=False)))
    los = [1.0 + 0.25 * i for i in range(8)]  # distinct: no cache hits
    for lo in los[:2]:
        # Cold start + optimistic E[batches]: the first queries route
        # "improve" — exactly the pre-intel engine.
        q = s.query().avg("v0").where(vd.between("x0", lo, 9.5)).build()
        s.execute(q, tight)
        twin.execute(q, tight)
    assert s.stats()["intel"]["routes"]["scan"] == 0
    for lo in los[2:]:
        # E[batches] has learned ≈ max_batches: improving every round buys
        # nothing, the router flips to "scan" — and the answer stays
        # bitwise-equal to the always-improve engine (both exhaust the
        # budget; the full-budget answer is the most refined one).
        q = s.query().avg("v0").where(vd.between("x0", lo, 9.5)).build()
        a, w = s.execute(q, tight), twin.execute(q, tight)
        assert a.batches_used == w.batches_used == 4
        assert _cells(a) == _cells(w)
    routes = s.stats()["intel"]["routes"]
    assert routes["scan"] > 0
    # The deterministic probe re-measures the improve path periodically.
    assert routes["improve"] > 2
    fb = max(s.stats()["intel"]["router"]["expected_batches"])
    assert s.stats()["intel"]["router"]["expected_batches"][fb] == 4.0


def test_learned_ladder_floors_are_answer_invariant(relation):
    cfg = IntelConfig(router=RouterConfig(ladder_every=3))
    s = vd.connect(relation, _cfg(), cache=cfg)
    plain = vd.connect(relation, _cfg())
    qs = [s.query().avg("v0").where(vd.between("x0", 1.0 + 0.5 * i, 9.0))
          .group_by("c0").build() for i in range(4)]
    for q in qs:
        assert _cells(s.execute(q, B)) == _cells(plain.execute(q, B))
    floors = s.stats()["intel"]["router"]["learned_floors"]
    assert floors is not None
    assert s.config.min_q_bucket == floors[0]
    # The ladder moved the serve tiles, not the answers: a fresh query is
    # still bitwise-equal to the static-floor engine.
    fresh = s.query().sum("v0").where(vd.between("x1", 2.0, 7.0)).build()
    assert _cells(s.execute(fresh, B)) == _cells(plain.execute(fresh, B))


def test_intel_lock_guards_lookups_against_concurrent_inserts(relation):
    """The prescreen's lookups run beside a flush's ``observe`` without the
    engine lock: 4 threads look up (subsumption on, full cache, so each
    lookup walks every entry) while another inserts past capacity. Without
    the plane's lock the walk can raise ``OrderedDict mutated during
    iteration`` and the counters' ``+=`` can lose updates."""
    s = vd.connect(relation, _cfg(), cache=IntelConfig(capacity=16))
    intel, eng = s.intel, s.engine
    qs = [s.query().avg("v0").where(vd.between("x0", 1.0 + 0.2 * i, 9.5))
          .build() for i in range(32)]
    res = eng.execute(qs[0])
    lps = plan_workload(eng, qs).logical
    occ = [intel.occupancy(eng, lp) for lp in lps]
    for k in range(16):
        intel.observe(eng, lps[k], res, None, 4, "scan", occ[k])
    assert intel.stats()["entries"] == 16
    probe = s.query().avg("v0").where(vd.between("x0", 0.5, 9.0)).build()
    n_lookups, n_threads = 200, 4
    before = intel.telemetry.lookups
    errors = []
    start = threading.Barrier(n_threads + 1)

    def looker():
        start.wait()
        try:
            for _ in range(n_lookups):
                assert intel.lookup(eng, probe) is None
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    def inserter():
        start.wait()
        try:
            for k in range(320):  # 32 keys round an LRU of 16: all evict
                intel.observe(eng, lps[k % 32], res, None, 4, "scan",
                              occ[k % 32])
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=looker) for _ in range(n_threads)]
    threads.append(threading.Thread(target=inserter))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # switch threads often: races show
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert intel.telemetry.lookups - before == n_threads * n_lookups
    assert intel.stats()["entries"] == 16


# --------------------------------------------------------- serving surface


def test_service_prescreen_skips_microbatch(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    svc = s.serve(budget=B)
    q = _q_grouped(s)
    t1 = svc.submit(q)
    first = t1.result()  # flushes
    t2 = svc.submit(q)
    # Resolved at submit: never occupied a microbatch slot.
    assert t2._done and svc.pending == 0
    assert svc.prescreened == 1
    got = t2.result()
    assert got.served_from == "cache:exact"
    assert _cells(got) == _cells(first)
    st = svc.stats()
    assert st["prescreened"] == 1 and st["intel"]["enabled"]


def test_explain_reports_cache_status_and_is_readonly(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    q = _q_grouped(s)
    rep = s.explain(q, budget=B)
    assert rep.cache == "miss" and rep.route in ("improve", "scan")
    s.execute(q, B)
    lookups = s.stats()["intel"]["lookups"]
    rep2 = s.explain(q, budget=B)
    assert rep2.cache == "exact" and rep2.route == "cache"
    assert "served from cache: exact → route=cache" in str(rep2)
    # Peeking never moves counters, LRU order, or probe streaks.
    assert s.stats()["intel"]["lookups"] == lookups
    pin = (s.query().avg("v0").where(vd.between("x0", 2.0, 8.0))
           .where(vd.equals("c0", 1)).group_by("c0").build())
    assert s.explain(pin, budget=B).cache == "subsumed"


def test_stream_short_circuits_on_hit(relation):
    s = vd.connect(relation, _cfg(), cache=True)
    q = _q_plain(s)
    first = s.execute(q, B)
    rounds = list(s.stream(q, B))
    assert len(rounds) == 1 and rounds[0].final
    assert rounds[0].served_from == "cache:exact"
    assert _cells(rounds[0]) == _cells(first)


def test_deadline_degraded_answer_never_cached_as_full_accuracy(relation):
    """Satellite gate: an answer that returned early on a deadline is honest
    but WEAKER — recording it into the answer cache would replay a degraded
    CI as if it were the full-budget answer. It must never be inserted, and
    the next full-budget call must execute (then cache normally)."""
    s = vd.connect(relation, _cfg(), cache=True)
    q = _q_grouped(s)
    ans = s.execute(q, vd.ErrorBudget(deadline_s=0.0))
    assert ans.degraded and "deadline" in ans.degraded_reasons
    st = s.stats()["intel"]
    assert st["insertions"] == 0 and st["entries"] == 0
    # Full-budget re-execute: a MISS (nothing degraded was cached) ...
    full = s.execute(q, B)
    assert full.served_from is None and not full.degraded
    # ... which now caches, and the repeat serves at full accuracy.
    hit = s.execute(q, B)
    assert hit.served_from == "cache:exact" and not hit.degraded
    assert _cells(hit) == _cells(full)


def test_per_tenant_intel_counters_and_roundtrip(relation):
    """The serving front's per-tenant hit-rate surface: one shared intel
    plane splits lookups/hits by the tenant label threaded through
    ``Session.attached`` sessions, and the split survives a state_dict
    round-trip."""
    from repro.verdict.session import Session

    s = vd.connect(relation, _cfg(), cache=True)
    alice = Session.attached(s, tenant="alice")
    bob = Session.attached(s, tenant="bob")
    q = _q_grouped(s)
    a1 = alice.execute(q, B)          # miss (cold), then cached
    b1 = bob.execute(q, B)            # exact hit from alice's entry
    assert b1.served_from == "cache:exact"
    assert _cells(b1) == _cells(a1)
    pt = s.stats()["intel"]["per_tenant"]
    assert pt["alice"] == {"lookups": 1, "hits": 0, "hit_rate": 0.0}
    assert pt["bob"] == {"lookups": 1, "hits": 1, "hit_rate": 1.0}
    # Unlabeled traffic stays out of the per-tenant split.
    s.execute(q, B)
    assert s.stats()["intel"]["per_tenant"] == pt
    # Persistence: the split rides the same blob the cache/router use.
    state = s.intel.state_dict(s.store)
    fresh = vd.connect(relation, _cfg(), cache=True)
    fresh.intel.load_state_dict(state, fresh.store)
    assert fresh.intel.telemetry.per_tenant == {
        "alice": {"lookups": 1, "hits": 0},
        "bob": {"lookups": 1, "hits": 1}}
