"""AqpService microbatcher: auto-flush threshold, ticket resolution, stats
propagation, and bitwise parity of microbatched answers vs direct
``execute_many`` (previously untested beyond one smoke case)."""
import threading

import pytest

import repro.verdict as vd
from repro.aqp import workload as W
from repro.core.engine import EngineConfig, VerdictEngine
from repro.serving.aqp import AqpService


@pytest.fixture(scope="module")
def relation():
    return W.make_relation(seed=0, n_rows=5_000, n_num=2, cat_sizes=(4,),
                           n_measures=1, lengthscale=0.4, noise=0.2)


@pytest.fixture(scope="module")
def workload(relation):
    return W.make_workload(1, relation.schema, 12,
                           agg_kinds=("AVG", "COUNT", "SUM"),
                           cat_pred_prob=0.3)


def _cfg(**kw):
    base = dict(sample_rate=0.15, n_batches=4, capacity=128, seed=0)
    base.update(kw)
    return EngineConfig(**base)


def test_auto_flush_threshold(relation, workload):
    svc = AqpService(VerdictEngine(relation, _cfg()), max_batch=4)
    tickets = [svc.submit(q) for q in workload[:3]]
    assert svc.flushes == 0 and svc.pending == 3
    assert not any(t._done for t in tickets)
    t4 = svc.submit(workload[3])  # hits the threshold exactly
    assert svc.flushes == 1 and svc.pending == 0
    assert all(t._done for t in tickets) and t4._done
    # Resolved tickets answer without another flush.
    assert t4.result() is not None
    assert svc.flushes == 1


def test_ticket_result_triggers_flush_once(relation, workload):
    svc = AqpService(VerdictEngine(relation, _cfg()), max_batch=8)
    t1 = svc.submit(workload[0])
    t2 = svc.submit(workload[1])
    assert svc.flushes == 0
    r1 = t1.result()  # forces the flush for the whole pending batch
    assert svc.flushes == 1 and svc.pending == 0
    assert r1 is not None and t2._done
    assert t2.result() is not None
    assert svc.flushes == 1  # no extra flush for the sibling


def test_stats_propagation(relation, workload):
    svc = AqpService(VerdictEngine(relation, _cfg()), max_batch=5)
    assert svc.last_stats is None
    svc.execute(workload[:5])
    assert svc.flushes == 1
    st = svc.last_stats
    assert st is not None and st.n_queries == 5
    assert st.eval_calls > 0 and st.batches_scanned > 0
    assert st.n_snippets_fused <= st.n_snippets_total
    svc.execute(workload[5:8])
    assert svc.flushes == 2 and svc.last_stats.n_queries == 3


def test_microbatched_parity_vs_direct_execute_many(relation, workload):
    """Flushing a workload in microbatches is bitwise identical to direct
    ``execute_many`` with the same flush boundaries — and, because replay is
    per query in submission order, to ONE big fused call too."""
    svc = AqpService(VerdictEngine(relation, _cfg()), max_batch=5)
    tickets = [svc.submit(q) for q in workload[:10]]
    r_svc = [t.result() for t in tickets]
    assert svc.flushes == 2  # 5 + 5

    ref = VerdictEngine(relation, _cfg())
    r_ref = ref.execute_many(workload[:5]) + ref.execute_many(workload[5:10])
    one = VerdictEngine(relation, _cfg())
    r_one = one.execute_many(workload[:10])
    for a, b, c in zip(r_svc, r_ref, r_one):
        assert a.cells == b.cells == c.cells  # dict float equality == bitwise
        assert a.batches_used == b.batches_used == c.batches_used
        assert a.supported == b.supported == c.supported


def test_service_accepts_session_facade(relation, workload):
    session = vd.connect(relation, _cfg())
    svc = session.serve(max_batch=4,
                        budget=vd.ErrorBudget(target_rel_error=0.05))
    assert svc.engine is session.engine
    assert svc.target_rel_error == 0.05
    assert svc.executor.mesh is session._executor.mesh  # sharding preserved
    results = svc.execute(workload[:4])
    assert len(results) == 4
    assert all(r.batches_used >= 1 for r in results)
    # Constructing AqpService directly from a Session works too (the
    # executor must be bound to the unwrapped engine, not the facade).
    svc2 = AqpService(session, max_batch=8)
    assert svc2.engine is session.engine
    assert svc2.execute(workload[:2])[0].supported


def test_service_honors_full_error_budget(relation, workload):
    """serve(budget=...) threads max_batches AND delta through every flush,
    not just the target."""
    session = vd.connect(relation, _cfg())
    svc = session.serve(budget=vd.ErrorBudget(max_batches=2, delta=0.9))
    results = svc.execute(workload[:4])
    assert all(r.batches_used == 2 for r in results)
    assert svc.max_batches == 2 and svc.stop_delta == 0.9


def test_serve_returns_typed_answers_and_lowers_builders(relation):
    """Through session.serve() the microbatcher speaks the facade types:
    QueryBuilder in, QueryAnswer (typed Cells) out — same as execute."""
    from repro.verdict.answer import Cell, QueryAnswer

    session = vd.connect(relation, _cfg())
    svc = session.serve(max_batch=4)
    q = session.query().avg("v0").where(vd.between("x0", 2.0, 8.0))
    ticket = svc.submit(q)  # builder, not AggQuery
    ans = ticket.result()
    assert isinstance(ans, QueryAnswer)
    assert ans.cells and isinstance(ans.cells[0], Cell)
    # Bitwise-equal to the session's own execute on a fresh twin.
    twin = vd.connect(relation, _cfg())
    direct = twin.execute(twin.query().avg("v0")
                          .where(vd.between("x0", 2.0, 8.0)))
    assert [c.to_dict() for c in ans.cells] == \
           [c.to_dict() for c in direct.cells]
    # The raw engine-level service still lowers builders too.
    raw_svc = AqpService(VerdictEngine(relation, _cfg()), max_batch=4)
    assert raw_svc.submit(session.query().count()).result().supported


def test_forced_raw_only_contract(relation, workload):
    """_execute_raw_only forces the raw-only lifecycle even for a supported
    query: raw answers over the probe, supported=False, nothing learned."""
    eng = VerdictEngine(relation, _cfg())
    q = workload[0]  # a supported query
    r = eng._execute_raw_only(q, "forced by caller", max_batches=2)
    assert not r.supported and r.unsupported_reason == "forced by caller"
    assert r.batches_used == 2 and r.cells
    assert len(eng.store) == 0  # no learning happened


# ------------------------------------------------ concurrency + retry ladder


def test_concurrent_submit_every_ticket_resolves_exactly_once(relation,
                                                              workload):
    """Stress the lock-free-era races: many threads submitting through the
    auto-flush threshold concurrently. Every ticket must resolve to a real
    answer EXACTLY once — no lost entries, no double-flushed batches, no
    premature None from a result() racing another thread's flush."""
    svc = AqpService(VerdictEngine(relation, _cfg()), max_batch=3)
    n_threads, per_thread = 6, 4
    tickets = [[] for _ in range(n_threads)]
    start = threading.Barrier(n_threads)

    def submitter(slot):
        start.wait()
        for i in range(per_thread):
            q = workload[(slot * per_thread + i) % len(workload)]
            tickets[slot].append(svc.submit(q))

    threads = [threading.Thread(target=submitter, args=(s,))
               for s in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    svc.flush()  # drain the sub-threshold remainder
    all_tickets = [t for slot in tickets for t in slot]
    assert len(all_tickets) == n_threads * per_thread
    for t in all_tickets:
        ans = t.result(timeout=60)
        assert ans is not None and ans.supported is not None
        assert t.resolutions == 1  # exactly once, despite racing flushes
    assert svc.pending == 0
    # No query lost or duplicated across the racing flushes.
    assert sum(t.resolutions for t in all_tickets) == len(all_tickets)


def test_transient_fault_retries_whole_slice_before_bisecting(relation,
                                                              workload):
    """The docstring's promised order — retry the FULL failed slice with
    backoff first, only then bisect. A transient fault (fires once) must
    cost exactly 2 ``_execute_slice`` calls (fail + clean retry), never the
    O(log n) bisect cascade, and every answer stays a real QueryAnswer."""
    from repro.ft import faults

    svc = AqpService(VerdictEngine(relation, _cfg()), max_batch=64,
                     max_retries=2, backoff_base_s=0.001)
    calls = []
    inner = svc._execute_slice

    def counting(queries):
        calls.append(len(queries))
        return inner(queries)

    svc._execute_slice = counting
    tickets = [svc.submit(q) for q in workload[:4]]
    with faults.inject(faults.FaultSpec("scan.eval", hits=(0,))):
        svc.flush()
    assert calls == [4, 4]  # full slice, failed; full slice again, clean
    assert (svc.counters()["retries"], svc.counters()["bisects"]) == (1, 0)
    for t in tickets:
        ans = t.result()
        assert not getattr(ans, "failed", False)
        assert t.resolutions == 1
    # Bitwise: the retried batch matches a never-faulted twin engine.
    twin = AqpService(VerdictEngine(relation, _cfg()), max_batch=64)
    clean = twin.execute(workload[:4])
    for t, c in zip(tickets, clean):
        assert t.result().cells == c.cells


def test_deadline_degraded_flush_never_primes_the_answer_cache(relation):
    """A deadline-bounded service returns best-so-far degraded answers; the
    workload-intel prescreen must never serve those back as full-accuracy
    cache hits on the next submit."""
    session = vd.connect(relation, _cfg(), cache=True)
    svc = session.serve(max_batch=4,
                        budget=vd.ErrorBudget(deadline_s=0.0))
    q = session.query().avg("v0").where(vd.between("x0", 2.0, 8.0)).build()
    first = svc.submit(q).result()
    assert first.degraded and "deadline" in first.degraded_reasons
    # Nothing degraded was recorded: the repeat is NOT prescreened, it
    # re-enters a microbatch and executes again.
    second_ticket = svc.submit(q)
    assert svc.prescreened == 0 and svc.pending == 1
    second = second_ticket.result()
    assert second.degraded and second.served_from is None
    assert session.stats()["intel"]["insertions"] == 0


def test_wait_counters_from_a_scripted_clock(relation, workload):
    """Three submits, one flush: the flushed queries, their summed queue
    wait and the prescreens' summed engine-lock wait, exactly, from the
    times the service's clock gave (before the lock, after it, at enqueue;
    then the flush's queue swap)."""
    times = iter([0.0, 0.25, 0.5,
                  1.0, 1.5, 2.0,
                  3.0, 3.0, 3.5,
                  5.0])
    session = vd.connect(relation, _cfg(), cache=True)
    svc = AqpService(session, max_batch=8, clock=lambda: next(times))
    tickets = [svc.submit(q) for q in workload[:3]]
    assert svc.counters()["flushed_queries"] == 0
    svc.flush()
    assert all(t._done for t in tickets)
    c = svc.counters()
    assert (c["flushes"], c["flushed_queries"], c["pending"]) == (1, 3, 0)
    assert c["queue_wait_s"] == (5.0 - 0.5) + (5.0 - 2.0) + (5.0 - 3.5)
    assert c["prescreen_lookups"] == 3
    assert c["prescreen_lock_wait_s"] == 0.25 + 0.5 + 0.0
    assert (c["retries"], c["bisects"], c["prescreened"]) == (0, 0, 0)
    assert {k: svc.stats()[k] for k in c} == c
