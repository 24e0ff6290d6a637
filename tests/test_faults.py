"""Chaos suite: deterministic fault injection across every serving seam.

Acceptance contract (the degraded-mode half of the paper's Theorem 1 story):
under an armed fault plan EVERY query resolves — bitwise-equal to the
no-fault oracle when the fault misses it, raw + ``degraded`` when it hits,
typed ``FailedAnswer`` when it keeps failing — with no hung tickets and no
store-wide drain poison; after ``heal()`` the learned state is bitwise-equal
to a never-failed run. The whole suite runs under the CI device matrix
(``REPRO_FORCE_HOST_DEVICES`` ∈ {1, 8}); the sharded legs skip gracefully on
a single-device topology.
"""
import warnings

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import repro.verdict as vd
from repro.aqp import workload as W
from repro.core.engine import EngineConfig
from repro.core.store import agg_key, state_key
from repro.core.types import AVG
from repro.ft import faults
from repro.ft.checkpoint import CheckpointCorruptError, CheckpointManager
from repro.serving.aqp import AqpService
from repro.verdict.answer import FailedAnswer


@pytest.fixture(scope="module")
def relation():
    return W.make_relation(seed=0, n_rows=4_000, n_num=2, cat_sizes=(4,),
                           n_measures=1, lengthscale=0.4, noise=0.2)


def _cfg(**kw):
    base = dict(sample_rate=0.2, n_batches=4, capacity=128, seed=0)
    base.update(kw)
    return EngineConfig(**base)


def _queries(session):
    b = vd.between
    return [
        (session.query().avg("v0").where(b("x0", 2.0, 8.0))
         .group_by("c0").build()),
        session.query().count().where(b("x0", 1.0, 6.0)).build(),
        session.query().sum("v0").where(b("x1", 0.0, 7.0)).build(),
        session.query().avg("v0").where(b("x1", 3.0, 9.0)).build(),
    ]


def _cells(ans):
    return [c.to_dict() for c in ans.cells]


AVG_KEY = state_key(agg_key(AVG, 0))


# ------------------------------------------------------------------ registry
def test_registry_determinism_and_zero_cost():
    # Disabled: one global load + None check; no counters, no stats.
    assert not faults.active()
    assert faults.stats() == {}
    faults.fire("scan.eval")  # no-op, must not raise
    with pytest.raises(ValueError, match="unknown injection point"):
        faults.FaultSpec("not.a.point")

    # hits schedule: per-(point, key) counters, key filter honored.
    with faults.inject(faults.FaultSpec("ingest.apply", key="a",
                                        hits=(1, 3))) as plan:
        fired = []
        for i in range(5):
            try:
                faults.fire("ingest.apply", key="a")
            except faults.InjectedFault as e:
                fired.append((i, e.point, e.key, e.hit))
            faults.fire("ingest.apply", key="b")  # never fires: key filter
        assert fired == [(1, "ingest.apply", "a", 1),
                         (3, "ingest.apply", "a", 3)]
        assert faults.stats() == {"ingest.apply": {"calls": 10, "fires": 2}}
        assert plan.calls == {"ingest.apply": 10}
    assert not faults.active()
    assert faults.stats() == {}

    # Seeded Bernoulli stream: same seed → same fire pattern; max_fires caps.
    def pattern(seed, max_fires=None):
        out = []
        spec = faults.FaultSpec("scan.eval", rate=0.5, max_fires=max_fires)
        with faults.inject(spec, seed=seed):
            for i in range(40):
                try:
                    faults.fire("scan.eval")
                except faults.InjectedFault:
                    out.append(i)
        return out

    assert pattern(7) == pattern(7)
    assert pattern(7) != pattern(8)
    assert len(pattern(7, max_fires=3)) == 3
    assert pattern(7, max_fires=3) == pattern(7)[:3]


# ------------------------------------------------- service-level isolation
def test_transient_scan_fault_absorbed_bitwise(relation):
    """A transient scan fault (fires once) is absorbed by bisect/retry: every
    ticket resolves to a REAL answer, bitwise-equal to a no-fault oracle."""
    oracle = vd.connect(relation, _cfg())
    chaos = vd.connect(relation, _cfg())
    qs = _queries(oracle)
    oracle_svc = oracle.serve(budget=vd.ErrorBudget(max_batches=3))
    want = [oracle_svc.submit(q) for q in qs]
    oracle_svc.flush()
    svc = chaos.serve(budget=vd.ErrorBudget(max_batches=3))
    tickets = [svc.submit(q) for q in qs]
    with faults.inject(faults.FaultSpec("scan.eval", hits=(0,))) as plan:
        svc.flush()
        assert plan.fires.get("scan.eval") == 1
    for t, w in zip(tickets, want):
        assert t._done and not t.result().failed
        assert _cells(t.result()) == _cells(w.result())


def test_persistent_scan_fault_typed_failure_no_hung_tickets(relation):
    """A persistent scan fault cannot hang the microbatch: every ticket
    resolves to a typed FailedAnswer after bounded retries."""
    session = vd.connect(relation, _cfg())
    svc = AqpService(session.engine, max_batch=64, max_batches=3,
                     max_retries=1, backoff_base_s=0.001)
    tickets = [svc.submit(q) for q in _queries(session)]
    with faults.inject(faults.FaultSpec("scan.eval", rate=1.0)):
        out = svc.flush()
    assert len(out) == len(tickets)
    for t in tickets:
        assert t._done
        ans = t.result()
        assert isinstance(ans, FailedAnswer) and ans.failed
        assert ans.error_type == "InjectedFault"
        # attempts counts ACTUAL executions of this query (not a retry-loop
        # bound): full batch of 4 (1 + max_retries backoff retry), its
        # bisected half of 2, then the single (1 + max_retries).
        assert ans.attempts == 5
    # One retry of the whole batch and one of each single; the batch, then
    # each half, bisected.
    assert (svc.counters()["retries"], svc.counters()["bisects"]) == (5, 3)
    # The service stays usable after the chaos clears.
    ok = svc.submit(_queries(session)[1])
    svc.flush()
    assert not isinstance(ok.result(), FailedAnswer)  # raw QueryResult again


# --------------------------------------------- quarantine → degrade → heal
def test_ingest_fault_quarantines_degrades_and_heals_bitwise(relation):
    """The tentpole end-to-end: a poisoned ingest apply quarantines ONE
    synopsis, queries keep resolving (raw floor, flagged degraded), health
    telemetry surfaces it everywhere, and heal() replays the parked batches
    back to a store bitwise-identical to a never-failed oracle session."""
    oracle = vd.connect(relation, _cfg())
    chaos = vd.connect(relation, _cfg())
    qs = _queries(oracle)
    want = oracle.execute_many(qs)
    # Quiesce the oracle's async ingest BEFORE arming the plan: its pending
    # applies share the fault key (same state_key) and would otherwise race
    # the chaos session for the scheduled hit.
    oracle.drain()
    with faults.inject(faults.FaultSpec("ingest.apply", key=AVG_KEY,
                                        hits=(0,))):
        got = chaos.execute_many(qs)
        # Every query resolved; the AVG key is quarantined after its first
        # record, so the LATER avg query is degraded (raw floor) while
        # non-AVG queries stay bitwise-equal to the oracle.
        assert len(got) == len(qs)
        assert got[3].degraded and AVG_KEY in got[3].degraded_reasons
        assert got[2].degraded  # SUM improves through the AVG synopsis too
        assert not got[1].degraded  # COUNT rides the FREQ key: unaffected
        assert _cells(got[1]) == _cells(want[1])
        # Health is visible at every level.
        health = chaos.stats()["health"]
        assert AVG_KEY in health["quarantined"]
        assert health["faults"]["ingest.apply"]["fires"] == 1
        rep = chaos.explain(qs[0])
        assert AVG_KEY in rep.quarantined
        assert "QUARANTINED" in str(rep)
        # drain() is a plain barrier — the poison no longer raises here.
        chaos.drain()
    # Disarmed: telemetry goes quiet, quarantine persists until heal().
    assert chaos.stats()["health"]["faults"] == {}
    assert AVG_KEY in chaos.stats()["health"]["quarantined"]
    assert chaos.heal() == {AVG_KEY: True}
    assert chaos.stats()["health"]["quarantined"] == {}
    # Learned state is bitwise-identical to the never-failed session: the
    # parked batches replayed in their original FIFO order.
    got_sd = chaos.engine.store.state_dict()
    want_sd = oracle.engine.store.state_dict()
    assert sorted(got_sd) == sorted(want_sd)
    for name in want_sd:
        for k in want_sd[name]:
            if k == "ingest_high_water":  # telemetry, not model state
                continue
            np.testing.assert_array_equal(got_sd[name][k], want_sd[name][k],
                                          err_msg=f"{name}/{k}")
    # And serving is bitwise-equal from here on.
    got2 = chaos.execute_many(qs)
    want2 = oracle.execute_many(qs)
    for g, w in zip(got2, want2):
        assert not g.degraded
        assert _cells(g) == _cells(w)


def test_drain_fault_blast_radius_is_one_synopsis(relation):
    """A failed ingest barrier quarantines the ONE synopsis it struck —
    drain() never raises and the rest of the store keeps serving."""
    session = vd.connect(relation, _cfg())
    qs = _queries(session)
    session.execute_many(qs)
    assert len(session.store) >= 2
    with faults.inject(faults.FaultSpec("store.drain", key=AVG_KEY,
                                        hits=(0,))):
        session.drain()  # never raises
    quarantined = session.stats()["health"]["quarantined"]
    assert list(quarantined) == [AVG_KEY]
    assert session.heal() == {AVG_KEY: True}
    assert session.stats()["health"]["quarantined"] == {}


def test_heal_restores_from_last_good_checkpoint(tmp_path, relation):
    """Session.heal(manager) heals from the newest committed checkpoint and
    replays parked batches — model state matches a never-failed twin."""
    chaos = vd.connect(relation, _cfg())
    twin = vd.connect(relation, _cfg())
    qs = _queries(chaos)
    chaos.execute_many(qs)
    twin.execute_many(qs)
    twin.drain()  # its async applies must not race the armed plan below
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    chaos.save(mgr, step=0)
    with faults.inject(faults.FaultSpec("ingest.apply", key=AVG_KEY,
                                        hits=(0,))):
        chaos.execute_many(qs)
        chaos.drain()  # quarantine lands while the plan is armed
    twin.execute_many(qs)
    assert AVG_KEY in chaos.stats()["health"]["quarantined"]
    assert chaos.heal(mgr) == {AVG_KEY: True}
    got_sd = chaos.engine.store.state_dict()
    want_sd = twin.engine.store.state_dict()
    for name in want_sd:
        for k in want_sd[name]:
            if k == "ingest_high_water":
                continue
            np.testing.assert_array_equal(got_sd[name][k], want_sd[name][k],
                                          err_msg=f"{name}/{k}")
    # heal(manager) with no committed checkpoint degrades to rebuild —
    # warn, not fail.
    with faults.inject(faults.FaultSpec("ingest.apply", key=AVG_KEY,
                                        hits=(0,))):
        chaos.execute_many(qs)
    empty_mgr = CheckpointManager(str(tmp_path / "nothing"))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        healed = chaos.heal(empty_mgr)
    assert healed == {AVG_KEY: True}
    assert any("restore unavailable" in str(w.message) for w in caught)


# ------------------------------------------------------------------ deadline
def test_deadline_returns_best_so_far_degraded(relation):
    session = vd.connect(relation, _cfg())
    q = _queries(session)[0]
    ans = session.execute(q, vd.ErrorBudget(deadline_s=0.0))
    assert ans.final
    assert ans.batches_used == 1  # at least one round always runs
    assert ans.degraded and "deadline" in ans.degraded_reasons
    assert len(ans.cells) > 0  # best-so-far answer, honest wider CI
    # A generous deadline changes nothing, bitwise.
    s2 = vd.connect(relation, _cfg())
    s3 = vd.connect(relation, _cfg())
    slow = s2.execute(q, vd.ErrorBudget(deadline_s=3600.0))
    free = s3.execute(q)
    assert not slow.degraded
    assert _cells(slow) == _cells(free)


def test_deadline_in_stream_and_serve(relation):
    session = vd.connect(relation, _cfg())
    q = _queries(session)[0]
    seen = list(session.stream(q, vd.ErrorBudget(deadline_s=0.0)))
    assert seen[-1].final and seen[-1].degraded
    assert "deadline" in seen[-1].degraded_reasons
    svc = vd.connect(relation, _cfg()).serve(
        budget=vd.ErrorBudget(deadline_s=0.0))
    t = svc.submit(q)
    svc.flush()
    ans = t.result()
    assert not ans.failed and ans.degraded
    assert "deadline" in ans.degraded_reasons


# ---------------------------------------------------------------- checkpoint
def test_checkpoint_write_fault_is_invisible_torn_write(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "c"), keep=5)
    tree = {"a": np.arange(4.0), "b": np.ones((2, 2))}
    mgr.save(0, tree)
    assert mgr.all_steps() == [0]
    with faults.inject(faults.FaultSpec("checkpoint.write", hits=(0,))):
        with pytest.raises(faults.InjectedFault):
            mgr.save(1, tree)
    # Torn write: no COMMITTED marker, step invisible, older step intact.
    assert mgr.all_steps() == [0]
    restored, _ = mgr.restore_blind()
    np.testing.assert_array_equal(restored["a"], tree["a"])
    mgr.save(1, tree)  # the seam recovers once the fault clears
    assert mgr.all_steps() == [0, 1]


def test_async_save_failure_surfaces_on_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "c"), keep=5)
    tree = {"a": np.arange(3.0)}
    mgr.save(0, tree)
    with faults.inject(faults.FaultSpec("checkpoint.write", hits=(0,))):
        mgr.save_async(1, tree)
        with pytest.raises(RuntimeError, match="async checkpoint save"):
            mgr.wait()  # inside the with: the daemon thread must see the plan
    assert mgr.all_steps() == [0]
    mgr.save_async(1, tree)  # exception was consumed; the manager recovers
    mgr.wait()
    assert mgr.all_steps() == [0, 1]


def test_corrupt_checkpoint_falls_back_to_earlier_step(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "c"), keep=5)
    mgr.save(0, {"a": np.zeros(3)})
    mgr.save(1, {"a": np.ones(3)})
    # Bit-rot the newest shard: checksum verification must reject it and
    # restore must fall back to step 0 with a warning, not crash.
    shard = tmp_path / "c" / "step_0000000001" / "shard_0.npz"
    raw = bytearray(shard.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    shard.write_bytes(bytes(raw))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        restored, _ = mgr.restore_blind()
    np.testing.assert_array_equal(restored["a"], np.zeros(3))
    assert any("falling back" in str(w.message) for w in caught)
    # An injected read fault walks back the same way.
    mgr2 = CheckpointManager(str(tmp_path / "d"), keep=5)
    mgr2.save(0, {"a": np.zeros(3)})
    mgr2.save(1, {"a": np.ones(3)})
    with faults.inject(faults.FaultSpec("checkpoint.read", key="step_1", hits=(0,))):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            restored, _ = mgr2.restore_blind()
    np.testing.assert_array_equal(restored["a"], np.zeros(3))
    assert any("falling back" in str(w.message) for w in caught)
    # No intact step left → the typed corruption error.
    with faults.inject(faults.FaultSpec("checkpoint.read", rate=1.0)):
        with pytest.raises(CheckpointCorruptError), warnings.catch_warnings():
            warnings.simplefilter("ignore")
            mgr2.restore_blind()


# ------------------------------------------------------------ sharded matrix
def test_sharded_store_quarantine_blast_radius(relation, forced_devices):
    """Sharded leg of the chaos matrix: the quarantine blast radius stays
    one synopsis (hence at most one shard); drain never raises across the
    shard barrier threads, and heal restores bitwise parity with a
    never-failed sharded twin."""
    n_dev = min(8, jax.device_count())
    if n_dev < 2:
        pytest.skip("needs a multi-device topology")
    mesh = Mesh(np.array(forced_devices(n_dev)), ("data",))
    chaos = vd.connect(relation, _cfg(), mesh=mesh)
    twin = vd.connect(relation, _cfg(), mesh=mesh)
    qs = _queries(chaos)
    with faults.inject(faults.FaultSpec("ingest.apply", key=AVG_KEY,
                                        hits=(0,))):
        got = chaos.execute_many(qs)
        assert len(got) == len(qs)
        assert got[3].degraded
        assert list(chaos.stats()["health"]["quarantined"]) == [AVG_KEY]
        chaos.drain()  # parallel per-shard barrier; never raises
    want = twin.execute_many(qs)
    assert _cells(got[1]) == _cells(want[1])  # fault missed → bitwise oracle
    assert chaos.heal() == {AVG_KEY: True}
    got_sd = chaos.engine.store.state_dict()
    want_sd = twin.engine.store.state_dict()
    assert sorted(got_sd) == sorted(want_sd)
    for name in want_sd:
        for k in want_sd[name]:
            if k == "ingest_high_water":
                continue
            np.testing.assert_array_equal(got_sd[name][k], want_sd[name][k],
                                          err_msg=f"{name}/{k}")
