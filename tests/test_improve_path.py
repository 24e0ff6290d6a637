"""Serve/learn hot path: bucket-padded improve parity vs the capacity-padded
baseline, bounded jit program counts across mixed-Q workloads, stacked
multi-synopsis dispatch parity, and async-ingest determinism under drain().

Strictness notes (pinned by probes on the XLA CPU backend, same on TPU dot
paths): padding columns/rows carry exact zeros (identity Sigma^{-1} blocks,
zero alpha), so padding itself never changes a partial sum. What CAN change
between *different* padded widths is how XLA groups the live elements inside
a reduction (gemv vs gemm strategies, k-blocking), which perturbs results by
O(eps). Hence:
  - bucketed vs capacity-padded baseline: ULP-level allclose + identical
    validation decisions;
  - everything that runs through ONE program family — async vs sync ingest,
    stacked vs per-synopsis dispatch, batched vs sequential engines — is
    asserted strictly bitwise.
"""
import threading

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.synopsis import (
    Synopsis,
    SynopsisQuarantinedError,
    _improve_padded,
)
from repro.core.types import (
    AVG,
    FREQ,
    RawAnswer,
    Schema,
    SnippetBatch,
    bucket_size,
    make_snippets,
)


def _schema():
    return Schema(num_lo=(0.0, 0.0), num_hi=(1.0, 1.0), cat_sizes=(4,),
                  n_measures=1)


def _random_batch(rng, sch, n, agg=AVG):
    ranges = []
    for _ in range(n):
        r = {}
        for d in range(sch.n_num):
            a = rng.uniform(0, 0.6)
            r[d] = (a, a + rng.uniform(0.05, 0.4))
        ranges.append(r)
    return make_snippets(sch, agg=agg, measure=0, num_ranges=ranges)


def _filled(rng, sch, n, capacity, **kw):
    syn = Synopsis(sch, capacity=capacity, **kw)
    syn.add(_random_batch(rng, sch, n), rng.normal(1.0, 0.3, n),
            rng.uniform(0.01, 0.05, n))
    syn.drain()
    return syn


def _capacity_padded_improve(syn, new, raw):
    """The pre-PR serve path: state padded to full capacity, Q unpadded."""
    C = syn.capacity
    rows = np.asarray(syn._order, np.int64)
    n = len(rows)
    idx = np.concatenate([rows, np.zeros((C - n,), np.int64)])
    past = syn._row_batch(idx)
    valid = jnp.asarray(np.arange(C) < n, jnp.float64)
    sinv = np.eye(C)
    sinv[:n, :n] = np.asarray(syn._sigma_inv)
    alpha = np.zeros((C,))
    alpha[:n] = np.asarray(syn._alpha)
    theta, beta2, accepted = _improve_padded(
        past, valid, jnp.asarray(sinv), jnp.asarray(alpha), syn.params,
        new, raw.theta, raw.beta2, syn.delta_v,
    )
    return np.asarray(theta), np.asarray(beta2), np.asarray(accepted)


# ------------------------------------------------------------------- buckets
def test_bucket_size():
    assert bucket_size(0) == 8
    assert bucket_size(1) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(100) == 128
    assert bucket_size(100, cap=96) == 96  # clamped to capacity
    assert bucket_size(3, minimum=4) == 4


def test_fill_bucket_tracks_fill_not_capacity():
    rng = np.random.default_rng(0)
    sch = _schema()
    syn = _filled(rng, sch, 10, capacity=2000)
    assert syn._fill_bucket() == 16
    state = syn._padded_state()
    assert state[2].shape == (16, 16)  # Sigma^{-1} tile, not (2000, 2000)


def test_bucketed_improve_matches_capacity_padded_baseline():
    """Across fill levels and Q sizes the bucketed program returns the
    capacity-padded baseline's answers to within ULPs, with identical
    validation (accept/reject) decisions."""
    rng = np.random.default_rng(1)
    sch = _schema()
    for capacity in (64, 128):
        for fill in (1, 3, 17, 60):
            if fill > capacity:
                continue
            syn = _filled(rng, sch, fill, capacity=capacity)
            for q in (1, 5, 16, 33):
                new = _random_batch(rng, sch, q)
                raw = RawAnswer(jnp.asarray(rng.normal(1.0, 0.3, q)),
                                jnp.asarray(np.full(q, 0.02)))
                imp = syn.improve(new, raw)
                t0, b0, a0 = _capacity_padded_improve(syn, new, raw)
                np.testing.assert_allclose(np.asarray(imp.theta), t0,
                                           rtol=1e-12, atol=1e-13)
                np.testing.assert_allclose(np.asarray(imp.beta2), b0,
                                           rtol=1e-12, atol=1e-13)
                np.testing.assert_array_equal(np.asarray(imp.accepted), a0)


def test_improve_compile_count_bounded_across_mixed_q():
    """One compiled program per (Q-bucket, fill-bucket) pair — a mixed-Q
    workload against evolving fills must not recompile per distinct Q."""
    rng = np.random.default_rng(2)
    sch = _schema()
    syn = _filled(rng, sch, 5, capacity=256)   # fill bucket 8
    syn2 = _filled(rng, sch, 20, capacity=256)  # fill bucket 32
    before = _improve_padded._cache_size()
    for q in list(range(1, 9)) + [12, 16, 23, 31]:  # Q buckets: 8, 16, 32
        for s in (syn, syn2):
            new = _random_batch(rng, sch, q)
            raw = RawAnswer(jnp.asarray(rng.normal(1.0, 0.3, q)),
                            jnp.asarray(np.full(q, 0.02)))
            s.improve(new, raw)
    added = _improve_padded._cache_size() - before
    assert added <= 3 * 2  # |{8,16,32}| Q-buckets x |{8,32}| fill-buckets
    # Steady state: repeating the workload compiles nothing new.
    before = _improve_padded._cache_size()
    for q in (1, 5, 12, 31):
        new = _random_batch(rng, sch, q)
        raw = RawAnswer(jnp.asarray(rng.normal(1.0, 0.3, q)),
                        jnp.asarray(np.full(q, 0.02)))
        syn.improve(new, raw)
    assert _improve_padded._cache_size() == before


# ------------------------------------------------------------------- stacked
def test_stacked_dispatch_bitwise_matches_per_synopsis_improve():
    """VerdictEngine._improve's single stacked dispatch over multiple
    aggregate keys equals the per-synopsis improve calls bit for bit."""
    from repro.aqp import workload as W
    from repro.core.engine import EngineConfig, VerdictEngine

    rng = np.random.default_rng(3)
    rel = W.make_relation(seed=0, n_rows=4_000, n_num=2, cat_sizes=(4,),
                          n_measures=1)
    eng = VerdictEngine(rel, EngineConfig(sample_rate=0.2, n_batches=3,
                                          capacity=64, seed=0))
    # Train both synopses (AVG measure 0 and FREQ).
    for q in W.make_workload(1, rel.schema, 8, agg_kinds=("AVG", "COUNT")):
        eng.execute(q)
    snips = SnippetBatch.concat([
        _random_batch(rng, rel.schema, 5, agg=AVG),
        _random_batch(rng, rel.schema, 3, agg=FREQ),
    ])
    raw = RawAnswer(jnp.asarray(rng.normal(1.0, 0.3, snips.n)),
                    jnp.asarray(np.full(snips.n, 0.02)))
    assert len(eng.store) == 2  # the dispatch actually stacks two groups
    imp = eng._improve(snips, raw)
    agg = np.asarray(snips.agg)
    theta = np.asarray(raw.theta)
    beta2 = np.asarray(raw.beta2)
    for key in ((AVG, 0), (FREQ, 0)):
        rows = np.where(agg == key[0])[0]
        syn = eng.synopsis_for(*key)
        ref = syn.improve(
            snips[jnp.asarray(rows)],
            RawAnswer(jnp.asarray(theta[rows]), jnp.asarray(beta2[rows])),
        )
        np.testing.assert_array_equal(np.asarray(imp.theta)[rows],
                                      np.asarray(ref.theta))
        np.testing.assert_array_equal(np.asarray(imp.beta2)[rows],
                                      np.asarray(ref.beta2))
        np.testing.assert_array_equal(np.asarray(imp.accepted)[rows],
                                      np.asarray(ref.accepted))


# -------------------------------------------------------------- async ingest
def test_async_ingest_matches_sync_bitwise():
    """Interleaved add/improve through the ingest thread produces bitwise the
    same model state and answers as synchronous ingestion (FIFO application
    makes the post-drain state independent of worker timing)."""
    rng_a = np.random.default_rng(4)
    rng_b = np.random.default_rng(4)
    sch = _schema()
    a = Synopsis(sch, capacity=32, async_ingest=True)
    b = Synopsis(sch, capacity=32, async_ingest=False)
    for step in range(6):
        for syn, rng in ((a, rng_a), (b, rng_b)):
            n = 3 + step % 3
            snips = _random_batch(rng, sch, n)
            theta = rng.normal(1.0, 0.3, n)
            beta2 = rng.uniform(0.01, 0.05, n)
            syn.add(snips, theta, beta2)
            new = _random_batch(rng, sch, 4)
            raw = RawAnswer(jnp.asarray(rng.normal(1.0, 0.3, 4)),
                            jnp.asarray(np.full(4, 0.02)))
            imp = syn.improve(new, raw)
            syn._last = (np.asarray(imp.theta), np.asarray(imp.beta2))
        np.testing.assert_array_equal(a._last[0], b._last[0])
        np.testing.assert_array_equal(a._last[1], b._last[1])
    a.drain()
    assert a.n == b.n
    np.testing.assert_array_equal(np.asarray(a._sigma_inv),
                                  np.asarray(b._sigma_inv))
    np.testing.assert_array_equal(a._theta[: a.n], b._theta[: b.n])


def test_add_is_nonblocking_and_drain_is_the_barrier():
    """add() returns while the model update is still pending; drain() applies
    everything. Uses a gate inside the apply function, so the assertion is
    deterministic, not timing-dependent."""
    rng = np.random.default_rng(5)
    sch = _schema()
    syn = Synopsis(sch, capacity=16, async_ingest=True)
    gate = threading.Event()
    inner = syn._apply_add

    def gated(*args):
        gate.wait(timeout=30)
        inner(*args)

    syn._apply_add = gated  # picked up when add() lazily builds the queue
    syn.add(_random_batch(rng, sch, 3), np.ones(3), np.full(3, 0.1))
    assert syn.n == 0  # returned with the covariance build still queued
    gate.set()
    syn.drain()
    assert syn.n == 3
    assert len(syn._order) == 3


def test_failed_ingest_quarantines_not_poisons():
    """A mid-apply failure QUARANTINES this synopsis instead of poisoning
    every later barrier: drain() stays a plain barrier (never raises), the
    failed batch and everything after it park unapplied in FIFO order,
    improve degrades to the raw sample estimate, state_dict refuses with a
    typed error, and heal() replays the parked batches to a state bitwise
    identical to a synopsis that never failed."""
    rng = np.random.default_rng(6)
    sch = _schema()
    syn = Synopsis(sch, capacity=16, async_ingest=True)
    b1 = (_random_batch(rng, sch, 2), np.ones(2), np.full(2, 0.1))
    b2 = (_random_batch(rng, sch, 2), np.full(2, 2.0), np.full(2, 0.2))
    applied = {"n": 0}

    def boom(*args):
        applied["n"] += 1
        raise ValueError("injected ingest failure")

    syn._apply_add = boom
    syn.add(*b1)
    syn.add(*b2)
    syn.drain()  # plain barrier — a failed apply no longer raises here
    assert applied["n"] == 1  # batch 1 failed; batch 2 parked, never applied
    assert syn.quarantined
    assert "injected ingest failure" in syn.quarantine_reason
    stats = syn.ingest_stats()
    assert stats["quarantined"] and stats["quarantine_count"] == 1
    assert stats["unapplied"] == 2  # the failed batch AND the one behind it
    # Serving degrades to the raw floor (Theorem 1's equality case).
    raw = RawAnswer(theta=jnp.asarray([1.5, 2.5]), beta2=jnp.asarray([0.3, 0.4]))
    imp = syn.improve(_random_batch(rng, sch, 2), raw)
    np.testing.assert_array_equal(np.asarray(imp.theta), [1.5, 2.5])
    np.testing.assert_array_equal(np.asarray(imp.beta2), [0.3, 0.4])
    assert not bool(np.asarray(imp.accepted).any())
    with pytest.raises(SynopsisQuarantinedError):
        syn.state_dict()  # a half-applied model never checkpoints
    # Heal: restore the real applier and replay the parked batches in order.
    del syn._apply_add
    assert syn.heal()
    assert not syn.quarantined
    assert syn.ingest_stats()["unapplied"] == 0
    twin = Synopsis(sch, capacity=16, async_ingest=False)
    twin.add(*b1)
    twin.add(*b2)
    got, want = syn.state_dict(), twin.state_dict()
    for k in want:
        if k == "ingest_high_water":  # telemetry, not model state
            continue
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_state_dict_returns_copies_not_views():
    """Snapshots must not mutate when the ring buffers evolve afterwards."""
    rng = np.random.default_rng(7)
    sch = _schema()
    syn = _filled(rng, sch, 4, capacity=4)
    snap = syn.state_dict()
    theta_before = snap["theta"].copy()
    lo_before = snap["lo"].copy()
    # Overflow the capacity so every ring-buffer row is rewritten.
    syn.add(_random_batch(rng, sch, 4), rng.normal(5.0, 0.1, 4),
            rng.uniform(0.001, 0.002, 4))
    syn.drain()
    np.testing.assert_array_equal(snap["theta"], theta_before)
    np.testing.assert_array_equal(snap["lo"], lo_before)
    # And the snapshot still round-trips into an equivalent synopsis.
    syn2 = Synopsis(sch, capacity=4)
    syn2.load_state_dict(snap)
    np.testing.assert_array_equal(np.asarray(syn2.theta()), theta_before)


def test_model_math_on_a_host_device_is_answer_invariant(monkeypatch,
                                                         forced_devices):
    """Off the CPU the f64 model math runs on the host CPU device
    (``model_device``) while the improve kernel serves from the serving
    device. Here a second host device stands in for the host: every
    transfer that path makes runs, and answers and model state stay
    bitwise equal to the all-on-one-device engine."""
    from repro.aqp import workload as W
    from repro.core import synopsis as synopsis_mod
    from repro.core.engine import EngineConfig, VerdictEngine

    serve, host = forced_devices(2)
    rel = W.make_relation(seed=3, n_rows=4000, n_num=2, cat_sizes=(4,),
                          n_measures=1)
    qs = W.make_workload(4, rel.schema, 6, agg_kinds=("AVG", "COUNT"))
    cfg = dict(sample_rate=0.2, n_batches=3, seed=1, use_kernels=True)

    def run():
        eng = VerdictEngine(rel, EngineConfig(**cfg))
        cells = [c for q in qs for c in eng.execute(q).cells]
        eng.refit(steps=3)
        cells += [c for q in qs for c in eng.execute(q).cells]
        eng.drain()
        return eng, cells

    _, want = run()
    monkeypatch.setattr(synopsis_mod, "model_device",
                        lambda device=None: host)
    eng, got = run()
    assert got == want
    for key in eng.store.keys():
        syn = eng.store.get(key)
        assert syn.n > 0
        assert syn._sigma_inv.devices() == {host}
        assert syn._padded_state()[2].devices() == {serve}
