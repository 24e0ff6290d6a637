"""Smoke run of the Verdict query path on a TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the sharded path over four chips

The deployment is the TPC-H lineitem fact table at scale factor 10
(59,986,052 rows, TPC-H spec 4.2.5), denormalised by
``repro.aqp.workload.tpch_like`` and held on the device, with the default
10 % sample. The workload is ``tpch_workload``: 16 training queries, then 8
serving queries.

One chip: a ``ServingFront`` with one shared tenant serves the queries over
HTTP on localhost (16 concurrent training queries, a store drain, then the
other 8, one of them as an NDJSON stream); the same 24 queries then run
through a NoLearn session over the same sample. Four chips: the same
queries run on a Session whose scan and synopsis store are sharded over a
``("data",)`` mesh of four devices, and on a single-chip Session, and the
two must agree.

Answers are checked against an independent NumPy f64 reference computed on
the host from the sample rows and the full relation. The script exits
non-zero, printing no result line, when JAX finds no TPU or any check or
phase fails. Its last line of output is one JSON object.
"""
from __future__ import annotations

import argparse
import http.client
import json
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
SF10_ROWS = 59_986_052  # TPC-H lineitem at SF10 (spec 4.2.5: ~SF x 6,000,000)
N_TRAIN = 16
N_QUERIES = 24
DELTA = 0.95

# Scan tolerance. On the chip the fused scan compares and accumulates in
# f32, so an answer differs from the f64 reference in two ways, both
# bounded here: (1) a row within f32 rounding of a range bound may fall on
# either side of it -- BOUNDARY_WINDOW (normalized units; 2^-22, 4 ulps of
# 1.0) marks such rows and the allowance is what flipping any of them could
# change; (2) f32 accumulation of partial sums over ~600k-row batches and
# the cumulative f64 sum over 10 batches -- F32_REL covers it with a
# margin (f32 epsilon is 1.2e-7).
BOUNDARY_WINDOW = 2.0 ** -22
F32_REL = 2e-5
# Error bounds are sqrt(E[x^2] - mean^2): the subtraction costs about one
# decimal digit on this data, and boundary rows move the variance too.
BOUND_REL = 1e-3
# Theorem 1 (improved bound <= raw bound) is exact arithmetic; the improve
# kernel runs in f32, so a bound it leaves unchanged may round up by a few
# f32 ulps.
THEOREM1_REL = 1e-5
# Nominal coverage of the 95 % intervals is 0.95; with ~60 cells the share
# falls below 0.8 by chance with negligible probability.
MIN_COVERAGE = 0.8
# Sharded vs single-chip answers (four chips): both scans run in f32 but
# build the predicate mask differently (f64 jnp compare on the sharded
# path, f32 in the fused kernel), and the learned models are fitted to
# those slightly different raw answers. The improved answer is a linear
# combination of past raw answers whose weights can exceed 1, so the raw
# f32 tolerance is widened tenfold.
MESH_REL = 10 * F32_REL


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def _log(*args):
    print(*args, flush=True)


# ----------------------------------------------------------------- reference
def _to_wire(q, schema) -> dict:
    """The serving front's JSON for a ``tpch_workload`` AggQuery."""
    from repro.aqp.queries import CatEq, NumRange

    aggs = []
    for a in q.aggs:
        agg = {"kind": a.kind.lower()}
        if a.measure is not None:
            agg["measure"] = schema.measure_names[a.measure]
        aggs.append(agg)
    where = []
    for p in q.predicates:
        if isinstance(p, NumRange):
            where.append({"op": "between", "column": schema.num_names[p.dim],
                          "lo": p.lo, "hi": p.hi})
        elif isinstance(p, CatEq):
            where.append({"op": "equals", "column": schema.cat_names[p.dim],
                          "value": p.value})
        else:
            raise TypeError(f"no wire form for predicate {p!r}")
    return {"aggs": aggs, "where": where,
            "group_by": [schema.cat_names[d] for d in q.groupby]}


class Table:
    """Host f64 copy of a relation's rows, for the NumPy reference."""

    def __init__(self, rel):
        self.num = np.asarray(rel.num, np.float64)
        self.cat = np.asarray(rel.cat)
        self.meas = np.asarray(rel.measures, np.float64)
        self.lo = np.asarray(rel.schema.num_lo)
        self.span = np.asarray(rel.schema.num_hi) - self.lo
        self.n = self.num.shape[0]

    def normalized(self, dim: int):
        """Column ``dim`` in the [0, 1] units the engine compares in."""
        return (self.num[:, dim] - self.lo[dim]) / self.span[dim]


def reference_cells(q, table: Table, cardinality: int, groups,
                    allowance: bool = True):
    """``{(group, agg): (estimate, beta2, allowance)}`` computed in NumPy f64.

    ``cardinality``: rows of the full relation; the estimator scales a
    table of ``table.n`` rows up to it (1:1 for the full relation, which
    makes the estimates exact answers). ``groups``: the group tuples to
    answer. ``allowance``: how far the estimate can move if any row within
    ``BOUNDARY_WINDOW`` of a numeric bound falls on the other side (0 when
    not asked for).
    """
    from repro.aqp.executor import BIG_BETA2
    from repro.aqp.queries import CatEq, NumRange

    preds, near = [], []
    for p in q.predicates:
        if isinstance(p, NumRange):
            x = table.num[:, p.dim]
            preds.append((x >= p.lo) & (x <= p.hi))
            if not allowance:
                near.append(None)
                continue
            xn = table.normalized(p.dim)
            bn = (np.array([p.lo, p.hi]) - table.lo[p.dim]) / table.span[p.dim]
            near.append((np.abs(xn - bn[0]) <= BOUNDARY_WINDOW)
                        | (np.abs(xn - bn[1]) <= BOUNDARY_WINDOW))
        elif isinstance(p, CatEq):
            preds.append(table.cat[:, p.dim] == p.value)
            near.append(None)
        else:
            raise TypeError(f"no reference for predicate {p!r}")
    inside = np.ones(table.n, bool)
    for m in preds:
        inside &= m
    # A row can flip only across a bound it is near while every other
    # predicate holds.
    flip = np.zeros(table.n, bool)
    for j, nj in enumerate(near):
        if nj is None:
            continue
        others = nj.copy()
        for i, m in enumerate(preds):
            if i != j:
                others &= m
        flip |= others
    scale = cardinality / table.n
    out = {}
    for g in groups:
        sel = inside.copy()
        amb = flip.copy()
        for d, v in zip(q.groupby, g):
            sel &= table.cat[:, d] == v
            amb &= table.cat[:, d] == v
        cnt = int(sel.sum())
        n_amb = int(amb.sum())
        p_hat = cnt / table.n
        freq_b2 = p_hat * (1 - p_hat) / table.n
        for ai, a in enumerate(q.aggs):
            if a.kind == "COUNT":
                est = cardinality * p_hat
                b2 = freq_b2 * cardinality ** 2
                allow = scale * n_amb
            else:
                x = table.meas[sel, a.measure]
                if cnt < 2:  # no support: the engine's raw answer is 0 with
                    mean, avg_b2 = 0.0, BIG_BETA2  # a vacuous error bound
                else:
                    mean = x.mean()
                    avg_b2 = max((x * x).mean() - mean * mean, 0.0) / cnt
                xa = table.meas[amb, a.measure]
                if a.kind == "AVG":
                    est, b2 = mean, avg_b2
                    allow = np.abs(xa - mean).sum() / max(cnt - n_amb, 1)
                else:  # SUM
                    est = mean * p_hat * cardinality
                    b2 = (avg_b2 * (p_hat * cardinality) ** 2
                          + freq_b2 * (mean * cardinality) ** 2)
                    allow = scale * np.abs(xa).sum()
            out[(tuple(g), ai)] = (est, b2, allow)
    return out


def _cells(cells) -> dict:
    """``{(group, agg): (estimate, beta2)}`` from wire JSON or ``Cell``s."""
    out = {}
    for c in cells:
        if isinstance(c, dict):
            out[(tuple(c["group"]), c["agg"])] = (c["estimate"], c["beta2"])
        else:
            out[(tuple(c.group), c.agg)] = (c.estimate, c.beta2)
    return out


def _z(delta: float = DELTA) -> float:
    from repro.utils.stats import confidence_multiplier

    return float(confidence_multiplier(delta))


# ------------------------------------------------------------------- checks
class Checks:
    """Counts passed checks; raises ``SmokeFailure`` on the first failure."""

    def __init__(self):
        self.passed = 0

    def check(self, ok: bool, what: str):
        if not ok:
            raise SmokeFailure(what)
        self.passed += 1

    def close(self, got: float, want: float, tol: float, what: str):
        self.check(abs(got - want) <= tol,
                   f"{what}: got {got!r}, want {want!r} (tol {tol!r})")


def check_scan_reference(chk: Checks, answers, refs, label: str):
    """Estimates and bounds of raw answers against the NumPy sample
    estimator (tolerances: see ``F32_REL``/``BOUNDARY_WINDOW``/
    ``BOUND_REL``)."""
    for qi, (cells, ref) in enumerate(zip(answers, refs)):
        chk.check(set(cells) == set(ref),
                  f"{label} q{qi}: cells {sorted(cells)} != {sorted(ref)}")
        for key, (est, b2) in cells.items():
            r_est, r_b2, allow = ref[key]
            chk.close(est, r_est, allow + F32_REL * abs(r_est),
                      f"{label} q{qi} {key} estimate")
            chk.close(np.sqrt(b2), np.sqrt(r_b2),
                      BOUND_REL * np.sqrt(r_b2) + 1e-12,
                      f"{label} q{qi} {key} bound")


def check_answer_flags(chk: Checks, answers_json, label: str):
    for qi, a in enumerate(answers_json):
        chk.check(a.get("kind") == "answer",
                  f"{label} q{qi}: not an answer: {a}")
        chk.check(not a["degraded"],
                  f"{label} q{qi}: degraded {a['degraded_reasons']}")
        chk.check(a["supported"], f"{label} q{qi}: unsupported "
                  f"({a['unsupported_reason']})")


def coverage(answers, exact) -> float:
    """Share of cells whose exact answer lies in the reported interval."""
    z = _z()
    hits = total = 0
    for cells, ex in zip(answers, exact):
        for key, (est, b2) in cells.items():
            total += 1
            hits += abs(ex[key][0] - est) <= z * np.sqrt(b2)
    return hits / max(total, 1)


def check_session_health(chk: Checks, session, evaluator: str, label: str):
    stats = session.stats()
    chk.check(stats["health"]["quarantined"] == {},
              f"{label}: quarantined synopses {stats['health']}")
    for key, ing in session.ingest_stats().items():
        chk.check(not ing["quarantined"] and ing["unapplied"] == 0,
                  f"{label}: synopsis {key} ingest {ing}")
    chk.check(stats["scan"]["evaluator"] == evaluator,
              f"{label}: scan evaluator {stats['scan']['evaluator']!r}, "
              f"want {evaluator!r}")


# -------------------------------------------------------------------- HTTP
def _post(port: int, path: str, body: dict):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=3600)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read().decode()
        if resp.status != 200:
            raise SmokeFailure(f"POST {path}: HTTP {resp.status}: {data}")
        return data
    finally:
        conn.close()


def http_execute(port: int, body: dict) -> dict:
    return json.loads(_post(port, "/v1/tenants/shared/execute", body))


def http_stream(port: int, body: dict) -> list:
    data = _post(port, "/v1/tenants/shared/stream", body)
    return [json.loads(line) for line in data.splitlines() if line]


# ------------------------------------------------------------------ phases
class CompileClock:
    """Seconds spent in XLA backend compiles since construction."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def device_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if not stats:
        return "not reported by this backend"
    return (f"in use {stats.get('bytes_in_use')} B, peak "
            f"{stats.get('peak_bytes_in_use')} B, limit "
            f"{stats.get('bytes_limit')} B")


def build(n_rows: int, seed: int):
    """The relation on the device, its workload, and the host reference
    tables (full relation; the sample is added per session)."""
    import jax

    from repro.aqp.workload import tpch_like, tpch_workload

    rel = tpch_like(seed, n_rows)
    jax.block_until_ready((rel.num, rel.cat, rel.measures,
                           rel.num_normalized))
    _log(f"relation: {rel.cardinality} rows on {jax.devices()[0]}; "
         f"device memory {device_bytes()}")
    queries = tpch_workload(seed, rel.schema, N_QUERIES)
    return rel, queries, Table(rel)


def _groups(cells) -> list:
    return sorted({g for g, _ in cells})


def run_one_chip(n_rows: int = SF10_ROWS, seed: int = SEED) -> dict:
    """The one-chip phase: HTTP front with learning, NoLearn, checks.

    Runs on whatever backend JAX has (interpret-mode kernels on a CPU);
    returns a summary dict and raises ``SmokeFailure`` on a failed check.
    """
    import repro.verdict as vd
    from repro.serving.front import ServingFront, TenantSpec, serve_http

    chk = Checks()
    rel, queries, full = build(n_rows, seed)
    schema = rel.schema
    wire = [{"query": _to_wire(q, schema)} for q in queries]

    front = ServingFront(rel, vd.EngineConfig(use_kernels=True))
    front.add_tenant(TenantSpec("shared"))
    server = serve_http(front, "127.0.0.1", 0)
    port = server.server_address[1]
    try:
        with ThreadPoolExecutor(N_TRAIN) as pool:
            trained = list(pool.map(lambda b: http_execute(port, b),
                                    wire[:N_TRAIN]))
        session = front.tenant("shared").session
        session.drain()
        streamed = http_stream(port, wire[N_TRAIN])
        chk.check(len(streamed) >= 2 and streamed[-1].get("final")
                  and not any(s.get("final") for s in streamed[:-1]),
                  f"stream: {len(streamed)} lines, bad final flags")
        with ThreadPoolExecutor(N_QUERIES - N_TRAIN - 1) as pool:
            served = list(pool.map(lambda b: http_execute(port, b),
                                   wire[N_TRAIN + 1:]))
    finally:
        server.shutdown()
        server.server_close()
    verdict_json = trained + [streamed[-1]] + served
    check_answer_flags(chk, verdict_json, "verdict")
    session.drain()
    check_session_health(chk, session, "fused_masked_scan", "verdict")
    _log(f"front: {len(verdict_json)} answers over HTTP "
         f"({N_TRAIN} training, 1 streamed in {len(streamed)} rounds, "
         f"{len(served)} served)")

    nolearn = vd.connect(rel, vd.EngineConfig(use_kernels=True,
                                              learning=False))
    raw = nolearn.execute_many(queries)
    check_answer_flags(chk, [{"kind": "answer", **vars(a)} for a in raw],
                       "nolearn")
    check_session_health(chk, nolearn, "fused_masked_scan", "nolearn")
    verdict = [_cells(a["cells"]) for a in verdict_json]
    raw = [_cells(a.cells) for a in raw]

    sample = Table(nolearn.engine.batches.relation)
    card = rel.cardinality
    sample_ref = [reference_cells(q, sample, card, _groups(c))
                  for q, c in zip(queries, raw)]
    exact = [reference_cells(q, full, card, _groups(c), allowance=False)
             for q, c in zip(queries, raw)]
    check_scan_reference(chk, raw, sample_ref, "nolearn")
    z = _z()
    n_cells = improved = 0
    for qi, (q, v, r) in enumerate(zip(queries, verdict, raw)):
        chk.check(set(v) == set(r), f"verdict q{qi}: cells differ from "
                  f"nolearn: {sorted(v)} vs {sorted(r)}")
        for key, (_, b2) in v.items():
            n_cells += 1
            if q.aggs[key[1]].kind == "SUM":
                # Theorem 1 bounds each snippet's variance. A SUM cell is
                # the delta-method product of an AVG and a FREQ snippet at
                # the improved estimates, so its bound may grow when an
                # estimate moves up even though both snippet bounds shrank.
                continue
            raw_bound = z * np.sqrt(r[key][1])
            bound = z * np.sqrt(b2)
            chk.check(bound <= raw_bound * (1 + THEOREM1_REL),
                      f"verdict q{qi} {key}: bound {bound!r} > raw bound "
                      f"{raw_bound!r} (Theorem 1)")
            improved += bound < raw_bound * (1 - THEOREM1_REL)
    cov_verdict = coverage(verdict, exact)
    cov_raw = coverage(raw, exact)
    chk.check(cov_verdict >= MIN_COVERAGE,
              f"verdict coverage {cov_verdict} < {MIN_COVERAGE}")
    chk.check(cov_raw >= MIN_COVERAGE,
              f"nolearn coverage {cov_raw} < {MIN_COVERAGE}")
    _log(f"answers: {n_cells} cells; {improved} AVG/COUNT bounds tightened "
         f"by learning; 95% interval coverage of the exact answer: verdict "
         f"{cov_verdict:.3f}, nolearn {cov_raw:.3f}")
    return {"checks": chk.passed, "cells": n_cells,
            "coverage_verdict": cov_verdict, "coverage_nolearn": cov_raw}


def _run_session(session, queries):
    """Training queries, a drain, then the serving queries (one of them
    streamed); returns the answers in query order."""
    train = session.execute_many(queries[:N_TRAIN])
    session.drain()
    *_, streamed = session.stream(queries[N_TRAIN])
    served = session.execute_many(queries[N_TRAIN + 1:])
    session.drain()
    return train + [streamed] + served


def run_four_chips(n_rows: int = SF10_ROWS, seed: int = SEED,
                   n_devices: int = 4) -> dict:
    """The mesh phase: sharded scan + sharded store vs one chip."""
    import jax
    from jax.sharding import Mesh

    import repro.verdict as vd

    chk = Checks()
    rel, queries, _ = build(n_rows, seed)
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("data",))
    cfg = vd.EngineConfig(use_kernels=True)
    sharded = vd.connect(rel, cfg, mesh=mesh)
    single = vd.connect(rel, vd.EngineConfig(use_kernels=True))
    answers = {}
    for label, session, evaluator in (
            ("sharded", sharded, "sharded_mask+kernel_agg"),
            ("single", single, "fused_masked_scan")):
        got = _run_session(session, queries)
        check_answer_flags(chk, [{"kind": "answer", **vars(a)} for a in got],
                           label)
        check_session_health(chk, session, evaluator, label)
        answers[label] = [_cells(a.cells) for a in got]
    sample = Table(single.engine.batches.relation)
    n_cells = 0
    for qi, (a, b) in enumerate(zip(answers["sharded"], answers["single"])):
        chk.check(set(a) == set(b), f"q{qi}: sharded cells {sorted(a)} "
                  f"!= single-chip cells {sorted(b)}")
        ref = reference_cells(queries[qi], sample, rel.cardinality,
                              _groups(b))
        for key in b:
            allow = ref[key][2]
            chk.close(a[key][0], b[key][0], allow + MESH_REL * abs(b[key][0]),
                      f"q{qi} {key} sharded vs single estimate")
            chk.close(np.sqrt(a[key][1]), np.sqrt(b[key][1]),
                      BOUND_REL * np.sqrt(b[key][1]) + 1e-12,
                      f"q{qi} {key} sharded vs single bound")
            n_cells += 1
    shards = sharded.stats()["store"].get("n_shards")
    _log(f"mesh: {n_devices} devices, store over {shards} shards; "
         f"{n_cells} cells agree with the single-chip session")
    return {"checks": chk.passed, "cells": n_cells}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path over four chips and "
                         "its single-chip comparison")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    n_devices = 4 if args.four_chips else 1
    if len(jax.devices()) < n_devices:
        print(f"chip_smoke: needs {n_devices} TPU devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1

    from repro.utils.compile_cache import use_compile_cache

    cache = use_compile_cache()
    clock = CompileClock()
    _log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
         f"compile cache {cache}")
    if args.four_chips:
        summary = run_four_chips(SF10_ROWS, SEED, n_devices)
    else:
        summary = run_one_chip(SF10_ROWS, SEED)
    _log(f"rows: {SF10_ROWS}; device memory {device_bytes()}")
    _log(f"compile: {clock.seconds:.1f} s in {clock.count} backend "
         f"compiles")
    _log(f"checks passed: {summary['checks']}")
    _log(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
