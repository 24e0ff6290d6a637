"""The plain reference that decides ``correct``, and its lower-precision control.

The reference imports nothing of the program. It makes the relation again
from the seed (``datagen``), draws the engine's sample as the configuration
states it -- ``numpy.random.default_rng(seed).choice(N, round(N * rate),
replace=False)``, then a seeded permutation dealt round-robin into
``n_batches`` batches, so every prefix of batches is a uniform sample --
and answers each query in NumPy float64 from the rows of the batches the
answer used: the CLT sample estimator of the paper (AVG: mean and
variance / count; COUNT: cardinality * p, p(1-p)/n; SUM: their
delta-method product).

``reference_cells`` and its boundary-row allowance are copied from
``chip_smoke.py``. On the chip the fused scan compares and accumulates in
float32, so a row within float32 rounding of a range bound may fall on
either side of it: ``BOUNDARY_WINDOW`` (normalised units) marks such rows,
and the allowance is how far flipping any of them could move the estimate.

The served answers are held against the exact answers over the whole
relation (``exact_cells``, float64 on the device): a served interval,
estimate +- z(delta) * beta, has to hold the exact answer at the rate the
budget's delta states. That covers what the learned path serves -- the
improved estimates and their bounds -- and not only the scan under it.

The control is the same estimator with the scan's inputs -- the normalised
columns, the normalised bounds and the measures -- rounded to bfloat16 and
summed in float32: the step below the float32 scan that the configuration
states, and the one that would tempt a later change.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

BOUNDARY_WINDOW = 2.0 ** -22
BIG_BETA2 = 1e12  # the estimator's bound for an AVG with fewer than 2 rows

CellKey = Tuple[Tuple[int, ...], int]


def z_two_sided(delta: float) -> float:
    """A standard normal lies within (-z, z) with probability ``delta``."""
    return NormalDist().inv_cdf(0.5 + delta / 2.0)


def sample_order(n: int, rate: float, n_batches: int, seed: int):
    """Relation row ids of the engine's sample, in batch order, and the
    number of rows in each batch."""
    rng = np.random.default_rng(seed)
    k = max(int(round(n * rate)), 1)
    rows = rng.choice(n, size=k, replace=False)
    order = rng.permutation(k)
    batches = [order[i::n_batches] for i in range(n_batches)]
    batches = [b for b in batches if len(b)]
    return rows[np.concatenate(batches)], [len(b) for b in batches]


class Query:
    """A wire query resolved against a configuration's columns."""

    def __init__(self, wire: dict, layout: dict):
        num = list(layout["num_names"])
        cat = list(layout["cat_names"])
        meas = list(layout["measure_names"])
        self.aggs = [(a["kind"].upper(),
                      meas.index(a["measure"]) if "measure" in a else None)
                     for a in wire["aggs"]]
        self.ranges, self.equals = [], []
        for p in wire["where"]:
            if p["op"] == "between":
                self.ranges.append((num.index(p["column"]), float(p["lo"]),
                                    float(p["hi"])))
            elif p["op"] == "equals":
                self.equals.append((cat.index(p["column"]), int(p["value"])))
            else:
                raise ValueError(f"no reference for predicate {p!r}")
        self.groupby = [cat.index(c) for c in wire.get("group_by", ())]


class SampleTable:
    """Host float64 copy of the sample rows, in batch order."""

    def __init__(self, num, cat, meas, layout: dict, batch_sizes):
        self.num = np.asarray(num, np.float64)
        self.cat = np.asarray(cat)
        self.meas = np.asarray(meas, np.float64)
        self.lo = np.asarray(layout["num_lo"])
        self.span = np.asarray(layout["num_hi"]) - self.lo
        self.ends = np.cumsum(batch_sizes)

    def rows(self, batches: int) -> int:
        return int(self.ends[batches - 1])

    def normalized(self, dim: int, n: int):
        return (self.num[:n, dim] - self.lo[dim]) / self.span[dim]


def build_table(config: dict, layout: dict, seed: int, engine: dict,
                rows: int, exact_for=(), device=None):
    """Make the relation again from ``seed``; returns the sample's rows as
    a ``SampleTable`` and, for each ``(Query, groups)`` of ``exact_for``,
    its exact cells over the whole relation (``exact_cells``)."""
    import jax
    import jax.numpy as jnp

    from chipbench import datagen

    ids, sizes = sample_order(rows, engine["sample_rate"], engine["n_batches"],
                              seed)
    num, cat, meas = datagen.make_columns(config["data"], seed, rows, device)
    idx = jax.device_put(jnp.asarray(ids, jnp.int32),
                         next(iter(num.devices())))
    got = [np.asarray(jnp.take(a, idx, axis=0)) for a in (num, cat, meas)]
    exact = [exact_cells(q, groups, num, cat, meas, layout)
             for q, groups in exact_for]
    del num, cat, meas
    return SampleTable(*got, layout, sizes), exact


def _exact_sums(num, cat, meas, lo, hi, equal, radix, n_groups: int):
    """Matching rows and their measure sums per group, float64."""
    import jax.numpy as jnp

    inside = jnp.all((num >= lo) & (num <= hi), axis=1)
    inside &= jnp.all((equal < 0) | (cat == equal), axis=1)
    gid = jnp.sum(cat * radix, axis=1)
    hit = (inside[:, None] & (gid[:, None] == jnp.arange(n_groups)))
    hit = hit.astype(meas.dtype)
    count = jnp.sum(hit, axis=0)
    sums = jnp.sum(hit[:, :, None] * meas[:, None, :], axis=0)
    return count, sums


_EXACT = {}


def exact_cells(q: Query, groups, num, cat, meas, layout: dict
                ) -> Dict[CellKey, float]:
    """``{(group, agg): exact answer}`` of ``q`` over every row of the
    relation (device arrays, float64): AVG the mean, COUNT the rows, SUM
    the total of the rows that match."""
    import jax

    l, c = num.shape[1], cat.shape[1]
    lo, hi = np.full(l, -np.inf), np.full(l, np.inf)
    for dim, a, b in q.ranges:
        lo[dim], hi[dim] = max(lo[dim], a), min(hi[dim], b)
    equal = np.full(c, -1, np.int32)
    for dim, val in q.equals:
        equal[dim] = val
    radix = np.zeros(c, np.int32)
    n_groups = 1
    for dim in reversed(q.groupby):
        radix[dim] = n_groups
        n_groups *= int(layout["cat_sizes"][dim])
    fn = _EXACT.get(n_groups)
    if fn is None:
        fn = _EXACT[n_groups] = jax.jit(_exact_sums, static_argnums=7)
    count, sums = jax.device_get(fn(num, cat, meas, lo, hi, equal, radix,
                                    n_groups))
    out = {}
    for g in groups:
        gid = int(sum(v * radix[d] for d, v in zip(q.groupby, g)))
        for ai, (kind, m) in enumerate(q.aggs):
            if kind == "COUNT":
                value = float(count[gid])
            elif kind == "SUM":
                value = float(sums[gid, m])
            else:
                value = float(sums[gid, m]) / max(float(count[gid]), 1.0)
            out[(tuple(g), ai)] = value
    return out


def groups_of(q: Query, table: SampleTable, n_max: int = 1000):
    """The group-by values present among the first batch's matching rows
    (the engine discovers groups on its first sample batch)."""
    if not q.groupby:
        return [()]
    n = table.rows(1)
    inside = _inside(q, table, n, np.float64)
    vals = table.cat[:n][inside][:, q.groupby]
    return [tuple(int(x) for x in g) for g in np.unique(vals, axis=0)][:n_max]


def _inside(q: Query, table: SampleTable, n: int, dtype):
    inside = np.ones(n, bool)
    for dim, lo, hi in q.ranges:
        if dtype == np.float64:
            x = table.num[:n, dim]
            inside &= (x >= lo) & (x <= hi)
        else:
            x = table.normalized(dim, n).astype(dtype)
            bn = ((np.array([lo, hi]) - table.lo[dim])
                  / table.span[dim]).astype(dtype)
            inside &= (x >= bn[0]) & (x <= bn[1])
    for dim, val in q.equals:
        inside &= table.cat[:n, dim] == val
    return inside


def reference_cells(q: Query, table: SampleTable, batches: int,
                    cardinality: int, groups, precision: str = "f64"
                    ) -> Dict[CellKey, Tuple[float, float, float]]:
    """``{(group, agg): (estimate, beta2, allowance)}`` over the rows of the
    first ``batches`` batches, scaled to ``cardinality``.

    ``precision="f64"``: the reference, with the boundary-row allowance.
    ``"bf16"``: the control (allowance 0)."""
    import ml_dtypes

    n = table.rows(batches)
    low = precision == "bf16"
    dtype = ml_dtypes.bfloat16 if low else np.float64
    acc = np.float32 if low else np.float64
    inside = _inside(q, table, n, dtype)
    flip = np.zeros(n, bool)
    if not low:
        # A row can flip only across a bound it is near while every other
        # predicate holds.
        near = []
        for dim, lo, hi in q.ranges:
            xn = table.normalized(dim, n)
            bn = (np.array([lo, hi]) - table.lo[dim]) / table.span[dim]
            near.append(((np.abs(xn - bn[0]) <= BOUNDARY_WINDOW)
                         | (np.abs(xn - bn[1]) <= BOUNDARY_WINDOW),
                         table.num[:n, dim], lo, hi))
        for j, (nj, _, _, _) in enumerate(near):
            others = nj.copy()
            for i, (_, x, lo, hi) in enumerate(near):
                if i != j:
                    others &= (x >= lo) & (x <= hi)
            for dim, val in q.equals:
                others &= table.cat[:n, dim] == val
            flip |= others
    meas = table.meas[:n]
    if low:
        meas = meas.astype(ml_dtypes.bfloat16).astype(acc)
    scale = cardinality / n
    out = {}
    for g in groups:
        sel = inside.copy()
        amb = flip.copy()
        for d, v in zip(q.groupby, g):
            sel &= table.cat[:n, d] == v
            amb &= table.cat[:n, d] == v
        cnt = int(sel.sum())
        n_amb = int(amb.sum())
        p_hat = cnt / n
        freq_b2 = p_hat * (1 - p_hat) / n
        for ai, (kind, m) in enumerate(q.aggs):
            if kind == "COUNT":
                est, b2, allow = cardinality * p_hat, freq_b2 * cardinality ** 2, scale * n_amb
                out[(tuple(g), ai)] = (est, b2, allow)
                continue
            x = meas[sel, m]
            if cnt < 2:
                mean, avg_b2 = 0.0, BIG_BETA2
            else:
                mean = float(x.sum(dtype=acc)) / cnt
                avg_b2 = max(float((x * x).sum(dtype=acc)) / cnt - mean * mean,
                             0.0) / cnt
            xa = table.meas[:n][amb, m]
            if kind == "AVG":
                est, b2 = mean, avg_b2
                allow = np.abs(xa - mean).sum() / max(cnt - n_amb, 1)
            else:  # SUM
                est = mean * p_hat * cardinality
                b2 = (avg_b2 * (p_hat * cardinality) ** 2
                      + freq_b2 * (mean * cardinality) ** 2)
                allow = scale * np.abs(xa).sum()
            out[(tuple(g), ai)] = (est, b2, float(allow))
    return out


def cells_by_key(cells) -> Dict[CellKey, Tuple[str, float, float]]:
    """``{(group, agg): (kind, estimate, beta2)}`` from wire-form cells."""
    return {(tuple(int(v) for v in c["group"]), int(c["agg"])):
            (c["kind"], float(c["estimate"]), float(c["beta2"]))
            for c in cells}


# ------------------------------------------------------------- comparisons
def scan_gaps(raw, ref) -> Tuple[float, float]:
    """(estimate gap beyond the boundary allowance, bound gap), each
    relative to the reference, worst cell of one answer's raw cells."""
    est_gap = bound_gap = 0.0
    for key, (_, est, b2) in raw.items():
        r_est, r_b2, allow = ref[key]
        est_gap = max(est_gap, max(abs(est - r_est) - allow, 0.0)
                      / max(abs(r_est), 1e-12))
        if 0.0 < r_b2 < BIG_BETA2:
            bound_gap = max(bound_gap, abs(math.sqrt(b2) - math.sqrt(r_b2))
                            / math.sqrt(r_b2))
    return est_gap, bound_gap


def interval_misses(served, exact, z: float) -> Tuple[int, int]:
    """(cells whose exact answer lies outside the served interval
    ``estimate +- z * beta``, cells held against their exact answer)."""
    misses = cells = 0
    for key, (_, est, b2) in served.items():
        if not b2 < BIG_BETA2:
            continue
        cells += 1
        misses += abs(exact[key] - est) > z * math.sqrt(max(b2, 0.0))
    return misses, cells


def worst(values: List[float]) -> float:
    return max(values, default=0.0)
