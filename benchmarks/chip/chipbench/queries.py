"""Query streams for the traffic mixes, as the serving front's wire JSON.

One general generator reads a mix's data file (``traffic/<name>.json``):
its ``queries`` block names a template family and its parameters. The
families are copies of the repository's own generators in
``repro/aqp/workload.py``, kept here so that a change to the program cannot
change the traffic:

- ``tpch_templates``: ``tpch_workload``'s three Q1/Q6-style templates over
  the ``tpch_lineitem`` columns;
- ``power_law``: ``make_workload``'s range/equality aggregates whose
  predicate columns follow the paper's section 8.6 power-law access
  scheme (``power_law_probs``).

Streams are drawn from ``numpy.random.default_rng([seed, stream])``, so the
warm-up and the measured window are disjoint draws of the same mix, and the
answers the check samples are drawn apart from both.
"""
from __future__ import annotations

from typing import List

import numpy as np

STREAMS = {"warmup": 2, "window": 3, "sample": 5, "cover": 6}


def power_law_probs(n_cols: int, frac_frequent: float) -> np.ndarray:
    """Paper section 8.6: the first ``ceil(n_cols * frac)`` columns are
    equally likely; each later column is half as likely as the one before."""
    k = max(int(np.ceil(n_cols * frac_frequent)), 1)
    probs = np.ones(n_cols)
    for i in range(k, n_cols):
        probs[i] = probs[i - 1] / 2.0
    return probs / probs.sum()


def _between(column: str, lo: float, hi: float) -> dict:
    return {"op": "between", "column": column, "lo": float(lo),
            "hi": float(hi)}


def tpch_templates(rng, data: dict, n: int, params: dict) -> List[dict]:
    """``tpch_workload``: Q6-style revenue SUM in a date, discount and
    quantity window; Q1-style AVG price and COUNT grouped by return flag;
    a nation's AVG revenue in a date window."""
    date, qty, disc = (c["name"] for c in data["numeric"])
    rflag, _, nation = (c["name"] for c in data["categorical"])
    price, revenue = data["measures"]
    out = []
    for _ in range(n):
        template = rng.integers(0, 3)
        start = rng.uniform(0, 2557 - 400)
        span = rng.uniform(90, 400)
        date_pred = _between(date, start, start + span)
        if template == 0:
            d0 = rng.uniform(0.0, 0.06)
            out.append({"aggs": [{"kind": "sum", "measure": revenue}],
                        "where": [date_pred, _between(disc, d0, d0 + 0.02),
                                  _between(qty, 1, 24)],
                        "group_by": []})
        elif template == 1:
            out.append({"aggs": [{"kind": "avg", "measure": price},
                                 {"kind": "count"}],
                        "where": [date_pred], "group_by": [rflag]})
        else:
            out.append({"aggs": [{"kind": "avg", "measure": revenue}],
                        "where": [date_pred,
                                  {"op": "equals", "column": nation,
                                   "value": int(rng.integers(0, 25))}],
                        "group_by": []})
    return out


def power_law(rng, data: dict, n: int, params: dict) -> List[dict]:
    """``make_workload``: 1 to ``max_predicates`` predicates per query on
    power-law-chosen columns, range widths a uniform share of the span."""
    num, cat = data["numeric"], data["categorical"]
    lo_n, hi_n = params["n_predicates"]
    frac = float(params["frac_frequent"])
    w_lo, w_hi = params["width_range"]
    kinds = list(params["agg_kinds"])
    cat_prob = float(params["cat_pred_prob"])
    n_meas = len(data["measures"])
    out = []
    for _ in range(n):
        n_preds = rng.integers(lo_n, hi_n + 1)
        where, used = [], set()
        for _ in range(n_preds):
            if cat and rng.random() < cat_prob:
                dim = int(rng.choice(len(cat), p=power_law_probs(len(cat), frac)))
                if ("c", dim) in used:
                    continue
                used.add(("c", dim))
                where.append({"op": "equals", "column": cat[dim]["name"],
                              "value": int(rng.integers(0, cat[dim]["size"]))})
            else:
                dim = int(rng.choice(len(num), p=power_law_probs(len(num), frac)))
                if ("n", dim) in used:
                    continue
                used.add(("n", dim))
                lo, hi = float(num[dim]["lo"]), float(num[dim]["hi"])
                width = rng.uniform(w_lo, w_hi) * (hi - lo)
                start = rng.uniform(lo, hi - width)
                where.append(_between(num[dim]["name"], start, start + width))
        kind = str(rng.choice(kinds))
        agg = {"kind": kind.lower()}
        measure = int(rng.integers(0, n_meas)) if kind != "COUNT" else None
        if measure is not None:
            agg["measure"] = data["measures"][measure]
        out.append({"aggs": [agg], "where": where, "group_by": []})
    return out


FAMILIES = {"tpch_templates": tpch_templates, "power_law": power_law}


def stream(data: dict, traffic: dict, seed: int, name: str, n: int) -> List[dict]:
    """``n`` wire queries of the mix's stream ``name`` (see ``STREAMS``)."""
    spec = traffic["queries"]
    rng = np.random.default_rng([int(seed), STREAMS[name]])
    return FAMILIES[spec["family"]](rng, data, n, spec)
