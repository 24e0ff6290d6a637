"""The numbers ``correct`` compares, on hand-made inputs: exact answers
over the whole relation, interval misses, and the raw answers that have to
be seen for every scanned answer."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import harness, reference  # noqa: E402

LAYOUT = {"num_names": ("a", "b"), "num_lo": (0.0, 0.0),
          "num_hi": (1.0, 1.0), "cat_names": ("g", "h"),
          "cat_sizes": (3, 4), "measure_names": ("m0", "m1")}


def _relation(n=500, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.random((n, 2)), rng.integers(0, [3, 4], (n, 2)).astype(np.int32),
            rng.normal(10.0, 2.0, (n, 2)))


def test_exact_cells_match_a_row_by_row_count():
    import jax

    jax.config.update("jax_enable_x64", True)
    num, cat, meas = _relation()
    wire = {"aggs": [{"kind": "avg", "measure": "m1"}, {"kind": "count"},
                     {"kind": "sum", "measure": "m0"}],
            "where": [{"op": "between", "column": "a", "lo": 0.2, "hi": 0.7},
                      {"op": "equals", "column": "h", "value": 2}],
            "group_by": ["g"]}
    q = reference.Query(wire, LAYOUT)
    got = reference.exact_cells(q, [(0,), (2,)], num, cat, meas, LAYOUT)
    for g in (0, 2):
        rows = ((num[:, 0] >= 0.2) & (num[:, 0] <= 0.7) & (cat[:, 1] == 2)
                & (cat[:, 0] == g))
        assert got[((g,), 0)] == pytest.approx(meas[rows, 1].mean(), rel=1e-12)
        assert got[((g,), 1)] == rows.sum()
        assert got[((g,), 2)] == pytest.approx(meas[rows, 0].sum(), rel=1e-12)
    assert len(got) == 6


def test_interval_misses_count_exact_answers_outside_the_interval():
    served = {((), 0): ("AVG", 10.0, 1.0), ((), 1): ("COUNT", 100.0, 4.0),
              ((), 2): ("AVG", 5.0, reference.BIG_BETA2)}
    exact = {((), 0): 11.5, ((), 1): 105.0, ((), 2): 0.0}
    # z = 2: 10 +- 2 holds 11.5; 100 +- 4 misses 105; the last is unbounded.
    assert reference.interval_misses(served, exact, 2.0) == (1, 2)


def test_a_scanned_answer_with_no_raw_answer_is_counted():
    num, cat, meas = _relation(2000)
    table = reference.SampleTable(num, cat, meas, LAYOUT, [1000, 1000])
    wire = {"aggs": [{"kind": "avg", "measure": "m0"}],
            "where": [{"op": "between", "column": "b", "lo": 0.1,
                       "hi": 0.9}], "group_by": []}
    ref = reference.reference_cells(reference.Query(wire, LAYOUT), table, 1,
                                    10_000, [()])
    est, b2, _ = ref[((), 0)]
    cells = [{"group": [], "agg": 0, "kind": "AVG", "estimate": est,
              "beta2": b2}]
    answer = {"cells": cells, "batches_used": 1}
    seen = harness._compare([(wire, answer, cells)], table, LAYOUT, 10_000,
                            "f64")
    assert seen["raw_missing"] == 0 and seen["scan_estimate_gap"] == 0
    unseen = harness._compare([(wire, answer, None)], table, LAYOUT, 10_000,
                              "f64")
    assert unseen["raw_missing"] == 1
    cached = harness._compare(
        [(wire, dict(answer, served_from="cache:exact"), None)], table,
        LAYOUT, 10_000, "f64")
    assert cached["raw_missing"] == 0
