"""The benchmark's data and query generators: deterministic from the seed,
and copies of the repository's generators in distribution and range."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from chipbench import datagen, queries  # noqa: E402

BIG_SEED = 3_000_000_123  # wider than the 32 bits jax.random.key takes


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["tpch_sf10", "synth_pl6"])
def test_device_columns_are_deterministic_and_seed_dependent(name):
    data = _config(name)["data"]
    a = datagen.make_columns(data, BIG_SEED, 4096)
    b = datagen.make_columns(data, BIG_SEED, 4096)
    c = datagen.make_columns(data, BIG_SEED + 1, 4096)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    num, cat, meas = a
    assert num.dtype == np.float64 and meas.dtype == np.float64
    assert cat.dtype == np.int32


def test_tpch_columns_span_tpch_like_domains():
    from repro.aqp.workload import tpch_like

    data = _config("tpch_sf10")["data"]
    num, cat, meas = (np.asarray(x) for x in
                      datagen.make_columns(data, BIG_SEED, 50_000))
    ref = tpch_like(0, 50_000)
    ref_num = np.asarray(ref.num)
    lay = datagen.schema_arrays(data)
    assert lay["num_lo"] == ref.schema.num_lo
    assert lay["num_hi"] == ref.schema.num_hi
    assert lay["cat_sizes"] == ref.schema.cat_sizes
    for d in range(3):
        assert num[:, d].min() >= ref.schema.num_lo[d]
        assert num[:, d].max() <= ref.schema.num_hi[d]
        # uniform on the same domain: means agree to sampling error
        span = ref.schema.num_hi[d] - ref.schema.num_lo[d]
        assert abs(num[:, d].mean() - ref_num[:, d].mean()) < 0.01 * span
    for d, size in enumerate(ref.schema.cat_sizes):
        assert set(np.unique(cat[:, d])) == set(range(size))
    ref_meas = np.asarray(ref.measures)
    # Each seed draws its own 25 nation multipliers on U(0.7, 1.3): their
    # mean has a standard deviation of 0.035, so two seeds' mean prices
    # differ by up to about 15 % at three standard deviations.
    for m in range(2):
        assert abs(meas[:, m].mean() / ref_meas[:, m].mean() - 1) < 0.15
    np.testing.assert_allclose(meas[:, 1], meas[:, 0] * (1 - num[:, 2]),
                               rtol=1e-6)


def test_synthetic_columns_span_make_relation_domains():
    from repro.aqp.workload import make_relation

    data = _config("synth_pl6")["data"]
    num, cat, meas = (np.asarray(x) for x in
                      datagen.make_columns(data, BIG_SEED, 50_000))
    ref = make_relation(0, 50_000, n_num=6, cat_sizes=(), n_measures=1,
                        lengthscale=0.4, noise=0.3)
    lay = datagen.schema_arrays(data)
    assert lay["num_lo"] == ref.schema.num_lo
    assert lay["num_hi"] == ref.schema.num_hi
    assert cat.shape == (50_000, 0)
    assert num.min() >= 0.0 and num.max() <= 10.0
    ref_meas = np.asarray(ref.measures)[:, 0]
    # measure = 10 + 2 f(x) + noise: same centre, same order of spread
    assert abs(meas[:, 0].mean() - 10.0) < 1.0
    assert 0.3 < meas[:, 0].std() / ref_meas.std() < 3.0


def _wire(q, schema):
    """A repository ``AggQuery`` in the front's wire JSON."""
    from repro.aqp.queries import CatEq, NumRange

    aggs = [{"kind": a.kind.lower(), **({"measure": schema.measure_names[
        a.measure]} if a.measure is not None else {})} for a in q.aggs]
    where = []
    for p in q.predicates:
        if isinstance(p, NumRange):
            where.append({"op": "between", "column": schema.num_names[p.dim],
                          "lo": p.lo, "hi": p.hi})
        else:
            assert isinstance(p, CatEq)
            where.append({"op": "equals", "column": schema.cat_names[p.dim],
                          "value": p.value})
    return {"aggs": aggs, "where": where,
            "group_by": [schema.cat_names[d] for d in q.groupby]}


def test_tpch_templates_copy_tpch_workload_draw_for_draw():
    from repro.aqp.workload import tpch_like, tpch_workload

    schema = tpch_like(0, 100).schema
    data = _config("tpch_sf10")["data"]
    got = queries.tpch_templates(np.random.default_rng(11), data, 60, {})
    want = [_wire(q, schema) for q in tpch_workload(11, schema, 60)]
    assert got == want


def test_power_law_copies_make_workload_draw_for_draw():
    from repro.aqp.workload import make_relation, make_workload

    schema = make_relation(0, 100, n_num=6, cat_sizes=(), n_measures=1).schema
    data = _config("synth_pl6")["data"]
    params = json.loads((BENCH / "traffic" / "ranges.json").read_text())
    spec = params["queries"]
    got = queries.power_law(np.random.default_rng(9), data, 80, spec)
    want = [_wire(q, schema) for q in make_workload(
        9, schema, 80, n_predicates=tuple(spec["n_predicates"]),
        frac_frequent=spec["frac_frequent"],
        width_range=tuple(spec["width_range"]),
        agg_kinds=tuple(spec["agg_kinds"]),
        cat_pred_prob=spec["cat_pred_prob"])]
    assert got == want


@pytest.mark.parametrize("mix,config", [("adhoc", "tpch_sf10"),
                                        ("ranges", "synth_pl6")])
def test_streams_are_deterministic_and_disjoint(mix, config):
    data = _config(config)["data"]
    traffic = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    a = queries.stream(data, traffic, BIG_SEED, "window", 50)
    assert a == queries.stream(data, traffic, BIG_SEED, "window", 50)
    assert a != queries.stream(data, traffic, BIG_SEED + 1, "window", 50)
    warm = queries.stream(data, traffic, BIG_SEED, "warmup", 50)
    assert not {json.dumps(q) for q in a} & {json.dumps(q) for q in warm}


def test_power_law_probs_halve_after_the_frequent_head():
    p = queries.power_law_probs(6, 0.2)  # ceil(6 * 0.2) = 2 frequent
    assert p[0] == pytest.approx(p[1])
    assert p[2] == pytest.approx(p[1] / 2)
    assert p[5] == pytest.approx(p[2] / 8)
    assert p.sum() == pytest.approx(1.0)
