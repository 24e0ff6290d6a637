"""Metric arithmetic, the scan's work count, and discovery by name."""
import inspect
import json
import math
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import e2e, harness, roofline  # noqa: E402
from chipbench_tiny import TPCH_ADHOC  # noqa: E402


def _rec(sent, latency_s, kind="answer", status=200, degraded=False):
    if status is None:
        return {"sent": sent, "done": None, "status": None, "error": "x"}
    return {"sent": sent, "done": sent + latency_s, "status": status,
            "answer": {"kind": kind, "degraded": degraded,
                       "batches_used": 2}}


def test_percentiles_rank_every_attempt_and_failures_last():
    recs = [_rec(0.0, 0.010 * (i + 1)) for i in range(18)]
    recs += [_rec(0.0, 0.001, kind="failed"), _rec(0.0, 0.001, status=None)]
    m = e2e.window_metrics(recs, 0.0, 10.0)
    assert m["attempted"] == 20
    assert m["failed"] == 2
    assert m["no_reply"] == 1
    assert m["latency_p50_ms"] == pytest.approx(100.0)  # rank 10 of 20
    assert m["latency_p95_ms"] == math.inf  # rank 19: a failure


def test_degraded_rejected_and_http_errors_count_as_failed():
    recs = [_rec(0.0, 0.1), _rec(0.0, 0.1, degraded=True),
            _rec(0.0, 0.1, kind="rejected", status=429),
            _rec(0.0, 0.1, status=500, kind="error")]
    m = e2e.window_metrics(recs, 0.0, 1.0)
    assert (m["attempted"], m["failed"]) == (4, 3)


def test_answers_per_s_counts_only_answers_completed_in_the_window():
    recs = [_rec(0.0, 0.5), _rec(0.5, 0.4), _rec(0.9, 0.3)]  # last ends 1.2
    m = e2e.window_metrics(recs, 0.0, 1.0)
    assert m["answers_per_s"] == pytest.approx(2.0)
    assert m["attempted"] == 3  # the late one still counts in the tails


def test_the_tail_is_read_from_the_window_and_nothing_when_none_answered():
    read = harness.metric_reader("latency_p95_ms")
    recs = [_rec(0.0, 0.010 * (i + 1)) for i in range(20)]
    window = e2e.window_metrics(recs, 0.0, 1.0)
    assert read({"window": window}) == pytest.approx(190.0)
    empty = e2e.window_metrics([_rec(0.0, 0.0, kind="failed")], 0.0, 1.0)
    assert read({"window": empty}) is None


def test_nearest_rank_percentile():
    assert e2e.percentile([5.0, 1.0, 3.0, 2.0, 4.0], 50) == 3.0
    assert e2e.percentile(list(range(1, 101)), 95) == 95
    assert e2e.percentile([], 50) == math.inf


def _shapes(num, cat, meas, n_snippets, valid=None):
    """What a scan call's operands say about the work: true rows (the valid
    mask's count) and column counts; nothing of dtype or padding."""
    rows = int(np.sum(valid)) if valid is not None else num.shape[0]
    return dict(rows=rows, n_num=num.shape[1], n_cat=cat.shape[1],
                measures_read=meas.shape[1], snippets=n_snippets,
                cat_width=30, n_measures=meas.shape[1])


def test_scan_work_count_takes_only_shapes():
    params = inspect.signature(roofline.scan_bytes).parameters
    assert set(params) == {"rows", "n_num", "n_cat", "measures_read",
                           "snippets", "cat_width", "n_measures"}
    num = np.zeros((1000, 3), np.float64)
    cat = np.zeros((1000, 3), np.int32)
    meas = np.zeros((1000, 2), np.float64)
    base = roofline.scan_bytes(**_shapes(num, cat, meas, 40))
    assert base == 4 * (1000 * 8 + 40 * (6 + 30 + 2 + 4 + 1))
    # operand dtype: float32 or bfloat16 columns need the same bytes
    for dt in (np.float32, np.float16):
        assert roofline.scan_bytes(**_shapes(
            num.astype(dt), cat, meas.astype(dt), 40)) == base
    # tuple padding to a tile (zero rows, valid = 0) adds nothing
    pad = 1024 - 1000
    valid = np.r_[np.ones(1000), np.zeros(pad)]
    assert roofline.scan_bytes(**_shapes(
        np.pad(num, ((0, pad), (0, 0))), np.pad(cat, ((0, pad), (0, 0))),
        np.pad(meas, ((0, pad), (0, 0))), 40, valid)) == base


def test_unknown_device_kind_has_no_peaks():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v99")


def test_a_new_metric_file_and_entry_are_found_by_name(tmp_path):
    """A later change adds ``metrics/<name>.py`` and a ``per_layer`` entry;
    the harness reads it without an edit, in the cells the entry lists."""
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "answers_seen", "unit": "answers", "better": "higher",
        "source": "program_counter", "layer": "front",
        "moves": "answers_per_s", "workloads": ["synth_pl6.ranges"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmarks" / "chip" / "metrics" / "answers_seen.py"
     ).write_text("def read(ctx):\n    return float(len(ctx['records']))\n")
    cell = harness.load_cell("synth_pl6.ranges", root=tmp_path)
    names = [m["name"] for m in cell.per_layer]
    assert "answers_seen" in names
    read = harness.metric_reader("answers_seen", cell.metrics_dir)
    assert read({"records": [1, 2, 3]}) == 3.0
    other = harness.load_cell("tpch_sf10.adhoc", root=tmp_path,
                              workload=TPCH_ADHOC)
    assert "answers_seen" not in [m["name"] for m in other.per_layer]
    assert "device_idle_share" in [m["name"] for m in other.per_layer]


def test_cells_report_the_metrics_benchmark_json_gives_them():
    ranges = harness.load_cell("synth_pl6.ranges")
    adhoc = harness.load_cell("tpch_sf10.adhoc", workload=TPCH_ADHOC)
    e2e_names = ["latency_p50_ms", "answers_per_s", "setup_s"]
    assert [m["name"] for m in ranges.end_to_end] == e2e_names
    assert {m["name"] for m in ranges.per_layer} == {
        "device_idle_share", "fused_masked_scan_roofline",
        "batches_per_answer", "compiles_in_window", "latency_p95_ms"}
    # The scan's roofline lists its cells; the others are every cell's.
    assert "fused_masked_scan_roofline" not in [m["name"]
                                                for m in adhoc.per_layer]
    for cell in (ranges, adhoc):
        for m in cell.per_layer:
            assert (cell.metrics_dir / f"{m['name']}.py").exists()


def test_runner_refuses_without_a_tpu(capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(["--workload", "synth_pl6.ranges", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU" in out.err
