"""``tools/span_window.py``: a traced run of a cell with the service
counters' gains over the window and the per-flush and per-query readings
made of them, on a cell cut to the CPU's size."""
import importlib.util
from pathlib import Path

import pytest
from chipbench_tiny import SEED, harness, tiny_cell

TOOL = Path(__file__).resolve().parents[1] / "tools" / "span_window.py"


def _tool():
    spec = importlib.util.spec_from_file_location("span_window", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_counters_over_a_traced_window_and_the_harness_left_as_it_was():
    span_window = _tool()
    trace_slice, reduce = harness._trace_slice, harness.tracing.reduce
    out, line = span_window.run(tiny_cell("synth_pl6.ranges"), SEED, 1.5,
                                require_tpu=False)
    assert out["correct"] and out["failed"] == 0
    assert harness._trace_slice is trace_slice
    assert harness.tracing.reduce is reduce
    service, got = line["service"], line["readings"]
    assert "pending" not in service
    assert 0 < service["flushes"] <= service["flushed_queries"]
    assert 0 < service["prescreen_lookups"] <= out["attempted"]
    assert got["queries_per_flush"] * service["flushes"] == pytest.approx(
        service["flushed_queries"])
    assert got["queue_wait_ms"] == pytest.approx(
        1e3 * service["queue_wait_s"] / service["flushed_queries"])
    assert got["prescreen_lock_wait_ms"] == pytest.approx(
        1e3 * service["prescreen_lock_wait_s"]
        / service["prescreen_lookups"])
    # No TPU plane in a CPU trace: no span table and no share read from it.
    assert line["spans"] == {}
    assert "flush_idle_share" not in got


def test_a_program_without_the_counters_reads_nothing():
    readings = _tool().readings
    assert readings({"flushes": 40, "prescreened": 0}, {}) == {}
    assert readings({"flushes": 0, "flushed_queries": 0}, {}) == {}
