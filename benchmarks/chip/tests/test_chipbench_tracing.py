"""Trace reduction: busy union, idle share, per-program device time, and
the ``breakdown`` lists, on a hand-made trace with known answers and on a
small trace recorded on a TPU v5e (``data/tiny_tpu.xplane.pb``, made by
``tools/record_tiny_trace.py``)."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import tracing  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "tiny_tpu.xplane.pb"

# Two overlapping ops (0-2 us and 1-3 us), then an idle gap while the host
# runs "host_wait" (3-10 us), then one op (10-11 us); a 20 us window.
HAND_MADE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call" } }
  event_metadata { key: 3 value { id: 3 name: "jit_f(42)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 7000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "host_wait" } }
  event_metadata { key: 2 value { id: 2 name: "short" } } }
'''


def _hand_made():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(HAND_MADE)


def test_busy_is_the_union_of_op_intervals():
    out = tracing.reduce(_hand_made(), window_s=20e-6)
    assert out["busy_s"] == pytest.approx(4e-6)  # 0-3 us and 10-11 us
    assert out["idle_share"] == pytest.approx(0.8)


def test_program_time_and_executions_by_name():
    out = tracing.reduce(_hand_made(), window_s=20e-6)
    assert out["programs"] == {"jit_f": [pytest.approx(4e-6), 2]}


def test_breakdown_lists_top_ops_and_gaps_named_by_host_work():
    bd = tracing.reduce(_hand_made(), window_s=20e-6)["breakdown"]
    assert [name for name, _ in bd["device_ops"]] == ["fusion.1",
                                                      "custom-call"]
    assert bd["device_ops"][0][1] == pytest.approx(3e-6)
    assert bd["idle_gaps"] == [["host_wait", pytest.approx(7e-6)]]


def test_no_device_plane_reduces_to_nothing():
    from jax.profiler import ProfileData

    host_only = HAND_MADE[HAND_MADE.index("planes { id: 2"):]
    assert tracing.reduce(ProfileData.from_text_proto(host_only), 1.0) == {}


def test_recorded_tpu_trace_reduces_to_its_programs_and_ops():
    """Three runs each of a jitted elementwise program and a Pallas kernel
    on one v5e chip, traced."""
    out = tracing.reduce(tracing.load(RECORDED), window_s=1.0)
    assert set(out["programs"]) == {"jit__lambda"}
    secs, runs = out["programs"]["jit__lambda"]
    assert runs == 6 and secs > 0
    ops = dict(out["breakdown"]["device_ops"])
    assert set(ops) == {"%multiply_add_fusion", "%_lambda_.1"}
    # busy is the union of the op intervals: no more than their sum, and
    # the programs (which contain them) cover at least as much
    assert 0 < out["busy_s"] <= sum(ops.values()) + 1e-12
    assert out["busy_s"] <= secs + 1e-12
    assert out["idle_share"] == pytest.approx(1 - out["busy_s"])
    assert len(out["breakdown"]["idle_gaps"]) == 5  # 6 ops, 5 gaps
    assert all(gap > 0 for _, gap in out["breakdown"]["idle_gaps"])
