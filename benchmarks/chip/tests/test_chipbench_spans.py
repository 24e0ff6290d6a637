"""Reduction of the program's spans against the device trace: idle inside
and outside flushes, innermost attribution, compile attribution, programs
by dispatching span, and the sum that must equal the idle share -- on a
hand-made trace with known answers, and on a small trace of the served path
recorded on a TPU v5e (``data/tiny_spans_tpu.xplane.pb.xz``, made by
``tools/record_span_trace.py``)."""
import lzma
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import spans, tracing  # noqa: E402

RECORDED = (Path(__file__).resolve().parent / "data"
            / "tiny_spans_tpu.xplane.pb.xz")
US = 1_000_000  # picoseconds

# One request (req 1) whose flush takes it and req 2; req 2's prescreen
# waits for the engine lock through the flush. The spans of both threads
# share one host line, as the profiler writes them on the CPU. Times in us;
# the traced window runs from the end of start_trace (1) to stop_trace (21).
HOST = [
    ("$profiler.py:101 start_trace", 0, 1, {}),
    ("verdict.request", 1, 20, {"req": 1}),
    ("verdict.prescreen", 1.5, 2, {"req": 1}),
    ("verdict.flush", 2, 12, {"flush": 1, "queries": 2, "reqs": "1 2"}),
    ("verdict.plan", 2, 3, {}),
    ("backend_compile_and_load", 2.2, 2.8, {}),
    ("backend_compile_and_load", 2.3, 2.4, {}),  # nested: counts once
    ("verdict.replay", 3, 10, {"q": 0}),
    ("verdict.gather", 3, 4, {"rows": 10}),
    ("verdict.scan", 4, 5, {}),
    ("verdict.estimates", 5, 6, {}),
    ("verdict.prescreen", 6, 12, {"req": 2}),
    ("verdict.resolve", 11, 12, {}),
    ("verdict.encode", 13, 14, {"req": 1}),
    ("backend_compile_and_load", 20.2, 20.5, {}),
    ("$profiler.py:213 stop_trace", 21, 22, {}),
]
ENQUEUE = [(3.2, 1), (4.2, 2), (14.5, 3)]  # (host time, run_id)
PROGRAMS = [("jit_gather(1)", 3.5, 4.5, 1),
            ("jit__eval_partials_fused(2)", 4.8, 5.5, 2),
            ("jit_other(3)", 15, 16, 3)]
WINDOW_S = 20e-6


def _text_proto(host=HOST, programs=PROGRAMS, enqueue=ENQUEUE, queue=()):
    names, stats = {}, {}

    def meta(table, name):
        return table.setdefault(name, len(table) + 1)

    def event(name, a, b, st):
        fields = []
        for k, v in st.items():
            kind = "str_value" if isinstance(v, str) else "int64_value"
            val = f'"{v}"' if isinstance(v, str) else v
            fields.append(f"stats {{ metadata_id: {meta(stats, k)} "
                          f"{kind}: {val} }}")
        return (f"events {{ metadata_id: {meta(names, name)} "
                f"offset_ps: {round(a * US)} duration_ps: {round((b - a) * US)} "
                f"{' '.join(fields)} }}")

    def metadata():
        out = [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
               for n, i in names.items()]
        out += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                for n, i in stats.items()]
        names.clear()
        stats.clear()
        return "\n".join(out)

    ops = " ".join(event(n.split("(")[0], a, b, {}) for n, a, b, _ in programs)
    mods = " ".join(event(n, a, b, {"run_id": r}) for n, a, b, r in programs)
    device = (f'planes {{ id: 1 name: "/device:TPU:0" '
              f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000 {ops} }} '
              f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000 {mods} }}'
              f'\n{metadata()} }}')
    py = " ".join(event(n, a, b, st) for n, a, b, st in host)
    enq = " ".join(event("DoEnqueueProgram", t, t + 0.1, {"run_id": r})
                   for t, r in enqueue)
    deferred = " ".join(event(n, a, b, st) for n, a, b, st in queue)
    host_plane = (f'planes {{ id: 2 name: "/host:CPU" '
                  f'lines {{ id: 1 name: "python" timestamp_ns: 1000 {py} }} '
                  f'lines {{ id: 2 name: "main/1" timestamp_ns: 1000 {enq} }}'
                  f'lines {{ id: 3 name: "queue/2" timestamp_ns: 1000 '
                  f'{deferred} }}\n{metadata()} }}')
    return device + "\n" + host_plane


def _profile(**kw):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_text_proto(**kw))


@pytest.fixture(scope="module")
def reduced():
    return spans.reduce(_profile(), WINDOW_S)


def test_idle_inside_and_outside_flushes(reduced):
    # busy 3.5-4.5, 4.8-5.5, 15-16 (2.7 us); the flush 2-12 holds 1.7 of it
    assert reduced["interval_s"] == pytest.approx(20e-6)
    assert reduced["idle_s"] == pytest.approx(17.3e-6)
    assert reduced["flush_s"] == pytest.approx(10e-6)
    assert reduced["flushes"] == 1
    assert reduced["idle_in_flush_s"] == pytest.approx(8.3e-6)
    assert reduced["idle_outside_flush_s"] == pytest.approx(9.0e-6)
    assert reduced["device_ahead_s"] == 0  # each program ran after dispatch


def test_idle_goes_to_the_innermost_open_span(reduced):
    want = {"verdict.request": 6.5, "verdict.prescreen": 0.5,
            "verdict.plan": 1.0, "verdict.gather": 0.5, "verdict.scan": 0.3,
            "verdict.estimates": 0.5, "verdict.replay": 4.0,
            "verdict.flush": 1.0,  # 10-11: the waiting prescreen ranks lower
            "verdict.resolve": 1.0, "verdict.encode": 1.0, "none": 1.0}
    got = reduced["idle_by_span"]
    assert set(got) == set(want)
    for name, us in want.items():
        assert got[name] == pytest.approx(us * 1e-6), name
    assert sum(got.values()) == pytest.approx(reduced["idle_s"])


def test_compiles_go_to_the_span_they_began_in(reduced):
    got = reduced["compiles_by_span"]
    assert got == {"verdict.plan": [1, pytest.approx(0.6e-6)],
                   "none": [1, pytest.approx(0.3e-6)]}


def test_programs_go_to_the_span_that_enqueued_them(reduced):
    got = reduced["programs_by_span"]
    assert got == {
        "jit_gather": {"verdict.gather": [pytest.approx(1e-6), 1]},
        "jit__eval_partials_fused": {"verdict.scan": [pytest.approx(0.7e-6),
                                                      1]},
        "jit_other": {"verdict.request": [pytest.approx(1e-6), 1]}}
    # executions starting between a scan's start and its flush's end
    assert reduced["started_in_scan"] == {
        "jit_gather": [0, 1], "jit__eval_partials_fused": [1, 1],
        "jit_other": [0, 1]}


def test_without_enqueue_events_a_program_goes_to_its_device_start():
    got = spans.reduce(_profile(enqueue=[]), WINDOW_S)["programs_by_span"]
    assert got["jit_gather"] == {"verdict.gather": [pytest.approx(1e-6), 1]}
    assert got["jit__eval_partials_fused"] == {
        "verdict.scan": [pytest.approx(0.7e-6), 1]}


def test_a_deferred_enqueue_goes_to_the_span_that_dispatched_it():
    """The scan is dispatched at 4.3 us (in ``verdict.scan``) and enqueued
    by a queue thread at 5.25 us (in ``verdict.estimates``), which consumes
    the dispatch's flow."""
    host = HOST + [("tpu::System::Execute", 4.3, 4.4, {"_p": 77})]
    queue = [("IssueSequencedEvent", 5.2, 5.4, {"_c": 77}),
             ("DoEnqueueProgram", 5.25, 5.3, {"run_id": 2})]
    profile = _profile(host=host, queue=queue,
                       enqueue=[e for e in ENQUEUE if e[1] != 2])
    assert spans.dispatch_times(profile)[2] == 1000 + 4300
    got = spans.reduce(profile, WINDOW_S)["programs_by_span"]
    assert got["jit__eval_partials_fused"] == {
        "verdict.scan": [pytest.approx(0.7e-6), 1]}
    assert spans.reduce(profile, WINDOW_S)["device_ahead_s"] == 0
    # without the flow, the enqueue's own time
    profile = _profile(queue=[("DoEnqueueProgram", 5.25, 5.3, {"run_id": 2})],
                       enqueue=[e for e in ENQUEUE if e[1] != 2])
    assert spans.reduce(profile, WINDOW_S)["programs_by_span"][
        "jit__eval_partials_fused"] == {
        "verdict.estimates": [pytest.approx(0.7e-6), 1]}


def test_the_two_shares_sum_to_the_idle_share(reduced):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "span_window", BENCH / "tools" / "span_window.py")
    span_window = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(span_window)

    got = span_window.readings({}, reduced)
    assert got["idle_outside_flush_share"] == pytest.approx(45.0)
    assert got["flush_idle_share"] == pytest.approx(83.0)
    idle = tracing.reduce(_profile(), WINDOW_S)["idle_share"]
    assert got["idle_share_sum"] == pytest.approx(100.0 * idle)


def test_a_device_clock_ahead_of_the_host_shows():
    """The gather is dispatched at 3.2 us but starts on the device at 3.0."""
    programs = [("jit_gather(1)", 3.0, 4.5, 1)] + PROGRAMS[1:]
    out = spans.reduce(_profile(programs=programs), WINDOW_S)
    assert out["device_ahead_s"] == pytest.approx(0.2e-6)


def test_a_trace_without_spans_reduces_to_nothing():
    """A program without spans (the parent of this change) reads nothing."""
    host = [h for h in HOST if not h[0].startswith("verdict.")]
    assert spans.reduce(_profile(host=host), WINDOW_S) == {}


def test_without_window_marks_the_extent_of_the_events_is_traced():
    host = [h for h in HOST if "_trace" not in h[0]]
    out = spans.reduce(_profile(host=host), WINDOW_S)
    assert out["interval_s"] == pytest.approx(19.5e-6)  # 1 to 20.5 us


def test_recorded_tpu_trace_reduces_by_the_programs_spans():
    """Four concurrent HTTP clients, one fresh range query each, on one
    v5e: two flushes, of one query and of three."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(
        lzma.decompress(RECORDED.read_bytes()))
    out = spans.reduce(profile, window_s=1.0)
    counts = out["span_counts"]
    n = counts["verdict.request"]
    assert n == 4
    assert counts["verdict.prescreen"] == counts["verdict.encode"] == n
    assert counts["verdict.replay"] == n  # no answer came from the cache
    requests = {s.stats["req"] for s in spans.host_spans(profile)
                if s.name == "verdict.request"}
    answered = [int(r) for s in spans.host_spans(profile)
                if s.name == spans.FLUSH for r in str(s.stats["reqs"]).split()]
    assert sorted(answered) == sorted(requests)
    assert out["flushes"] == counts[spans.FLUSH] < n  # a flush took several
    # the flush of three compiled four programs while planning
    assert out["compiles_by_span"] == {"verdict.plan": [4, pytest.approx(
        0.2769, abs=1e-4)]}
    # idle splits exactly, and is what the trace's busy union leaves
    assert out["idle_in_flush_s"] + out["idle_outside_flush_s"] == \
        pytest.approx(out["idle_s"])
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])
    busy = tracing.reduce(profile, out["interval_s"])["busy_s"]
    assert out["idle_s"] == pytest.approx(out["interval_s"] - busy)
    # each program goes to the span that dispatched it
    programs = out["programs_by_span"]
    assert set(programs["jit__eval_partials_fused"]) == {"verdict.scan"}
    assert set(programs["jit_estimates_from_partials"]) == {
        "verdict.estimates"}
    assert "verdict.gather" in programs["jit_gather"]
    # the device clock reads ahead of the host's, by about a millisecond
    assert 0 < out["device_ahead_s"] < 5e-3
