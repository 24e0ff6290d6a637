"""One traced run of a cell, as ``run.py --trace 1`` makes it, with what the
harness does not read yet: the program's span tables (``chipbench.spans``)
over the traced slice, and what the tenant service's counters
(``Tenant.stats()["service"]``) gained in the window.

    python3 benchmarks/chip/tools/span_window.py --workload <cell> \\
        --seed <n> --seconds <s>

Prints the harness's result line, then one JSON line with ``service`` (the
counters' gains from the window's start to its end), ``spans``
(``spans.reduce`` of the traced slice) and ``readings``:

- ``queries_per_flush``: flushed queries / flushes;
- ``queue_wait_ms``: queue wait / flushed queries;
- ``prescreen_lock_wait_ms``: engine-lock wait / prescreen lookups;
- ``idle_outside_flush_share``: device idle with no flush span open, % of
  the traced seconds;
- ``flush_idle_share``: device idle inside flush spans, % of their union;
- ``idle_share_sum``: ``idle_outside_flush_share`` + ``flush_idle_share`` x
  flush union / traced seconds, which should equal the result line's
  ``device_idle_share``.

A reading whose counters or spans the program lacks is left out. The
harness's run is not changed: the tool only wraps the functions it calls
at the window's start (``_trace_slice``) and after its end
(``tracing.reduce``).
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from chipbench import harness, spans, tracing  # noqa: E402


def _ratio(num, den, scale=1.0):
    return scale * num / den if num is not None and den else None


def readings(service: dict, tables: dict) -> dict:
    """The five per-layer readings and the idle-share sum, from the
    counters' gains and the span tables."""
    out = {
        "queries_per_flush": _ratio(service.get("flushed_queries"),
                                    service.get("flushes")),
        "queue_wait_ms": _ratio(service.get("queue_wait_s"),
                                service.get("flushed_queries"), 1e3),
        "prescreen_lock_wait_ms": _ratio(service.get("prescreen_lock_wait_s"),
                                         service.get("prescreen_lookups"),
                                         1e3),
    }
    if tables:
        outside = 100.0 * tables["idle_outside_flush_s"] / tables["window_s"]
        inside = _ratio(tables["idle_in_flush_s"], tables["flush_s"], 100.0)
        out["idle_outside_flush_share"] = outside
        out["flush_idle_share"] = inside
        out["idle_share_sum"] = outside + (inside or 0.0) * (
            tables["flush_s"] / tables["window_s"])
    return {k: v for k, v in out.items() if v is not None}


def run(cell, seed: int, seconds: float, **kw):
    """``harness.run_cell`` with tracing on; returns its result and the
    tool's line."""
    from repro.serving.front import ServingFront

    seen = {}
    add_tenant = ServingFront.add_tenant
    trace_slice = harness._trace_slice
    reduce = tracing.reduce

    def counters():
        return dict(seen["tenant"].stats()["service"])

    def _add_tenant(self, *a, **k):
        seen["tenant"] = add_tenant(self, *a, **k)
        return seen["tenant"]

    def _trace_slice(*a, **k):
        seen["before"] = counters()
        return trace_slice(*a, **k)

    def _reduce(profile, window_s, *a, **k):
        seen["after"] = counters()
        seen["spans"] = spans.reduce(profile, window_s)
        return reduce(profile, window_s, *a, **k)

    ServingFront.add_tenant = _add_tenant
    harness._trace_slice = _trace_slice
    tracing.reduce = _reduce
    try:
        out = harness.run_cell(cell, seed, seconds, True, **kw)
    finally:
        ServingFront.add_tenant = add_tenant
        harness._trace_slice = trace_slice
        tracing.reduce = reduce
    before, after = seen.get("before", {}), seen.get("after", {})
    service = {k: after[k] - before[k] for k in after
               if k in before and k != "pending"}  # pending is a level
    tables = seen.get("spans") or {}
    return out, {"service": service, "spans": tables,
                 "readings": readings(service, tables)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out, line = run(harness.load_cell(args.workload), args.seed,
                        args.seconds)
    except harness.NothingToRun as e:
        print(f"span_window: {e}; nothing run", file=sys.stderr)
        return 2
    print(json.dumps(out))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
