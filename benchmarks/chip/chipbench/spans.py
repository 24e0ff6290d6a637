"""Reduction of the program's own host spans (``verdict.*``) against the
device trace of the same ``.xplane.pb``.

The served path opens ``jax.profiler.TraceAnnotation`` spans at each layer
boundary (``verdict.request`` in the HTTP handler down to
``verdict.gather``/``verdict.scan`` in the physical plan). They land on the
profiler's host plane, on the device events' clock. The profiler may put
the spans of several threads on one host line, so nothing here assumes a
line per thread: a span's rank comes from the program's own nesting
(``DEPTH``), and of the spans open at one time, the one of highest rank,
then the latest begun, is the innermost.

From one profile:

- device-idle seconds with and without a ``verdict.flush`` span open, and
  the union of flush-span time;
- device-idle seconds by the innermost open span (``none`` where no span is
  open);
- the compile events (``COMPILE_EVENTS``) by the innermost span open where
  each began;
- each program's device seconds and executions by the innermost span open
  when the host dispatched it (``dispatch_times``; at its device start
  where the trace cannot tell);
- for each program, how many executions started after a ``verdict.scan``
  span began and before the flush that encloses the scan ended;
- how far the device's clock reads ahead of the host's at least: the most
  by which an execution starts on the device before its dispatch.

Idle time is the complement of the union of the device's operations (as in
``tracing.reduce``) within the traced window, averaged over the chips
traced. The window is the one the harness times (``window_s``): from the
return of the tracing thread's ``start_trace`` to its call of
``stop_trace``, as the Python tracer records them; where the trace lacks
them, the extent of its events.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from chipbench import tracing

PREFIX = "verdict."
FLUSH = "verdict.flush"
SCAN = "verdict.scan"
NONE = "none"

# A span outranks the spans it runs inside: prescreen, encode and flush run
# inside a request; plan, replay and resolve inside a flush; discover inside
# plan; gather, scan, estimates and improve inside replay. A flush also
# outranks a prescreen, which waits for the engine lock the flush holds. The
# ingest thread's spans rank with a flush; a span not listed ranks lowest.
DEPTH = {
    "verdict.request": 0,
    "verdict.prescreen": 1, "verdict.encode": 1,
    "verdict.flush": 2, "verdict.ingest": 2,
    "verdict.plan": 3, "verdict.replay": 3, "verdict.resolve": 3,
    "verdict.discover": 4, "verdict.gather": 4, "verdict.scan": 4,
    "verdict.estimates": 4, "verdict.improve": 4,
}

# JAX's own host annotation around one XLA backend compile (the event the
# ``CompileClock`` listener times); a compile served from the persistent
# cache has none.
COMPILE_EVENTS = ("backend_compile_and_load",)
# The TPU runtime's event that hands one execution (``run_id``, as on the
# device's ``XLA Modules`` event) to the device queue.
ENQUEUE_EVENT = "DoEnqueueProgram"


class Span(NamedTuple):
    start: int
    end: int
    name: str
    stats: dict


def _host_events(profile):
    for plane in profile.planes:
        if plane.name != tracing.HOST_PLANE:
            continue
        for line in plane.lines:
            yield from line.events


def host_spans(profile) -> List[Span]:
    """Every ``verdict.*`` event on the host plane, by start."""
    out = []
    for ev in _host_events(profile):
        if ev.name.startswith(PREFIX):
            start = int(ev.start_ns)
            out.append(Span(start, start + int(ev.duration_ns), ev.name,
                            dict(ev.stats)))
    return sorted(out)


def _rank(span: Span):
    return DEPTH.get(span.name, -1), span.start


def _attribute(spans: Sequence[Span], pieces: Sequence[Tuple[int, int]]
              ) -> List[List[Tuple[str, int]]]:
    """For each ``(start, end)`` piece (sorted by start, pairwise disjoint
    unless of length 0), its ``(innermost span name, ns)`` sub-pieces. A
    piece of length 0 gets one sub-piece: the innermost span open at its
    start."""
    bounds = sorted([(s.end, 0, i) for i, s in enumerate(spans)]
                    + [(s.start, 1, i) for i, s in enumerate(spans)])
    open_: Dict[int, Span] = {}
    k = 0

    def advance(t, inclusive):
        nonlocal k
        while k < len(bounds) and (bounds[k][0] < t or (
                inclusive and bounds[k][0] == t)):
            _, is_start, i = bounds[k]
            if is_start:
                open_[i] = spans[i]
            else:
                open_.pop(i, None)
            k += 1

    def innermost():
        return max(open_.values(), key=_rank).name if open_ else NONE

    out = []
    for a, b in pieces:
        advance(a, inclusive=True)
        if b <= a:
            out.append([(innermost(), 0)])
            continue
        parts, cur = [], a
        while k < len(bounds) and bounds[k][0] < b:
            t = bounds[k][0]
            if t > cur:
                parts.append((innermost(), t - cur))
                cur = t
            advance(t, inclusive=True)
        parts.append((innermost(), b - cur))
        out.append(parts)
    return out


def _complement(cover: List[Tuple[int, int]], lo: int, hi: int):
    gaps, cur = [], lo
    for a, b in cover:
        if a > cur:
            gaps.append((cur, min(a, hi)))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    return [(a, b) for a, b in gaps if b > a]


def _overlap(xs: List[Tuple[int, int]], ys: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two disjoint sorted covers."""
    total = i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _enclosing(spans: Sequence[Span], inner: Span, name: str
               ) -> Optional[Span]:
    best = None
    for s in spans:
        if s.name == name and s.start <= inner.start and inner.end <= s.end:
            if best is None or s.start > best.start:
                best = s
    return best


def dispatch_times(profile) -> Dict[int, int]:
    """``{run_id: host ns}``: when the calling thread dispatched each
    execution. The runtime may enqueue an execution later, on a queue
    thread, once its inputs are ready; the event around that enqueue
    consumes a flow (``_c``) that the dispatching thread's event produced
    (``_p``), and the producer's start is the dispatch. Without such a flow,
    the enqueue's own start."""
    producers: Dict[int, int] = {}
    enqueues = []  # (run_id, start, consumer ids around it, innermost first)
    for plane in profile.planes:
        if plane.name != tracing.HOST_PLANE:
            continue
        for line in plane.lines:
            stack: List[Tuple[int, Optional[int]]] = []
            for ev in sorted(line.events,
                             key=lambda e: (e.start_ns, -e.duration_ns)):
                start = int(ev.start_ns)
                stats = dict(ev.stats)
                if "_p" in stats:
                    producers.setdefault(stats["_p"], start)
                while stack and stack[-1][0] <= start:
                    stack.pop()
                if ev.name == ENQUEUE_EVENT and "run_id" in stats:
                    enqueues.append((stats["run_id"], start, [
                        c for _, c in reversed(stack) if c is not None]))
                stack.append((start + int(ev.duration_ns), stats.get("_c")))
    return {run: next((producers[c] for c in consumed if c in producers),
                      start)
            for run, start, consumed in enqueues}


def _window(host, planes) -> Tuple[int, int]:
    starts, ends, lo, hi = [], [], None, None
    for e in host:
        start = int(e.start_ns)
        starts.append(start)
        ends.append(start + int(e.duration_ns))
        if e.name.endswith(" start_trace"):
            lo = ends[-1]
        elif e.name.endswith(" stop_trace"):
            hi = start
    for p in planes:
        for a, b, _ in tracing.line_events(p, tracing.OPS_LINE):
            starts.append(a)
            ends.append(b)
    if lo is None or hi is None or hi <= lo:
        return min(starts), max(ends)
    return lo, hi


def _clip(cover, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in cover if b > lo and a < hi]


def _top_level(events: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The events no other event of the list contains (nested compile
    annotations count once)."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted(events, key=lambda e: (e[0], -e[1])):
        if out and out[-1][0] <= a and b <= out[-1][1]:
            continue
        out.append((a, b))
    return out


def reduce(profile, window_s: float) -> dict:
    """The span tables of one profile; ``{}`` when it has no device plane or
    no ``verdict.*`` span (a program without spans)."""
    planes = tracing.device_planes(profile)
    spans = host_spans(profile)
    if not planes or not spans:
        return {}
    host = list(_host_events(profile))
    lo, hi = _window(host, planes)
    flush = _clip(tracing.union([(s.start, s.end, s.name) for s in spans
                                 if s.name == FLUSH]), lo, hi)
    n = len(planes)
    idle_ns = in_flush_ns = 0
    idle_by: Dict[str, float] = {}
    for plane in planes:
        idle = _complement(tracing.union(
            tracing.line_events(plane, tracing.OPS_LINE)), lo, hi)
        idle_ns += sum(b - a for a, b in idle)
        in_flush_ns += _overlap(idle, flush)
        for parts in _attribute(spans, idle):
            for name, ns in parts:
                idle_by[name] = idle_by.get(name, 0.0) + ns * 1e-9 / n

    runs = _executions(profile, planes)
    return {
        "window_s": window_s,
        "interval_s": (hi - lo) * 1e-9,
        "idle_s": idle_ns * 1e-9 / n,
        "flush_s": sum(b - a for a, b in flush) * 1e-9,
        "flushes": sum(1 for s in spans if s.name == FLUSH),
        "span_counts": {name: sum(1 for s in spans if s.name == name)
                        for name in sorted({s.name for s in spans})},
        "idle_in_flush_s": in_flush_ns * 1e-9 / n,
        "idle_outside_flush_s": (idle_ns - in_flush_ns) * 1e-9 / n,
        "idle_by_span": dict(sorted(idle_by.items(), key=lambda kv: -kv[1])),
        "compiles_by_span": _compiles_by_span(host, spans),
        "programs_by_span": _programs_by_span(runs, spans),
        "started_in_scan": _started_in_scan(runs, spans),
        "device_ahead_s": max([0] + [at - start for at, start, _, _ in runs])
        * 1e-9,
    }


def _compiles_by_span(host, spans) -> Dict[str, List[float]]:
    """``{span: [compiles, seconds]}`` by the span open where each began."""
    out: Dict[str, List[float]] = {}
    events = _top_level([(int(e.start_ns), int(e.start_ns + e.duration_ns))
                         for e in host if e.name in COMPILE_EVENTS])
    points = [(a, a) for a, _ in events]
    for (a, b), parts in zip(events, _attribute(spans, points)):
        acc = out.setdefault(parts[0][0], [0, 0.0])
        acc[0] += 1
        acc[1] += (b - a) * 1e-9
    return out


def _executions(profile, planes) -> List[Tuple[int, int, int, str]]:
    """Every program execution as ``(dispatched, device start, duration,
    program)``, by dispatch."""
    dispatched = dispatch_times(profile)
    runs = []
    for plane in planes:
        for line in plane.lines:
            if line.name != tracing.MODULES_LINE:
                continue
            for ev in line.events:
                start = int(ev.start_ns)
                at = dispatched.get(dict(ev.stats).get("run_id"), start)
                runs.append((at, start, int(ev.duration_ns),
                             tracing.program_name(ev.name)))
    return sorted(runs)


def _programs_by_span(runs, spans) -> Dict[str, Dict[str, List[float]]]:
    """``{program: {span: [device seconds, executions]}}`` by the span open
    where each execution was dispatched."""
    out: Dict[str, Dict[str, List[float]]] = {}
    points = [(r[0], r[0]) for r in runs]
    for (_, _, dur, prog), parts in zip(runs, _attribute(spans, points)):
        acc = out.setdefault(prog, {}).setdefault(parts[0][0], [0.0, 0])
        acc[0] += dur * 1e-9
        acc[1] += 1
    return out


def _started_in_scan(runs, spans) -> Dict[str, List[int]]:
    """``{program: [executions, of]}`` that started on the device after a
    ``verdict.scan`` span began and before the flush enclosing it ended."""
    windows = []
    for s in spans:
        if s.name == SCAN:
            outer = _enclosing(spans, s, FLUSH)
            if outer is not None:
                windows.append((s.start, outer.end))
    out: Dict[str, List[int]] = {}
    for _, start, _, prog in runs:
        acc = out.setdefault(prog, [0, 0])
        acc[0] += any(a <= start < b for a, b in windows)
        acc[1] += 1
    return out
