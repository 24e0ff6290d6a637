"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

A device plane (``/device:TPU:<n>``) holds one line of XLA programs
(``XLA Modules``) and one of the operations inside them (``XLA Ops``);
each event has a start and a duration in nanoseconds. From them:

- busy time: the union of the device's operation intervals (so nested or
  overlapping events count once), averaged over the chips traced;
- idle share: 1 - busy / the traced window;
- per-program device time and executions, under each program's name
  without the trailing ``(<id>)``;
- ``breakdown``: the operations that took the most device time, and the
  longest idle gaps, each named after the host event that overlapped it
  most (what the host was doing while the device waited).
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Interval = Tuple[int, int, str]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def device_planes(profile) -> list:
    return [p for p in profile.planes if p.name.startswith(DEVICE_PREFIX)]


def line_events(plane, line_name: str) -> List[Interval]:
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            start = int(ev.start_ns)
            out.append((start, start + int(ev.duration_ns), ev.name))
    return out


def union(intervals: Iterable[Interval]) -> List[Tuple[int, int]]:
    """Disjoint, sorted cover of ``intervals``."""
    merged: List[List[int]] = []
    for start, end, _ in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def program_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """An op event's HLO instruction name, without its text after ``=``."""
    return event_name.split(" = ", 1)[0]


def program_seconds(planes) -> Dict[str, List[float]]:
    """``{program: [device seconds, executions]}`` over all chips."""
    out: Dict[str, List[float]] = {}
    for plane in planes:
        for start, end, name in line_events(plane, MODULES_LINE):
            acc = out.setdefault(program_name(name), [0.0, 0])
            acc[0] += (end - start) * 1e-9
            acc[1] += 1
    return out


def _host_events(profile) -> List[Interval]:
    out = []
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    start = int(ev.start_ns)
                    out.append((start, start + int(ev.duration_ns), ev.name))
    return out


def _gap_name(gap: Tuple[int, int], host: List[Interval]) -> str:
    best, name = 0, "no host event"
    for start, end, ev in host:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > best:
            best, name = overlap, ev
    return name


def reduce(profile, window_s: float, top: int = 10) -> dict:
    """Device busy seconds (mean over chips), idle share of ``window_s``,
    per-program seconds and the ``breakdown`` lists."""
    planes = device_planes(profile)
    if not planes:
        return {}
    busy, gaps, ops = [], [], {}
    for plane in planes:
        events = line_events(plane, OPS_LINE)
        cover = union(events)
        busy.append(sum(b - a for a, b in cover) * 1e-9)
        gaps += [(cover[i][1], cover[i + 1][0])
                 for i in range(len(cover) - 1)]
        for start, end, name in events:
            key = op_name(name)
            ops[key] = ops.get(key, 0.0) + (end - start) * 1e-9
    busy_s = sum(busy) / len(planes)
    host = _host_events(profile)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "programs": program_seconds(planes),
        "breakdown": {
            "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
            "idle_gaps": [[_gap_name(g, host), (g[1] - g[0]) * 1e-9]
                          for g in gaps[:top]],
        },
    }
