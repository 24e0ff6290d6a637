"""End-to-end metrics from the clients' records of one measured window.

Every request sent in the window counts. A request is answered when its
reply is HTTP 200 with an answer that is not degraded; anything else -- no
reply, an HTTP error, a ``failed`` answer, a rejection, a degraded answer --
is a failure, counted against the attempts and ranked above every answered
latency in the percentiles (it met no latency limit).
"""
from __future__ import annotations

import math
from typing import Dict, List


def answered(rec: dict) -> bool:
    ans = rec.get("answer") or {}
    return (rec.get("status") == 200 and ans.get("kind") == "answer"
            and not ans.get("degraded"))


def percentile(values: List[float], p: float) -> float:
    """Nearest-rank percentile (``p`` in (0, 100]) of ``values``."""
    if not values:
        return math.inf
    ranked = sorted(values)
    k = max(int(math.ceil(p / 100.0 * len(ranked))), 1)
    return ranked[k - 1]


def window_metrics(records: List[dict], start: float,
                   seconds: float) -> Dict[str, float]:
    """Latency percentiles over all attempts and the rate of answers that
    completed inside ``[start, start + seconds]``."""
    lat = [(r["done"] - r["sent"]) * 1e3 if answered(r) else math.inf
           for r in records]
    end = start + seconds
    done_in = sum(1 for r in records
                  if answered(r) and start <= r["done"] <= end)
    return {
        "latency_p50_ms": percentile(lat, 50),
        "latency_p95_ms": percentile(lat, 95),
        "answers_per_s": done_in / seconds,
        "attempted": len(records),
        "failed": sum(1 for r in records if not answered(r)),
        "no_reply": sum(1 for r in records if r.get("status") is None),
    }
