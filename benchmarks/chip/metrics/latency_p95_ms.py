"""The 95th percentile of every request's latency in the window, from the
clients' side: the tail of a closed loop, kept beside the bounded metrics
because its runs spread too widely for a bound (PERF.md)."""
import math


def read(ctx):
    value = ctx["window"]["latency_p95_ms"]
    return None if math.isinf(value) else value
