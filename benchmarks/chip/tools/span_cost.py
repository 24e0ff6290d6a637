"""Host cost of one program span while no profiler session is active.

    python3 benchmarks/chip/tools/span_cost.py

Times a loop of 10**5 ``jax.profiler.TraceAnnotation`` spans with two
keyword arguments, as the served path opens them, against the same loop
without the span, and prints the difference per span in nanoseconds.
"""
import time

from jax.profiler import TraceAnnotation

N = 100_000


def _loop(span: bool) -> float:
    t0 = time.perf_counter()
    if span:
        for i in range(N):
            with TraceAnnotation("verdict.flush", flush=i, queries=2):
                pass
    else:
        for i in range(N):
            pass
    return time.perf_counter() - t0


def main() -> None:
    _loop(True)  # warm
    best = min(_loop(True) - _loop(False) for _ in range(5))
    print(f"span_ns {1e9 * best / N:.1f}")


if __name__ == "__main__":
    main()
