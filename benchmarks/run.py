"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. 'us_per_call' is populated for
timing benchmarks; claim-check rows put their metric in 'derived'.

    PYTHONPATH=src python -m benchmarks.run [--only table4,fig5]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (batch_bench, cache_bench, improve_bench,
                            kernels_bench, paper_tables, roofline_report,
                            serving_bench, shard_bench)

    suites = {
        "batch": batch_bench.run,
        "cache": cache_bench.run,
        "serving": serving_bench.run,
        "improve": improve_bench.run,
        "shard": shard_bench.run,
        "table3": paper_tables.table3_generality,
        "table4": paper_tables.table4_speedup_error,
        "table5": paper_tables.table5_overhead,
        "fig5": paper_tables.fig5_bound_coverage,
        "fig6": paper_tables.fig6_sweeps,
        "fig7": paper_tables.fig7_param_learning,
        "fig9": paper_tables.fig9_model_validation,
        "fig12": paper_tables.fig12_data_append,
        "fig13": paper_tables.fig13_intertuple_covariance,
        "kernels": kernels_bench.run,
        "roofline": roofline_report.run,
    }
    only = [s for s in args.only.split(",") if s]
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception:
            traceback.print_exc()
            print(f"{name}/ERROR,,")
            failed += 1
            continue
        for key, val in rows:
            if key.startswith("kernel/") or key.endswith("_us"):
                print(f"{key},{val:.1f},")
            else:
                print(f"{key},,{val:.6g}")
        print(f"# {name} took {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
