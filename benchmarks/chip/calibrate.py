"""Readings that set the limits of ``correct``: the program's and the
control's, over many seeds, in one process (the compiled programs are
reused, so each seed costs its set-up's data and warm-up, not compiles).

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds <n,n,...> --seconds <s>

Prints one JSON line per seed: the numbers compared (``program``), the
same numbers with the control in the program's place (``control``), and
the window's end-to-end metrics. The benchmark's own runs never run the
control.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False, control=True)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "program": {k: v["value"] for k, v in out["checks"].items()},
            "control": out["control"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "attempted": out["attempted"], "failed": out["failed"],
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
