"""A cell cut to a size the CPU test suite can run (interpret-mode kernels):
the whole run -- set-up, the HTTP window, the reference -- with only the
sizes made small and the look for a chip skipped."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import harness  # noqa: E402

SEED = 3_000_000_017

# Measured on the chip, not in BENCHMARK.json (see PERF.md).
TPCH_ADHOC = {"name": "tpch_sf10.adhoc", "config": "tpch_sf10",
              "traffic": "adhoc", "chips": 1}


def tiny_cell(name: str):
    cell = harness.load_cell(
        name, workload=TPCH_ADHOC if name == TPCH_ADHOC["name"] else None)
    cell.config["data"]["rows"] = 20_000
    cell.traffic.update(warmup_requests=16, check_sample=8,
                        warmup_microbatches=[16, 8, 1])
    return cell


def run_tiny(name: str, seed: int = SEED, control: bool = False):
    return harness.run_cell(tiny_cell(name), seed, 1.5, False,
                            require_tpu=False, control=control)
