"""``chip_smoke.py``'s phases at a tiny size on the CPU.

Interpret-mode kernels, the HTTP front, the NoLearn comparison, the
independent NumPy reference and every check run exactly as on the chip;
only the relation is small. The script itself refuses to run without a
TPU, which is pinned here too.
"""
import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
TINY_ROWS = 20_000


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phase_passes_its_checks_at_tiny_size():
    summary = _smoke().run_one_chip(TINY_ROWS, seed=0)
    assert summary["cells"] > 24
    assert summary["checks"] > 3 * summary["cells"]
    assert summary["coverage_verdict"] >= 0.8
    assert summary["coverage_nolearn"] >= 0.8


def test_four_chip_phase_passes_its_checks_at_tiny_size(forced_devices):
    forced_devices(4)
    summary = _smoke().run_four_chips(TINY_ROWS, seed=0, n_devices=4)
    assert summary["cells"] > 24


def test_reference_flags_a_wrong_scan_answer():
    """The NoLearn check is not vacuous: an estimate moved by more than
    the stated tolerance fails it."""
    smoke = _smoke()
    ref = {((), 0): (100.0, 4.0, 0.0)}
    smoke.check_scan_reference(smoke.Checks(), [{((), 0): (100.0, 4.0)}],
                               [ref], "exact")
    moved = {((), 0): (100.0 * (1 + 2 * smoke.F32_REL), 4.0)}
    with pytest.raises(smoke.SmokeFailure, match="estimate"):
        smoke.check_scan_reference(smoke.Checks(), [moved], [ref], "moved")


def test_script_refuses_without_a_tpu(capsys):
    rc = _smoke().main([])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err
