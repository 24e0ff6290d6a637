"""``correct`` at a tiny size: sound runs pass their limits, and the
lower-precision control (bfloat16 inputs to the scan) fails them."""
import pytest
from chipbench_tiny import run_tiny


@pytest.fixture(scope="module", params=["tpch_sf10.adhoc",
                                        "synth_pl6.ranges"])
def sound(request):
    return run_tiny(request.param, control=True), request.param


def test_sound_run_is_correct_with_every_check_printed(sound):
    sound, cell = sound
    assert sound["correct"], sound["checks"]
    assert list(sound)[-1] == "checks"
    assert sound["attempted"] > 0 and sound["failed"] == 0
    held = {"groups_differ", "no_reply", "raw_missing", "scan_estimate_gap",
            "scan_bound_gap"}
    if cell == "tpch_sf10.adhoc":
        held.add("served_miss_share")
    assert set(sound["checks"]) == held
    assert sound["control"]["answers_checked"] > 0


def test_control_fails_a_limit(sound):
    sound, _ = sound
    checks = sound["checks"]
    failed = [k for k, v in sound["control"].items()
              if k in checks and v > checks[k]["limit"]]
    assert failed, (sound["control"], checks)
