"""Launch-layer unit tests that don't require compiles: HLO collective parser,
roofline math, cell list policy, mesh builders (shape only)."""
import pytest

from repro.launch import hlo_analysis as H
from repro.launch import roofline as R
from repro.launch.cells import LONG_OK, SHAPES, cell_list

HLO = """
HloModule jit_step

%body.1 (arg: (f32[8,16], s32[])) -> (f32[8,16], s32[]) {
  %p = f32[8,16] parameter(0)
  %ar = f32[8,16]{1,0} all-reduce(%p), replica_groups={}
  ROOT %t = (f32[8,16], s32[]) tuple(%ar, %c)
}

%cond.1 (arg: (f32[8,16], s32[])) -> pred[] {
  %iv = s32[] get-tuple-element(%arg), index=1
  %n = s32[] constant(12)
  ROOT %lt = pred[] compare(%iv, %n), direction=LT
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16] parameter(0)
  %ag = f32[16,16]{1,0} all-gather(%a), dimensions={0}
  %w = (f32[8,16], s32[]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[8,16] get-tuple-element(%w), index=0
}
"""


def test_collective_parser_loop_multiplication():
    out = H.collective_bytes(HLO)
    # all-gather in main: 16*16*4 = 1024 B; all-reduce in the 12-trip body:
    # 8*16*4 = 512 B * 12 trips * wire factor 2.
    assert out["bytes_by_kind"]["all-gather"] == pytest.approx(1024)
    assert out["bytes_by_kind"]["all-reduce"] == pytest.approx(512 * 12)
    assert out["wire_bytes_by_kind"]["all-reduce"] == pytest.approx(512 * 12 * 2)
    assert out["op_counts"]["all-reduce"] == 12


def test_shape_bytes_tuple_and_dtypes():
    assert H._shape_bytes("(bf16[2,3], f32[4])") == 2 * 3 * 2 + 4 * 4
    assert H._shape_bytes("s8[10]") == 10
    assert H._shape_bytes("pred[]") == 1


def test_roofline_terms_and_dominance():
    r = R.roofline("TPU v5 lite", 197e12, 819e9 * 2, 50e9 * 0.5)
    assert r["compute_s"] == pytest.approx(1.0)
    assert r["memory_s"] == pytest.approx(2.0)
    assert r["collective_s"] == pytest.approx(0.5)
    assert r["dominant"] == "memory_s"
    assert r["roofline_fraction"] == pytest.approx(0.5)


def test_roofline_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        R.roofline("cpu", 1.0, 1.0, 1.0)


def test_combine_costs():
    tot = R.combine_costs({"flops": 10.0, "bytes accessed": 100.0},
                          [(3, {"flops": 2.0, "bytes accessed": 5.0})])
    assert tot["flops_per_device"] == 16.0
    assert tot["bytes_per_device"] == 115.0


def test_cell_list_policy():
    cells = cell_list()
    assert len(cells) == 33  # 10 archs x 3 shapes + 3 long_500k
    longs = {a for a, s in cells if s == "long_500k"}
    assert longs == LONG_OK
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k", "long_500k"}


def test_model_flops():
    from repro import configs

    cfg = configs.get("qwen2.5-3b")
    mf = R.model_flops(cfg, "train", 4096, 256)
    assert mf == pytest.approx(6 * cfg.n_params * 4096 * 256)
    moe = configs.get("arctic-480b")
    assert moe.n_active_params < 0.1 * moe.n_params  # top-2 of 128 + dense


def test_sharding_rules_resolve():
    from repro.distributed.sharding import DEFAULT_RULES, PURE_DP_RULES

    assert DEFAULT_RULES["ffn"] == "model"
    assert PURE_DP_RULES["_batch_axes"] == ("data", "model")
