"""A run with the timed path broken underneath comes out not correct: the
faults a cell of this benchmark can have, planted in the scan that produces
every raw answer (the engine binds the scan it runs when it is built, so
the fault is in place before the run starts)."""
import pytest
from chipbench_tiny import run_tiny


def _planted(monkeypatch, fault):
    from repro.kernels.fused_masked_scan import ops

    orig = ops.eval_partials_fused

    def half_batch(num, cat, meas, snippets, valid=None, **kw):
        """Half of the batch left out; the mean is taken over the rest."""
        t = num.shape[0] // 2
        return orig(num[:t], cat[:t], meas[:t], snippets,
                    None if valid is None else valid[:t], **kw)

    def altered(num, cat, meas, snippets, valid=None, **kw):
        """Every partial sum altered by a part in a thousand."""
        part = orig(num, cat, meas, snippets, valid, **kw)
        return type(part)(part.sums * 1.001, part.sumsq, part.count,
                          part.scanned)

    monkeypatch.setattr(ops, "eval_partials_fused",
                        {"half_batch": half_batch, "altered": altered}[fault])


@pytest.mark.parametrize("cell", ["synth_pl6.ranges", "tpch_sf10.adhoc"])
@pytest.mark.parametrize("fault", ["half_batch", "altered"])
def test_broken_scan_is_not_correct(monkeypatch, fault, cell):
    _planted(monkeypatch, fault)
    out = run_tiny(cell)
    assert not out["correct"], out["checks"]
    assert out["checks"]["scan_estimate_gap"]["value"] > \
        out["checks"]["scan_estimate_gap"]["limit"]
