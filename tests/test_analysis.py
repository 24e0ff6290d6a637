"""The analyzer's own contract: every rule flags its deliberately-violating
fixture AND stays silent on the real codebase.

The trace-layer fixtures are mini-programs reproducing real historical bugs:
``_reverted_masked_tile_fold`` is pinned to the PR-6 pre-fix fold shape
(tiles only the tuple axis, full-width snippet dots) so T001 reproduces the
1-ulp Q-pad-invariance break as a *diagnostic* instead of a parity flake,
and the ``badrepo/local_eps.py`` fixture is literally the pre-PR-6
kernel-local ``1e-7`` epsilon drift.
"""
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import ast_rules
from repro.analysis import trace_rules as tr
from repro.analysis.cli import main, run_repo_analysis
from repro.analysis.findings import (ERROR, INFO, WARN, Finding, gate_count,
                                     render_json, render_text, sort_findings)
from repro.analysis.programs import (REP_M, REP_Q, REP_T, Program,
                                     engine_programs)

TESTS = pathlib.Path(__file__).resolve().parent
BADREPO = TESTS / "badrepo"

MASK = jax.ShapeDtypeStruct((REP_T, REP_Q), jnp.float64)
PAYLOAD = jax.ShapeDtypeStruct((REP_T, 2 * REP_M + 1), jnp.float64)
FOLD_DN = (((0,), (0,)), ((), ()))


def _rules(findings):
    return {f.rule for f in findings}


# ----------------------------------------------------------- findings layer


def test_finding_severity_validated():
    with pytest.raises(ValueError):
        Finding("T999", "fatal", "x", "y")


def test_gate_count_strict_vs_lax():
    fs = [Finding("R", ERROR, "a", "m"), Finding("R", WARN, "b", "m"),
          Finding("R", INFO, "c", "m")]
    assert gate_count(fs, strict=True) == 2
    assert gate_count(fs, strict=False) == 1
    assert [f.severity for f in sort_findings(fs)] == [ERROR, WARN, INFO]
    assert "T999" not in render_json(fs)
    assert "1 error, 1 warn, 1 info" in render_text(fs)


# ------------------------------------------------- T001: the PR-6 fold bug


def _reverted_masked_tile_fold(mask, payload):
    """masked_tile_fold as it stood BEFORE PR 6: pads/tiles only the tuple
    axis and contracts the full snippet width in one variable-shape dot per
    tuple tile. XLA picks its contraction order from the operand shapes, so
    the reduction order — and hence the last ulp — changed with Q padding.
    Pinned here so T001 reproduces that bug as a diagnostic forever."""
    from repro.kernels import SCAN_TILE_T as TT

    t, q = mask.shape
    tp = -(-t // TT) * TT
    mask = jnp.pad(mask, ((0, tp - t), (0, 0)))
    payload = jnp.pad(payload, ((0, tp - t), (0, 0)))
    acc = jnp.zeros((q, payload.shape[1]), payload.dtype)
    for i in range(tp // TT):
        sl = slice(i * TT, (i + 1) * TT)
        acc = acc + jax.lax.dot_general(
            mask[sl], payload[sl], FOLD_DN,
            preferred_element_type=payload.dtype)
    return acc


def test_t001_reverted_fold_reproduces_pr6_bug():
    p = Program("reverted_masked_tile_fold", _reverted_masked_tile_fold,
                (MASK, PAYLOAD), frozenset({"fold-dot"}))
    found = tr.check_fold_dot_shapes(p)
    assert found and all(f.rule == "T001" and f.severity == ERROR
                         for f in found)
    # the diagnostic names the actual (512, Q) shape the bug compiled
    assert any(f"(512, {REP_Q})" in f.message for f in found)


def test_t001_requires_a_fold_dot_at_all():
    p = Program("sum_everything", lambda m, pl: (m.sum() + pl.sum()),
                (MASK, PAYLOAD), frozenset({"fold-dot"}))
    found = tr.check_fold_dot_shapes(p)
    assert [f.rule for f in found] == ["T001"]
    assert "no tuple-axis fold dot" in found[0].message


# ------------------------------------------------------- T002: fold order


def _tiled_fold(mask, payload, order="asc", shape_tree=False):
    from repro.kernels import SCAN_TILE_Q as TQ, SCAN_TILE_T as TT

    t, q = mask.shape
    tp, qp = -(-t // TT) * TT, -(-q // TQ) * TQ
    mask = jnp.pad(mask, ((0, tp - t), (0, qp - q)))
    payload = jnp.pad(payload, ((0, tp - t), (0, 0)))
    cols = []
    for j in range(qp // TQ):
        dots = [
            jax.lax.dot_general(
                mask[i * TT:(i + 1) * TT, j * TQ:(j + 1) * TQ],
                payload[i * TT:(i + 1) * TT], FOLD_DN,
                preferred_element_type=payload.dtype)
            for i in range(tp // TT)
        ]
        if shape_tree:
            acc = (dots[0] + dots[1]) + (dots[2] + dots[0])
        elif order == "desc":
            acc = dots[-1]
            for d in reversed(dots[:-1]):
                acc = acc + d
        else:
            acc = dots[0]
            for d in dots[1:]:
                acc = acc + d
        cols.append(acc)
    return jnp.concatenate(cols, 0)[:q]


def test_t002_descending_fold_flagged():
    p = Program("descending_fold",
                lambda m, pl: _tiled_fold(m, pl, order="desc"),
                (MASK, PAYLOAD), frozenset({"fold-order"}))
    found = tr.check_fold_order(p)
    assert found and _rules(found) == {"T002"}
    assert any("ascending" in f.message for f in found)


def test_t002_tree_fold_flagged():
    p = Program("tree_fold", lambda m, pl: _tiled_fold(m, pl, shape_tree=True),
                (MASK, PAYLOAD), frozenset({"fold-order"}))
    found = tr.check_fold_order(p)
    assert found and _rules(found) == {"T002"}
    assert any("tree" in f.message for f in found)


def test_t002_canonical_fold_clean():
    from repro.aqp import executor

    p = Program("ok", executor.masked_tile_fold, (MASK, PAYLOAD),
                frozenset({"fold-order"}))
    assert tr.check_fold_order(p) == []


# ----------------------------------------- T003/T004: collective discipline


def _psum_mask_build():
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))

    def build(x):
        return jax.shard_map(lambda v: v - jax.lax.psum(v.sum(), "data"),
                             mesh=mesh, in_specs=(P("data"),),
                             out_specs=P("data"))(x)

    n = 64 * len(jax.devices())
    return Program("psum_mask_build", jax.jit(build),
                   (jax.ShapeDtypeStruct((n, 2), jnp.float64),),
                   frozenset({"mask-build", "agg"}), t=n, q=2)


def test_t003_stray_psum_flagged():
    found = tr.check_mask_build_collectives(_psum_mask_build())
    assert [f.rule for f in found] == ["T003"]
    assert "all_reduce" in found[0].message


def test_t004_bound_zero_flags_the_same_psum():
    assert _rules(tr.check_agg_collectives(_psum_mask_build(), bound=0)) \
        == {"T004"}
    assert tr.check_agg_collectives(_psum_mask_build(), bound=1) == []


# ------------------------------------------------------- T005: HBM escape


def test_t005_oracle_mask_would_be_flagged_fused_is_not():
    from repro.aqp import executor
    from repro.analysis.programs import abstract_snippets, block_structs
    from repro.kernels.fused_masked_scan import ops as fms_ops

    num, cat, meas, valid = block_structs()
    snips = abstract_snippets()
    oracle = Program("oracle_as_fused", executor.eval_partials,
                     (num, cat, meas, snips, valid), frozenset({"fused"}))
    found = tr.check_no_tq_buffer(oracle)
    assert [f.rule for f in found] == ["T005"]

    fused = Program("fused", fms_ops.eval_partials_fused,
                    (num, cat, meas, snips, valid), frozenset({"fused"}))
    assert tr.check_no_tq_buffer(fused) == []


# ------------------------------------------------------------ T006: dtype


def test_t006_f32_leak_flagged():
    from repro.aqp import executor

    def leaky(mask, payload):
        lossy = mask.astype(jnp.float32).astype(jnp.float64)
        return executor.masked_tile_fold(lossy, payload)

    p = Program("f32_leak", leaky, (MASK, PAYLOAD),
                frozenset({"partials-f64"}))
    found = tr.check_partials_f64(p)
    assert found and _rules(found) == {"T006"}
    assert any("convert_element_type" in f.message for f in found)


def test_t006_f32_output_flagged():
    p = Program("f32_out", lambda m, pl: (m.T @ pl).astype(jnp.float32),
                (MASK, PAYLOAD), frozenset({"partials-f64"}))
    found = tr.check_partials_f64(p)
    assert any("output has dtype float32" in f.message for f in found)


# ------------------------------------------------------------ T007: cache


class _FakeJit:
    """Mimics a jitted callable whose cache key leaks per-call state."""

    def __init__(self, leak):
        self.leak = leak
        self.keys = set()

    def _clear_cache(self):
        self.keys.clear()

    def _cache_size(self):
        return len(self.keys)

    def __call__(self, past, valid, sinv, alpha, params, new, *rest):
        key = (past.lo.shape, new.lo.shape)  # the padded (fill, Q) buckets
        if self.leak:
            key += (len(self.keys),)  # a fresh compile every call
        self.keys.add(key)


def test_t007_cache_key_leak_flagged():
    found = tr.check_improve_cache_cardinality(jitted=_FakeJit(leak=True))
    assert [f.rule for f in found] == ["T007"]
    assert "compiled" in found[0].message


def test_t007_bucketed_cache_clean():
    assert tr.check_improve_cache_cardinality(jitted=_FakeJit(leak=False)) \
        == []


def test_t007_unhashable_static_arg_flagged():
    from functools import partial

    # static_argnums=1 makes the `valid` ndarray part of the cache key
    bad = partial(jax.jit, static_argnums=(1,))(
        lambda past, valid, *rest: past.lo.sum())
    found = tr.check_improve_cache_cardinality(jitted=bad)
    assert found and found[0].rule == "T007"
    assert "unhashable" in found[0].message


# --------------------------------------------------------- AST-layer rules


@pytest.fixture(scope="module")
def bad_files():
    return ast_rules.parse_tree(BADREPO)


def test_a001_direct_synopses_write_flagged(bad_files):
    found = ast_rules.check_synopses_access(bad_files)
    locs = {f.location for f in found}
    assert _rules(found) == {"A001"}
    assert any(loc.startswith("uses_synopses.py:") for loc in locs)
    assert len(found) == 2  # the shim write AND the private-dict read


def test_a002_unguarded_apply_flagged(bad_files):
    found = ast_rules.check_guarded_apply(bad_files)
    assert _rules(found) == {"A002"}
    assert found[0].location.startswith("direct_apply.py:")


def test_a003_unregistered_seam_flagged(bad_files):
    found = ast_rules.check_fault_seams(bad_files)
    bad = [f for f in found if "store.drian" in f.message]
    assert bad and bad[0].severity == ERROR
    assert bad[0].location.startswith("bad_seam.py:")


def test_a003_unwrapped_registration_flagged():
    found = ast_rules.check_fault_seams([], points=("ghost.seam",))
    assert [f.rule for f in found] == ["A003"]
    assert "never wrapped" in found[0].message


def test_a004_clock_and_rng_in_kernel_flagged(bad_files):
    found = ast_rules.check_kernel_determinism(bad_files)
    assert _rules(found) == {"A004"}
    msgs = " ".join(f.message for f in found)
    assert "time" in msgs and "np.random" in msgs
    # scope: the same sins OUTSIDE kernels/ are not this rule's business
    outside = [f for f in found
               if not f.location.startswith("kernels/")]
    assert outside == []


def test_a007_clock_and_rng_in_intel_flagged(bad_files):
    found = ast_rules.check_intel_determinism(bad_files)
    assert _rules(found) == {"A007"}
    msgs = " ".join(f.message for f in found)
    assert "time" in msgs and "np.random" in msgs
    # scope: the rule only polices the workload-intelligence plane — the
    # kernels fixture's identical sins belong to A004, not A007
    outside = [f for f in found if not f.location.startswith("intel/")]
    assert outside == []


def test_a008_clock_and_rng_in_front_decisions_flagged(bad_files):
    found = ast_rules.check_front_determinism(bad_files)
    assert _rules(found) == {"A008"}
    msgs = " ".join(f.message for f in found)
    assert "time" in msgs and "random" in msgs
    # scope: only the DECISION modules (admission/metrics) — the transport
    # layer (front.py, http.py) legitimately owns the clock
    outside = [f for f in found
               if not f.location.startswith("serving/front/")]
    assert outside == []


def test_a008_scope_is_exactly_the_decision_modules():
    assert set(ast_rules.FRONT_DECISION_MODULES) == {
        "serving/front/admission.py", "serving/front/metrics.py"}
    assert "A008" in ast_rules.AST_RULES


def test_a005_orphan_module_flagged():
    found = ast_rules.check_dead_code(BADREPO, importer_roots=())
    orphans = [f for f in found if f.location == "orphan.py"]
    assert orphans and orphans[0].severity == ERROR
    assert "dead module" in orphans[0].message


def test_a006_local_epsilon_flagged(bad_files):
    found = ast_rules.check_epsilon_discipline(bad_files, scope=None)
    assert _rules(found) == {"A006"}
    assert found[0].location.startswith("local_eps.py:")
    assert "1e-07" in found[0].message
    # the shared epsilon of record is NOT in the violating band's allowlist
    # by accident: the definition site is excluded by name
    defsite = ast_rules.check_epsilon_discipline(
        bad_files, scope=None, def_sites=("local_eps.py",))
    assert defsite == []


# --------------------------------------- the real codebase passes, strict


@pytest.fixture(scope="module")
def repo_findings():
    return run_repo_analysis()


def test_repo_is_clean_under_strict_gate(repo_findings):
    bad = [f for f in repo_findings if f.severity in (ERROR, WARN)]
    assert gate_count(repo_findings, strict=True) == 0, render_text(bad)


def test_repo_inventory_is_explicit(repo_findings):
    # the dead-code inventory emits INFO entries, each carrying its reason
    inv = [f for f in repo_findings if f.rule == "A005"]
    assert inv and all(f.severity == INFO for f in inv)
    assert all("kept:" in f.message or "importlib" in f.message
               for f in inv)


def test_every_engine_program_lowers(repo_findings):
    # reaching here means jaxpr+StableHLO lowering succeeded for all of them
    names = {p.name for p in engine_programs()}
    assert {"masked_tile_fold", "eval_partials", "eval_partials_fused",
            "masked_partials_fused", "sharded_mask_build"} <= names


def test_cli_ast_layer_exits_zero(capsys):
    rc = main(["--layer", "ast", "--strict"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "finding" in out


def test_cli_json_format(capsys):
    rc = main(["--layer", "ast", "--rules", "A005", "--format", "json"])
    assert rc == 0
    import json

    data = json.loads(capsys.readouterr().out)
    assert all(d["rule"] == "A005" for d in data)
