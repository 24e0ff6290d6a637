"""Layer 1: jaxpr / StableHLO rules over the engine's lowered programs.

Each rule proves one clause of the scan plane's parity or performance
contract *statically* — by walking the traced jaxpr or the lowered StableHLO
of the programs in ``repro.analysis.programs`` — instead of hoping a parity
test happens to trip it at runtime:

T001  canonical fold-dot shape: every tuple-axis contraction is a FIXED
      (512, 128) x (512, P) dot. This is the PR-6 invariant: XLA's CPU
      matmul picks its contraction order by operand shape, so a single
      variable-width dot (the pre-PR-6 form) breaks Q-pad invariance — the
      bug that surfaced as a 1-ulp parity flake.
T002  ascending left-fold: per snippet tile, tuple-tile partials accumulate
      strictly left-to-right in ascending tile order (``acc + part``, never
      a tree or a descending fold — fp addition is not associative).
T003  collective-free mask build: the shard_map'd predicate-mask program
      contains ZERO collective ops (the design gathers the mask and replays
      the oracle reduction; any collective here re-partitions the compare
      work and breaks bitwise parity with the oracle).
T004  bounded aggregation collectives: aggregation programs carry at most
      ``PSUM_BOUND`` all-reduces (today: zero — a psum tree rounds
      differently than the oracle fold).
T005  no (T, Q) buffer in HBM: the fused-kernel path must never materialize
      an intermediate as large as the (tuples x snippets) mask — that is
      the entire point of the fusion (~554x modeled traffic reduction).
T006  f64 policy: programs feeding ``Partials`` run f64 end to end on the
      CPU path (the programs here lower for the CPU, where the kernels
      run in interpret mode) — no f64->f32 ``convert_element_type``, no
      f32 output produced from f64 inputs (weak-type promotion), f64
      outputs only. The compiled TPU scan casts to f32 by policy; that
      path is compiled in ``tests/test_chip_compile.py`` and checked
      against an f64 reference by ``chip_smoke.py``.
T007  compile-cache cardinality: driving the power-of-two (Q, fill) improve
      ladder yields EXACTLY one jit cache entry per (Q-bucket, fill-bucket)
      pair — catching unhashable static args and cache-key leaks (a key
      that varies with the unpadded size compiles one program per query).
"""
from __future__ import annotations

import itertools
import re
from typing import Iterable, Iterator, List, Optional, Sequence

from repro.analysis.findings import ERROR, Finding
from repro.analysis.programs import PSUM_BOUND, Program, engine_programs

# ------------------------------------------------------------- jaxpr walking

FOLD_DIMS = (((0,), (0,)), ((), ()))  # contract the leading (tuple) axis


def _subjaxprs(eqn) -> Iterator:
    """Every (Closed)Jaxpr hiding in an eqn's params (pjit, scan, while,
    cond branches, custom_* call jaxprs, pallas interpret bodies...)."""
    import jax.extend.core as jcore

    def visit(val):
        if isinstance(val, jcore.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, jcore.Jaxpr):
            yield val
        elif isinstance(val, (tuple, list)):
            for v in val:
                yield from visit(v)

    for val in eqn.params.values():
        yield from visit(val)


def iter_jaxprs(jaxpr) -> Iterator:
    """The jaxpr and, recursively, every sub-jaxpr it calls."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _subjaxprs(eqn):
            yield from iter_jaxprs(sub)


def iter_eqns(closed_jaxpr) -> Iterator:
    for j in iter_jaxprs(closed_jaxpr.jaxpr):
        yield from j.eqns


def _is_fold_dot(eqn) -> bool:
    """A tuple-axis contraction: 2-D x 2-D dot_general contracting dim 0 of
    both operands with no batch dims — the shape class of the canonical
    ``masked_tile_fold`` dot (other dots — one-hot membership, GP solves —
    contract differently and are not fold dots)."""
    if eqn.primitive.name != "dot_general":
        return False
    if tuple(map(tuple, eqn.params["dimension_numbers"][0])) != ((0,), (0,)):
        return False
    if any(eqn.params["dimension_numbers"][1]):
        return False
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    return lhs.ndim == 2 and rhs.ndim == 2


# ----------------------------------------------------------------- T001/T002


def check_fold_dot_shapes(program: Program, tile_t: Optional[int] = None,
                          tile_q: Optional[int] = None) -> List[Finding]:
    """T001: every fold dot is exactly (tile_t, tile_q) x (tile_t, P)."""
    from repro.kernels import SCAN_TILE_Q, SCAN_TILE_T

    tile_t = SCAN_TILE_T if tile_t is None else tile_t
    tile_q = SCAN_TILE_Q if tile_q is None else tile_q
    out: List[Finding] = []
    n_fold = 0
    for eqn in iter_eqns(program.jaxpr()):
        if not _is_fold_dot(eqn):
            continue
        n_fold += 1
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        if lhs.shape != (tile_t, tile_q) or rhs.shape[0] != tile_t:
            out.append(Finding(
                "T001", ERROR, f"program:{program.name}",
                f"tuple-axis fold dot has shape {lhs.shape} x {rhs.shape}; "
                f"the canonical fold requires ({tile_t}, {tile_q}) x "
                f"({tile_t}, P) for every dot",
                "route the reduction through repro.aqp.executor."
                "masked_tile_fold (fixed SCAN_TILE_T x SCAN_TILE_Q tiles); "
                "variable-shape dots change XLA's contraction order and "
                "break Q-pad/block-size bitwise invariance (the PR-6 1-ulp "
                "bug)",
            ))
    if n_fold == 0:
        out.append(Finding(
            "T001", ERROR, f"program:{program.name}",
            "no tuple-axis fold dot found — the program no longer performs "
            "the canonical masked_tile_fold reduction",
            "aggregate mask x payload through masked_tile_fold so all scan "
            "paths share one bitwise reduction order",
        ))
    return out


def _lookup(mapping, var):
    """dict lookup tolerating jaxpr Literals (unhashable)."""
    try:
        return mapping.get(var)
    except TypeError:
        return None


def check_fold_order(program: Program) -> List[Finding]:
    """T002: fold partials accumulate as an ascending left-fold."""
    out: List[Finding] = []
    for jaxpr in iter_jaxprs(program.jaxpr().jaxpr):
        produced = {}
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                produced[v] = eqn
        # var -> (min tuple-tile start, max tuple-tile start, is_single_dot)
        info = {}
        for eqn in jaxpr.eqns:
            if _is_fold_dot(eqn):
                src = _lookup(produced, eqn.invars[0])
                t0 = 0
                if src is not None and src.primitive.name == "slice":
                    t0 = int(src.params["start_indices"][0])
                info[eqn.outvars[0]] = (t0, t0, True)
            elif eqn.primitive.name == "add":
                a, b = eqn.invars
                ia, ib = _lookup(info, a), _lookup(info, b)
                if ia is None or ib is None:
                    continue
                if not ib[2]:
                    out.append(Finding(
                        "T002", ERROR, f"program:{program.name}",
                        "fold add combines two accumulated subtrees — a "
                        "tree reduction, not the canonical left-fold",
                        "accumulate per-tile dot partials strictly "
                        "left-to-right (acc = acc + part), as "
                        "masked_tile_fold does",
                    ))
                elif ia[1] >= ib[0]:
                    out.append(Finding(
                        "T002", ERROR, f"program:{program.name}",
                        f"fold accumulates tuple tile t={ib[0]} after tile "
                        f"t={ia[1]} — not an ascending left-fold",
                        "fold tuple tiles in ascending start order; fp "
                        "addition is not associative, so any other order "
                        "breaks bitwise parity with the oracle",
                    ))
                info[eqn.outvars[0]] = (
                    min(ia[0], ib[0]), max(ia[1], ib[1]), False)
    return out


# ------------------------------------------------------------ T003/T004 HLO

COLLECTIVE_OPS = (
    "all_reduce", "all_gather", "all_to_all", "collective_permute",
    "collective_broadcast", "reduce_scatter",
)
_STABLEHLO_OP_RE = re.compile(r"stablehlo\.([a-z0-9_]+)")


def collective_counts(stablehlo_text: str) -> dict:
    """Occurrences of each collective op mnemonic in a StableHLO module."""
    counts: dict = {}
    for m in _STABLEHLO_OP_RE.finditer(stablehlo_text):
        op = m.group(1)
        if op in COLLECTIVE_OPS:
            counts[op] = counts.get(op, 0) + 1
    return counts


def check_mask_build_collectives(program: Program) -> List[Finding]:
    """T003: the sharded mask build lowers with ZERO collectives."""
    counts = collective_counts(program.stablehlo())
    if not counts:
        return []
    detail = ", ".join(f"{k} x{v}" for k, v in sorted(counts.items()))
    return [Finding(
        "T003", ERROR, f"program:{program.name}",
        f"sharded mask build contains collective ops ({detail}); the "
        "mask-build stage must be embarrassingly parallel over the tuple "
        "axis",
        "keep the shard_map stage to per-shard predicate compares "
        "(out_specs=P(axis)); gather the mask and replay the oracle "
        "reduction instead of reducing across shards",
    )]


def check_agg_collectives(program: Program,
                          bound: int = PSUM_BOUND) -> List[Finding]:
    """T004: aggregation programs carry a bounded collective count."""
    counts = collective_counts(program.stablehlo())
    total = sum(counts.values())
    if total <= bound:
        return []
    detail = ", ".join(f"{k} x{v}" for k, v in sorted(counts.items()))
    return [Finding(
        "T004", ERROR, f"program:{program.name}",
        f"aggregation program lowers {total} collective op(s) ({detail}), "
        f"above the bound of {bound}",
        "a per-shard psum tree rounds differently than the oracle fold; "
        "reduce on one device in canonical tile order",
    )]


# ------------------------------------------------------------------ T005 HLO

_TENSOR_RE = re.compile(r"tensor<(\d+)x(\d+)(?:x\d+)*x(?:f64|f32|i1|i8)>")


def check_no_tq_buffer(program: Program) -> List[Finding]:
    """T005: no intermediate >= (T, Q) in the fused path's lowered module."""
    t, q = program.t, program.q
    bad = set()
    for m in _TENSOR_RE.finditer(program.stablehlo()):
        a, b = int(m.group(1)), int(m.group(2))
        if (a >= t and b >= q) or (a >= q and b >= t):
            bad.add((a, b))
    if not bad:
        return []
    shapes = ", ".join(f"({a}, {b})" for a, b in sorted(bad))
    return [Finding(
        "T005", ERROR, f"program:{program.name}",
        f"fused-kernel path materializes buffer(s) of shape {shapes} — at "
        f"least the full ({t}, {q}) predicate mask escaped to HBM",
        "the mask must live tile-by-tile in VMEM only "
        "(SCAN_TILE_T x SCAN_TILE_Q blocks inside the Pallas grid); a "
        "full-mask intermediate un-fuses the scan and collapses "
        "scan/bytes_per_sec_frac_of_peak",
    )]


# ---------------------------------------------------------------- T006 dtype


def check_partials_f64(program: Program) -> List[Finding]:
    """T006: interpret-mode f64 end to end on every path feeding Partials."""
    import numpy as np

    out: List[Finding] = []
    for eqn in iter_eqns(program.jaxpr()):
        name = eqn.primitive.name
        if name == "convert_element_type":
            src = eqn.invars[0].aval
            dst = eqn.params.get("new_dtype")
            if (getattr(src, "dtype", None) == np.float64
                    and dst == np.float32):
                out.append(Finding(
                    "T006", ERROR, f"program:{program.name}",
                    "f64 -> f32 convert_element_type on a path feeding "
                    "Partials (precision truncation)",
                    "interpret mode runs f64 end to end (see "
                    "repro/kernels/fused_masked_scan/ops.py dtype policy); "
                    "only the compiled TPU path may cast to f32",
                ))
            continue
        out_f32 = any(
            getattr(v.aval, "dtype", None) == np.float32
            for v in eqn.outvars)
        in_f64 = any(
            getattr(v.aval, "dtype", None) == np.float64
            for v in eqn.invars if hasattr(v, "aval"))
        if out_f32 and in_f64:
            out.append(Finding(
                "T006", ERROR, f"program:{program.name}",
                f"op '{name}' produces f32 from f64 input(s) — silent "
                "precision drop (weak-type promotion or dtype drift)",
                "keep the scan plane's arithmetic in f64; check for f32 "
                "literals / weak-typed constants contaminating the path",
            ))
    for aval in program.jaxpr().out_avals:
        dt = getattr(aval, "dtype", None)
        if dt is not None and np.issubdtype(dt, np.floating) \
                and dt != np.float64:
            out.append(Finding(
                "T006", ERROR, f"program:{program.name}",
                f"program output has dtype {dt}, expected float64",
                "Partials fields are f64 by contract; cast at the epilogue "
                "only on the compiled TPU path",
            ))
    return out


# ---------------------------------------------------------------- T007 cache


def _snips(q: int, l: int = 2, c: int = 1, v: int = 3):
    import jax.numpy as jnp

    from repro.core.types import SnippetBatch

    return SnippetBatch(
        lo=jnp.zeros((q, l)), hi=jnp.ones((q, l)),
        cat=jnp.ones((q, c, v), bool),
        agg=jnp.ones((q,), jnp.int32),
        measure=jnp.zeros((q,), jnp.int32),
    )


def check_improve_cache_cardinality(
    jitted=None,
    q_values: Sequence[int] = (3, 8, 12, 20),
    fill_values: Sequence[int] = (5, 8, 13, 27),
) -> List[Finding]:
    """T007: one compiled improve program per (Q-bucket, fill-bucket) pair.

    Drives ``_improve_padded`` (or ``jitted``, for fixtures) exactly the way
    ``Synopsis.improve`` does — shapes padded to the power-of-two ladder —
    and counts jit cache entries. More entries than distinct bucket pairs
    means the cache key leaks the unpadded size (one compile per query, the
    regression ``improve/mixed_q_programs`` gates dynamically); a TypeError
    means an unhashable static argument.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core.synopsis import (MIN_FILL_BUCKET, MIN_Q_BUCKET,
                                     _improve_padded)
    from repro.core.types import GPParams, Schema, bucket_size, pad_snippets

    fn = _improve_padded if jitted is None else jitted
    where = "program:improve_ladder"
    schema = Schema(num_lo=(0.0, 0.0), num_hi=(1.0, 1.0), cat_sizes=(3,),
                    n_measures=1)
    params = GPParams.init(schema)
    buckets = sorted({
        (bucket_size(q, MIN_Q_BUCKET), bucket_size(f, MIN_FILL_BUCKET))
        for q, f in itertools.product(q_values, fill_values)
    })
    fn._clear_cache()
    findings: List[Finding] = []
    for q, fill in itertools.product(q_values, fill_values):
        qb = bucket_size(q, MIN_Q_BUCKET)
        fb = bucket_size(fill, MIN_FILL_BUCKET)
        past = pad_snippets(_snips(fill), fb)
        new = pad_snippets(_snips(q), qb)
        valid = jnp.asarray(np.arange(fb) < fill, jnp.float64)
        sinv = jnp.eye(fb)
        alpha = jnp.zeros((fb,))
        raw_theta = jnp.zeros((qb,))
        raw_beta2 = jnp.ones((qb,))
        try:
            fn(past, valid, sinv, alpha, params, new,
               raw_theta, raw_beta2, 0.99)
        except (TypeError, ValueError) as e:
            findings.append(Finding(
                "T007", ERROR, where,
                f"improve dispatch rejected a call (unhashable static "
                f"argument?): {e}",
                "jit static args must be hashable; shape-only cache keys "
                "come from padding, not from static args",
            ))
            return findings
    size = int(fn._cache_size())
    if size != len(buckets):
        findings.append(Finding(
            "T007", ERROR, where,
            f"(Q, fill) ladder over {len(q_values)}x{len(fill_values)} "
            f"calls compiled {size} program(s); expected exactly "
            f"{len(buckets)} (one per bucket pair {buckets})",
            "the jit cache key must depend only on the PADDED shapes; a "
            "leaked unpadded size or a value-dependent static arg compiles "
            "per call instead of per bucket",
        ))
    return findings


def check_scan_jit_cache() -> List[Finding]:
    """T007 (scan leg): ``eval_partials`` is a plain shape-keyed jit — same
    shape twice is ONE cache entry, a second shape is a second entry. Pins
    that dropping the historical no-op ``static_argnames=()`` wrappers
    changed nothing about caching."""
    import jax.numpy as jnp

    from repro.aqp.executor import eval_partials

    fn = eval_partials
    where = "program:eval_partials"
    fn._clear_cache()
    num = jnp.zeros((4, 2))
    cat = jnp.zeros((4, 1), jnp.int32)
    meas = jnp.zeros((4, 1))
    snips = _snips(2)
    eval_partials(num, cat, meas, snips)
    eval_partials(num, cat, meas, snips)
    after_same = int(fn._cache_size())
    eval_partials(num[:3], cat[:3], meas[:3], snips)
    after_new = int(fn._cache_size())
    out: List[Finding] = []
    if after_same != 1 or after_new != 2:
        out.append(Finding(
            "T007", ERROR, where,
            f"eval_partials cache cardinality drifted: {after_same} "
            "entr(ies) after two same-shape calls (expected 1), "
            f"{after_new} after one new shape (expected 2)",
            "eval_partials must stay a plain shape-keyed jax.jit",
        ))
    return out


# ------------------------------------------------------------------- driver

TRACE_RULES = ("T001", "T002", "T003", "T004", "T005", "T006", "T007")


def run_trace_rules(programs: Optional[Iterable[Program]] = None,
                    rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """All Layer-1 findings over the engine's programs (or a custom set)."""
    rules = set(TRACE_RULES if rules is None else rules)
    progs = list(engine_programs() if programs is None else programs)
    out: List[Finding] = []
    for p in progs:
        if "fold-dot" in p.tags and "T001" in rules:
            out.extend(check_fold_dot_shapes(p))
        if "fold-order" in p.tags and "T002" in rules:
            out.extend(check_fold_order(p))
        if "mask-build" in p.tags and "T003" in rules:
            out.extend(check_mask_build_collectives(p))
        if "agg" in p.tags and "T004" in rules:
            out.extend(check_agg_collectives(p))
        if "fused" in p.tags and "T005" in rules:
            out.extend(check_no_tq_buffer(p))
        if "partials-f64" in p.tags and "T006" in rules:
            out.extend(check_partials_f64(p))
    if "T007" in rules and programs is None:
        out.extend(check_improve_cache_cardinality())
        out.extend(check_scan_jit_cache())
    return out
