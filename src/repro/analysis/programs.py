"""Representative-shape lowering harness for the trace/HLO rules.

The parity and performance contracts of the scan plane are properties of the
*compiled artifacts*, not of the Python source: the 512x128 canonical fold,
the collective-free sharded mask build, the fused kernel's (T, Q)-mask-free
HBM footprint and the end-to-end f64 policy all live in the jaxpr /
StableHLO the engine actually runs. This module lowers the engine's jitted
programs once, for one deliberately awkward representative shape (tuple and
snippet counts that are NOT tile multiples, so every padding branch is
exercised), and hands the artifacts to ``repro.analysis.trace_rules``.

Nothing here executes a scan: ``jax.make_jaxpr`` and ``.lower()`` trace and
lower without running the computation.

Every program carries *tags* naming which rules apply:

``fold-dot``     the canonical tuple-axis fold: every contraction over the
                 tuple axis must be a fixed (512, 128) x (512, P) dot.
``fold-order``   the fold must be an ascending left-fold (checkable only
                 where the tile slices are static, i.e. the jnp paths).
``partials-f64`` feeds ``Partials``: interpret-mode f64 end to end, no
                 f64->f32 truncation anywhere on the path.
``mask-build``   the sharded predicate-mask build: ZERO collectives.
``agg``          an aggregation program: collective count bounded by
                 ``PSUM_BOUND``.
``fused``        the fused-kernel path: no intermediate >= (T, Q) may appear
                 in the lowered module (the mask must stay tiled in VMEM).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np

from repro.core.types import SnippetBatch

# Representative abstract shape: every axis chosen to be tile-unaligned so
# the lowered programs contain the padding + multi-tile structure (T pads to
# 1536 = 3 tuple tiles, Q pads to 256 = 2 snippet tiles).
REP_T = 1500  # tuples per block
REP_Q = 200  # snippets per fused batch
REP_L = 2  # numeric dimension attributes
REP_C = 1  # categorical dimension attributes
REP_V = 3  # padded one-hot width
REP_M = 2  # measure attributes

# Collective budget of aggregation programs. The current design needs ZERO
# (the gathered mask is reduced on one device, replaying the oracle order);
# a future per-shard partial-reduction would be allowed at most one psum.
PSUM_BOUND = 1


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_snippets(q: int = REP_Q, l: int = REP_L, c: int = REP_C,
                      v: int = REP_V) -> SnippetBatch:
    """A ShapeDtypeStruct SnippetBatch (tracing/lowering only, no data)."""
    return SnippetBatch(
        lo=_sds((q, l), jnp.float64),
        hi=_sds((q, l), jnp.float64),
        cat=_sds((q, c, v), jnp.bool_),
        agg=_sds((q,), jnp.int32),
        measure=_sds((q,), jnp.int32),
    )


def block_structs(t: int = REP_T, l: int = REP_L, c: int = REP_C,
                  m: int = REP_M):
    """(num_normalized, cat, measures, valid) structs for one tuple block."""
    return (
        _sds((t, l), jnp.float64),
        _sds((t, c), jnp.int32),
        _sds((t, m), jnp.float64),
        _sds((t,), jnp.float64),
    )


@dataclasses.dataclass
class Program:
    """One lowered engine program plus its rule applicability tags."""

    name: str
    fn: Callable
    args: tuple
    tags: frozenset
    # The true (unpadded) block shape the args describe — what the
    # no-(T, Q)-buffer rule measures "escaped to HBM" against.
    t: int = REP_T
    q: int = REP_Q
    _jaxpr: Optional[jex_core.ClosedJaxpr] = None
    _stablehlo: Optional[str] = None

    def jaxpr(self) -> jex_core.ClosedJaxpr:
        if self._jaxpr is None:
            self._jaxpr = jax.make_jaxpr(self.fn)(*self.args)
        return self._jaxpr

    def stablehlo(self) -> str:
        if self._stablehlo is None:
            fn = self.fn
            lower = getattr(fn, "lower", None)
            if lower is None:
                lower = jax.jit(fn).lower
            self._stablehlo = lower(*self.args).as_text()
        return self._stablehlo


def _mesh_for_analysis():
    """A 1-D mesh over every visible device (the CLI forces 8 fake host
    devices before jax initializes, mirroring conftest.py; under a pre-locked
    single-device topology the mesh degenerates to one shard — the rules
    still apply, shard_map lowers either way)."""
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), ("data",))


def engine_programs() -> List[Program]:
    """Lower the scan plane's jitted programs for the representative shape.

    The program set mirrors the bitwise-parity contract surface: the
    canonical fold and its three consumers (oracle, sharded gathered-mask,
    fused kernel), the sharded mask builder, and the remaining public kernel
    ops (`repro.kernels`).
    """
    from repro.aqp import executor
    from repro.kernels.fused_masked_scan import ops as fms_ops
    from repro.kernels.gp_batch_infer import ops as gp_ops
    from repro.kernels.range_mask_agg import ops as rma_ops
    from repro.kernels.se_covariance import ops as se_ops

    num, cat, meas, valid = block_structs()
    snips = abstract_snippets()
    mask = _sds((REP_T, REP_Q), jnp.float64)
    payload = _sds((REP_T, 2 * REP_M + 1), jnp.float64)
    scanned = _sds((), jnp.float64)

    progs = [
        Program(
            "masked_tile_fold", executor.masked_tile_fold, (mask, payload),
            frozenset({"fold-dot", "fold-order", "partials-f64"}),
        ),
        Program(
            "_partials_from_mask", executor._partials_from_mask,
            (mask, meas, snips, scanned),
            frozenset({"fold-dot", "fold-order", "partials-f64", "agg"}),
        ),
        Program(
            "eval_partials", executor.eval_partials,
            (num, cat, meas, snips, valid),
            frozenset({"fold-dot", "fold-order", "partials-f64"}),
        ),
        # The fused Pallas kernel (interpret mode): the grid accumulation is
        # dynamic (no static slice offsets to order-check), but the fold-dot
        # shape, the f64 policy and the no-(T, Q)-in-HBM contract all hold in
        # its lowered module.
        Program(
            "eval_partials_fused", fms_ops.eval_partials_fused,
            (num, cat, meas, snips, valid),
            frozenset({"fold-dot", "partials-f64", "fused"}),
        ),
        Program(
            "masked_partials_fused", fms_ops.masked_partials_fused,
            (mask, meas, snips, scanned),
            frozenset({"fold-dot", "partials-f64", "agg"}),
        ),
        # Legacy partial-coverage scan kernel: off the engine path since
        # PR 6. Deliberately NOT tagged partials-f64 — it accumulates in
        # f32 by design (TPU-style) and casts back at the epilogue; running
        # check_partials_f64 over it emits ~18 truncation diagnostics,
        # which is precisely why fused_masked_scan replaced it. Kept under
        # the collective-bound rule only.
        Program(
            "range_mask_agg.eval_partials_kernel",
            rma_ops.eval_partials_kernel, (num, cat, meas, snips, valid),
            frozenset({"agg"}),
        ),
        Program(
            "se_cov_matrix", se_ops.se_cov_matrix,
            (_sds((REP_Q, REP_L), jnp.float64),
             _sds((REP_Q, REP_L), jnp.float64),
             _sds((REP_Q, REP_L), jnp.float64),
             _sds((REP_Q, REP_L), jnp.float64),
             _sds((REP_L,), jnp.float64), 1.0,
             _sds((REP_Q,), jnp.float64), _sds((REP_Q,), jnp.float64)),
            frozenset({"agg"}),
        ),
        Program(
            "gp_batch_infer", gp_ops.gp_batch_infer,
            (_sds((REP_Q, 64), jnp.float64), _sds((64, 64), jnp.float64),
             _sds((64,), jnp.float64), _sds((REP_Q,), jnp.float64),
             _sds((REP_Q,), jnp.float64), _sds((REP_Q,), jnp.float64),
             _sds((REP_Q,), jnp.float64)),
            frozenset({"agg"}),
        ),
    ]
    mesh = _mesh_for_analysis()
    sharded_fn = executor._sharded_mask_fn(mesh, "data")
    # The mask builder consumes the PADDED block (what eval_partials_sharded
    # hands it): pad the tuple axis to the mesh-divisible power-of-two tile.
    t_pad = executor.padded_tuple_count(REP_T, mesh.shape["data"])
    num_p, cat_p, _, valid_p = (
        _sds((t_pad, REP_L), jnp.float64),
        _sds((t_pad, REP_C), jnp.int32),
        None,
        _sds((t_pad,), jnp.float64),
    )
    progs.append(Program(
        "sharded_mask_build", sharded_fn, (num_p, cat_p, valid_p, snips),
        frozenset({"mask-build", "partials-f64"}),
        t=t_pad,
    ))
    return progs


def by_tag(programs: List[Program]) -> Dict[str, List[Program]]:
    out: Dict[str, List[Program]] = {}
    for p in programs:
        for tag in p.tags:
            out.setdefault(tag, []).append(p)
    return out
