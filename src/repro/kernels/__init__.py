"""Pallas TPU kernels for Verdict's compute hot spots.

Each kernel package follows the kernel.py (pl.pallas_call + BlockSpec VMEM
tiling) / ops.py (jit'd public wrapper with padding + epilogue) / ref.py
(pure-jnp oracle) convention.

How a kernel runs is decided per call from where its operands live
(``interpret_for``): on a TPU the BlockSpecs compile through Mosaic; on any
other backend (the CPU test suite) the same kernel bodies run in Pallas
interpret mode. Nothing falls back: a kernel Mosaic refuses raises.

Kernels:
  se_covariance    -- blocked closed-form SE double-integral covariance build
                      (offline learning hot loop: O(n^2 l) erf evaluations).
  fused_masked_scan-- THE scan hot loop: one pass that streams relation tiles
                      through VMEM — predicate compare (RANGE_EPS widened),
                      categorical membership (one-hot MXU matmul), per-tuple
                      validity mask, and [measures, measures^2, 1] partials
                      accumulation, fused.  Accumulation is a FIXED tile-order
                      fold over SCAN_TILE_T tuple tiles — the SAME reduction
                      ``repro.aqp.executor._partials_from_mask`` performs —
                      so kernel partials are BITWISE equal to the
                      ``eval_partials`` oracle under interpret mode (f64),
                      for any block size and under local AND sharded
                      placement (``tests/test_fused_scan.py``).
  range_mask_agg   -- legacy partial-coverage scan kernel ((tuples x snippets)
                      mask then mask^T @ payload); superseded by
                      fused_masked_scan on the engine path but kept as a
                      stable public wrapper (now valid-mask aware and on the
                      shared RANGE_EPS).
  gp_batch_infer   -- gamma^2 = diag(K Sigma^-1 K^T) + prior blend, tiled on
                      the MXU (the query-time inference hot loop, Eq. 11/12).

Numeric policy:
  CPU (interpret mode, the test suite): the scan kernels run f64 end to end;
  the fused scan's fixed tile-order fold makes its partials bitwise equal
  to the jnp oracle — the repo-wide raw-answer-consistency discipline.
  TPU (compiled): the MXU has no f64 path, so the scan kernels accumulate
  in f32 (payload dots at HIGHEST precision) and agree with the f64
  oracle to f32 tolerance; ``chip_smoke.py`` checks that tolerance on the
  chip. ``gp_batch_infer`` runs in f32 on both.

Shared numeric constants (imported by kernels, the executor oracle and the
refs — ONE epsilon, ONE tile, so kernel and oracle can never drift):

  RANGE_EPS    -- predicate range-boundary widening. All range compares are
                  ``lo - RANGE_EPS <= x <= hi + RANGE_EPS``; kernel, oracle
                  and ref share this constant (a kernel-local 1e-7 once made
                  ``use_kernels=True`` change answers near snippet bounds).
  SCAN_TILE_T  -- the tuple-axis accumulation tile of the scan plane. The
                  oracle's reduction and the fused kernel's grid both fold
                  (SCAN_TILE_T x SCAN_TILE_Q) dot partials in ascending tile
                  order, so their sums agree bit for bit by construction.
  SCAN_TILE_Q  -- the snippet-axis tile. Every dot in the canonical fold has
                  the FIXED shape (SCAN_TILE_T, SCAN_TILE_Q) x (SCAN_TILE_T,
                  P): XLA's CPU matmul picks its contraction order by shape,
                  so only fixed-shape per-tile dots make per-snippet partials
                  bitwise independent of how many snippets ride along
                  (Q-padding invariance — pinned by the verdict-API tests).
"""
import jax
import jax.numpy as jnp

RANGE_EPS = 1e-12  # shared predicate-boundary epsilon (kernel == oracle == ref)

SCAN_TILE_T = 512  # tuple-axis tile of the scan's fixed-order accumulation fold
SCAN_TILE_Q = 128  # snippet-axis tile (= core.types.SNIPPET_TILE serve tiles)


def interpret_for(x) -> bool:
    """Whether a kernel over operand ``x`` runs in Pallas interpret mode.

    Compiled (Mosaic) when ``x`` lives on a TPU, interpreted anywhere else.
    A tracer (a kernel called inside an outer ``jit``) carries no device,
    so the default backend decides.
    """
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        platform = next(iter(x.devices())).platform
    else:
        platform = jax.default_backend()
    return platform != "tpu"


def block_spec(block_shape, index_map):
    """``pl.BlockSpec`` whose index map returns int32 block indices.

    The engine enables x64 globally (``repro.core.types``), which makes a
    literal ``0`` in an index map lower to i64; Mosaic refuses a mixed
    (i32, i64) index tuple. Every kernel builds its specs through here.
    """
    from jax.experimental import pallas as pl

    def index_map_i32(*grid_idx):
        return tuple(jnp.asarray(i, jnp.int32) for i in index_map(*grid_idx))

    return pl.BlockSpec(block_shape, index_map_i32)
