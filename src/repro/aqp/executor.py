"""Snippet evaluation over tuple blocks + CLT error bounds.

The TPU-idiomatic form of a multi-snippet scan: build a (tuples × snippets)
predicate mask with vectorized compares, then aggregate with mask^T @ values on
the MXU (see ``repro.kernels.fused_masked_scan`` for the fused Pallas kernel;
this module is the pure-jnp oracle and the host-side accumulation / estimate
logic). The canonical reduction is ``masked_tile_fold`` — a fixed
ascending-tile-order fold shared by the oracle, the gathered sharded mask, and
the kernel's sequential-grid accumulator, which is what makes all three paths
bitwise-identical by construction.

Distribution: the scan is shape-agnostic. A tuple block of ANY size runs over
a mesh of ANY size: the tuple axis is padded to a power-of-two tile divisible
by the mesh, padding rows carry an explicit per-tuple *validity mask* (so
``Partials.scanned`` is the mask sum — a real tuple count, never the padded
shape), and the predicate-mask build — the O(T·n·(l+c)) compare work — runs
sharded via ``shard_map``. The masked mask is then gathered and the final
(2m+1)-column aggregation replays the unsharded oracle's exact reduction
order, so sharded partials are BITWISE equal to ``eval_partials`` for every
(relation size, mesh size) combination (pinned by
``tests/test_sharded_scan.py``; a per-shard matmul + psum tree would be
deterministic but NOT oracle-bitwise — fp addition is not associative).

``ScanPlacement`` is the placement seam of the scan plane (the data-plane
mirror of ``repro.core.store.SynopsisStore``): it owns where tuple blocks
live (``NamedSharding(mesh, P(axis))`` + ``jax.device_put``) and how a block
is evaluated. The ROADMAP multi-host item extends exactly this seam to
``jax.process_count() > 1`` (per-process addressable shards + a cross-host
gather of the mask blocks).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.types import AVG, SnippetBatch
from repro.ft import faults
from repro.kernels import RANGE_EPS, SCAN_TILE_Q, SCAN_TILE_T

BIG_BETA2 = 1e12  # raw error for snippets with no support in the scanned sample


@dataclasses.dataclass(frozen=True)
class Partials:
    """Sufficient statistics accumulated over scanned tuples."""

    sums: jnp.ndarray  # (n,) sum of measure over matching tuples
    sumsq: jnp.ndarray  # (n,)
    count: jnp.ndarray  # (n,) matching tuples
    scanned: jnp.ndarray  # () total VALID tuples scanned (mask sum, a real
    # count — zero-padded tuples never inflate it)

    @staticmethod
    def zeros(n: int) -> "Partials":
        z = jnp.zeros((n,))
        return Partials(z, z, z, jnp.zeros(()))

    def __add__(self, other: "Partials") -> "Partials":
        return Partials(
            self.sums + other.sums,
            self.sumsq + other.sumsq,
            self.count + other.count,
            self.scanned + other.scanned,
        )


def predicate_mask(num_normalized, cat, snippets: SnippetBatch, valid=None):
    """(T, n) float mask of tuples satisfying each snippet's predicates.

    ``valid``: optional (T,) 0/1 per-tuple validity mask; invalid (padding)
    rows are forced to exactly 0.0 in every column, valid rows are untouched
    bitwise (multiplication by 1.0 is exact).
    """
    x = num_normalized  # (T, l), normalized units — same as snippet lo/hi
    m_num = jnp.all(
        (x[:, None, :] >= snippets.lo[None, :, :] - RANGE_EPS)
        & (x[:, None, :] <= snippets.hi[None, :, :] + RANGE_EPS),
        axis=-1,
    )
    mask = m_num
    c = cat.shape[1] if cat.ndim == 2 else 0
    for k in range(c):
        # snippets.cat[:, k, :]: (n, V); cat[:, k]: (T,) codes
        mk = jnp.take(snippets.cat[:, k, :], cat[:, k], axis=1)  # (n, T)
        mask = mask & mk.T
    mask = mask.astype(jnp.float64)
    if valid is not None:
        mask = mask * valid[:, None]
    return mask


def masked_tile_fold(mask, payload, tile_t: int = SCAN_TILE_T,
                     tile_q: int = SCAN_TILE_Q):
    """out[q, p] = sum_t mask[t, q] * payload[t, p] — the canonical
    fixed-tile-order reduction of the scan plane.

    Zero-pads BOTH axes to tile multiples and, per snippet tile, left-folds
    the per-tile (tile_t, tile_q) x (tile_t, P) dot partials in ascending
    tuple-tile order — EXACTLY the accumulation the fused Pallas kernel's
    grid performs (``repro.kernels.fused_masked_scan``), so the jnp oracle
    and the kernel agree bit for bit by construction instead of by rounding
    luck.  Every dot has the same FIXED shape: XLA's CPU matmul picks its
    contraction order by operand shape, so fixed-shape tiles are what makes
    per-snippet partials bitwise independent of block size AND of how many
    snippets ride along (Q-padding invariance).  Padding rows/columns are
    zeros and sliced away — they contribute exact-zero partials.  (A single
    big matmul would round differently — fp addition is not associative.)
    """
    t, q = mask.shape
    p = payload.shape[1]
    pad_t = (-t) % tile_t
    pad_q = (-q) % tile_q
    if pad_t:
        mask = jnp.concatenate([mask, jnp.zeros((pad_t, q), mask.dtype)])
        payload = jnp.concatenate(
            [payload, jnp.zeros((pad_t, p), payload.dtype)])
    if pad_q:
        mask = jnp.concatenate(
            [mask, jnp.zeros((mask.shape[0], pad_q), mask.dtype)], axis=1)
    dn = (((0,), (0,)), ((), ()))
    cols = []
    for j in range(mask.shape[1] // tile_q):
        sq = slice(j * tile_q, (j + 1) * tile_q)
        acc = None
        for i in range(mask.shape[0] // tile_t):
            st = slice(i * tile_t, (i + 1) * tile_t)
            part = jax.lax.dot_general(mask[st, sq], payload[st], dn,
                                       preferred_element_type=payload.dtype)
            acc = part if acc is None else acc + part
        if acc is None:  # zero-row block
            acc = jnp.zeros((tile_q, p), payload.dtype)
        cols.append(acc)
    out = jnp.concatenate(cols) if cols else jnp.zeros((0, p), payload.dtype)
    return out[:q]


@jax.jit
def _partials_from_mask(mask, measures, snippets: SnippetBatch,
                        scanned) -> Partials:
    """The mask → sufficient-statistics aggregation, factored out so every
    path (local oracle, gathered sharded mask, fused kernel) performs the
    SAME reduction: the payload packs [measures, measures², 1] and the
    contraction is the canonical ``masked_tile_fold`` — the fused kernel's
    own accumulation order — so all paths are bitwise-identical."""
    t, m = measures.shape
    payload = jnp.concatenate(
        [measures, measures * measures, jnp.ones((t, 1), measures.dtype)],
        axis=1)  # (T, 2m+1)
    out = masked_tile_fold(mask, payload)  # (n, 2m+1)
    idx = snippets.measure[:, None]
    sums = jnp.take_along_axis(out[:, :m], idx, axis=1)[:, 0]
    sumsq = jnp.take_along_axis(out[:, m:2 * m], idx, axis=1)[:, 0]
    return Partials(sums, sumsq, out[:, 2 * m], scanned)


@jax.jit
def eval_partials(num_normalized, cat, measures, snippets: SnippetBatch,
                  valid=None) -> Partials:
    """Partial statistics for one tuple block (pure-jnp oracle path).

    ``valid``: optional (T,) validity mask for zero-padded tuple blocks.
    Padding rows contribute exactly nothing to sums/sumsq/count (their mask
    row is exactly 0.0 and their payload is zeros), and ``scanned`` is the
    mask sum — the true number of tuples scanned, not the padded shape.
    """
    mask = predicate_mask(num_normalized, cat, snippets, valid)  # (T, n)
    scanned = (jnp.asarray(float(num_normalized.shape[0]))
               if valid is None else jnp.sum(valid))
    return _partials_from_mask(mask, measures, snippets, scanned)


jax.tree_util.register_dataclass(
    Partials, data_fields=("sums", "sumsq", "count", "scanned"), meta_fields=()
)


def padded_tuple_count(t: int, n_shards: int) -> int:
    """Tuple-axis tile for a ``t``-row block over ``n_shards`` devices.

    Smallest power of two >= t, rounded up to a multiple of the shard count
    (the round-up is a no-op for power-of-two meshes). Power-of-two tiling
    keeps the number of compiled scan programs logarithmic in the largest
    block seen; mesh divisibility lets ``shard_map`` split the tuple axis
    evenly with NO precondition on the relation/mesh combination.
    """
    n_shards = max(int(n_shards), 1)
    b = 1
    while b < t:
        b *= 2
    return -(-b // n_shards) * n_shards


def pad_tuple_axis(n_shards: int, num_normalized, cat, measures, valid=None):
    """Zero-pad the tuple axis to ``padded_tuple_count`` rows.

    Returns ``(num, cat, measures, valid)`` where ``valid`` marks the
    original rows with 1.0 and the padding with 0.0 (an existing ``valid``
    is extended). Padding payloads are zeros; categorical codes pad with 0,
    which is always an in-domain index — the validity mask, not the padded
    values, is what guarantees they contribute nothing. ``measures`` may be
    None (the sharded mask stage has no use for the payload — the
    oracle-order reduction reads the original, unpadded measures).
    """
    t = num_normalized.shape[0]
    if valid is None:
        valid = jnp.ones((t,))
    k = padded_tuple_count(t, n_shards) - t
    if k == 0:
        return num_normalized, cat, measures, valid
    return (
        jnp.concatenate([num_normalized,
                         jnp.zeros((k, num_normalized.shape[1]))]),
        jnp.concatenate([cat, jnp.zeros((k, cat.shape[1]), cat.dtype)]),
        None if measures is None else
        jnp.concatenate([measures, jnp.zeros((k, measures.shape[1]))]),
        jnp.concatenate([valid, jnp.zeros((k,))]),
    )


@jax.jit
def _mask_rows(num_normalized, cat, valid, snippets):
    return predicate_mask(num_normalized, cat, snippets, valid=valid)


_SHARDED_MASK_FNS = {}


def _sharded_mask_fn(mesh, axis: str):
    """Jitted shard_map mask builder, cached per (mesh, axis) so repeated
    block evals reuse one compiled program per shape bucket instead of
    re-tracing the shard_map every call."""
    from jax.sharding import PartitionSpec as P

    key = (mesh, axis)
    fn = _SHARDED_MASK_FNS.get(key)
    if fn is None:
        fn = jax.jit(jax.shard_map(
            _mask_rows,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P()),
            out_specs=P(axis),
        ))
        _SHARDED_MASK_FNS[key] = fn
    return fn


def eval_partials_sharded(mesh, axis: str, num_normalized, cat, measures,
                          snippets, valid=None, place_fn=None, agg_fn=None):
    """Distributed partials over the ``axis`` mesh axis — shape-agnostic.

    Accepts ANY (tuple count, mesh size) combination: the tuple axis is
    padded to the next mesh-divisible power-of-two tile with a validity mask
    (``pad_tuple_axis``), and the padded block is placed over the mesh
    (``place_fn``, normally ``ScanPlacement.place``). The sharded stage is
    the predicate-mask build — the O(T·n·(l+c)) compare work; the masked
    mask is then gathered and the final aggregation replays the unsharded
    oracle's exact reduction over the TRUE rows, so the result is BITWISE
    equal to ``eval_partials`` (a per-shard matmul + psum tree would round
    differently). ``scanned`` is the validity-mask sum: an all-padding shard
    contributes exactly nothing.

    ``agg_fn``: optional replacement for the gathered-mask aggregation,
    called as ``agg_fn(mask, measures, snippets, scanned)``. The kernel path
    passes ``repro.kernels.fused_masked_scan.masked_partials_fused`` here —
    the same canonical tile fold run inside a Pallas kernel, so the result
    stays bitwise-identical while the aggregation exercises the kernel
    (``use_kernels=True`` composing with a mesh).
    """
    t = num_normalized.shape[0]
    # Only what the sharded mask stage consumes is padded/placed; the
    # payload never crosses devices — the reduction reads the original
    # ``measures``.
    num_p, cat_p, _, valid_p = pad_tuple_axis(
        mesh.shape[axis], num_normalized, cat, None, valid)
    # The true scanned count, computed BEFORE placement so the scalar stays
    # on the default device (mesh-wide scalars can't join the single-device
    # reduction program below).
    scanned = jnp.sum(valid_p)
    if place_fn is not None:
        num_p, cat_p, valid_p = place_fn(num_p, cat_p, valid_p)
    mask = _sharded_mask_fn(mesh, axis)(num_p, cat_p, valid_p, snippets)
    # Gather the masked rows of the ORIGINAL block onto one device and
    # replay the oracle's reduction bit for bit. (The [: t] slice drops
    # whole padding rows; rows invalidated by a caller-supplied mask are
    # already exactly 0.0 columns inside ``mask``. A single-device mask
    # keeps GSPMD from re-partitioning the reduction.)
    mask = jax.device_put(mask[:t], mesh.devices.flat[0])
    if agg_fn is not None:
        return agg_fn(mask, measures, snippets, scanned)
    return _partials_from_mask(mask, measures, snippets, scanned)


def _kernel_agg_for(local_eval):
    """Map the engine's per-block evaluator to the matching gathered-mask
    aggregation (None -> the jnp oracle ``_partials_from_mask``).

    This is how ``use_kernels=True`` composes with a mesh: the sharded mask
    build stays shard_map'd, and the post-gather fold runs through the
    aggregation-only Pallas kernel instead of silently falling back to jnp.
    """
    from repro.kernels.fused_masked_scan import ops as fms_ops

    if local_eval is fms_ops.eval_partials_fused:
        return fms_ops.masked_partials_fused
    return None


def _evaluator_name(local_eval) -> str:
    """Stable name of a per-block evaluator for placement telemetry."""
    from repro.kernels.fused_masked_scan import ops as fms_ops
    from repro.kernels.range_mask_agg import ops as rma_ops

    if local_eval is None or local_eval is eval_partials:
        return "oracle"
    if local_eval is fms_ops.eval_partials_fused:
        return "fused_masked_scan"
    if local_eval is rma_ops.eval_partials_kernel:
        return "range_mask_agg"
    return getattr(local_eval, "__name__", "custom")


class ScanPlacement:
    """Placement seam of the scan plane (data-plane mirror of
    ``repro.core.store.SynopsisStore``).

    Owns WHERE tuple blocks live and HOW a block is evaluated; the query
    lifecycle (``PhysicalPlan``/``BatchExecutor``/``VerdictEngine``) only
    ever calls ``eval_block`` and stays layout-oblivious — block placement
    is a non-observable implementation detail, proven bitwise by
    ``tests/test_sharded_scan.py`` rather than by convention.

    The base class is local placement: blocks stay where they are and the
    engine's per-block evaluator (pure-jnp oracle or Pallas kernel) runs
    unpadded — bit-identical to the historical direct call.
    ``ShardedScanPlacement`` pads/masks/places over a mesh. The ROADMAP
    multi-host item extends exactly this seam to
    ``jax.process_count() > 1`` (per-process addressable shards, cross-host
    mask gather).
    """

    kind = "local"
    mesh = None
    axis = "data"

    def __init__(self):
        self.blocks_evaluated = 0
        self.pad_rows = 0  # padding rows appended across all blocks
        self.tuples_placed = 0  # true (valid) tuples routed through eval
        self.last_evaluator = None  # evaluator actually used by eval_block

    @property
    def n_shards(self) -> int:
        return 1

    def describe(self) -> str:
        """Human-readable placement (``Session.explain``/``stats``)."""
        return "local"

    def place(self, num_normalized, cat, valid):
        """Place one (padded) block's mask-stage arrays; local placement is
        the identity. (The measure payload is never placed: the
        oracle-order reduction always reads it where it already lives.)"""
        return num_normalized, cat, valid

    def evaluator_for(self, local_eval) -> str:
        """Name of the evaluator ``eval_block`` WILL use for ``local_eval``
        — what ``Session.explain`` reports before any block runs."""
        return _evaluator_name(local_eval)

    def eval_block(self, block, snippets: SnippetBatch,
                   local_eval=None) -> Partials:
        """Partials for one tuple block through this placement."""
        faults.fire("scan.eval")  # seam: before dispatch, state untouched
        self.blocks_evaluated += 1
        self.tuples_placed += int(block.num_normalized.shape[0])
        self.last_evaluator = self.evaluator_for(local_eval)
        fn = local_eval if local_eval is not None else eval_partials
        return fn(block.num_normalized, block.cat, block.measures, snippets)

    def stats(self) -> dict:
        """Operator-facing snapshot of the scan plane's placement."""
        return {
            "kind": self.kind,
            "n_shards": self.n_shards,
            "axis": self.axis,
            "blocks_evaluated": self.blocks_evaluated,
            "tuples_scanned": self.tuples_placed,
            "pad_rows": self.pad_rows,
            "evaluator": self.last_evaluator,
        }


class ShardedScanPlacement(ScanPlacement):
    """Tuple blocks sharded over a mesh axis via ``NamedSharding`` +
    ``jax.device_put``; evaluation through the masked, shape-agnostic
    ``eval_partials_sharded`` — any block size over any mesh size, bitwise
    equal to the local oracle."""

    kind = "sharded"

    def __init__(self, mesh, axis: str = "data"):
        super().__init__()
        self.mesh = mesh
        self.axis = axis

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    def describe(self) -> str:
        return f"sharded:{self.n_shards}x{self.axis}"

    def place(self, num_normalized, cat, valid):
        """Shard the (padded) tuple axis over the mesh devices.

        The single ``device_put`` call the multi-host extension will widen:
        with ``jax.process_count() > 1`` the same ``NamedSharding`` places
        per-process addressable shards from globally-consistent specs.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self.mesh, P(self.axis))
        return tuple(jax.device_put(x, sharding)
                     for x in (num_normalized, cat, valid))

    def evaluator_for(self, local_eval) -> str:
        """Sharded blocks always build the mask via shard_map; the kernel,
        when requested AND supported, runs the post-gather aggregation —
        never silently dropped without the name saying so."""
        if _kernel_agg_for(local_eval) is not None:
            return "sharded_mask+kernel_agg"
        return "sharded_mask+oracle_agg"

    def eval_block(self, block, snippets: SnippetBatch,
                   local_eval=None) -> Partials:
        faults.fire("scan.eval")  # same seam as the local placement
        t = int(block.num_normalized.shape[0])
        self.blocks_evaluated += 1
        self.tuples_placed += t
        self.pad_rows += padded_tuple_count(t, self.n_shards) - t
        self.last_evaluator = self.evaluator_for(local_eval)
        return eval_partials_sharded(
            self.mesh, self.axis,
            block.num_normalized, block.cat, block.measures, snippets,
            place_fn=self.place,
            agg_fn=_kernel_agg_for(local_eval),
        )


def scan_placement(mesh=None, axis: str = "data") -> ScanPlacement:
    """Build the placement for an optional mesh (the ``connect`` wiring)."""
    if mesh is None:
        return ScanPlacement()
    return ShardedScanPlacement(mesh, axis)


@partial(jax.jit, static_argnames=("exact",))
def estimates_from_partials(parts: Partials, snippets: SnippetBatch, exact: bool = False):
    """CLT raw answers (theta_i, beta_i^2) from accumulated partials.

    FREQ: p_hat = count/scanned, beta^2 = p(1-p)/scanned.
    AVG:  x_bar = sum/count,     beta^2 = sample_var/count.
    ``exact=True`` zeroes the errors (used for ground-truth evaluation).
    """
    scanned = jnp.maximum(parts.scanned, 1.0)
    cnt = parts.count
    p_hat = cnt / scanned
    freq_beta2 = p_hat * (1.0 - p_hat) / scanned

    safe_cnt = jnp.maximum(cnt, 1.0)
    mean = parts.sums / safe_cnt
    var = jnp.maximum(parts.sumsq / safe_cnt - mean * mean, 0.0)
    avg_beta2 = var / safe_cnt

    is_avg = snippets.agg == AVG
    theta = jnp.where(is_avg, mean, p_hat)
    beta2 = jnp.where(is_avg, avg_beta2, freq_beta2)
    no_support = is_avg & (cnt < 2)
    theta = jnp.where(no_support, 0.0, theta)
    beta2 = jnp.where(no_support, BIG_BETA2, beta2)
    if exact:
        beta2 = jnp.zeros_like(beta2)
    valid = ~no_support
    return theta, beta2, valid
