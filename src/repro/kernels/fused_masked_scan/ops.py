"""Public wrappers: padding, dtype policy, Partials epilogue.

``eval_partials_fused`` is the kernel-backed drop-in for
``repro.aqp.executor.eval_partials`` — same signature including ``valid=``,
same ``Partials`` out, and (interpret mode, f64) the SAME bits: the kernel's
sequential tuple-tile accumulation is the scan plane's canonical fixed-order
fold (``masked_tile_fold``), which ``_partials_from_mask`` also performs.

``masked_partials_fused`` is the aggregation-only drop-in for
``_partials_from_mask`` used by the sharded placement: the mask is built
sharded over the mesh, gathered, and reduced here through the kernel — the
composition that makes ``use_kernels=True`` meaningful under a mesh.

Dtype policy: interpret mode (operands on the CPU) runs f64 end to end —
that is the configuration the bitwise gate pins.  Compiled on a TPU, inputs
are cast to f32 (the MXU has no f64 path) and parity degrades to an f32
tolerance; see ``repro.kernels`` docstring.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import SCAN_TILE_Q, SCAN_TILE_T, interpret_for
from repro.kernels.fused_masked_scan.kernel import (
    fused_masked_scan_pallas,
    masked_partials_pallas,
)

TILE_Q = SCAN_TILE_Q  # snippet-axis tile; SNIPPET_TILE batches use 1 tile


def _pad_rows(x, mult, fill=0.0):
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def _payload(measures, dt):
    t_n = measures.shape[0]
    meas = measures.astype(dt)
    return jnp.concatenate(
        [meas, meas * meas, jnp.ones((t_n, 1), dt)], axis=1)  # (T, 2M+1)


def _epilogue(out, snippets, m, scanned):
    """(Q, 2M+1) kernel accumulator -> Partials (f64, oracle layout)."""
    from repro.aqp.executor import Partials

    out = out.astype(jnp.float64)
    idx = snippets.measure[:, None]
    sums = jnp.take_along_axis(out[:, :m], idx, axis=1)[:, 0]
    sumsq = jnp.take_along_axis(out[:, m:2 * m], idx, axis=1)[:, 0]
    return Partials(sums, sumsq, out[:, 2 * m], scanned)


def eval_partials_fused(num_normalized, cat, measures, snippets, valid=None,
                        *, tile_t: int = SCAN_TILE_T, tile_q: int = TILE_Q):
    """Fused-kernel partials for one tuple block (drop-in for
    ``eval_partials``; bitwise-equal to it in interpret mode).

    ``valid``: optional (T,) 0/1 validity mask for zero-padded blocks —
    invalid rows contribute exactly nothing and ``scanned`` is the mask sum
    (the TRUE tuple count), matching the oracle's contract exactly.
    """
    return _eval_partials_fused(
        num_normalized, cat, measures, snippets, valid, tile_t=tile_t,
        tile_q=tile_q, interpret=interpret_for(num_normalized))


@partial(jax.jit, static_argnames=("tile_t", "tile_q", "interpret"))
def _eval_partials_fused(num_normalized, cat, measures, snippets, valid,
                         *, tile_t: int, tile_q: int, interpret: bool):
    dt = jnp.float64 if interpret else jnp.float32
    t_n, m = measures.shape
    q_n = snippets.lo.shape[0]
    scanned = (jnp.asarray(float(t_n)) if valid is None
               else jnp.sum(valid))
    if valid is None:
        valid = jnp.ones((t_n,), dt)
    # Tuple-axis padding: zero rows with valid=0 — their mask rows are exact
    # 0.0, so they add exact-zero partials (the fold is padding-oblivious).
    x_p = _pad_rows(num_normalized.astype(dt), tile_t)
    valid_p = _pad_rows(valid.astype(dt), tile_t)[:, None]
    payload_p = _pad_rows(_payload(measures, dt), tile_t)
    c = cat.shape[1] if cat.ndim == 2 else 0
    if c:
        codes_p = _pad_rows(cat.astype(jnp.int32), tile_t)
        snip_cat = snippets.cat.astype(dt).reshape(q_n, -1)  # (Q, C*V)
    else:
        # Cat-free schema: one dummy all-member dim keeps the kernel
        # signature static (code 0 is always a member of the {0} set).
        codes_p = jnp.zeros((x_p.shape[0], 1), jnp.int32)
        snip_cat = jnp.ones((q_n, 1), dt)
    # Snippet-axis padding: full-domain rows, sliced away after the call.
    lo_p = _pad_rows(snippets.lo.astype(dt), tile_q)
    hi_p = _pad_rows(snippets.hi.astype(dt), tile_q, fill=1.0)
    cat_p = _pad_rows(snip_cat, tile_q, fill=1.0)
    out = fused_masked_scan_pallas(
        x_p, codes_p, valid_p, payload_p, lo_p, hi_p, cat_p,
        tile_t=tile_t, tile_q=tile_q, interpret=interpret,
    )[:q_n]
    return _epilogue(out, snippets, m, scanned)


def masked_partials_fused(mask, measures, snippets, scanned,
                          *, tile_t: int = SCAN_TILE_T, tile_q: int = TILE_Q):
    """Kernel-backed drop-in for ``_partials_from_mask``: fold a pre-built
    (T, Q) predicate mask (e.g. gathered from the sharded mask build)
    against [measures, measures^2, 1] in the canonical tile order."""
    return _masked_partials_fused(mask, measures, snippets, scanned,
                                  tile_t=tile_t, tile_q=tile_q,
                                  interpret=interpret_for(mask))


@partial(jax.jit, static_argnames=("tile_t", "tile_q", "interpret"))
def _masked_partials_fused(mask, measures, snippets, scanned,
                           *, tile_t: int, tile_q: int, interpret: bool):
    dt = jnp.float64 if interpret else jnp.float32
    t_n, m = measures.shape
    q_n = mask.shape[1]
    mask_p = _pad_rows(mask.astype(dt), tile_t)
    mask_p = jnp.pad(mask_p, ((0, 0), (0, (-q_n) % tile_q)))
    payload_p = _pad_rows(_payload(measures, dt), tile_t)
    out = masked_partials_pallas(
        mask_p, payload_p, tile_t=tile_t, tile_q=tile_q, interpret=interpret,
    )[:q_n]
    return _epilogue(out, snippets, m, scanned)
