"""Fused masked-scan kernel (TPU Pallas) — the whole scan hot path in one pass.

Replaces the three-piece hot path (jnp ``predicate_mask`` compare, separate
``mask^T @ payload`` matmul, and the partial-coverage ``range_mask_agg``
kernel) with ONE kernel that streams relation tiles through VMEM exactly
once.  Per (snippet-tile q, tuple-tile t) grid step:

  1. range compare (VPU): ``lo - RANGE_EPS <= x <= hi + RANGE_EPS`` over all
     numeric dims — the SAME shared epsilon the jnp oracle uses;
  2. categorical membership (MXU): one-hot(codes) @ snip_cat_k^T per cat dim
     — exactly 0.0/1.0, bit-identical to the oracle's ``jnp.take`` gather;
  3. per-tuple validity mask: padding rows multiply to exact 0.0;
  4. partials accumulation (MXU): ``mask^T @ [measures, measures^2, 1]``,
     accumulated over the sequential tuple-tile axis.

Bitwise parity by construction: the tuple axis is the sequential grid axis,
so the accumulator performs a FIXED ascending-tile-order fold of
(SCAN_TILE_T x tile_q) dot partials — the same fold
``repro.aqp.executor._partials_from_mask`` performs (same dot shapes, same
order, f64 in interpret mode), so kernel partials equal the jnp oracle bit
for bit.  Column (snippet) tiling is bitwise-free: each output column's
reduction over tuples is independent of its siblings.

``_mpa_kernel`` is the aggregation-only variant for the sharded placement:
the predicate mask is built sharded (``shard_map`` over the mesh), gathered,
and fed here pre-built — the same accumulation body, hence the same bits,
which is what lets ``use_kernels=True`` compose with a mesh.

Grid: (Q / TQ, T / TT); out block indexed by q only, initialized at t == 0.
HBM traffic is O(T·(L+C+P)) — each relation tile is read once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import RANGE_EPS, block_spec


def _range_cat_mask(x, codes, lo_ref, hi_ref, cat_ref, valid_ref,
                    *, n_dims: int, n_cat: int, vmax: int):
    """(TT, TQ) validity-masked predicate mask, exact 0.0/1.0 entries."""
    dt = x.dtype
    mask = None
    for k in range(n_dims):
        xk = x[:, k][:, None]  # (TT, 1)
        mk = ((xk >= lo_ref[:, k][None, :] - RANGE_EPS)
              & (xk <= hi_ref[:, k][None, :] + RANGE_EPS))
        mask = mk if mask is None else (mask & mk)
    for k in range(n_cat):
        # one-hot(codes_k) @ snip_cat_k^T: exactly 1.0 iff the tuple's code
        # is a member of snippet q's category set (one 1-entry per row).
        onehot = (codes[:, k][:, None]
                  == jax.lax.broadcasted_iota(jnp.int32,
                                              (x.shape[0], vmax), 1))
        catk = cat_ref[:, k * vmax:(k + 1) * vmax]  # (TQ, V) 0/1
        member = jax.lax.dot_general(
            onehot.astype(dt), catk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=dt,
        )  # (TT, TQ)
        mk = member > 0.5
        mask = mk if mask is None else (mask & mk)
    if mask is None:  # no predicate dims at all: every tuple matches
        m = jnp.ones((x.shape[0], lo_ref.shape[0]), dt)
    else:
        m = mask.astype(dt)
    return m * valid_ref[...]  # (TT, TQ) * (TT, 1)


def _fold_dot(mask, payload, dt):
    """mask^T @ payload for one (TT, TQ) x (TT, P) tile. HIGHEST precision:
    the TPU MXU would otherwise round the f32 measures to bf16."""
    return jax.lax.dot_general(
        mask, payload,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=dt,
    )


def _accumulate(acc, out_ref, t):
    @pl.when(t == 0)
    def _init():
        out_ref[...] = acc.astype(out_ref.dtype)

    @pl.when(t != 0)
    def _accum():
        out_ref[...] = out_ref[...] + acc.astype(out_ref.dtype)


def _fms_kernel(x_ref, codes_ref, valid_ref, payload_ref, lo_ref, hi_ref,
                cat_ref, out_ref, *, n_dims: int, n_cat: int, vmax: int):
    t = pl.program_id(1)
    m = _range_cat_mask(x_ref[...], codes_ref[...], lo_ref, hi_ref, cat_ref,
                        valid_ref, n_dims=n_dims, n_cat=n_cat, vmax=vmax)
    acc = _fold_dot(m, payload_ref[...], out_ref.dtype)  # (TQ, P)
    _accumulate(acc, out_ref, t)


def fused_masked_scan_pallas(x, codes, valid, payload, lo, hi, cat,
                             *, tile_t: int, tile_q: int, interpret: bool):
    """Raw pallas_call; T and Q must be pre-padded to tile multiples.

    x: (T, L) normalized numerics; codes: (T, C) int32 category codes
    (C >= 1 — wrappers pass a zero dummy column for cat-free schemas);
    valid: (T, 1); payload: (T, P); lo/hi: (Q, L); cat: (Q, C*V) 0/1.
    Accumulator dtype follows the payload dtype (f64 interpret / f32 TPU).
    """
    t_n, l = x.shape
    q_n = lo.shape[0]
    p = payload.shape[1]
    c = codes.shape[1]
    vmax = cat.shape[1] // max(c, 1)
    assert t_n % tile_t == 0 and q_n % tile_q == 0
    grid = (q_n // tile_q, t_n // tile_t)
    kern = functools.partial(_fms_kernel, n_dims=l, n_cat=c, vmax=vmax)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            block_spec((tile_t, l), lambda q, t: (t, 0)),  # x
            block_spec((tile_t, c), lambda q, t: (t, 0)),  # codes
            block_spec((tile_t, 1), lambda q, t: (t, 0)),  # valid
            block_spec((tile_t, p), lambda q, t: (t, 0)),  # payload
            block_spec((tile_q, l), lambda q, t: (q, 0)),  # lo
            block_spec((tile_q, l), lambda q, t: (q, 0)),  # hi
            block_spec((tile_q, cat.shape[1]), lambda q, t: (q, 0)),  # cat
        ],
        out_specs=block_spec((tile_q, p), lambda q, t: (q, 0)),
        out_shape=jax.ShapeDtypeStruct((q_n, p), payload.dtype),
        interpret=interpret,
    )(x, codes, valid, payload, lo, hi, cat)


def _mpa_kernel(mask_ref, payload_ref, out_ref):
    t = pl.program_id(1)
    acc = _fold_dot(mask_ref[...], payload_ref[...], out_ref.dtype)
    _accumulate(acc, out_ref, t)


def masked_partials_pallas(mask, payload, *, tile_t: int, tile_q: int,
                           interpret: bool):
    """Aggregation-only entry: a pre-built (T, Q) mask (e.g. gathered from a
    sharded mask build) folded against the payload in the SAME fixed tile
    order as the fused kernel — the mesh-composition path of the scan."""
    t_n, q_n = mask.shape
    p = payload.shape[1]
    assert t_n % tile_t == 0 and q_n % tile_q == 0
    grid = (q_n // tile_q, t_n // tile_t)
    return pl.pallas_call(
        _mpa_kernel,
        grid=grid,
        in_specs=[
            block_spec((tile_t, tile_q), lambda q, t: (t, q)),  # mask
            block_spec((tile_t, p), lambda q, t: (t, 0)),  # payload
        ],
        out_specs=block_spec((tile_q, p), lambda q, t: (q, 0)),
        out_shape=jax.ShapeDtypeStruct((q_n, p), payload.dtype),
        interpret=interpret,
    )(mask, payload)
