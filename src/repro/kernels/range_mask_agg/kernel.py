"""Predicate-mask aggregation kernel (TPU Pallas) — the AQP scan hot loop.

TPU adaptation of the paper's Spark tuple scan: instead of evaluating snippets
tuple-at-a-time, a (TT x TQ) 0/1 predicate mask is materialized in VMEM with
vectorized range compares (VPU), then ``mask^T @ payload`` runs on the MXU,
aggregating *all concurrent snippets* in one matmul. payload packs
[measures, measures^2, 1] so sum/sumsq/count come out of a single pass.

Grid: (Q / TQ, T / TT); the tuple axis is the sequential accumulation axis
(out block indexed by q only; initialized at t == 0). Tuples stream through
VMEM tile by tile — HBM traffic is O(T·(L+P)) and compute O(T·Q·(L+P)), so
for Q snippets in flight the scan is Q-fold work-shared vs. one-at-a-time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import RANGE_EPS, block_spec


def _rma_kernel(x_ref, payload_ref, lo_ref, hi_ref, em_ref, out_ref, *, n_dims: int):
    t = pl.program_id(1)
    x = x_ref[...]  # (TT, L)
    mask = None
    for k in range(n_dims):
        xk = x[:, k][:, None]  # (TT, 1)
        mk = ((xk >= lo_ref[:, k][None, :] - RANGE_EPS)
              & (xk <= hi_ref[:, k][None, :] + RANGE_EPS))
        mask = mk if mask is None else (mask & mk)
    m = em_ref[...] if mask is None else mask.astype(x.dtype) * em_ref[...]
    acc = jax.lax.dot_general(
        m, payload_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (TQ, P)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = acc.astype(out_ref.dtype)

    @pl.when(t != 0)
    def _accum():
        out_ref[...] = out_ref[...] + acc.astype(out_ref.dtype)


def range_mask_agg_pallas(x, payload, lo, hi, extra_mask,
                          *, tile_t: int = 512, tile_q: int = 128,
                          interpret: bool):
    """Raw pallas_call; T and Q must be pre-padded to tile multiples."""
    t_n, l = x.shape
    q_n = lo.shape[0]
    p = payload.shape[1]
    assert t_n % tile_t == 0 and q_n % tile_q == 0
    grid = (q_n // tile_q, t_n // tile_t)
    kern = functools.partial(_rma_kernel, n_dims=l)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            block_spec((tile_t, l), lambda q, t: (t, 0)),  # x
            block_spec((tile_t, p), lambda q, t: (t, 0)),  # payload
            block_spec((tile_q, l), lambda q, t: (q, 0)),  # lo
            block_spec((tile_q, l), lambda q, t: (q, 0)),  # hi
            block_spec((tile_t, tile_q), lambda q, t: (t, q)),  # extra mask
        ],
        out_specs=block_spec((tile_q, p), lambda q, t: (q, 0)),
        out_shape=jax.ShapeDtypeStruct((q_n, p), jnp.float32),
        interpret=interpret,
    )(x, payload, lo, hi, extra_mask)
