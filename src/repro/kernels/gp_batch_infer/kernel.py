"""Fused improved-answer kernel (TPU Pallas) — query-time inference, Eq. 11/12.

For Q new snippets against a synopsis of C past snippets:

    gamma2[q] = kappa2[q] - K[q,:] @ Sigma^{-1} @ K[q,:]^T
    prior[q]  = mu[q] + K[q,:] @ alpha
    theta[q]  = (beta2[q]·prior + gamma2·raw) / (beta2 + gamma2)
    beta2'[q] = beta2[q]·gamma2 / (beta2 + gamma2)

Grid: (Q/TQ, C/TC, C/TC). The quadratic form streams Sigma^{-1} tiles through
VMEM once (the dominant traffic, C^2 floats); per (c1, c2) step a
(TQ, TC)·(TC, TC) matmul runs on the MXU and a row-sum folds into a VMEM
scratch accumulator. The Eq. 12 blend is fused into the final grid step, so
improved answers never round-trip through HBM — this is how the paper's
"negligible overhead" property is kept at serving batch sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import block_spec

GAMMA_FLOOR = 1e-30


def _gp_kernel(k1_ref, k2_ref, sinv_ref, alpha_ref, kappa2_ref, mu_ref,
               rawt_ref, rawb_ref, theta_ref, beta2_ref, gamma2_ref,
               gacc, tacc):
    c1 = pl.program_id(1)
    c2 = pl.program_id(2)
    nc1 = pl.num_programs(1)
    nc2 = pl.num_programs(2)

    @pl.when((c1 == 0) & (c2 == 0))
    def _zero():
        gacc[...] = jnp.zeros_like(gacc)
        tacc[...] = jnp.zeros_like(tacc)

    p = jax.lax.dot_general(
        k1_ref[...], sinv_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (TQ, TC2)
    gacc[...] = gacc[...] + jnp.sum(p * k2_ref[...], axis=1, keepdims=True)

    @pl.when(c2 == 0)
    def _theta_acc():
        # k1 @ alpha as a lane reduction: alpha is a (1, TC) row.
        tacc[...] = tacc[...] + jnp.sum(k1_ref[...] * alpha_ref[...], axis=1,
                                        keepdims=True)

    @pl.when((c1 == nc1 - 1) & (c2 == nc2 - 1))
    def _finalize():
        gamma2 = jnp.maximum(kappa2_ref[...] - gacc[...], GAMMA_FLOOR)
        prior = mu_ref[...] + tacc[...]
        rawb = rawb_ref[...]
        rawt = rawt_ref[...]
        denom = rawb + gamma2
        theta = (rawb * prior + gamma2 * rawt) / denom
        beta2 = rawb * gamma2 / denom
        exact = rawb <= 0.0
        theta_ref[...] = jnp.where(exact, rawt, theta)
        beta2_ref[...] = jnp.where(exact, 0.0, beta2)
        gamma2_ref[...] = gamma2


def gp_batch_infer_pallas(k_mat, sigma_inv, alpha, kappa2, mu_new, raw_theta,
                          raw_beta2, *, tile_q: int = 128, tile_c: int = 128,
                          interpret: bool):
    """Raw pallas_call; Q and C must be pre-padded to tile multiples.

    Per-snippet vectors are (Q, 1) columns and ``alpha`` a (1, C) row:
    Mosaic tiles a 1-D f32 operand at 1024 lanes, so a 1-D block of 128
    does not match its layout.
    """
    q_n, c_n = k_mat.shape
    assert q_n % tile_q == 0 and c_n % tile_c == 0
    grid = (q_n // tile_q, c_n // tile_c, c_n // tile_c)
    dt = k_mat.dtype
    col = block_spec((tile_q, 1), lambda q, c1, c2: (q, 0))
    return pl.pallas_call(
        _gp_kernel,
        grid=grid,
        in_specs=[
            block_spec((tile_q, tile_c), lambda q, c1, c2: (q, c1)),  # K (c1)
            block_spec((tile_q, tile_c), lambda q, c1, c2: (q, c2)),  # K (c2)
            block_spec((tile_c, tile_c), lambda q, c1, c2: (c1, c2)),  # Sinv
            block_spec((1, tile_c), lambda q, c1, c2: (0, c1)),  # alpha
            col,  # kappa2
            col,  # mu
            col,  # raw theta
            col,  # raw beta2
        ],
        out_specs=[col, col, col],
        out_shape=[jax.ShapeDtypeStruct((q_n, 1), dt)] * 3,
        scratch_shapes=[
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(k_mat, k_mat, sigma_inv, alpha, kappa2, mu_new, raw_theta, raw_beta2)
