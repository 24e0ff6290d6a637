"""Blocked SE double-integral covariance kernel (TPU Pallas).

Grid: (n_i / TI, n_j / TJ). Each program instance holds a (TI, l) and (TJ, l)
tile of pre-widened predicate ranges in VMEM plus the (l,) lengthscales, and
emits a (TI, TJ) covariance tile:

    out[a, b] = sigma2 / (norm_i[a] * norm_j[b])
                * prod_k II(lo_i[a,k], hi_i[a,k], lo_j[b,k], hi_j[b,k]; ls[k])

The per-dimension closed form needs exp and erf only — both VPU-native.
The k-loop is a static Python loop (l is small), so the whole tile stays in
registers/VMEM; arithmetic intensity is O(l) per output element, making the
kernel compute-bound for l >= 3 (see DESIGN.md roofline notes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import block_spec
from jax.scipy.special import erf

SQRT_PI = 1.7724538509055159


def _antideriv(u, z):
    return -0.5 * z * z * jnp.exp(-((u / z) ** 2)) - 0.5 * SQRT_PI * z * u * erf(u / z)


def _integral(a, b, c, d, z):
    return _antideriv(b - d, z) - _antideriv(b - c, z) - _antideriv(a - d, z) + _antideriv(a - c, z)


def _se_cov_kernel(lo_i_ref, hi_i_ref, lo_j_ref, hi_j_ref, ls_ref, ni_ref, nj_ref,
                   sigma2_ref, out_ref, *, n_dims: int):
    acc = None
    for k in range(n_dims):
        a = lo_i_ref[:, k][:, None]  # (TI, 1)
        b = hi_i_ref[:, k][:, None]
        c = lo_j_ref[:, k][None, :]  # (1, TJ)
        d = hi_j_ref[:, k][None, :]
        z = ls_ref[k]
        g = jnp.maximum(_integral(a, b, c, d, z), 0.0)  # (TI, TJ)
        acc = g if acc is None else acc * g
    if acc is None:  # zero numeric dims: pure categorical schema
        acc = jnp.ones_like(out_ref[...])
    scale = sigma2_ref[0] / (ni_ref[:][:, None] * nj_ref[:][None, :])
    out_ref[...] = acc * scale


def se_cov_pallas(lo_i, hi_i, lo_j, hi_j, ls, sigma2, norm_i, norm_j,
                  *, tile_i: int = 128, tile_j: int = 128, interpret: bool):
    """Raw pallas_call; inputs must be pre-padded to tile multiples (see ops)."""
    n_i, l = lo_i.shape
    n_j = lo_j.shape[0]
    assert n_i % tile_i == 0 and n_j % tile_j == 0
    grid = (n_i // tile_i, n_j // tile_j)
    kern = functools.partial(_se_cov_kernel, n_dims=l)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            block_spec((tile_i, l), lambda i, j: (i, 0)),  # lo_i
            block_spec((tile_i, l), lambda i, j: (i, 0)),  # hi_i
            block_spec((tile_j, l), lambda i, j: (j, 0)),  # lo_j
            block_spec((tile_j, l), lambda i, j: (j, 0)),  # hi_j
            block_spec((l,), lambda i, j: (0,)),  # ls
            block_spec((tile_i,), lambda i, j: (i,)),  # norm_i
            block_spec((tile_j,), lambda i, j: (j,)),  # norm_j
            block_spec((1,), lambda i, j: (0,)),  # sigma2
        ],
        out_specs=block_spec((tile_i, tile_j), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_i, n_j), lo_i.dtype),
        interpret=interpret,
    )(lo_i, hi_i, lo_j, hi_j, ls, norm_i, norm_j, sigma2)
