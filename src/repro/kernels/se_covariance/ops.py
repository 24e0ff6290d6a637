"""Public jit'd wrapper for the SE-covariance kernel: padding + epilogue."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_for
from repro.kernels.se_covariance.kernel import se_cov_pallas


def _pad_rows(x, mult, fill):
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    fill_arr = jnp.full((pad,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, fill_arr], axis=0)


def se_cov_matrix(lo_i, hi_i, lo_j, hi_j, ls, sigma2, norm_i, norm_j,
                  *, tile_i: int = 128, tile_j: int = 128):
    """sigma2 * prod_k II_k / (norm_i norm_j) as an (n_i, n_j) matrix.

    Pads both snippet batches to tile multiples (padding rows use unit-width
    ranges and norm=1 so they are numerically benign), runs the Pallas kernel,
    slices the result back. Compiled on TPU, interpreted elsewhere.
    """
    return _se_cov_matrix(lo_i, hi_i, lo_j, hi_j, ls, sigma2, norm_i, norm_j,
                          tile_i=tile_i, tile_j=tile_j,
                          interpret=interpret_for(lo_i))


@partial(jax.jit, static_argnames=("tile_i", "tile_j", "interpret"))
def _se_cov_matrix(lo_i, hi_i, lo_j, hi_j, ls, sigma2, norm_i, norm_j,
                   *, tile_i: int, tile_j: int, interpret: bool):
    n_i, n_j = lo_i.shape[0], lo_j.shape[0]
    dt = jnp.float32 if lo_i.dtype == jnp.float32 else lo_i.dtype
    args_i = [_pad_rows(x.astype(dt), tile_i, f) for x, f in
              ((lo_i, 0.0), (hi_i, 1.0))]
    args_j = [_pad_rows(x.astype(dt), tile_j, f) for x, f in
              ((lo_j, 0.0), (hi_j, 1.0))]
    ni = _pad_rows(norm_i.astype(dt), tile_i, 1.0)
    nj = _pad_rows(norm_j.astype(dt), tile_j, 1.0)
    out = se_cov_pallas(
        args_i[0], args_i[1], args_j[0], args_j[1],
        ls.astype(dt), jnp.asarray([sigma2], dt), ni, nj,
        tile_i=tile_i, tile_j=tile_j, interpret=interpret,
    )
    return out[:n_i, :n_j]
