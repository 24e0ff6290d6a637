"""Public jit'd wrapper for the fused improved-answer kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_for
from repro.kernels.gp_batch_infer.kernel import gp_batch_infer_pallas


def _pad1(x, mult, fill=0.0):
    pad = (-x.shape[0]) % mult
    return x if pad == 0 else jnp.concatenate(
        [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])


@partial(jax.jit, static_argnames=("tile_q", "tile_c", "interpret"))
def _gp_batch_infer(k_mat, sigma_inv, alpha, kappa2, mu_new, raw_theta,
                    raw_beta2, *, tile_q: int, tile_c: int, interpret: bool):
    q_n, c_n = k_mat.shape
    dt = jnp.float32
    k_p = _pad1(k_mat.astype(dt), tile_q)
    k_p = jnp.pad(k_p, ((0, 0), (0, (-c_n) % tile_c)))
    s_p = jnp.pad(sigma_inv.astype(dt),
                  ((0, (-c_n) % tile_c), (0, (-c_n) % tile_c)))
    a_p = _pad1(alpha.astype(dt), tile_c)[None, :]

    def col(x, fill=0.0):
        return _pad1(x.astype(dt), tile_q, fill=fill)[:, None]

    theta, beta2, gamma2 = gp_batch_infer_pallas(
        k_p, s_p, a_p, col(kappa2, 1.0), col(mu_new), col(raw_theta),
        col(raw_beta2, 1.0),
        tile_q=tile_q, tile_c=tile_c, interpret=interpret,
    )
    return theta[:q_n, 0], beta2[:q_n, 0], gamma2[:q_n, 0]


def gp_batch_infer(k_mat, sigma_inv, alpha, kappa2, mu_new, raw_theta,
                   raw_beta2, *, tile_q: int = 128, tile_c: int = 128):
    """(theta_dd, beta2_dd, gamma2) for Q new snippets; f32 on the MXU.

    Zero-padding C is exact (zero K columns/Sinv blocks contribute nothing);
    padded Q rows are sliced away. Compiled on TPU, interpreted elsewhere
    (``repro.kernels.interpret_for``).
    """
    return _gp_batch_infer(k_mat, sigma_inv, alpha, kappa2, mu_new,
                           raw_theta, raw_beta2, tile_q=tile_q,
                           tile_c=tile_c, interpret=interpret_for(sigma_inv))
