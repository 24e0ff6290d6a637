"""Public wrappers: padding, categorical pre-mask, Partials assembly."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_for
from repro.kernels.range_mask_agg.kernel import range_mask_agg_pallas


def _pad_axis(x, axis, mult, fill=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def range_mask_agg(x, payload, lo, hi, extra_mask=None,
                   *, tile_t: int = 512, tile_q: int = 128):
    """out[q, p] = sum over tuples matching snippet q of payload[t, p].
    Compiled on TPU, interpreted elsewhere."""
    return _range_mask_agg(x, payload, lo, hi, extra_mask, tile_t=tile_t,
                           tile_q=tile_q, interpret=interpret_for(x))


@partial(jax.jit, static_argnames=("tile_t", "tile_q", "interpret"))
def _range_mask_agg(x, payload, lo, hi, extra_mask,
                    *, tile_t: int, tile_q: int, interpret: bool):
    t_n, _ = x.shape
    q_n = lo.shape[0]
    dt = jnp.float32
    if extra_mask is None:
        extra_mask = jnp.ones((t_n, q_n), dt)
    # Padded tuples are masked off via extra_mask=0 (so the count column stays
    # exact); padded snippets are sliced away after the call.
    x_p = _pad_axis(x.astype(dt), 0, tile_t)
    payload_p = _pad_axis(payload.astype(dt), 0, tile_t)
    lo_p = _pad_axis(lo.astype(dt), 0, tile_q)
    hi_p = _pad_axis(hi.astype(dt), 0, tile_q, fill=1.0)
    em = _pad_axis(_pad_axis(extra_mask.astype(dt), 0, tile_t), 1, tile_q)
    out = range_mask_agg_pallas(
        x_p, payload_p, lo_p, hi_p, em,
        tile_t=tile_t, tile_q=tile_q, interpret=interpret,
    )
    return out[:q_n]


def categorical_premask(cat_codes, snip_cat):
    """(T, Q) mask of categorical-membership, one-hot matmul per cat dim.

    cat_codes: (T, c) int; snip_cat: (Q, c, V) bool.
    """
    t_n = cat_codes.shape[0]
    q_n = snip_cat.shape[0]
    mask = jnp.ones((t_n, q_n), jnp.float32)
    for k in range(cat_codes.shape[1]):
        onehot = jax.nn.one_hot(cat_codes[:, k], snip_cat.shape[2], dtype=jnp.float32)
        mask = mask * (onehot @ snip_cat[:, k, :].T.astype(jnp.float32))
    return mask


@jax.jit
def eval_partials_kernel(num_normalized, cat, measures, snippets, valid=None):
    """Kernel-backed drop-in for ``repro.aqp.executor.eval_partials``.

    ``valid``: optional (T,) 0/1 per-tuple validity mask for zero-padded
    blocks. Invalid rows are zeroed out of every snippet column and
    ``scanned`` is the mask sum — the TRUE tuple count, never the padded
    shape (reporting ``float(t_n)`` here deflated every CLT error bound on
    padded blocks).
    """
    from repro.aqp.executor import Partials

    t_n, m = measures.shape
    meas32 = measures.astype(jnp.float32)
    payload = jnp.concatenate(
        [meas32, meas32 * meas32, jnp.ones((t_n, 1), jnp.float32)], axis=1
    )  # (T, 2M+1)
    extra = categorical_premask(cat, snippets.cat) if cat.shape[1] else None
    scanned = (jnp.asarray(float(t_n)) if valid is None else jnp.sum(valid))
    if valid is not None:
        v = valid.astype(jnp.float32)[:, None]
        extra = v * jnp.ones((t_n, snippets.lo.shape[0]), jnp.float32) \
            if extra is None else extra * v
    out = range_mask_agg(
        num_normalized, payload, snippets.lo, snippets.hi, extra
    ).astype(jnp.float64)  # (Q, 2M+1)
    idx = snippets.measure[:, None]
    sums = jnp.take_along_axis(out[:, :m], idx, axis=1)[:, 0]
    sumsq = jnp.take_along_axis(out[:, m : 2 * m], idx, axis=1)[:, 0]
    count = out[:, 2 * m]
    return Partials(sums, sumsq, count, scanned)
