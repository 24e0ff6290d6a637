"""Relations made on the device from a seed.

One jitted call per relation makes every column in one pass, so a run's
set-up neither builds tens of millions of rows on the host nor copies them
to the chip. The distributions are those of the repository's own
generators in ``repro/aqp/workload.py`` (``tpch_like`` and
``make_relation``), copied here so that a change to the program cannot
change the benchmark's data:

- ``tpch_lineitem``: ship date, quantity and discount uniform on their
  domains; return flag, line status and nation uniform codes; a seasonal
  extended price scaled by a per-nation multiplier and the quantity, with
  Gaussian noise; revenue = price * (1 - discount).
- ``smooth_field``: uniform numeric columns and each measure
  ``10 + 2 * f(x) + noise``, where ``f`` is a random-Fourier-feature draw of
  a Gaussian process with a squared-exponential kernel of the configured
  lengthscale (paper section 8.6, Figure 6(a)).

Values are drawn in float32 and widened to the float64 columns the engine
stores. The same seed gives the same bits on the same device, which is what
lets the reference make the rows again once the program's state is freed.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def jax_key(seed: int):
    """A threefry key from any non-negative whole number (wider than the
    32 bits ``jax.random.key`` takes)."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def _tpch_lineitem(key, rows: int, data: dict):
    import jax
    import jax.numpy as jnp

    k = [jax.random.fold_in(key, i) for i in range(9)]
    f32 = jnp.float32
    date = jax.random.uniform(k[0], (rows,), f32, 0.0, 2557.0)
    qty = jax.random.uniform(k[1], (rows,), f32, 1.0, 50.0)
    disc = jax.random.uniform(k[2], (rows,), f32, 0.0, 0.1)
    rf = jax.random.randint(k[3], (rows,), 0, 3, jnp.int32)
    ls = jax.random.randint(k[4], (rows,), 0, 2, jnp.int32)
    nation = jax.random.randint(k[5], (rows,), 0, 25, jnp.int32)
    season = (1.0 + 0.3 * jnp.sin(2 * np.pi * date / 365.0)
              + 0.1 * (date / 2557.0))
    nation_mult = jax.random.uniform(k[6], (25,), f32, 0.7, 1.3)
    price = (900.0 * season * nation_mult[nation] * (qty / 25.0)
             + 40.0 * jax.random.normal(k[7], (rows,), f32))
    revenue = price * (1 - disc)
    num = jnp.stack([date, qty, disc], axis=1)
    cat = jnp.stack([rf, ls, nation], axis=1)
    meas = jnp.stack([price, revenue], axis=1)
    return num, cat, meas


def _smooth_field(key, rows: int, data: dict):
    import jax
    import jax.numpy as jnp

    params = data["params"]
    n_num = len(data["numeric"])
    n_meas = len(data["measures"])
    n_feat = int(params["features"])
    ls = float(params["lengthscale"])
    f32 = jnp.float32
    lo = jnp.asarray([c["lo"] for c in data["numeric"]], f32)
    hi = jnp.asarray([c["hi"] for c in data["numeric"]], f32)
    x = lo + (hi - lo) * jax.random.uniform(
        jax.random.fold_in(key, 0), (rows, n_num), f32)
    x_norm = (x - lo) / (hi - lo)
    cols = []
    for m in range(n_meas):
        km = jax.random.fold_in(key, 1 + m)
        omega = jax.random.normal(jax.random.fold_in(km, 0),
                                  (n_feat, n_num), f32) / ls
        phase = jax.random.uniform(jax.random.fold_in(km, 1), (n_feat,),
                                   f32, 0.0, 2 * np.pi)

        # One feature at a time: the (rows, features) projection of the
        # NumPy original would not fit on the chip.
        def feature(j, acc, omega=omega, phase=phase):
            proj = jnp.sum(x_norm * omega[j], axis=1)  # no MXU rounding
            return acc + jnp.cos(proj + phase[j])

        field = jax.lax.fori_loop(0, n_feat, feature,
                                  jnp.zeros((rows,), f32))
        field = np.sqrt(2.0 / n_feat) * field
        noise = float(params["noise"]) * jax.random.normal(
            jax.random.fold_in(km, 2), (rows,), f32)
        cols.append(10.0 + 2.0 * field + noise)
    meas = jnp.stack(cols, axis=1)
    cat = jnp.zeros((rows, 0), jnp.int32)
    return x, cat, meas


GENERATORS = {"tpch_lineitem": _tpch_lineitem, "smooth_field": _smooth_field}


def make_columns(data: dict, seed: int, rows: int, device=None):
    """``(num f64 (N, l), cat int32 (N, c), measures f64 (N, m))`` on
    ``device`` (the default device when None), made in one jitted call."""
    import jax
    import jax.numpy as jnp

    # The engine stores float64 columns (it runs with x64 on, as this does).
    jax.config.update("jax_enable_x64", True)
    gen = GENERATORS[data["generator"]]

    def make(key):
        num, cat, meas = gen(key, rows, data)
        return num.astype(jnp.float64), cat, meas.astype(jnp.float64)

    key = jax_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    out = jax.jit(make)(key)
    return jax.block_until_ready(out)


def schema_arrays(data: dict) -> Dict[str, Tuple]:
    """The column layout a configuration states, as plain tuples."""
    return {
        "num_names": tuple(c["name"] for c in data["numeric"]),
        "num_lo": tuple(float(c["lo"]) for c in data["numeric"]),
        "num_hi": tuple(float(c["hi"]) for c in data["numeric"]),
        "cat_names": tuple(c["name"] for c in data["categorical"]),
        "cat_sizes": tuple(int(c["size"]) for c in data["categorical"]),
        "measure_names": tuple(data["measures"]),
    }
