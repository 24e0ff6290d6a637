"""Kernel micro-benchmarks: us/call for the jnp oracle path (the CPU-real
number) and interpret-mode kernel validation timing (correctness path; TPU
wall-time comes from the dry-run roofline, not this container).

``scan_metrics`` is the CI-gated subset for the fused masked-scan kernel:
a bitwise-parity flag and a machine-portable roofline fraction (deterministic
BlockSpec traffic arithmetic — never wall-clock)."""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

TARGET_KIND = "TPU v5 lite"  # device the traffic model is priced against


def _timeit(fn, *args, reps=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else None
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    rows = []
    rng = np.random.default_rng(0)

    # se_covariance oracle at synopsis scale (n=512, l=4)
    from repro.kernels.se_covariance.ref import se_cov_matrix_ref

    n, l = 512, 4
    lo = jnp.asarray(rng.uniform(0, 0.6, (n, l)))
    hi = lo + 0.2
    ls = jnp.ones((l,))
    norm = jnp.ones((n,))
    f = jax.jit(lambda a, b: se_cov_matrix_ref(a, b, a, b, ls, 1.0, norm, norm))
    rows.append(("kernel/se_covariance_ref_512x512_us", _timeit(f, lo, hi)))

    # range_mask_agg oracle at scan-block scale (T=65536, Q=128)
    from repro.kernels.range_mask_agg.ref import range_mask_agg_ref

    t, q = 65536, 128
    x = jnp.asarray(rng.uniform(0, 1, (t, 3)), jnp.float32)
    payload = jnp.asarray(rng.normal(size=(t, 5)), jnp.float32)
    qlo = jnp.asarray(rng.uniform(0, 0.6, (q, 3)), jnp.float32)
    qhi = qlo + 0.3
    em = jnp.ones((t, q), jnp.float32)
    g = jax.jit(range_mask_agg_ref)
    rows.append(("kernel/range_mask_agg_ref_64k_x128_us",
                 _timeit(g, x, payload, qlo, qhi, em)))

    # gp_batch_infer oracle at serving scale (Q=256, C=1024)
    from repro.kernels.gp_batch_infer.ref import gp_batch_infer_ref

    qn, c = 256, 1024
    k = jnp.asarray(rng.normal(0, 0.1, (qn, c)), jnp.float32)
    sinv = jnp.eye(c, dtype=jnp.float32)
    h = jax.jit(gp_batch_infer_ref)
    args = (k, sinv, jnp.zeros((c,), jnp.float32),
            jnp.ones((qn,), jnp.float32), jnp.zeros((qn,), jnp.float32),
            jnp.zeros((qn,), jnp.float32), jnp.full((qn,), 0.01, jnp.float32))
    rows.append(("kernel/gp_batch_infer_ref_256x1024_us", _timeit(h, *args)))

    # fused masked scan: the canonical fold (jnp, CPU-real) at 64k x 128
    from repro.aqp.executor import eval_partials

    cat = jnp.asarray(rng.integers(0, 4, (t, 1)), jnp.int32)
    meas = jnp.asarray(rng.normal(size=(t, 2)))
    snips = _scan_snippets()
    s = jax.jit(eval_partials)
    rows.append(("kernel/fused_scan_oracle_64k_x128_us",
                 _timeit(s, jnp.asarray(rng.uniform(0, 1, (t, 2))), cat,
                         meas, snips)))
    rows.extend(scan_metrics())
    return rows


# --------------------------------------------------------- fused-scan gate
def _scan_snippets(n: int = 5):
    from repro.core.types import Schema, make_snippets, pad_snippets

    sch = Schema(num_lo=(0.0, 0.0), num_hi=(1.0, 1.0), cat_sizes=(4,),
                 n_measures=2)
    return pad_snippets(make_snippets(
        sch, agg=[0] * n, measure=[0] * n,
        num_ranges=[{0: (0.1 * i, 0.1 * i + 0.5)} for i in range(n)]))


def fused_scan_traffic_bytes(t_n: int, q_n: int, l: int, c: int, vmax: int,
                             m: int, tile_t: int, tile_q: int) -> float:
    """HBM traffic of one fused-kernel pass, from its BlockSpec tile model.

    Per snippet tile the relation streams through VMEM once (x f64, codes
    i32, valid f64, payload [m, m^2, 1] f64); lo/hi/cat are fetched once per
    snippet tile and the (Q, 2m+1) accumulator is written once. No (T, Q)
    mask ever touches HBM — that is the fusion; un-fusing it adds
    ~2*T*Q*8 bytes and collapses the roofline fraction below."""
    p = 2 * m + 1
    q_tiles = -(-q_n // tile_q)
    stream = q_tiles * t_n * (l * 8 + c * 4 + 1 * 8 + p * 8)
    snippet_side = q_n * (2 * l + c * vmax) * 8
    out = q_n * p * 8
    return float(stream + snippet_side + out)


def min_relation_stream_bytes(t_n: int, l: int, c: int, m: int) -> float:
    """The un-beatable floor: every relation byte read exactly once."""
    return float(t_n * (l * 8 + c * 4 + m * 8))


def scan_metrics():
    """CI-gated fused-scan metrics (machine-portable, no wall-clock).

    scan/kernel_bitwise_parity -- 1.0 iff fused-kernel partials equal the
        jnp oracle BIT FOR BIT on a mini parity matrix: tuple counts
        {1, 100, 1000}, a validity-masked padded block, and the
        aggregation-only (sharded gathered-mask) kernel leg.
    scan/bytes_per_sec_frac_of_peak -- achieved fraction of HBM peak
        bandwidth on the roofline model: with the kernel memory-bound at
        peak (memory_s = traffic / HBM_BW, see repro.launch.roofline), the
        useful byte rate is HBM_BW * min_stream / traffic. Deterministic
        BlockSpec arithmetic, so the gate is meaningful on any runner.
    """
    from repro.aqp.executor import eval_partials, pad_tuple_axis, \
        predicate_mask
    from repro.kernels import SCAN_TILE_Q, SCAN_TILE_T
    from repro.kernels.fused_masked_scan import (eval_partials_fused,
                                                 masked_partials_fused)

    rng = np.random.default_rng(7)
    snips = _scan_snippets()
    parity = 1.0

    def _bitwise(a, b):
        return all(
            np.array_equal(np.asarray(getattr(a, f)),
                           np.asarray(getattr(b, f)))
            for f in ("sums", "sumsq", "count", "scanned"))

    for t in (1, 100, 1000):
        num = jnp.asarray(rng.uniform(0, 1, (t, 2)))
        cat = jnp.asarray(rng.integers(0, 4, (t, 1)), jnp.int32)
        meas = jnp.asarray(rng.normal(size=(t, 2)))
        want = eval_partials(num, cat, meas, snips)
        parity *= float(_bitwise(eval_partials_fused(num, cat, meas, snips),
                                 want))
        mask = predicate_mask(num, cat, snips)
        parity *= float(_bitwise(
            masked_partials_fused(mask, meas, snips, want.scanned), want))
    num_p, cat_p, meas_p, valid = pad_tuple_axis(
        8, num, cat, meas)  # 1000 -> 1024: a genuinely padded block
    parity *= float(_bitwise(
        eval_partials_fused(num_p, cat_p, meas_p, snips, valid),
        eval_partials(num_p, cat_p, meas_p, snips, valid)))

    t_n, q_n, l, c, vmax, m = 65536, 128, 2, 1, 4, 2
    frac = (min_relation_stream_bytes(t_n, l, c, m)
            / fused_scan_traffic_bytes(t_n, q_n, l, c, vmax, m,
                                       SCAN_TILE_T, SCAN_TILE_Q))
    return [("scan/kernel_bitwise_parity", parity),
            ("scan/bytes_per_sec_frac_of_peak", frac)]


def scan_roofline_rows():
    """Roofline-report rows for the scan plane (reported, not gated).

    Contrasts the fused kernel's modeled HBM traffic against the compiled
    jnp oracle's XLA ``bytes accessed`` (the mask materialization the fusion
    eliminates), converts both to memory-bound seconds at HBM peak
    (``repro.launch.roofline``), and runs ``repro.launch.hlo_analysis`` over
    the sharded mask builder's post-SPMD HLO to certify the mask build is
    collective-free (the only cross-device traffic is the final gather).
    """
    from repro.aqp.executor import eval_partials
    from repro.kernels import SCAN_TILE_Q, SCAN_TILE_T
    from repro.launch.roofline import peaks

    rng = np.random.default_rng(11)
    t_n, q_n, l, c, vmax, m = 65536, 128, 2, 1, 4, 2
    num = jnp.asarray(rng.uniform(0, 1, (t_n, l)))
    cat = jnp.asarray(rng.integers(0, vmax, (t_n, c)), jnp.int32)
    meas = jnp.asarray(rng.normal(size=(t_n, m)))
    snips = _scan_snippets()

    fused_bytes = fused_scan_traffic_bytes(t_n, q_n, l, c, vmax, m,
                                           SCAN_TILE_T, SCAN_TILE_Q)
    rows = [
        ("scan/fused_hbm_model_bytes", fused_bytes),
        # Modelled for the v5e target, not measured on this host.
        ("scan/fused_memory_s_at_hbm_peak",
         fused_bytes / peaks(TARGET_KIND)["hbm_bytes_per_s"]),
    ]
    ca = jax.jit(eval_partials).lower(num, cat, meas, snips) \
        .compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    oracle_bytes = float(ca.get("bytes accessed", 0.0))
    if oracle_bytes:
        rows.append(("scan/jnp_oracle_bytes_accessed", oracle_bytes))
        rows.append(("scan/fused_traffic_reduction_x",
                     oracle_bytes / fused_bytes))
    try:
        from jax.sharding import Mesh

        from repro.aqp.executor import _sharded_mask_fn, pad_tuple_axis
        from repro.launch.hlo_analysis import collective_bytes

        n_dev = min(4, jax.device_count())
        mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
        num_p, cat_p, _, valid = pad_tuple_axis(n_dev, num, cat, None)
        hlo = _sharded_mask_fn(mesh, "data") \
            .lower(num_p, cat_p, valid, snips).compile().as_text()
        rows.append(("scan/sharded_mask_collective_bytes",
                     float(collective_bytes(hlo)["wire_bytes_total"])))
    except Exception:
        pass  # single-device container without forced topology
    return rows
