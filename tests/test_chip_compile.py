"""Compile the chip path for a described TPU v5e, without a chip.

The TPU compiler is installed here and compiles for a described topology.
These tests compile the kernels of the query path at the widths of the
``chip_smoke.py`` deployment (TPC-H lineitem at SF10: L=3 numeric, C=3
categorical dims of up to V=25 codes, M=2 measures, a 10 % sample in ten
batches of ~600k rows, one 128-snippet tile) and assert that Mosaic took
them (``tpu_custom_call``). Interpret mode on the CPU cannot catch what
Mosaic refuses: an i64 index map under global x64, or a 1-D block whose
layout does not match XLA's.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.synopsis import inv_append_block, inv_delete_block, model_device
from repro.core.types import SnippetBatch
from repro.kernels.fused_masked_scan import ops as fms_ops
from repro.kernels.gp_batch_infer import ops as gp_ops

T_BATCH = 599_861  # one sample batch: 10 % of 59,986,052 rows over 10 batches
Q_TILE = 128
L, C, V, M = 3, 3, 25, 2
CAPACITY = 2000  # EngineConfig.capacity: the synopsis at its fullest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _snippets(q, sharding):
    return SnippetBatch(
        lo=_sds((q, L), jnp.float64, sharding),
        hi=_sds((q, L), jnp.float64, sharding),
        cat=_sds((q, C, V), jnp.bool_, sharding),
        agg=_sds((q,), jnp.int32, sharding),
        measure=_sds((q,), jnp.int32, sharding),
    )


def _compiled_kernel_text(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def test_fused_masked_scan_compiles_for_v5e(one_chip, no_compile_cache):
    def scan(num, cat, measures, snippets):
        return fms_ops._eval_partials_fused(
            num, cat, measures, snippets, None, tile_t=512, tile_q=Q_TILE,
            interpret=False)

    text = _compiled_kernel_text(
        scan,
        _sds((T_BATCH, L), jnp.float64, one_chip),
        _sds((T_BATCH, C), jnp.int32, one_chip),
        _sds((T_BATCH, M), jnp.float64, one_chip),
        _snippets(Q_TILE, one_chip),
    )
    assert "tpu_custom_call" in text


def test_masked_partials_compiles_for_v5e(one_chip, no_compile_cache):
    def agg(mask, measures, snippets, scanned):
        return fms_ops._masked_partials_fused(
            mask, measures, snippets, scanned, tile_t=512, tile_q=Q_TILE,
            interpret=False)

    text = _compiled_kernel_text(
        agg,
        _sds((T_BATCH, Q_TILE), jnp.float64, one_chip),
        _sds((T_BATCH, M), jnp.float64, one_chip),
        _snippets(Q_TILE, one_chip),
        _sds((), jnp.float64, one_chip),
    )
    assert "tpu_custom_call" in text


def test_gp_batch_infer_compiles_for_v5e(one_chip, no_compile_cache):
    def improve(*args):
        return gp_ops._gp_batch_infer(*args, tile_q=Q_TILE, tile_c=128,
                                      interpret=False)

    vec = _sds((Q_TILE,), jnp.float64, one_chip)
    text = _compiled_kernel_text(
        improve,
        _sds((Q_TILE, CAPACITY), jnp.float64, one_chip),
        _sds((CAPACITY, CAPACITY), jnp.float64, one_chip),
        _sds((CAPACITY,), jnp.float64, one_chip),
        vec, vec, vec, vec,
    )
    assert "tpu_custom_call" in text


def test_f64_ingest_update_compiles_on_the_host_device(topo,
                                                       no_compile_cache):
    """Off-CPU, the synopsis' f64 model math runs on the host CPU device
    (``repro.core.synopsis.model_device``): XLA:TPU refuses f64 LU and
    takes minutes over the f64 covariance. The ingest update compiles
    there in f64."""
    host = model_device(topo.devices[0])
    assert host.platform == "cpu"
    on_host = SingleDeviceSharding(host)
    m, k = CAPACITY - 16, 16
    append = jax.jit(inv_append_block).lower(
        _sds((m, m), jnp.float64, on_host),
        _sds((k, m), jnp.float64, on_host),
        _sds((k, k), jnp.float64, on_host),
    ).compile()
    assert "f64[2000,2000]" in append.as_text()
    delete = jax.jit(lambda a: inv_delete_block(a, list(range(k)))).lower(
        _sds((CAPACITY, CAPACITY), jnp.float64, on_host)).compile()
    assert "f64" in delete.as_text()
