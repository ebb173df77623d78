"""Compile every main-path Pallas kernel for a described TPU v5e chip.

The kernel-vs-oracle tests run the kernels with ``interpret=True``, which
accepts layouts and ops the TPU compiler (Mosaic) refuses. These tests lower
and compile each kernel with ``interpret=False`` for a v5e chip that is only
described, never attached, at the shapes ``chip_smoke.py`` dispatches: the
paper's full-day Tables 1-3 sweep of 18 scenario rows, up to ~10.5 M
records per row and 3600-entry bucket tables. Nothing runs; a refused
layout, an unsupported op or a program that overflows the chip's memory
fails here instead of on the chip.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU compiler library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compact import (compact_positions_batched_pallas,
                                   compact_positions_pallas)
from repro.kernels.metrics_fused import (stream_metrics_carry_pallas,
                                         stream_metrics_pallas)
from repro.kernels.stream_sample import MAX_TABLE_WIDTH, stream_sample_pallas
from repro.kernels.trend_scan import (pair_stats_pallas,
                                      trend_scan_carry_pallas,
                                      trend_scan_pallas)

#: scenario rows of the smoke sweep: 3 datasets x 6 max_ranges
ROWS = 18
#: range-padded record width of a full-day, full-rate userbehavior row
#: (~10.5 M records, rounded up to the 1024-record tile)
RECORDS = 10_551_296
#: bucket-table width: the sweep's largest max_range
WIDTH = 3600
#: kept-record width after compression at max_range 3600 (~1/24 of RECORDS)
KEPT = 440_320
#: metrics histogram width: WIDTH padded to the 512-bucket block
BUCKETS = 4096
#: fidelity trend rows: one day of per-second counts, tile-padded
DAY = 87_040
#: v5e high-bandwidth memory per chip
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _cases():
    """kernel name -> (callable, [(shape, dtype), ...]) at smoke shapes."""
    i32, f32 = jnp.int32, jnp.float32
    return {
        "stream_sample": (
            functools.partial(stream_sample_pallas, max_range=WIDTH),
            [((ROWS, RECORDS), f32), ((ROWS, WIDTH), i32),
             ((ROWS, WIDTH), i32), ((ROWS, WIDTH), i32), ((ROWS, 3), f32)]),
        # the widest tables the domain guard admits must fit in SMEM
        "stream_sample_widest": (
            functools.partial(stream_sample_pallas,
                              max_range=MAX_TABLE_WIDTH),
            [((2, 8192), f32)] + [((2, MAX_TABLE_WIDTH), i32)] * 3 +
            [((2, 3), f32)]),
        "compact": (compact_positions_pallas, [((RECORDS,), i32)]),
        "compact_batched": (compact_positions_batched_pallas,
                            [((ROWS, RECORDS), i32)]),
        "stream_metrics": (
            functools.partial(stream_metrics_pallas, buckets=BUCKETS),
            [((ROWS, KEPT), i32)]),
        "stream_metrics_carry": (
            functools.partial(stream_metrics_carry_pallas, buckets=BUCKETS),
            [((ROWS, KEPT), i32), ((ROWS, 4), f32)]),
        "trend_scan": (trend_scan_pallas, [((2 * 3, DAY), i32)]),
        "trend_scan_carry": (trend_scan_carry_pallas,
                             [((ROWS, BUCKETS), i32), ((ROWS,), i32)]),
        "pair_stats": (pair_stats_pallas, [((2 * 3, BUCKETS), f32)]),
    }


@pytest.mark.parametrize("kernel", sorted(_cases()))
def test_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    fn, shapes = _cases()[kernel]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(functools.partial(fn, interpret=False)) \
        .lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
            mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{kernel} needs {used} bytes of HBM"
