"""Compile every main-path Pallas kernel for a described TPU v5e chip.

The kernel-vs-oracle tests run the kernels with ``interpret=True``, which
accepts layouts and ops the TPU compiler (Mosaic) refuses. These tests lower
and compile each kernel with ``interpret=False`` for a v5e chip that is only
described, never attached, at the shapes ``chip_smoke.py`` dispatches: the
paper's full-day Tables 1-3 sweep of 18 scenario rows, up to ~10.5 M
records per row and 3600-entry bucket tables. The kernels' tiles are fixed
module constants, so the same kernels are compiled again at the shapes of
the other benchmark cells: each shard of the four-chip grid, and the widest
chunk of the multi-day stream. Nothing runs; a refused layout, an
unsupported op or a program that overflows the chip's memory fails here
instead of on the chip.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU compiler library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
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
#: the four-chip grid's shard layouts (bench/configs/paper_t123_day_4chip
#: .json, planned by plan_sweep over 4 devices): three shards of 2
#: UserBehavior rows, the widest kept width at max_range 3600, and one of
#: the 12 SogouQ and Traffic rows; (rows, padded records, kept width)
SHARDS = {"4chip_ub": (2, 10_580_992, 441_344),
          "4chip_small": (12, 2_213_888, 93_184)}
#: the multi-day stream's widest chunk (bench/configs/taobao_ub_stream.json:
#: 6 rows over 2 days in 600 s chunks), as ChunkedNSA.chunk dispatches it:
#: the records it launches, its kept width and its bucket-table width
#: (2 days at max_range 3600); the same for every seed
CHUNK_ROWS = 6
CHUNK_RECORDS = 10_637_312
CHUNK_KEPT = 113_664
CHUNK_TABLE = 7_200
#: one 600 s chunk's histogram width, padded to the 512-bucket block
CHUNK_BUCKETS = 1024
#: the trend carry's window: the report's 59-bucket tail plus the 600 s
#: chunk, padded to the 1024-step tile
CHUNK_TREND = 1024


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
        "stream_sample_chunk": (
            functools.partial(stream_sample_pallas, max_range=CHUNK_TABLE),
            [((CHUNK_ROWS, CHUNK_RECORDS), f32)] +
            [((CHUNK_ROWS, CHUNK_TABLE), i32)] * 3 +
            [((CHUNK_ROWS, 3), f32)]),
        "compact_batched_chunk": (compact_positions_batched_pallas,
                                  [((CHUNK_ROWS, CHUNK_RECORDS), i32)]),
        "stream_metrics_carry_chunk": (
            functools.partial(stream_metrics_carry_pallas,
                              buckets=CHUNK_BUCKETS),
            [((CHUNK_ROWS, CHUNK_KEPT), i32), ((CHUNK_ROWS, 4), f32)]),
        "trend_scan_carry_chunk": (
            trend_scan_carry_pallas,
            [((CHUNK_ROWS, CHUNK_TREND), i32), ((CHUNK_ROWS,), i32)]),
        **{f"{kernel}_{name}": case
           for name, (rows, records, kept) in SHARDS.items()
           for kernel, case in _shard_cases(rows, records, kept).items()},
    }


def _shard_cases(rows, records, kept):
    """The grid's NSA and metrics kernels at one shard's layout."""
    i32, f32 = jnp.int32, jnp.float32
    return {
        "stream_sample": (
            functools.partial(stream_sample_pallas, max_range=WIDTH),
            [((rows, records), f32)] + [((rows, WIDTH), i32)] * 3 +
            [((rows, 3), f32)]),
        "compact_batched": (compact_positions_batched_pallas,
                            [((rows, records), i32)]),
        "stream_metrics": (
            functools.partial(stream_metrics_pallas, buckets=BUCKETS),
            [((rows, kept), i32)]),
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


def test_slice_records_compiles_to_copies_for_v5e(one_chip,
                                                  no_persistent_cache):
    """The chunk pipeline's record slice at the multi-day stream's shape
    (6 rows of ~21.2 M records, the widest chunk ~10.9 M): contiguous
    copies, no per-element gather, and the edge-padded plane is never
    materialized (its scratch stays under the output's size)."""
    rows, n, width = 6, 21_218_304, 10_863_616
    compiled = ops.slice_records.lower(
        jax.ShapeDtypeStruct((rows, n), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
        width).compile()
    assert "gather" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= mem.output_size_in_bytes


@pytest.mark.parametrize("datasets,per,n", [
    (1, 6, 21_218_304),          # the multi-day stream: 6 rows, 1 dataset
    (3, 6, 10_580_992),          # the paper grid: 18 rows, 3 datasets
])
def test_expand_rows_compiles_to_copies_for_v5e(datasets, per, n, one_chip,
                                               no_persistent_cache):
    """The row -> dataset expansion of the NSA timestamp plane at the
    benchmark's shapes: row copies, no gather, no scratch plane."""
    rows = tuple(d for d in range(datasets) for _ in range(per))
    planes = tuple(jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
                   for _ in range(datasets))
    compiled = ops._expand_rows.lower(planes, rows).compile()
    assert "gather" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
