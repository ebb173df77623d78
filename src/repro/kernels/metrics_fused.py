"""Pallas TPU kernel: fused, batched stream-metrics engine.

One pass over the record tiles of ``(S, N)`` stacked scale-stamp streams
produces, per stream, BOTH reporting quantities the paper's §5.2 statistics
need:

- the per-second count histogram ``q`` (``q[b] = |{i : ss_i == b}|``), and
- its first two moments ``[Σq, Σq²]`` (formulas (2)-(4) derive avg/var/σ).

This subsumes the seed's two unwired kernels (``bucket_hist.py``,
``volatility.py``): those needed two HBM passes (records then counts), did
O(n·B) one-hot work against the *whole* bucket axis in a single VMEM block
(a (1024, 86 400) f32 one-hot is ~340 MB — a day of seconds could never
fit), and accumulated counts in float32, which silently rounds once any
bucket exceeds 2²⁴ records.

Two ops-layer entry forms feed this kernel
(:mod:`repro.kernels.ops`): ``stream_metrics_batched`` stacks host
scale-stamp arrays into the padded ``(S, N)`` layout, while
``stream_metrics_batched_device`` consumes stamps that are ALREADY on
device — the sweep engine chains it directly after the batched NSA
compaction, masking each row's invalid tail to the padding id on device,
so kept stamps never round-trip through host between NSA and metrics.

Design
------
Grid ``(stream, record-tile)`` — the same 2-D layout as
``stream_sample_pallas``, so S streams' metrics are ONE dispatch. The
histogram accumulates directly in the per-stream output block (int32 — counts
are exact up to 2³¹, enforced by the ops wrapper), which stays VMEM-resident
across the record-tile axis because its index map ignores the tile index.
The histogram is laid out ``(S, 1, buckets)`` and the moments ``(S, 1,
128)`` (lanes 0.. hold the values), so every block's last two dimensions
equal the array's and every store is a whole vector.

The bucket axis is processed in LANE-multiple blocks of ``BUCKET_BLOCK``
inside the kernel, so the one-hot intermediate is a bounded
``(LANE, BUCKET_BLOCK)`` tile per record column no matter how large
``max_range`` is — ``max_range`` up to the full 86 400-second day fits
comfortably (86 528 int32 ≈ 340 KiB for the resident histogram block). The
record tile is transposed once so that records run down the sublanes.

Cost is data-adaptive: scale stamps are non-decreasing (Min-Max normalize is
monotone and streams are chronological), so each record tile spans a narrow
bucket range and a ``fori_loop`` with traced bounds touches only the bucket
blocks that range intersects — O(records · BUCKET_BLOCK) compare work for
sorted streams instead of O(records · max_range). Unsorted input stays
*correct* (the bounds just widen), only slower.

At the last record tile of each stream the kernel reduces the resident
histogram into ``[Σq, Σq²]``, so moments cost no extra HBM pass over either
records or counts. The reduction is f32 but uses pairwise-block + Kahan
(compensated) summation — each ``BUCKET_BLOCK`` slice collapses to one
partial, and the partials accumulate with a compensation term — so the
rounding error stays O(1) ulp regardless of the bucket-axis length (a naive
running f32 sum drifts O(B)·eps over a B = 86 400 day axis). Moments agree
with the exact f64 reference within ~1e-5 relative, an order tighter than
the 1e-3 the metrics layer historically promised.

Padding contract: the wrapper pads the record axis with bucket id
``>= buckets`` (it uses ``buckets`` itself); padded entries never match a
one-hot column and never contribute to any count or moment.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tuning import DEFAULT_CONFIG, TileConfig

LANE = 128
SUBLANE = 8
TILE = LANE * SUBLANE      # records per grid step (default TileConfig)
BUCKET_BLOCK = 4 * LANE    # bucket columns per inner step (default config)


def _accumulate_hist(ss_ref, hist_ref, *, buckets: int, bucket_block: int):
    """Fold one record tile's stamps into the resident ``(1, buckets)``
    histogram block.

    The tile is transposed once so records run down the sublanes; each of
    its columns then compares against a lane-dense ``(LANE, bucket_block)``
    iota of bucket ids, and the one-hot sums straight into the block's
    lane slice. A ``fori_loop`` with traced bounds walks only the bucket
    blocks the tile's stamps touch."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    ss = ss_ref[...]                                 # (sublane, LANE) int32
    valid = ss < buckets                             # padding id >= buckets

    # data-adaptive bucket-block range: sorted stamps => a tile spans few
    # blocks; an all-padding tile runs zero iterations
    lo = jnp.min(jnp.where(valid, ss, buckets - 1)) // bucket_block
    hi = jnp.max(jnp.where(valid, ss, 0)) // bucket_block
    upper = jnp.where(jnp.sum(valid.astype(jnp.int32)) > 0, hi + 1, lo)
    cols = ss.T                                      # (LANE, sublane)

    def body(blk, carry):
        base = pl.multiple_of(blk * bucket_block, LANE)
        ids = base + jax.lax.broadcasted_iota(
            jnp.int32, (LANE, bucket_block), 1)
        partial = jnp.zeros((1, bucket_block), jnp.int32)
        for c in range(cols.shape[1]):
            partial += jnp.sum((cols[:, c:c + 1] == ids).astype(jnp.int32),
                               axis=0, keepdims=True)
        hist_ref[:, pl.ds(base, bucket_block)] += partial
        return carry

    jax.lax.fori_loop(lo, upper, body, 0)


def _kahan_fold(hist_ref, init, *, buckets: int, bucket_block: int):
    """Pairwise-block + Kahan summation of ``[Σq, Σq²]`` over the resident
    histogram: each ``bucket_block`` slice reduces to one f32 partial
    (error ~ O(log BLOCK) ulp) and the partials accumulate through
    compensated addition, so the total error is independent of the
    bucket-axis length instead of growing O(B)·eps with a naive running f32
    sum (a day-long axis has B = 86 400). ``init`` and the returned state
    are ``(s1, c1, s2, c2)``, each a ``(1, 1)`` f32 vector."""
    def kahan(blk, carry):
        s1, c1, s2, c2 = carry
        base = pl.multiple_of(blk * bucket_block, LANE)
        q = hist_ref[:, pl.ds(base, bucket_block)] \
            .astype(jnp.float32)                     # padding buckets are 0
        y1 = jnp.sum(q, axis=1, keepdims=True) - c1
        t1 = s1 + y1
        y2 = jnp.sum(q * q, axis=1, keepdims=True) - c2
        t2 = s2 + y2
        return t1, (t1 - s1) - y1, t2, (t2 - s2) - y2

    return jax.lax.fori_loop(0, buckets // bucket_block, kahan, init)


def _lanes(mom_ref, values):
    """Write ``values`` (``(1, 1)`` vectors) into lanes 0.. of the
    lane-dense ``(1, LANE)`` moment block, zeros elsewhere."""
    lane = jax.lax.broadcasted_iota(jnp.int32, mom_ref.shape, 1)
    out = jnp.zeros(mom_ref.shape, jnp.float32)
    for j, v in enumerate(values):
        out = jnp.where(lane == j, v, out)
    mom_ref[...] = out


def _kernel(ss_ref, hist_ref, mom_ref, *, buckets: int, bucket_block: int):
    _accumulate_hist(ss_ref, hist_ref, buckets=buckets,
                     bucket_block=bucket_block)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _moments():
        zero = jnp.zeros((1, 1), jnp.float32)
        s1, _, s2, _ = _kahan_fold(hist_ref, (zero, zero, zero, zero),
                                   buckets=buckets,
                                   bucket_block=bucket_block)
        _lanes(mom_ref, (s1, s2))


def _kernel_carry(ss_ref, mcar_ref, hist_ref, mom_ref, *, buckets: int,
                  bucket_block: int):
    """Chunked variant of :func:`_kernel`: the final moment reduction seeds
    its pairwise+Kahan fold from a per-row carry-in ``[s1, c1, s2, c2]`` and
    emits the UPDATED 4-state instead of the bare ``[Σq, Σq²]`` pair, so
    moment accumulation composes across time chunks (histograms partition
    the bucket axis chunk-by-chunk, so chunk moments simply add; carrying
    the compensation terms keeps the error O(1) ulp over any number of
    chunks). With a zero carry the fold is bit-identical to
    :func:`_kernel`'s."""
    _accumulate_hist(ss_ref, hist_ref, buckets=buckets,
                     bucket_block=bucket_block)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _moments():
        mcar = mcar_ref[...]                         # (1, LANE) f32
        init = tuple(mcar[:, j:j + 1] for j in range(4))
        _lanes(mom_ref, _kahan_fold(hist_ref, init, buckets=buckets,
                                    bucket_block=bucket_block))


@functools.partial(jax.jit,
                   static_argnames=("buckets", "interpret", "config"))
def stream_metrics_carry_pallas(ss: jnp.ndarray, mcar: jnp.ndarray,
                                buckets: int, *, interpret: bool = False,
                                config: Optional[TileConfig] = None):
    """Fused histogram + carried Kahan moments over ONE time chunk.

    ss      : (S, N) int32 chunk-LOCAL scale stamps (the caller rebases the
              chunk's absolute bucket range to [0, buckets)), N % TILE == 0;
              entries >= buckets are padding.
    mcar    : (S, 4) f32 per-row Kahan moment state ``[s1, c1, s2, c2]``
              carried from the previous chunk (zeros for the first chunk).
    buckets : chunk histogram width, % BUCKET_BLOCK == 0.

    Returns ``(hist int32 (S, buckets), mom f32 (S, 4))`` — the chunk's
    histogram plus the UPDATED Kahan state with this chunk's buckets folded
    in; ``mom[:, 0]``/``mom[:, 2]`` are the running ``Σq``/``Σq²``. With a
    zero carry, ``(hist, mom[:, ::2])`` is bit-identical to
    :func:`stream_metrics_pallas` on the same input.
    """
    cfg = DEFAULT_CONFIG if config is None else config
    sublane = cfg.sublane
    S, n = ss.shape
    assert n % cfg.record_tile == 0, \
        f"pad records to a multiple of {cfg.record_tile}"
    assert buckets % cfg.bucket_block == 0, \
        f"pad buckets to a multiple of {cfg.bucket_block}"
    rows = n // LANE
    ss3 = ss.reshape(S, rows, LANE)
    grid = (S, rows // sublane)
    mcar3 = jnp.zeros((S, 1, LANE), jnp.float32).at[:, 0, :4].set(
        mcar.astype(jnp.float32))
    row_spec = pl.BlockSpec((None, 1, LANE), lambda s, i: (s, 0, 0))
    hist, mom = pl.pallas_call(
        functools.partial(_kernel_carry, buckets=buckets,
                          bucket_block=cfg.bucket_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, sublane, LANE), lambda s, i: (s, i, 0)),
            row_spec,
        ],
        out_specs=[
            pl.BlockSpec((None, 1, buckets), lambda s, i: (s, 0, 0)),
            row_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, 1, buckets), jnp.int32),
            jax.ShapeDtypeStruct((S, 1, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(ss3, mcar3)
    return hist[:, 0], mom[:, 0, :4]


@functools.partial(jax.jit,
                   static_argnames=("buckets", "interpret", "config"))
def stream_metrics_pallas(ss: jnp.ndarray, buckets: int, *,
                          interpret: bool = False,
                          config: Optional[TileConfig] = None):
    """Fused batched histogram + moments over stacked scale-stamp streams.

    ss      : (S, N) int32, N % TILE == 0; entries in [0, buckets) count,
              entries >= buckets are padding and are ignored everywhere.
    buckets : histogram width, % BUCKET_BLOCK == 0 (wrapper pads + slices).

    Returns ``(hist int32 (S, buckets), moments f32 (S, 2))`` with
    ``moments[s] = [Σ_b hist[s, b], Σ_b hist[s, b]²]``.
    """
    cfg = DEFAULT_CONFIG if config is None else config
    sublane = cfg.sublane
    S, n = ss.shape
    assert n % cfg.record_tile == 0, \
        f"pad records to a multiple of {cfg.record_tile}"
    assert buckets % cfg.bucket_block == 0, \
        f"pad buckets to a multiple of {cfg.bucket_block}"
    rows = n // LANE
    ss3 = ss.reshape(S, rows, LANE)
    grid = (S, rows // sublane)
    hist, mom = pl.pallas_call(
        functools.partial(_kernel, buckets=buckets,
                          bucket_block=cfg.bucket_block),
        grid=grid,
        in_specs=[pl.BlockSpec((None, sublane, LANE),
                               lambda s, i: (s, i, 0))],
        out_specs=[
            pl.BlockSpec((None, 1, buckets), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((None, 1, LANE), lambda s, i: (s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, 1, buckets), jnp.int32),
            jax.ShapeDtypeStruct((S, 1, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(ss3)
    return hist[:, 0], mom[:, 0, :2]
