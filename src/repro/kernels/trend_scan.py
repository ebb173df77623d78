"""Pallas TPU kernels: device-resident trend scan + S×S pair statistics.

Closes the host-side gap in the Fig.-6 validation path. After PR 2 the
fused metrics engine (:mod:`repro.kernels.metrics_fused`) produces each
stream's per-second counts ``q`` on device, but the *trend* (windowed
sliding mean of ``q``) and the *trend correlation* (Pearson r between two
streams' trends) still ran on host over a ``np.cumsum``. The two kernels
here keep the whole chain — counts → prefix sums → trend → S×S correlation
sufficient statistics — device-resident:

``trend_scan_pallas``
    Batched inclusive prefix sum over the time axis of ``(S, N)`` stacked
    count series — the same single-pass scan-with-carry pattern as
    :mod:`repro.kernels.compact`, lifted to a 2-D ``(stream, time-tile)``
    grid: each grid step computes the tile-local prefix sum (the exact
    log-step :func:`repro.kernels.compact.tile_prefix_sum`) and adds the
    running carry held in SMEM scratch, resetting the carry at each
    stream's first tile. Counts
    accumulate in int32, so prefix sums are *exact* while a stream's total
    record count stays below 2³¹ (enforced by the ops wrapper). The caller
    turns prefix sums into the windowed sliding mean with two clamped
    gathers and one divide (:func:`repro.kernels.ops.trend_scan`) — pure
    XLA, no host round-trip, mirroring how ``compact`` pairs its scan with
    one XLA scatter.

``pair_stats_pallas``
    Scan-with-carry accumulation of the Pearson sufficient statistics for
    ALL S×S stream pairs in one dispatch: the grid walks time tiles of the
    ``(S, K)`` trend matrix while the per-stream sums ``Σx`` and the Gram
    matrix ``G[a, b] = Σ_t x_a[t]·x_b[t]`` stay VMEM-resident (their output
    index maps ignore the tile index — the same residency trick as the
    metrics engine's histogram). From ``(sums, G)`` every pair's five
    sufficient statistics follow: ``Σx = sums[a]``, ``Σy = sums[b]``,
    ``Σxy = G[a, b]``, ``Σx² = G[a, a]``, ``Σy² = G[b, b]``. The per-tile
    update is one ``x_tile @ x_tileᵀ`` MXU matmul, so S×S cost rides the
    systolic array instead of an S²-pair host loop.

Numerical contract: the ops layer feeds ``pair_stats_pallas`` *centered*
trends (mean removed on device), so the correlation reduces to
``G[a,b] / √(G[a,a]·G[b,b])`` with no catastrophic ``K·Σxy − Σx·Σy``
cancellation; f32 accumulation then lands within the metrics layer's 1e-3
tolerance of the float64 host path. Zero padding (time tails, centered
series) contributes exactly 0 to every statistic.

Layout mirrors the other kernels: the time axis is padded to a multiple of
the (8, 128) record tile (``trend_scan``) or of ``PAIR_TILE`` lanes
(``pair_stats``); padded entries must be 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compact import tile_prefix_sum

LANE = 128
SUBLANE = 8
TILE = LANE * SUBLANE   # time steps per grid step
PAIR_TILE = 4 * LANE    # time steps per pair-stats step


def _scan_kernel(init_ref, q_ref, psum_ref, tail_ref, carry_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _seed():                                     # carry-IN, not a reset
        carry_ref[0] = init_ref[0, 0]

    q = q_ref[...].astype(jnp.int32)                 # (SUBLANE, LANE)
    carry = carry_ref[0]
    psum_ref[...] = carry + tile_prefix_sum(q)
    carry_ref[0] = carry + jnp.sum(q)

    @pl.when(i == pl.num_programs(1) - 1)
    def _tail():
        tail_ref[...] = jnp.full(tail_ref.shape, carry_ref[0], jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def trend_scan_pallas(q: jnp.ndarray, *, interpret: bool = False):
    """Batched inclusive prefix sum over stacked per-second count series.

    q : (S, N) int32, N % TILE == 0 (pad time tails with 0).

    Returns ``psum int32 (S, N)`` with
    ``psum[s, i] = Σ_{j <= i} q[s, j]`` — exact while each stream's total
    stays below 2³¹ (the ops wrapper guards this). The zero-carry case of
    :func:`trend_scan_carry_pallas`.
    """
    return trend_scan_carry_pallas(q, jnp.zeros(q.shape[0], jnp.int32),
                                   interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def trend_scan_carry_pallas(q: jnp.ndarray, init: jnp.ndarray, *,
                            interpret: bool = False):
    """Chunked form of :func:`trend_scan_pallas`: the SMEM running carry is
    *seeded* from a per-row carry-in instead of reset to zero, so prefix
    sums over consecutive time chunks compose exactly.

    q    : (S, N) int32 — one time chunk per row, N % TILE == 0 (pad time
           tails with 0).
    init : (S,) int32 — each row's inclusive prefix total through the last
           bucket of the PREVIOUS chunk (zeros for the first chunk, which
           makes this bit-identical to :func:`trend_scan_pallas`).

    Returns ``(psum int32 (S, N), tail int32 (S,))`` where
    ``psum[s, i] = init[s] + Σ_{j <= i} q[s, j]`` and ``tail[s]`` is the
    row's new running total — the ``init`` to feed the next chunk. Exact
    while the cumulative total stays below 2³¹ (ops-wrapper guarded).
    """
    S, n = q.shape
    assert n % TILE == 0, f"pad time steps to a multiple of {TILE}"
    rows = n // LANE
    q3 = q.reshape(S, rows, LANE)
    grid = (S, rows // SUBLANE)
    tile_spec = pl.BlockSpec((None, SUBLANE, LANE), lambda s, i: (s, i, 0))
    psum, tail = pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, 1), lambda s, i: (s, 0, 0),
                         memory_space=pltpu.SMEM),
            tile_spec,
        ],
        out_specs=[
            tile_spec,
            pl.BlockSpec((None, 1, LANE), lambda s, i: (s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, rows, LANE), jnp.int32),
            jax.ShapeDtypeStruct((S, 1, LANE), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(init.reshape(S, 1, 1).astype(jnp.int32), q3)
    return psum.reshape(S, n), tail[:, 0, 0]


def _pair_kernel(x_ref, sums_ref, gram_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        gram_ref[...] = jnp.zeros_like(gram_ref)

    x = x_ref[...]                                   # (S, PAIR_TILE) f32
    sums_ref[...] += jnp.sum(x, axis=1, keepdims=True)
    gram_ref[...] += jnp.dot(x, x.T, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_stats_pallas(x: jnp.ndarray, *, interpret: bool = False):
    """All-pairs Pearson sufficient statistics over stacked trend series.

    x : (S, K) float32, K % PAIR_TILE == 0 (pad time tails with 0.0 —
        zeros contribute nothing to any statistic).

    Returns ``(sums f32 (S, 1), gram f32 (S, S))`` where
    ``sums[s] = Σ_t x[s, t]`` and ``gram[a, b] = Σ_t x[a, t]·x[b, t]`` —
    together the ``[Σx, Σy, Σxy, Σx², Σy²]`` bundle for every stream pair,
    accumulated tile-by-tile with the (sums, gram) outputs VMEM-resident
    across the time grid.
    """
    S, k = x.shape
    assert k % PAIR_TILE == 0, f"pad time steps to a multiple of {PAIR_TILE}"
    grid = (k // PAIR_TILE,)
    sums, gram = pl.pallas_call(
        _pair_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((S, PAIR_TILE), lambda i: (0, i))],
        out_specs=[
            pl.BlockSpec((S, 1), lambda i: (0, 0)),
            pl.BlockSpec((S, S), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, S), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return sums, gram
