"""Public jit'd wrappers over the Pallas kernels.

Each op handles padding/layout, dispatches to the Pallas kernel (TPU) or its
``interpret=True`` execution (CPU — this container), and exposes exactly the
semantics the pure-jnp oracles in :mod:`repro.kernels.ref` define. Tests
sweep shapes/dtypes asserting allclose against the oracles.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import gpu_lowering as _gpu
from repro.kernels import ref, tuning
from repro.kernels.compact import compact_positions_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.metrics_fused import (BUCKET_BLOCK, TILE,
                                         stream_metrics_carry_pallas,
                                         stream_metrics_pallas)
from repro.kernels.stream_sample import stream_sample_pallas
from repro.kernels.trend_scan import TILE as TREND_TILE
from repro.kernels.trend_scan import (PAIR_TILE, pair_stats_pallas,
                                      trend_scan_carry_pallas,
                                      trend_scan_pallas)


def on_tpu() -> bool:
    """Single source of truth for the device-selection predicate."""
    return jax.default_backend() == "tpu"


def on_gpu() -> bool:
    """True on any CUDA/ROCm device — the Pallas GPU lowering path.

    Every kernel is rerouted to its lowering in
    :mod:`repro.kernels.gpu_lowering`: the scan/accumulate kernels rely
    on TPU's sequential grid, and ``stream_sample`` reads its tables from
    TPU scalar memory.
    """
    return jax.default_backend() in ("gpu", "cuda", "rocm")


def on_accelerator() -> bool:
    """TPU or GPU: compiled Pallas. Anywhere else the TPU kernels run
    under ``interpret=True`` (this container's CPU tier)."""
    return on_tpu() or on_gpu()


_on_tpu = on_tpu


class PallasDomainError(ValueError):
    """The inputs fall outside the Pallas kernels' exactness domain.

    Raised by the ops wrappers *before* dispatch; ``nsa(backend="pallas")``
    catches it and falls back to the numpy path, so callers only see it
    when invoking the ops layer directly.
    """


class KeepRuleOverflow(PallasDomainError):
    """The systematic keep rule ``(rank * k) % c`` would overflow int32.

    The kernel (and its oracle) compute the Bresenham product in int32 —
    the TPU-native width — which is exact only while ``(c - 1) * k < 2**31``
    for every bucket. Streams with enormous single buckets and weak
    compression (e.g. 100k identical timestamps at multiple ~3) violate
    this; the wrappers refuse them rather than silently diverge from the
    int64 numpy path, and ``nsa(backend="pallas")`` falls back to numpy.
    """


class HostFallbackWarning(RuntimeWarning):
    """A device-path call ran on the host because an input lies outside
    the kernels' exactness domain (see :class:`PallasDomainError`)."""


def warn_host_fallback(stage: str, err: PallasDomainError) -> None:
    """Announce a host fallback: ``stage`` names what fell back, ``err``
    the out-of-domain input that forced it. Every caller that catches a
    :class:`PallasDomainError` and reruns on the host warns through here,
    so a device run never turns into a host run unnoticed."""
    warnings.warn(f"{stage} falls back to the host path: {err}",
                  HostFallbackWarning, stacklevel=3)


def _pad_to(x: jnp.ndarray, mult: int, value) -> Tuple[jnp.ndarray, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), value, x.dtype)])
    return x, n


# --------------------------------------------------------------------- NSA
def _nsa_tables(t64: np.ndarray, max_range: int, multiple: float,
                width: Optional[int] = None):
    """Exact per-bucket tables + kernel inputs for one sorted stream.

    Computes (rebased f32 timestamps, starts, counts, ktab,
    (t_min, 1/span, n_buckets)) where the tables come from the *float64
    host formula* — the identical expression ``(t - t_min) / span *
    max_range`` that :func:`repro.streamsim.nsa.scale_stamps` floors — so
    the kernel's +-1-snapped scale stamps are bit-identical to the numpy
    path. O(n) vectorized host work for ``v`` plus O(max_range log n)
    searchsorted; everything per-record then runs on device.

    ``width`` (default ``max_range``) pads the table axis for range-padded
    sweeps mixing rows at different ``max_range``: tail buckets in
    ``[max_range, width)`` get ``starts = n``, ``counts = 0`` and a ZERO
    keep budget, so they can never claim a record or keep anything — the
    row's compute is fully determined by its ``n_buckets`` scalar.
    """
    from repro.kernels.stream_sample import MAX_RANGE_LIMIT, MAX_TABLE_WIDTH
    if max_range > MAX_RANGE_LIMIT:
        raise PallasDomainError(
            f"max_range {max_range} exceeds {MAX_RANGE_LIMIT}: the +-1 "
            "bucket snap no longer bounds the f32 normalize error; use the "
            "numpy NSA path")
    width = max_range if width is None else width
    assert width >= max_range
    if on_tpu() and width > MAX_TABLE_WIDTH:
        raise PallasDomainError(
            f"bucket tables of {width} entries exceed the TPU kernel's "
            f"{MAX_TABLE_WIDTH}-entry SMEM budget; use the numpy NSA path")
    n = len(t64)
    t_min, t_max = float(t64[0]), float(t64[-1])
    span = t_max - t_min
    if span <= 0.0:
        # degenerate stream (all timestamps equal): everything is bucket 0,
        # so bucket 0 spans [0, n) and every later bucket starts at n
        starts = np.full(width, n, np.int32)
        starts[0] = 0
        inv_span = 0.0
    else:
        v = (t64 - t_min) / span * max_range
        starts = np.full(width, n, np.int32)
        starts[:max_range] = np.searchsorted(v, np.arange(max_range))
        inv_span = 1.0 / span
    counts = np.zeros(width, np.int32)
    counts[:max_range] = np.diff(np.append(starts[:max_range], n))
    ktab = np.zeros(width, np.int32)
    ktab[:max_range] = np.clip(
        np.rint(counts[:max_range] / multiple), 1, None)
    prod = (counts.astype(np.int64) - 1).clip(0) * ktab.astype(np.int64)
    if prod.max(initial=0) >= 2 ** 31:
        raise KeepRuleOverflow(
            f"bucket with count={counts[prod.argmax()]} and "
            f"k={ktab[prod.argmax()]} overflows the int32 keep rule; "
            "use the numpy NSA path for this stream")
    t32 = (t64 - t_min).astype(np.float32)
    return t32, starts, counts, ktab, (0.0, inv_span, float(max_range))


def stream_sample(t: jnp.ndarray, max_range: int,
                  multiple: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused NSA inner loop on device (single stream == batch of one).

    t must be sorted ascending. Returns (scale_stamp int32, keep bool), both
    length n. Mirrors repro.streamsim.nsa semantics exactly (keep =
    'systematic', multiple precomputed by the caller).

    Epoch-second timestamps (~1.5e9) quantize to ~128 s in float32, so the
    wrapper re-bases to relative time in float64 *before* the cast. The
    per-bucket tables are computed with the exact float64 host formula and
    the kernel snaps its f32 bucket guess to them, so the outputs are
    bit-identical to the numpy NSA path — not merely allclose.
    """
    t64 = np.asarray(t, np.float64)
    n = len(t64)
    if n == 0:
        return jnp.zeros(0, jnp.int32), jnp.zeros(0, bool)
    t32, starts, counts, ktab, scalars = _nsa_tables(t64, max_range, multiple)
    cfg = tuning.config_for("stream_sample", s=1, n=n, r=max_range)
    tp, n0 = _pad_to(jnp.asarray(t32), cfg.record_tile, t32[-1])
    ss, keep = stream_sample_launch(
        tp[None, :], jnp.asarray(starts)[None, :],
        jnp.asarray(counts)[None, :], jnp.asarray(ktab)[None, :],
        jnp.asarray(scalars, jnp.float32)[None, :], max_range, cfg)
    return ss[0, :n0], keep[0, :n0].astype(bool)


def stream_sample_launch(t, starts, counts, ktab, scalars, width, cfg):
    """One launch of the stream-sample kernel on padded ``(S, N)`` inputs
    (the :func:`repro.kernels.stream_sample.stream_sample_pallas`
    contract): the GPU lowering on CUDA/ROCm, else the TPU kernel —
    compiled on the TPU, interpreted elsewhere."""
    if on_gpu():
        return _gpu.stream_sample_gpu(t, starts, counts, ktab, scalars,
                                      width, sublane=cfg.sublane)
    return stream_sample_pallas(t, starts, counts, ktab, scalars, width,
                                interpret=not on_tpu(), config=cfg)


def stream_sample_ref(t: jnp.ndarray, max_range: int, multiple: float):
    """Oracle with the same padding-free public signature."""
    t64 = np.asarray(t, np.float64)
    if len(t64) == 0:
        return jnp.zeros(0, jnp.int32), jnp.zeros(0, bool)
    t32, starts, counts, ktab, scalars = _nsa_tables(t64, max_range, multiple)
    ss, keep = ref.stream_sample_ref(
        jnp.asarray(t32)[None, :], jnp.asarray(starts)[None, :],
        jnp.asarray(counts)[None, :], jnp.asarray(ktab)[None, :],
        jnp.asarray(scalars, jnp.float32)[None, :], max_range)
    return ss[0], keep[0].astype(bool)


def stream_sample_batched(ts, max_range, multiples, *, device=None):
    """Batched fused NSA inner loop: S streams, ONE kernel dispatch.

    ts        : sequence of S sorted 1-D float64 timestamp arrays (ragged
                lengths allowed) or an (S, N) array.
    max_range : int, or a length-S sequence of per-row time ranges — the
                range-padded sweep form: every row normalizes into its OWN
                bucket count while the tables are padded to the sweep's
                maximum (tail buckets carry a zero keep budget), so one
                dispatch covers the whole (stream × max_range) grid.
    multiples : per-stream multiple (scalar broadcasts).
    device    : optional jax device the launch is committed to (the sweep
                engine places each plan shard on its own device; ``None``
                keeps jax's default placement).

    Pads every stream to the common TILE-aligned length and runs the 2-D-grid
    kernel once — replacing S sequential :func:`stream_sample` dispatches.
    Returns (scale_stamp int32 (S, N), keep bool (S, N), lengths int (S,));
    padded tail entries have keep == False. Per row the outputs are
    bit-identical to the single-stream :func:`stream_sample` at that row's
    ``max_range``, whatever the other rows' ranges are.
    """
    ts = [np.asarray(t, np.float64) for t in ts]
    S = len(ts)
    if S == 0:
        raise ValueError("need at least one stream")
    lengths = np.array([len(t) for t in ts])
    if np.any(lengths == 0):
        raise ValueError("batched path requires non-empty streams")
    ranges = np.broadcast_to(np.asarray(max_range, np.int64), (S,))
    if np.any(ranges <= 0):
        raise ValueError("max_range entries must be positive")
    width = int(ranges.max())
    mults = np.broadcast_to(np.asarray(multiples, np.float64), (S,))
    cfg = tuning.config_for("stream_sample", s=S, n=int(lengths.max()),
                            r=width)
    N = int(-(-lengths.max() // cfg.record_tile) * cfg.record_tile)
    t_b = np.empty((S, N), np.float32)
    starts_b = np.empty((S, width), np.int32)
    counts_b = np.empty((S, width), np.int32)
    k_b = np.empty((S, width), np.int32)
    scal_b = np.empty((S, 3), np.float32)
    for s, t64 in enumerate(ts):
        t32, starts, counts, ktab, scalars = _nsa_tables(
            t64, int(ranges[s]), float(mults[s]), width)
        t_b[s, :len(t32)] = t32
        t_b[s, len(t32):] = t32[-1]          # pad into the last bucket
        starts_b[s], counts_b[s], k_b[s] = starts, counts, ktab
        scal_b[s] = scalars

    def _dev(x):
        return jax.device_put(x, device) if device is not None \
            else jnp.asarray(x)

    def _launch(lo, hi):
        return stream_sample_launch(
            _dev(t_b[lo:hi]), _dev(starts_b[lo:hi]), _dev(counts_b[lo:hi]),
            _dev(k_b[lo:hi]), _dev(scal_b[lo:hi].astype(np.float32)), width,
            cfg)

    g = max(1, min(int(cfg.grid_split), S))
    if g == 1:
        ss, keep = _launch(0, S)
    else:
        # split the row axis into g near-equal launches — smaller grids
        # overlap better with transfers on GPU; per-row outputs are
        # unchanged (each launch sees the identical range-padded tables)
        bounds = [round(i * S / g) for i in range(g + 1)]
        parts = [_launch(a, b)
                 for a, b in zip(bounds[:-1], bounds[1:]) if b > a]
        ss = jnp.concatenate([p[0] for p in parts], axis=0)
        keep = jnp.concatenate([p[1] for p in parts], axis=0)
    valid = jnp.arange(N)[None, :] < _dev(lengths)[:, None]
    return ss, keep.astype(bool) & valid, lengths


# -------------------------------------------------------------- compaction
def compact_mask(mask: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    """Kept-record indices from a boolean keep mask, on device.

    Chains the Pallas scan-with-carry kernel (exclusive prefix sum over the
    mask -> per-record write position + total) with one XLA scatter that
    lands each kept record's index in its slot — no host round-trip over the
    record axis.

    Returns ``(idx int32 (n,), total int)``: ``idx[:total]`` are the indices
    of the set entries in ascending order; ``idx[total:]`` are ``n``.
    """
    mask = jnp.asarray(mask)
    n = mask.shape[0]
    if n == 0:
        return jnp.zeros(0, jnp.int32), 0
    cfg = tuning.config_for("compact", s=1, n=n)
    mp, _ = _pad_to(mask.astype(jnp.int32), cfg.record_tile, 0)
    if on_gpu():
        pos, total = _gpu.compact_positions_gpu(mp)
    else:
        pos, total = compact_positions_pallas(mp, interpret=not _on_tpu(),
                                              config=cfg)
    tgt = jnp.where(mask.astype(bool), pos[:n], n)
    idx = jnp.full((n,), n, jnp.int32).at[tgt].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return idx, int(total[0])


def compact_mask_batched(mask: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                     np.ndarray]:
    """Kept-record indices for R stacked keep masks, ONE device dispatch.

    mask : (R, N) boolean/0-1 keep masks; rows may describe streams of
    different true lengths — the caller masks padded tails to 0 (the
    :func:`stream_sample_batched` ``valid`` mask already does).

    Chains the batched Pallas scan (per-row exclusive prefix sums with the
    SMEM carry reset at each row's first tile) with ONE XLA scatter over the
    whole (R, N) grid — replacing R sequential :func:`compact_mask`
    dispatches.

    Returns ``(idx int32 (R, N), totals int64 (R,))``: ``idx[r, :totals[r]]``
    are row ``r``'s set-entry indices in ascending order; the tail is the
    sentinel ``N`` (the input width — TILE padding is internal and never
    shows up in the output). Per row this matches :func:`compact_mask` on
    that row exactly: same kept indices, same sentinel convention.
    """
    idx, totals = compact_mask_batched_device(mask)
    return idx, np.asarray(totals, np.int64).reshape(-1)


def compact_mask_batched_device(mask: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                            jnp.ndarray]:
    """:func:`compact_mask_batched` with the totals left ON DEVICE.

    Same scan + scatter chain and the same ``idx`` contract, but the
    per-row totals come back as an int32 device array instead of a host
    int64 one — reading them would force a device sync, which the chunked
    pipeline must NOT do at dispatch time (the host reads chunk ``k``'s
    totals only after chunk ``k+1``'s dispatch is in flight).
    """
    from repro.kernels.compact import compact_positions_batched_pallas
    mask = jnp.asarray(mask)
    if mask.ndim != 2:
        raise ValueError(f"mask must be (R, N), got shape {mask.shape}")
    R, n = mask.shape
    if n == 0 or R == 0:
        return jnp.zeros((R, n), jnp.int32), jnp.zeros(R, jnp.int32)
    cfg = tuning.config_for("compact", s=R, n=n)
    pad = (-n) % cfg.record_tile
    mi = mask.astype(jnp.int32)
    if pad:
        mi = jnp.concatenate(
            [mi, jnp.zeros((R, pad), jnp.int32)], axis=1)
    if on_gpu():
        pos, totals = _gpu.compact_positions_batched_gpu(mi)
    else:
        pos, totals = compact_positions_batched_pallas(
            mi, interpret=not _on_tpu(), config=cfg)
    del mi                   # an (R, N) plane: free it before the scatter
    return _scatter_kept(mask.astype(bool), pos), totals.reshape(-1)


@jax.jit
def _scatter_kept(mask: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """``idx[r, pos[r, i]] = i`` for every kept record ``i``; the sentinel
    ``n`` elsewhere. One program, so the target and source index planes
    are XLA temporaries instead of live eager arrays."""
    R, n = mask.shape
    tgt = jnp.where(mask, pos[:, :n], n)
    rows = jnp.arange(R, dtype=jnp.int32)[:, None]
    cols = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (R, n))
    return jnp.full((R, n), n, jnp.int32).at[rows, tgt].set(cols,
                                                            mode="drop")


@functools.partial(jax.jit, static_argnames=("width",))
def slice_records(t: jnp.ndarray, a: jnp.ndarray, width: int) -> jnp.ndarray:
    """``t[r, a[r] : a[r] + width]`` per row, clamped to the last column —
    one program, so the index plane is never a live array."""
    j = jnp.arange(width, dtype=jnp.int32)[None, :]
    gidx = jnp.clip(a[:, None] + j, 0, t.shape[1] - 1)
    return jnp.take_along_axis(t, gidx, axis=1)


@jax.jit
def gather_kept(ss: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Scale stamps of the kept records, row by row: ``ss[r, idx[r, j]]``
    (sentinel entries clip to the last column — garbage the caller masks
    by the row's kept total). ``idx`` may be narrower than ``ss``."""
    N = ss.shape[1]
    return jnp.take_along_axis(ss, jnp.clip(idx, 0, max(N - 1, 0)), axis=1)


# -------------------------------------------------------- metrics engine
# int32 histogram accumulation: exact while every bucket count < 2**31
# (the seed's f32 one-hot kernel silently rounded past 2**24)
_HIST_COUNT_LIMIT = 2 ** 31 - 1


def _check_metrics_domain(n_records: int) -> None:
    """A bucket count can at most reach the record count; refuse streams
    whose counts could wrap the int32 accumulator rather than round."""
    if n_records > _HIST_COUNT_LIMIT:
        raise PallasDomainError(
            f"{n_records} records could overflow the int32 histogram "
            f"accumulator (limit {_HIST_COUNT_LIMIT}); use the numpy "
            "metrics path")


def _metrics_padded(ss_list, max_range: int, cfg: tuning.TileConfig):
    """Stack ragged scale-stamp streams into the kernel's (S, N) layout."""
    S = len(ss_list)
    lengths = np.array([len(s) for s in ss_list], np.int64)
    _check_metrics_domain(int(lengths.max(initial=0)))
    tile, block = cfg.record_tile, cfg.bucket_block
    buckets = int(-(-max_range // block) * block)
    N = max(int(-(-lengths.max(initial=1) // tile) * tile), tile)
    ssb = np.full((S, N), buckets, np.int32)     # padding id >= buckets
    for s, row in enumerate(ss_list):
        if len(row) and (row.min() < 0 or row.max() >= max_range):
            raise ValueError(
                f"stream {s}: scale stamps must lie in [0, {max_range})")
        ssb[s, :len(row)] = row
    return ssb, buckets, lengths


def stream_metrics(ss: jnp.ndarray,
                   max_range: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused per-second histogram + count moments, one device pass.

    ss: (n,) integer scale stamps in [0, max_range) (any order; sorted input
    is fastest — see the kernel docstring). Returns
    ``(hist int32 (max_range,), moments f32 (2,) = [Σq, Σq²])``.
    """
    hist, mom, _ = stream_metrics_batched([ss], max_range)
    return hist[0], mom[0]


def stream_metrics_batched(ss_seq, max_range: int):
    """Batched fused metrics: S streams' histograms + moments, ONE dispatch.

    ss_seq: sequence of S 1-D integer scale-stamp arrays (ragged lengths
    allowed; empty streams yield all-zero rows). Returns
    ``(hist int32 (S, max_range), moments f32 (S, 2), lengths int64 (S,))``.
    """
    ss_list = [np.asarray(s, np.int32).reshape(-1) for s in ss_seq]
    if not ss_list:
        raise ValueError("need at least one stream")
    if max_range <= 0:
        raise ValueError("max_range must be positive")
    cfg = tuning.config_for(
        "metrics_fused", s=len(ss_list),
        n=max(int(max(len(s) for s in ss_list)), 1), r=max_range)
    ssb, buckets, lengths = _metrics_padded(ss_list, max_range, cfg)
    if on_gpu():
        hist, mom = _gpu.stream_metrics_gpu(jnp.asarray(ssb), buckets,
                                            bucket_block=cfg.bucket_block)
    else:
        hist, mom = stream_metrics_pallas(jnp.asarray(ssb), buckets,
                                          interpret=not _on_tpu(),
                                          config=cfg)
    return hist[:, :max_range], mom, lengths


def stream_metrics_batched_device(ss: jnp.ndarray, valid_counts,
                                  max_range: int):
    """Fused metrics over scale stamps that are ALREADY device-resident.

    The device-input form of :func:`stream_metrics_batched` — what the
    sweep engine chains straight after the batched NSA compaction so
    kept-stamp sets never round-trip through host between NSA and metrics.

    Parameters
    ----------
    ss : jnp.ndarray, int32, shape (S, N)
        Per-stream scale stamps on device. Row ``s``'s entries at columns
        ``>= valid_counts[s]`` may hold arbitrary garbage (e.g. clipped
        gather output) — they are masked to the kernel's padding id here,
        on device.
    valid_counts : array-like, int, shape (S,)
        Per-row count of valid leading entries. A host array costs one
        O(S) upload; a device array keeps the chain transfer-free.
    max_range : int
        Bucket-axis width; every valid stamp must lie in
        ``[0, max_range)`` (enforced by NSA upstream, not re-checked here
        — a host check would defeat the device residency).

    Returns
    -------
    (hist int32 (S, max_range) device, moments f32 (S, 2) device)
        Bit-identical counts / identical-kernel moments to feeding the
        same stamps through the host-input path.

    Raises
    ------
    PallasDomainError
        If ``N`` (the per-row capacity, an upper bound on any bucket
        count) exceeds the int32 histogram domain.
    """
    ss = jnp.asarray(ss)
    if ss.ndim != 2:
        raise ValueError(f"ss must be (S, N), got shape {ss.shape}")
    if max_range <= 0:
        raise ValueError("max_range must be positive")
    S, N = ss.shape
    _check_metrics_domain(N)
    cfg = tuning.config_for("metrics_fused", s=S, n=max(N, 1), r=max_range)
    tile, block = cfg.record_tile, cfg.bucket_block
    buckets = int(-(-max_range // block) * block)
    nvalid = jnp.asarray(valid_counts, jnp.int32).reshape(S, 1)
    ssb = jnp.where(jnp.arange(N, dtype=jnp.int32)[None, :] < nvalid,
                    ss.astype(jnp.int32), buckets)   # padding id >= buckets
    pad = (-N) % tile
    if pad or N == 0:
        ssb = jnp.concatenate(
            [ssb, jnp.full((S, pad or tile), buckets, jnp.int32)], axis=1)
    if on_gpu():
        hist, mom = _gpu.stream_metrics_gpu(ssb, buckets, bucket_block=block)
    else:
        hist, mom = stream_metrics_pallas(ssb, buckets,
                                          interpret=not _on_tpu(),
                                          config=cfg)
    return hist[:, :max_range], mom


# --------------------------------------------------------------- histogram
def bucket_hist(ss: jnp.ndarray, max_range: int) -> jnp.ndarray:
    """Per-bucket counts of scale stamps; returns (max_range,) int32.

    Legacy wrapper over the fused metrics engine: counts accumulate in int32
    (bit-exact up to 2**31 per bucket — the seed's f32 one-hot kernel lost
    exactness past 2**24) and :class:`PallasDomainError` is raised beyond
    that domain instead of returning silently wrong counts.
    """
    return stream_metrics(ss, max_range)[0]


# -------------------------------------------------------------- volatility
def volatility_moments(q: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused (Σq, Σq²) over an arbitrary count series.

    When the series comes from scale stamps, prefer :func:`stream_metrics`,
    which produces the histogram AND its moments in the same record pass;
    this reduction (which subsumed the seed's standalone volatility kernel)
    exists for series that are already materialized.
    """
    out = _volatility_moments_jit(jnp.asarray(q, jnp.float32))
    return out[0], out[1]


_volatility_moments_jit = jax.jit(ref.volatility_ref)


def volatility_stats(q: jnp.ndarray) -> Tuple[float, float, float]:
    """(average, variance, std) — device-fused version of formulas (2)-(4)."""
    n = q.shape[0]
    s, s2 = volatility_moments(q)
    avg = s / n
    var = jnp.maximum(s2 / n - avg * avg, 0.0)
    return avg, var, jnp.sqrt(var)


# ------------------------------------------------------- trend & correlation
# int32 prefix-sum accumulation: exact while a stream's total record count
# stays below 2**31 (same bound as the histogram accumulator)
_TREND_TOTAL_LIMIT = 2 ** 31 - 1


def _check_trend_domain(q_list) -> None:
    """Refuse count series outside the int32 scan's exactness domain.

    Both violations raise :class:`PallasDomainError` (not ``ValueError``)
    so the metrics layer falls back to the numpy path for any input the
    device path cannot take — the backends must never diverge on
    acceptance."""
    for s, q in enumerate(q_list):
        if len(q) and int(q.min()) < 0:
            raise PallasDomainError(
                f"stream {s}: negative counts are outside the device trend "
                "domain; use the numpy trend path")
        if int(q.sum(dtype=np.int64)) > _TREND_TOTAL_LIMIT:
            raise PallasDomainError(
                f"stream {s}: total count exceeds the int32 prefix-sum "
                f"domain (limit {_TREND_TOTAL_LIMIT}); use the numpy trend "
                "path")


def _window_tables(lengths: np.ndarray, window: int):
    """Per-stream effective window + half-width (the sliding-mean clamp:
    ``w_eff = clip(min(window, n), 1)``, matching the host semantics of
    ``np.convolve(q, ones(w)/w, mode="same")`` with w clamped to n)."""
    w_eff = np.maximum(np.minimum(window, lengths), 1).astype(np.int32)
    half = ((w_eff - 1) // 2).astype(np.int32)
    return w_eff, half


@jax.jit
def _trend_from_prefix(psum: jnp.ndarray, lengths: jnp.ndarray,
                       w_eff: jnp.ndarray, half: jnp.ndarray) -> jnp.ndarray:
    """Windowed sliding mean from inclusive prefix sums — two clamped
    gathers + one divide, all on device (the XLA tail of the scan kernel,
    as the scatter is to :func:`compact_mask`)."""
    S, N = psum.shape
    i = jnp.arange(N, dtype=jnp.int32)[None, :]
    n = lengths.astype(jnp.int32)[:, None]
    w = w_eff.astype(jnp.int32)[:, None]
    h = half.astype(jnp.int32)[:, None]
    hi = jnp.clip(i + h + 1, 0, n)          # exclusive-prefix index in [0, n]
    lo = jnp.clip(i + h + 1 - w, 0, n)

    def cex(j):                             # c[j] = sum(q[:j]); c[0] = 0
        g = jnp.take_along_axis(psum, jnp.maximum(j - 1, 0), axis=1)
        return jnp.where(j > 0, g, 0)

    win = (cex(hi) - cex(lo)).astype(jnp.float32)    # int32-exact window sums
    out = win / w.astype(jnp.float32)
    return jnp.where(i < n, out, 0.0)


def trend_scan_batched(qs, window: int):
    """Windowed sliding-mean trends of S count series, ONE scan dispatch.

    Parameters
    ----------
    qs : sequence of 1-D integer arrays
        Per-second count series (ragged lengths allowed; empty series yield
        all-zero rows).
    window : int
        Sliding-mean window in (simulated) seconds; per stream it clamps to
        ``max(min(window, n), 1)`` — the host :func:`repro.streamsim.
        metrics.sliding_mean` semantics.

    Returns
    -------
    trend : jnp.ndarray, float32, shape (S, N)
        Per-stream trends on the padded time axis; entries past a stream's
        true length are 0.
    lengths : np.ndarray, int64, shape (S,)
        True series lengths (slice each row with ``trend[s, :lengths[s]]``).

    Raises
    ------
    PallasDomainError
        If any stream's total count exceeds the int32 prefix-sum domain
        (2³¹ − 1). Window sums inside the domain are bit-exact; the final
        divide is f32 (vs. the host path's f64 — well inside the metrics
        layer's 1e-3 tolerance).
    ValueError
        If ``window < 1``, no streams are given, or counts are negative.
    """
    if window < 1:
        raise ValueError("window must be >= 1")
    q_list = [np.asarray(q).reshape(-1) for q in qs]
    if not q_list:
        raise ValueError("need at least one count series")
    _check_trend_domain(q_list)
    lengths = np.array([len(q) for q in q_list], np.int64)
    cfg = tuning.config_for("trend_scan", s=len(q_list),
                            n=int(lengths.max(initial=1)))
    tile = cfg.record_tile
    N = max(int(-(-lengths.max(initial=1) // tile) * tile), tile)
    qb = np.zeros((len(q_list), N), np.int32)
    for s, q in enumerate(q_list):
        qb[s, :len(q)] = q
    if on_gpu():
        psum = _gpu.trend_scan_gpu(jnp.asarray(qb))
    else:
        psum = trend_scan_pallas(jnp.asarray(qb), interpret=not _on_tpu(),
                                 config=cfg)
    w_eff, half = _window_tables(lengths, window)
    trend = _trend_from_prefix(psum, jnp.asarray(lengths),
                               jnp.asarray(w_eff), jnp.asarray(half))
    return trend, lengths


def trend_scan(q: jnp.ndarray, window: int) -> jnp.ndarray:
    """Windowed sliding-mean trend of one count series, on device.

    Single-stream convenience over :func:`trend_scan_batched` (a batch of
    one). Returns a float32 ``(n,)`` device array; same domain guards.
    """
    trend, lengths = trend_scan_batched([q], window)
    return trend[0, :int(lengths[0])]


def trend_scan_batched_device(qmat: jnp.ndarray, lengths, window: int,
                              totals=None):
    """Device-input form of :func:`trend_scan_batched`.

    qmat : (S, N) int32 count series already on device, zero-padded past
    each row's true length (the fused metrics engine's histograms are
    exactly this shape). ``lengths`` gives the true series lengths (host).
    ``totals`` — per-row total record counts for the int32 prefix-sum
    domain guard; the caller (who produced the counts) knows them as O(S)
    host scalars, so the guard costs no device→host transfer of the count
    matrix itself. ``None`` skips the guard — only for counts whose totals
    are already bounded elsewhere.

    Returns ``(trend f32 (S, N) device, lengths int64 (S,))``; same
    contract as the host-input form.
    """
    if window < 1:
        raise ValueError("window must be >= 1")
    qmat = jnp.asarray(qmat)
    if qmat.ndim != 2:
        raise ValueError(f"qmat must be (S, N), got shape {qmat.shape}")
    lengths = np.asarray(lengths, np.int64).reshape(-1)
    if len(lengths) != qmat.shape[0]:
        raise ValueError("lengths must align with qmat rows")
    if totals is not None:
        totals = np.asarray(totals, np.int64).reshape(-1)
        if np.any(totals > _TREND_TOTAL_LIMIT):
            raise PallasDomainError(
                "total count exceeds the int32 prefix-sum domain "
                f"(limit {_TREND_TOTAL_LIMIT}); use the numpy trend path")
    S, N = qmat.shape
    cfg = tuning.config_for("trend_scan", s=S, n=max(N, 1))
    tile = cfg.record_tile
    pad = (-N) % tile
    if pad or N == 0:
        qmat = jnp.concatenate(
            [qmat.astype(jnp.int32),
             jnp.zeros((S, pad or tile), jnp.int32)], axis=1)
    if on_gpu():
        psum = _gpu.trend_scan_gpu(qmat.astype(jnp.int32))
    else:
        psum = trend_scan_pallas(qmat.astype(jnp.int32),
                                 interpret=not _on_tpu(), config=cfg)
    w_eff, half = _window_tables(lengths, window)
    trend = _trend_from_prefix(psum, jnp.asarray(lengths),
                               jnp.asarray(w_eff), jnp.asarray(half))
    return trend, lengths


def trend_pair_stats(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-pairs Pearson sufficient statistics of stacked trend series.

    Parameters
    ----------
    x : jnp.ndarray, float32, shape (S, K)
        Trend series on a common time grid (pad tails with 0 — zeros
        contribute nothing to any statistic).

    Returns
    -------
    sums : jnp.ndarray, float32, shape (S, 1)
        ``sums[s] = Σ_t x[s, t]``.
    gram : jnp.ndarray, float32, shape (S, S)
        ``gram[a, b] = Σ_t x[a, t]·x[b, t]`` — with ``sums`` this is the
        ``[Σx, Σy, Σxy, Σx², Σy²]`` bundle for every stream pair.
    """
    x = jnp.asarray(x, jnp.float32)
    if x.ndim != 2 or x.shape[0] < 1:
        raise ValueError("x must be (S, K) with S >= 1")
    k = x.shape[1]
    cfg = tuning.config_for("pair_stats", s=x.shape[0], n=max(k, 1))
    pair_tile = cfg.bucket_block
    pad = (-k) % pair_tile
    if pad or k == 0:
        x = jnp.concatenate(
            [x, jnp.zeros((x.shape[0], pad or pair_tile), x.dtype)], axis=1)
    if on_gpu():
        return _gpu.pair_stats_gpu(x)
    return pair_stats_pallas(x, interpret=not _on_tpu(), config=cfg)


@functools.partial(jax.jit, static_argnames=("n_points",))
def _resample_uniform(x: jnp.ndarray, lengths: jnp.ndarray,
                      n_points: int) -> jnp.ndarray:
    """Linear resample of each (ragged) trend row onto a uniform grid of
    ``n_points`` — the device mirror of ``np.interp(linspace(0, 1, K),
    linspace(0, 1, n), row)``: lerp at position ``i·(n−1)/(K−1)``."""
    n = lengths.astype(jnp.float32)[:, None]
    i = jnp.arange(n_points, dtype=jnp.float32)[None, :]
    scale = (n - 1.0) / max(n_points - 1, 1)   # n_points == 1 -> pos stays 0
    pos = i * scale
    j = jnp.floor(pos).astype(jnp.int32)
    j = jnp.clip(j, 0, jnp.maximum(lengths.astype(jnp.int32)[:, None] - 2, 0))
    frac = pos - j.astype(jnp.float32)
    x0 = jnp.take_along_axis(x, j, axis=1)
    x1 = jnp.take_along_axis(
        x, jnp.minimum(j + 1, jnp.maximum(
            lengths.astype(jnp.int32)[:, None] - 1, 0)), axis=1)
    return x0 * (1.0 - frac) + x1 * frac


def _corr_from_gram(gram, live, S: int) -> np.ndarray:
    """Normalize a centered Gram matrix into the S×S Pearson matrix.

    The single source of the output contract — exact symmetry, clip to
    [-1, 1], unit diagonal for non-zero variance, NaN rows for empty or
    zero-variance streams — shared by the device path below and the f64
    numpy mirror (``repro.streamsim.metrics._corr_matrix_numpy``), so the
    two backends can never drift apart on convention. ``live`` indexes the
    non-empty streams ``gram`` covers within the full S×S output.
    """
    corr = np.full((S, S), np.nan)
    g = np.asarray(gram, np.float64)
    g = (g + g.T) / 2.0                       # exact symmetry
    d = np.sqrt(np.clip(np.diag(g), 0.0, None))
    denom = np.outer(d, d)
    with np.errstate(invalid="ignore", divide="ignore"):
        sub = np.where(denom > 0, g / np.where(denom > 0, denom, 1.0),
                       np.nan)
    np.clip(sub, -1.0, 1.0, out=sub)
    np.fill_diagonal(sub, np.where(d > 0, 1.0, np.nan))
    corr[np.ix_(live, live)] = sub
    return corr


def trend_correlation_batched(qs, window: int,
                              n_points: Optional[int] = None) -> np.ndarray:
    """S×S trend-correlation matrix from ONE batched device dispatch chain.

    The full Fig.-6 validation path on device: count series → prefix-sum
    scan (:func:`trend_scan_batched`) → sliding-mean trends → linear
    resample onto a common grid → mean-centering → all-pairs sufficient
    statistics (:func:`trend_pair_stats`, one Gram-matrix dispatch). Only
    the final ``O(S²)`` normalization runs on host, in float64.

    Parameters
    ----------
    qs : sequence of 1-D integer arrays
        Per-second count series, ragged lengths allowed.
    window : int
        Sliding-mean window (see :func:`trend_scan_batched`).
    n_points : int, optional
        Common resampling grid size. Defaults to the shortest non-empty
        series' length — for S = 2 this reproduces the pairwise host
        convention of :func:`repro.streamsim.metrics.
        trend_correlation_from_counts` exactly.

    Returns
    -------
    corr : np.ndarray, float64, shape (S, S)
        Symmetric Pearson matrix, clipped to [-1, 1], diagonal exactly 1
        for streams with non-zero trend variance. Rows/columns of empty or
        zero-variance streams are NaN (matching the pairwise convention).

    Raises
    ------
    PallasDomainError
        Propagated from :func:`trend_scan_batched`; callers that want the
        numpy fallback should catch it (``repro.streamsim.metrics.
        trend_correlation_matrix`` does).
    """
    trend, lengths = trend_scan_batched(qs, window)
    return _corr_from_trends(trend, lengths, n_points)


def _corr_from_trends(trend: jnp.ndarray, lengths: np.ndarray,
                      n_points: Optional[int]) -> np.ndarray:
    """Shared tail of the S×S matrix paths: trends → common-grid resample
    → centering → Gram kernel → host f64 normalization."""
    S = len(lengths)
    live = np.flatnonzero(lengths > 0)
    if len(live) == 0:
        return np.full((S, S), np.nan)
    K = int(n_points) if n_points is not None else int(lengths[live].min())
    if K < 1:
        raise ValueError("n_points must be >= 1")
    z = _resample_uniform(trend[live], jnp.asarray(lengths[live]), K)
    z = z - jnp.mean(z, axis=1, keepdims=True)
    _, gram = trend_pair_stats(z)
    return _corr_from_gram(gram, live, S)


def trend_correlation_batched_device(qmat: jnp.ndarray, lengths,
                                     window: int,
                                     n_points: Optional[int] = None,
                                     totals=None) -> np.ndarray:
    """S×S trend-correlation matrix from count series ALREADY on device.

    The device-input form of :func:`trend_correlation_batched`: the sweep
    engine feeds it the fused metrics engine's histogram rows directly, so
    the whole Fig.-6 chain — counts → scan → trends → resample → Gram —
    never moves the count matrix through host. Same output contract and
    the same O(S²) host-side f64 normalization at the end; ``totals``
    drives the int32 domain guard as in
    :func:`trend_scan_batched_device`.
    """
    trend, lengths = trend_scan_batched_device(qmat, lengths, window,
                                               totals=totals)
    return _corr_from_trends(trend, lengths, n_points)


# ------------------------------------------------- pairwise trend correlation
@functools.partial(jax.jit, static_argnames=("k_max",))
def _pairwise_corr_jit(qa, la, wa, ha, ai, qb, lb, wb, hb, kk, k_max: int):
    """P (original, simulated) pairs → P Pearson r's, one fused XLA chain.

    ``qa`` holds the D *unique* left-side series (e.g. one per dataset)
    and ``ai`` maps each pair to its left row, so every unique left
    trend is computed ONCE — the per-scenario host loop recomputed the
    original's full-day sliding mean for every (dataset, max_range) cell.
    Per pair: int32 prefix sums (exact — same domain as the scan kernel)
    → sliding-mean trends (`_trend_from_prefix` tail) → both series
    linearly resampled onto the pair's OWN ``min(n_a, n_b)``-point grid
    (matching the host pairwise convention of
    ``trend_correlation_from_counts``, where every pair gets its own
    grid; the left resample gathers straight from the unique trend rows,
    never materializing a (P, Na) copy) → masked mean-centering →
    Pearson. Ragged grids ride one padded (P, k_max) lane space with
    per-row valid masks, so the whole report statistic is ONE device
    program instead of a per-scenario host loop.
    """
    ta_u = _trend_from_prefix(jnp.cumsum(qa, axis=1, dtype=jnp.int32),
                              la, wa, ha)                  # (D, Na) once
    tb = _trend_from_prefix(jnp.cumsum(qb, axis=1, dtype=jnp.int32),
                            lb, wb, hb)

    def grid(n, k):
        n = n.astype(jnp.float32)[:, None]
        k = k.astype(jnp.float32)[:, None]
        i = jnp.arange(k_max, dtype=jnp.float32)[None, :]
        pos = i * (n - 1.0) / jnp.maximum(k - 1.0, 1.0)
        nn = n.astype(jnp.int32)
        j = jnp.clip(pos.astype(jnp.int32), 0, jnp.maximum(nn - 2, 0))
        frac = pos - j.astype(jnp.float32)
        j1 = jnp.minimum(j + 1, jnp.maximum(nn - 1, 0))
        return j, j1, frac

    i_lane = jnp.arange(k_max, dtype=jnp.int32)[None, :]
    kkc = kk.astype(jnp.int32)[:, None]
    valid = i_lane < kkc

    # left side: gather K points per pair from the unique trend rows
    ja, ja1, fa = grid(la[ai], kk)
    ra = ta_u[ai[:, None], ja] * (1.0 - fa) + ta_u[ai[:, None], ja1] * fa
    # right side: one row per pair already
    jb, jb1, fb = grid(lb, kk)
    rb = jnp.take_along_axis(tb, jb, axis=1) * (1.0 - fb) + \
        jnp.take_along_axis(tb, jb1, axis=1) * fb
    ra = jnp.where(valid, ra, 0.0)
    rb = jnp.where(valid, rb, 0.0)

    denom_k = jnp.maximum(kkc.astype(jnp.float32), 1.0)
    ra = jnp.where(valid, ra - jnp.sum(ra, axis=1, keepdims=True) / denom_k,
                   0.0)
    rb = jnp.where(valid, rb - jnp.sum(rb, axis=1, keepdims=True) / denom_k,
                   0.0)
    num = jnp.sum(ra * rb, axis=1)
    den = jnp.sum(ra * ra, axis=1) * jnp.sum(rb * rb, axis=1)
    r = num / jnp.sqrt(den)
    return jnp.where((den > 0.0) & (kk > 0), jnp.clip(r, -1.0, 1.0),
                     jnp.nan)


def trend_corr_pairwise(qa: jnp.ndarray, lengths_a, qb: jnp.ndarray,
                        lengths_b, window: int, totals=None,
                        a_index=None) -> np.ndarray:
    """Pairwise trend correlations for P (original, simulated) pairs.

    The batched device form of the per-report statistic
    ``trend_correlation_from_counts(original_counts, simulated_counts)``:
    P pairs in one fused XLA chain, instead of P sequential host
    sliding-mean/resample/Pearson passes. Pure XLA (int32 ``cumsum`` +
    the shared ``_trend_from_prefix`` tail) — device-resident without a
    Pallas leg, so it is fast in CPU tests too. When several pairs share
    a left-side series (every max_range of a sweep correlates against
    the SAME original), pass the unique rows plus ``a_index``: each
    unique trend is computed once and gathered per pair, where the host
    loop recomputed it per scenario.

    Parameters
    ----------
    qa : jnp.ndarray, int32, shape (D, Na)
        Unique left-side count rows on device (zero-padded tails) —
        ``D == P`` with ``a_index=None``.
    qb : jnp.ndarray, int32, shape (P, Nb)
        Right-side count rows (one per pair) — e.g. the fused metrics
        engine's histograms for the sims.
    lengths_a, lengths_b : array-like int, shape (D,) / (P,)
        True series lengths per row (host).
    window : int
        Sliding-mean window shared by both sides (>= 1).
    totals : array-like int, optional
        Per-row max total counts for the int32 domain guard (raises
        :class:`PallasDomainError` when exceeded).
    a_index : array-like int, shape (P,), optional
        Pair → left-row map; ``None`` means the identity (``D == P``).

    Returns
    -------
    np.ndarray, float64, shape (P,)
        Pearson r per pair, NaN for empty or zero-variance pairs — the
        host convention, within the documented 1e-3 f32 tolerance.
    """
    if window < 1:
        raise ValueError("window must be >= 1")
    la = np.asarray(lengths_a, np.int64).reshape(-1)
    lb = np.asarray(lengths_b, np.int64).reshape(-1)
    qa, qb = jnp.asarray(qa), jnp.asarray(qb)
    if a_index is None:
        a_index = np.arange(len(la))
    ai = np.asarray(a_index, np.int64).reshape(-1)
    if qa.ndim != 2 or qb.ndim != 2 or len(ai) != qb.shape[0] or \
            len(la) != qa.shape[0] or len(lb) != qb.shape[0]:
        raise ValueError("qa/qb must be 2-D with aligned lengths/index")
    if len(ai) and (ai.min() < 0 or ai.max() >= len(la)):
        raise ValueError("a_index out of range")
    if totals is not None:
        totals = np.asarray(totals, np.int64).reshape(-1)
        if np.any(totals > _TREND_TOTAL_LIMIT):
            raise PallasDomainError(
                "total count exceeds the int32 prefix-sum domain "
                f"(limit {_TREND_TOTAL_LIMIT}); use the numpy trend path")
    kk = np.minimum(la[ai], lb)
    k_max = max(int(kk.max(initial=1)), 1)
    wa, ha = _window_tables(la, window)
    wb, hb = _window_tables(lb, window)
    r = _pairwise_corr_jit(qa.astype(jnp.int32), jnp.asarray(la),
                           jnp.asarray(wa), jnp.asarray(ha),
                           jnp.asarray(ai),
                           qb.astype(jnp.int32), jnp.asarray(lb),
                           jnp.asarray(wb), jnp.asarray(hb),
                           jnp.asarray(kk), k_max)
    return np.asarray(r, np.float64)


# ------------------------------------------------------------- chunk carry
@dataclasses.dataclass
class ChunkCarry:
    """Device-resident cross-chunk carry state for the chunked sweep.

    The chunked pipeline splits each scenario's simulated timeline into
    fixed-size scale-stamp chunks (chunk ``k`` owns the absolute bucket
    range ``[k·chunk_s, (k+1)·chunk_s)``); because chunks partition the
    BUCKET axis, per-chunk outputs compose exactly:

    ``hist``       (S, width) int32 — the running absolute-bucket histogram;
                   each chunk's slice lands at its own column range, so the
                   finalized histogram is bit-identical to the monolithic
                   kernel's.
    ``mom``        (S, 4) f32 — the pairwise+Kahan moment state
                   ``[s1, c1, s2, c2]`` (``Σq`` / ``Σq²`` plus their
                   compensation terms), folded in-kernel chunk by chunk;
                   carrying the compensations keeps the error O(1) ulp
                   regardless of chunk count (the documented ~1e-5).
    ``psum_tail``  (S,) int32 — the inclusive prefix-sum total through the
                   last folded bucket (the trend scan kernel's carry-in).
    ``trend_tail`` (S, w-1) int32 — the last ``w-1`` bucket counts, i.e.
                   exactly the history a ``w``-second sliding-mean window
                   still needs once the next chunk arrives.

    All four live on device; only ``window``/``next_lo`` are host
    bookkeeping. Nothing here is ever transferred between chunks.
    """

    hist: jnp.ndarray
    mom: jnp.ndarray
    psum_tail: jnp.ndarray
    trend_tail: jnp.ndarray
    window: int
    next_lo: int = 0


def chunk_carry_init(n_rows: int, width: int, window: int = 1) -> ChunkCarry:
    """Fresh all-zero carry for ``n_rows`` scenario rows and a ``width``-
    bucket sweep axis. Per-scenario isolation is by construction: every
    scenario row has its own carry lane, and a new sweep (or a new scenario
    batch) starts from a new ``chunk_carry_init`` — never from a reused
    carry."""
    if n_rows < 1 or width < 1:
        raise ValueError("need n_rows >= 1 and width >= 1")
    w = max(int(window), 1)
    return ChunkCarry(
        hist=jnp.zeros((n_rows, width), jnp.int32),
        mom=jnp.zeros((n_rows, 4), jnp.float32),
        psum_tail=jnp.zeros((n_rows,), jnp.int32),
        trend_tail=jnp.zeros((n_rows, w - 1), jnp.int32),
        window=w)


def stream_metrics_chunk(carry: ChunkCarry, ss: jnp.ndarray, valid_counts,
                         lo: int, hi: int) -> ChunkCarry:
    """Fold one chunk's kept scale stamps into the carry — all on device.

    Parameters
    ----------
    carry : ChunkCarry
        State after the previous chunk (``chunk_carry_init`` for the
        first).
    ss : jnp.ndarray, int32, shape (S, N)
        ABSOLUTE scale stamps of this chunk's kept records, device-
        resident; row ``s``'s entries past ``valid_counts[s]`` may hold
        garbage (clipped gather output). Valid stamps must lie in
        ``[lo, hi)`` — guaranteed by NSA upstream, not re-checked here (a
        host check would defeat the device residency).
    valid_counts : array-like int, shape (S,)
        Per-row kept-record count for this chunk; a DEVICE array keeps the
        dispatch sync-free.
    lo, hi : int
        The chunk's absolute bucket range (``hi - lo`` buckets, ragged
        last chunk allowed); consecutive calls must tile the bucket axis
        in order.

    Returns a new :class:`ChunkCarry`: the chunk histogram (from the
    carried-Kahan metrics kernel) lands at columns ``[lo, hi)`` of
    ``hist``; ``mom`` is the kernel's updated Kahan state; ``psum_tail`` /
    ``trend_tail`` advance so the trend scan can continue seamlessly.
    """
    ss = jnp.asarray(ss)
    if ss.ndim != 2:
        raise ValueError(f"ss must be (S, N), got shape {ss.shape}")
    cw = int(hi) - int(lo)
    if cw <= 0:
        raise ValueError(f"empty chunk range [{lo}, {hi})")
    if lo != carry.next_lo:
        raise ValueError(
            f"chunk [{lo}, {hi}) out of order: carry expects lo == "
            f"{carry.next_lo} (chunks must tile the bucket axis in order)")
    if hi > carry.hist.shape[1]:
        raise ValueError(f"chunk [{lo}, {hi}) exceeds the carry's "
                         f"{carry.hist.shape[1]}-bucket axis")
    S, N = ss.shape
    _check_metrics_domain(N)
    cfg = tuning.config_for("metrics_fused", s=S, n=max(N, 1), r=cw)
    tile, block = cfg.record_tile, cfg.bucket_block
    buckets = int(-(-cw // block) * block)
    nvalid = jnp.asarray(valid_counts, jnp.int32).reshape(S, 1)
    local = ss.astype(jnp.int32) - jnp.int32(lo)     # chunk-local bucket ids
    ssb = jnp.where(jnp.arange(N, dtype=jnp.int32)[None, :] < nvalid,
                    local, buckets)                  # padding id >= buckets
    pad = (-N) % tile
    if pad or N == 0:
        ssb = jnp.concatenate(
            [ssb, jnp.full((S, pad or tile), buckets, jnp.int32)], axis=1)
    if on_gpu():
        hist_c, mom = _gpu.stream_metrics_carry_gpu(ssb, carry.mom, buckets,
                                                    bucket_block=block)
    else:
        hist_c, mom = stream_metrics_carry_pallas(ssb, carry.mom, buckets,
                                                  interpret=not _on_tpu(),
                                                  config=cfg)
    chunk_q = hist_c[:, :cw]
    hist = jax.lax.dynamic_update_slice(carry.hist, chunk_q, (0, lo))
    psum_tail = carry.psum_tail + jnp.sum(chunk_q, axis=1, dtype=jnp.int32)
    w = carry.window
    if w > 1:
        ext = jnp.concatenate([carry.trend_tail, chunk_q], axis=1)
        trend_tail = ext[:, -(w - 1):]
    else:
        trend_tail = carry.trend_tail
    return dataclasses.replace(carry, hist=hist, mom=mom,
                               psum_tail=psum_tail, trend_tail=trend_tail,
                               next_lo=int(hi))


def chunk_carry_finalize(carry: ChunkCarry) -> Tuple[jnp.ndarray,
                                                     jnp.ndarray]:
    """(hist int32 (S, width), moments f32 (S, 2)) — the monolithic
    engine's output shapes, recovered from a fully-folded carry: counts
    bit-identical to one whole-timeline dispatch, moments within the
    documented ~1e-5 (the Kahan fold sees the same buckets in the same
    block order, just split across launches)."""
    return carry.hist, carry.mom[:, ::2]


def trend_scan_chunk(q_chunk: jnp.ndarray, window: int, *, tail=None,
                     psum_carry=None, lo: int = 0, is_last: bool = False):
    """Streaming sliding-mean trend: emit the positions a chunk completes.

    The chunked counterpart of :func:`trend_scan_batched_device` for one
    time chunk of the count series. A centered ``w``-window at position
    ``p`` reaches ``half = (w-1)//2`` buckets PAST ``p``, so the emission
    frontier lags the fold frontier by ``half`` positions: after folding
    buckets ``[lo, lo+c)`` the positions ``[max(lo-half, 0), lo+c-half)``
    have their full window available (``is_last=True`` flushes the final
    ``half`` clamped positions). Window sums are int32-exact (the carry
    form of the scan kernel seeds its SMEM carry from ``psum_carry``), so
    concatenating the emitted segments over all chunks is BIT-identical to
    the monolithic trend — provided the total series length is >=
    ``window`` (the monolithic path clamps ``w`` to short series; a
    streaming consumer cannot know the final length mid-stream, so this op
    requires the un-clamped regime).

    Parameters
    ----------
    q_chunk : (S, c) int32 device — this chunk's bucket counts (uniform
        row length; the sweep's aligned chunk grid guarantees this).
    window : int — sliding-mean window ``w`` (>= 1).
    tail : (S, w-1) int32 device — the previous carry's ``trend_tail``
        (``None`` = zeros, first chunk).
    psum_carry : (S,) int32 device — the previous carry's ``psum_tail``
        (``None`` = zeros).
    lo : int — the chunk's first absolute bucket id.
    is_last : bool — flush the final ``half`` positions.

    Returns ``(seg f32 (S, m), start, new_tail, new_total)`` where ``seg``
    covers global trend positions ``[start, start + m)`` (``m`` may be 0
    for a tiny first chunk), and ``new_tail``/``new_total`` feed the next
    call.
    """
    w = int(window)
    if w < 1:
        raise ValueError("window must be >= 1")
    q_chunk = jnp.asarray(q_chunk, jnp.int32)
    if q_chunk.ndim != 2:
        raise ValueError(f"q_chunk must be (S, c), got {q_chunk.shape}")
    S, c = q_chunk.shape
    if tail is None:
        tail = jnp.zeros((S, w - 1), jnp.int32)
    tail = jnp.asarray(tail, jnp.int32)
    if tail.shape != (S, w - 1):
        raise ValueError(f"tail must be (S, {w - 1}), got {tail.shape}")
    if psum_carry is None:
        psum_carry = jnp.zeros((S,), jnp.int32)
    psum_carry = jnp.asarray(psum_carry, jnp.int32).reshape(S)

    # ext covers global buckets [lo - (w-1), lo + c): every window any
    # emittable position needs. Leading zeros (first chunks) reproduce the
    # monolithic lo-clamp exactly — zero counts add nothing to any window.
    ext = jnp.concatenate([tail, q_chunk], axis=1)        # (S, w-1+c)
    base = psum_carry - jnp.sum(tail, axis=1, dtype=jnp.int32)
    n_ext = ext.shape[1]
    cfg = tuning.config_for("trend_scan", s=S, n=max(n_ext, 1))
    tile = cfg.record_tile
    pad = (-n_ext) % tile
    if pad or n_ext == 0:
        ext_p = jnp.concatenate(
            [ext, jnp.zeros((S, pad or tile), jnp.int32)], axis=1)
    else:
        ext_p = ext
    if on_gpu():
        cinc, _ = _gpu.trend_scan_carry_gpu(ext_p, base)
    else:
        cinc, _ = trend_scan_carry_pallas(ext_p, base,
                                          interpret=not _on_tpu(),
                                          config=cfg)
    cinc = cinc[:, :n_ext]                  # inclusive global prefix sums

    half = (w - 1) // 2
    hi_abs = lo + c
    e0 = max(lo - half, 0)
    e1 = hi_abs if is_last else max(hi_abs - half, e0)
    new_tail = ext[:, -(w - 1):] if w > 1 else tail
    new_total = psum_carry + jnp.sum(q_chunk, axis=1, dtype=jnp.int32)
    m = e1 - e0
    if m <= 0:
        return jnp.zeros((S, 0), jnp.float32), e0, new_tail, new_total

    p = jnp.arange(e0, e1, dtype=jnp.int32)[None, :]      # global positions
    # local (ext) indices of the window's exclusive-prefix bounds
    jhi = jnp.minimum(p + half + 1, hi_abs) - lo + (w - 1)
    jlo = p + half - lo                                   # >= 0 by e0 choice

    def cex(j):                             # exclusive prefix at local j
        jb = jnp.broadcast_to(j, (S, m))
        g = jnp.take_along_axis(cinc, jnp.maximum(jb - 1, 0), axis=1)
        return jnp.where(jb > 0, g, base[:, None])

    win = (cex(jhi) - cex(jlo)).astype(jnp.float32)
    return win / jnp.float32(w), e0, new_tail, new_total


# ------------------------------------------------------------ flash decode
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 lengths: jnp.ndarray, *, block_s: int = 512) -> jnp.ndarray:
    """Blocked online-softmax GQA decode attention (see kernel docstring).

    Pads the cache axis to a block multiple; padded positions are masked by
    ``lengths`` automatically.
    """
    s = k.shape[1]
    pad = (-s) % block_s
    if pad:
        zk = jnp.zeros((k.shape[0], pad) + k.shape[2:], k.dtype)
        k = jnp.concatenate([k, zk], axis=1)
        v = jnp.concatenate([v, zk], axis=1)
    return flash_decode_pallas(q, k, v, lengths, block_s=block_s,
                               interpret=not _on_tpu())


__all__ = [
    "ChunkCarry", "HostFallbackWarning", "KeepRuleOverflow",
    "PallasDomainError", "bucket_hist",
    "chunk_carry_finalize", "chunk_carry_init", "compact_mask",
    "compact_mask_batched", "compact_mask_batched_device", "flash_decode",
    "on_accelerator", "on_gpu", "on_tpu",
    "stream_metrics", "stream_metrics_chunk", "trend_scan_chunk",
    "stream_metrics_batched", "stream_metrics_batched_device",
    "stream_sample", "stream_sample_batched", "stream_sample_launch",
    "stream_sample_ref",
    "trend_corr_pairwise", "trend_correlation_batched",
    "trend_correlation_batched_device", "trend_pair_stats", "trend_scan",
    "trend_scan_batched", "trend_scan_batched_device", "volatility_moments",
    "volatility_stats", "warn_host_fallback",
]
