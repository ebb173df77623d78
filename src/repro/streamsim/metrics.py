"""Volatility & trend metrics (paper §5.2, formulas (2)-(4)).

The paper evaluates simulation quality with three per-second statistics —
Average, Variance, Standard Variance — over the arrival-count series
``q_i`` (records in second ``i``). Formulas (3)/(4) in the paper text drop
the square on the deviation (an obvious typesetting slip); we implement the
standard population variance/σ, which reproduces the tables' magnitudes.

Backends
--------
Every metric takes the same ``backend="numpy|pallas|auto"`` knob as
:func:`repro.streamsim.nsa.nsa`:

- ``"numpy"`` — vectorized host path (one ``bincount`` pass + exact f64
  moments).
- ``"pallas"`` — the fused device engine
  (:func:`repro.kernels.ops.stream_metrics`): histogram AND moments from one
  pass over the record tiles, int32-exact counts.
- ``"auto"`` — pallas on TPU, numpy otherwise.

Counts are **bit-exact** across backends; the engine's raw ``[Σq, Σq²]``
moments agree with exact f64 within ~1e-5 relative (pairwise-block + Kahan
f32 reduction in the kernel); derived moments (average / variance / σ)
keep the documented 1e-3 relative tolerance (the variance subtraction can
amplify the moment error).

:func:`metrics_batched` evaluates S streams — possibly with different time
ranges — through ONE batched engine dispatch; the sweep engine
(:mod:`repro.streamsim.engine`) uses it for host-side streams (originals,
store-cache hits) and the device-input ops forms
(``ops.stream_metrics_batched_device``, ``ops.trend_corr_pairwise``) for
store-missing scenarios, so the whole reporting path re-reads each stream
once instead of ~4 times and never gathers kept stamps to host.

:func:`trend` is an O(n) cumulative-sum sliding mean (window sums via two
prefix-sum lookups), replacing the seed's O(n·window) ``np.convolve``. On
the pallas backend the cumsum is the device scan kernel
(:mod:`repro.kernels.trend_scan`); only the O(time_range) count series
crosses host for the domain guard — the O(records) histogramming and the
scan itself stay on device.

:func:`trend_correlation_matrix` evaluates the Fig.-6 "similar trend"
claim for ALL S×S stream pairs at once: on the pallas backend the whole
chain — counts → prefix-sum scan → sliding-mean trends → resample →
centered Gram matrix — is one batched device dispatch chain (no per-pair
host loop); the numpy backend mirrors it in float64. Out-of-domain inputs
(totals past the int32 prefix-sum limit) fall back to numpy via
:class:`repro.kernels.ops.PallasDomainError`, like every other metric.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from repro.streamsim.nsa import BACKENDS, _resolve_backend  # noqa: F401
from repro.streamsim.preprocess import Stream


@dataclasses.dataclass(frozen=True)
class Volatility:
    average: float
    variance: float
    std_variance: float
    time_range: int

    def as_row(self) -> str:
        return (f"{self.time_range},{self.average:.2f},"
                f"{self.variance:.2f},{self.std_variance:.2f}")


@dataclasses.dataclass(frozen=True)
class StreamMetrics:
    """One stream's reporting bundle from a single engine pass."""

    counts: np.ndarray          # int64 (time_range,) per-second counts q_i
    volatility: Volatility


# --------------------------------------------------------------- bucketing
def _bucket_series(stream: Stream, time_range: Optional[int],
                   use_scale_stamp: Optional[bool]):
    """Integer bucket per record + the series length (shared by backends).

    For simulated streams the bucket is ``scale_stamp``; for original
    streams it is ``floor(t - t_0)``. Returns ``(buckets int64, time_range)``
    — ``time_range`` 0 means the empty/degenerate series.
    """
    if use_scale_stamp is None:
        use_scale_stamp = stream.scale_stamp is not None
    if use_scale_stamp:
        if stream.scale_stamp is None:
            raise ValueError("stream has no scale_stamp; run NSA first")
        buckets = np.asarray(stream.scale_stamp, np.int64)
        if time_range is None:
            time_range = int(buckets.max()) + 1 if len(buckets) else 0
        elif len(buckets):
            # scale stamps are never clipped to a user time range (seed
            # bincount semantics: the series covers max(tr, max stamp + 1)
            # seconds), so a too-small tr expands rather than mis-binning
            # on numpy or raising on pallas
            time_range = max(time_range, int(buckets.max()) + 1)
    else:
        if len(stream.t) == 0:
            return np.zeros(0, np.int64), (time_range or 0)
        buckets = np.floor(stream.t - stream.t[0]).astype(np.int64)
        if time_range is None:
            time_range = int(buckets.max()) + 1
        buckets = np.clip(buckets, 0, time_range - 1)
    return buckets, time_range


def _volatility_from_moments(s: float, s2: float, tr: int) -> Volatility:
    if tr <= 0:
        return Volatility(0.0, 0.0, 0.0, 0)
    avg = s / tr
    var = max(s2 / tr - avg * avg, 0.0)
    return Volatility(float(avg), float(var), float(np.sqrt(var)), tr)


def _numpy_metrics(buckets: np.ndarray, tr: int) -> StreamMetrics:
    q = np.bincount(buckets, minlength=tr)
    s = float(q.sum())
    s2 = float((q.astype(np.float64) ** 2).sum())
    return StreamMetrics(q, _volatility_from_moments(s, s2, tr))


# ------------------------------------------------------------- public API
def per_second_counts(stream: Stream, time_range: Optional[int] = None,
                      *, use_scale_stamp: Optional[bool] = None,
                      backend: str = "numpy") -> np.ndarray:
    """Arrival counts q_i per (simulated or original) second.

    Parameters
    ----------
    stream : Stream
    time_range : int, optional
        Series length. ``None`` infers it: the NSA ``max_range``
        convention (``max scale_stamp + 1``) for simulated streams, the
        spanned seconds for originals. A ``time_range`` smaller than the
        largest scale stamp *expands* (seed bincount semantics) rather
        than mis-binning.
    use_scale_stamp : bool, optional
        Force bucketing by ``scale_stamp`` (simulated) or by wall time
        (original); ``None`` picks by whether ``scale_stamp`` is set.
    backend : {"numpy", "pallas", "auto"}
        ``"pallas"`` counts through the fused device engine
        (:func:`repro.kernels.ops.stream_metrics`, int32 accumulation —
        exact up to 2³¹ per bucket, guarded); ``"auto"`` is pallas on TPU.

    Returns
    -------
    np.ndarray, int64, shape (time_range,)
        **Bit-exact across backends.**
    """
    buckets, tr = _bucket_series(stream, time_range, use_scale_stamp)
    if _resolve_backend(backend) == "pallas" and tr > 0:
        from repro.kernels import ops
        hist, _ = ops.stream_metrics(buckets, tr)
        return np.asarray(hist, np.int64)
    return np.bincount(buckets, minlength=tr)


def volatility(stream: Stream, time_range: Optional[int] = None,
               *, backend: str = "numpy") -> Volatility:
    """Average / Variance / StdVariance of q_i (paper formulas (2)-(4)).

    Parameters
    ----------
    stream, time_range :
        As in :func:`per_second_counts`.
    backend : {"numpy", "pallas", "auto"}
        ``"numpy"`` reduces exact f64 moments on host; ``"pallas"`` reads
        the ``[Σq, Σq²]`` pair the fused engine produced in the same
        record pass as the histogram (f32 reduction — agrees with numpy
        within 1e-3 relative).

    Returns
    -------
    Volatility
        ``average``, ``variance``, ``std_variance`` over the count series,
        plus the ``time_range`` they were normalized by.
    """
    buckets, tr = _bucket_series(stream, time_range, None)
    if _resolve_backend(backend) == "pallas" and tr > 0:
        from repro.kernels import ops
        _, mom = ops.stream_metrics(buckets, tr)
        mom = np.asarray(mom, np.float64)
        return _volatility_from_moments(mom[0], mom[1], tr)
    return _numpy_metrics(buckets, tr).volatility


def metrics_batched(streams: Sequence[Stream],
                    time_ranges: Sequence[Optional[int]],
                    *, use_scale_stamps: Optional[Sequence[Optional[bool]]]
                    = None, backend: str = "auto") -> List[StreamMetrics]:
    """Counts + volatility for S streams from ONE batched engine call.

    Parameters
    ----------
    streams : sequence of Stream
        Ragged lengths, mixed simulated/original, and empty/degenerate
        members are all allowed.
    time_ranges : sequence of int or None
        Per-stream series length (``None`` infers it — see
        :func:`per_second_counts`). Must align with ``streams``.
    use_scale_stamps : sequence of bool or None, optional
        Per-stream ``use_scale_stamp`` override.
    backend : {"numpy", "pallas", "auto"}
        On ``"pallas"`` all S histograms and moment pairs come from a
        single 2-D-grid kernel dispatch padded to the largest time range —
        trailing zero buckets perturb neither counts nor moments;
        per-stream statistics divide by the true range. Inputs outside the
        engine's int32 domain fall back to numpy wholesale (the ops layer
        raises :class:`~repro.kernels.ops.PallasDomainError`, caught
        here).

    Returns
    -------
    list of StreamMetrics
        ``counts`` bit-exact across backends; ``volatility`` within 1e-3.

    Raises
    ------
    ValueError
        If ``streams`` and ``time_ranges`` lengths differ.
    """
    if len(streams) != len(time_ranges):
        raise ValueError("streams and time_ranges must align")
    if use_scale_stamps is None:
        use_scale_stamps = [None] * len(streams)
    series = [_bucket_series(s, tr, uss)
              for s, tr, uss in zip(streams, time_ranges, use_scale_stamps)]
    resolved = _resolve_backend(backend)
    max_tr = max((tr for _, tr in series), default=0)
    if resolved != "pallas" or max_tr == 0 or not series:
        return [_numpy_metrics(b, tr) for b, tr in series]
    from repro.kernels import ops
    try:
        hist, mom, _ = ops.stream_metrics_batched([b for b, _ in series],
                                                  max_tr)
    except ops.PallasDomainError as err:
        ops.warn_host_fallback("metrics_batched", err)
        return [_numpy_metrics(b, tr) for b, tr in series]
    hist = np.asarray(hist, np.int64)
    mom = np.asarray(mom, np.float64)
    return [StreamMetrics(hist[s, :tr],
                          _volatility_from_moments(mom[s, 0], mom[s, 1], tr))
            for s, (_, tr) in enumerate(series)]


# ------------------------------------------------------------------- trend
def sliding_mean(q: np.ndarray, window: int) -> np.ndarray:
    """O(n) cumulative-sum sliding mean, same semantics as
    ``np.convolve(q, np.ones(w)/w, mode="same")`` (zero-padded edges,
    constant 1/w weight) but without the O(n·w) inner product."""
    n = len(q)
    if n == 0:
        return q.astype(np.float64)
    w = max(min(window, n), 1)
    half = (w - 1) // 2
    # out[i] = (c[min(i+half+1, n)] - c[max(i+half+1-w, 0)]) / w over the
    # exclusive prefix sums c, written as three plain slice subtractions
    # (clamped head / core / clamped tail) with no index-array gathers and
    # only two allocations, so the O(n) path stays memory-bound
    c = np.empty(n + 1, np.float64)
    c[0] = 0.0
    np.cumsum(q, out=c[1:])
    out = np.empty(n, np.float64)
    head, tail = w - half - 1, half
    np.subtract(c[w:], c[:n + 1 - w], out=out[head:n - tail])
    out[:head] = c[half + 1:w]                       # lo clamped to 0
    np.subtract(c[n], c[n + 1 - w:n + 1 - w + tail],
                out=out[n - tail:])                  # hi clamped to n
    out /= w
    return out


def trend(stream: Stream, window_s: int = 600,
          time_range: Optional[int] = None,
          *, backend: str = "numpy") -> np.ndarray:
    """Moving-average trend of the per-second counts (the Figs. 1-3 curves).

    Parameters
    ----------
    stream : Stream
        Simulated (``scale_stamp`` set) or original stream.
    window_s : int, default 600
        Sliding-mean window in (simulated) seconds; clamped per series to
        ``max(min(window_s, n), 1)``.
    time_range : int, optional
        Series length; ``None`` infers it (see :func:`per_second_counts`).
    backend : {"numpy", "pallas", "auto"}
        ``"numpy"`` computes counts + an O(n) host cumsum sliding mean in
        float64. ``"pallas"`` chains the fused metrics engine into the
        device prefix-sum scan kernel (:func:`repro.kernels.ops.
        trend_scan`) — window sums are int32-exact, the final divide is
        f32, so the result agrees with numpy within 1e-3 relative.
        ``"auto"`` is pallas on TPU, numpy otherwise.

    Returns
    -------
    np.ndarray, float64, shape (time_range,)

    Notes
    -----
    Inputs past the device domain (total counts ≥ 2³¹) raise
    :class:`~repro.kernels.ops.PallasDomainError` inside the ops layer;
    this function catches it and falls back to the numpy path, so callers
    never see silently wrong trends.
    """
    buckets, tr = _bucket_series(stream, time_range, None)
    if _resolve_backend(backend) == "pallas" and tr > 0:
        from repro.kernels import ops
        try:
            hist, _ = ops.stream_metrics(buckets, tr)
            return np.asarray(ops.trend_scan(np.asarray(hist),
                                             max(window_s, 1)), np.float64)
        except ops.PallasDomainError as err:
            ops.warn_host_fallback("trend", err)
    q = np.bincount(buckets, minlength=tr)
    return sliding_mean(q.astype(np.float64), window_s)


def trend_correlation_from_counts(qa: np.ndarray, qb: np.ndarray,
                                  window_s: int = 60) -> float:
    """Pearson correlation between two count series' trends, resampled to
    the shorter series — quantifies the paper's 'similar trend' claim
    (Fig. 6). Takes precomputed counts so a batched metrics call can feed
    both streams without re-reading them."""
    ta = sliding_mean(np.asarray(qa, np.float64), window_s)
    tb = sliding_mean(np.asarray(qb, np.float64), window_s)
    if len(ta) == 0 or len(tb) == 0:
        return float("nan")
    n = min(len(ta), len(tb))
    # resample both to n points
    ra = np.interp(np.linspace(0, 1, n), np.linspace(0, 1, len(ta)), ta)
    rb = np.interp(np.linspace(0, 1, n), np.linspace(0, 1, len(tb)), tb)
    ra -= ra.mean()
    rb -= rb.mean()
    denom = np.sqrt((ra ** 2).sum() * (rb ** 2).sum())
    return float((ra * rb).sum() / denom) if denom > 0 else float("nan")


def trend_correlation(a: Stream, b: Stream, window_s: int = 60,
                      *, backend: str = "numpy") -> float:
    """Trend correlation of two streams.

    When counts are already in hand use
    :func:`trend_correlation_from_counts` (numpy) or
    :func:`trend_correlation_matrix` (batched, either backend).

    Parameters
    ----------
    a, b : Stream
    window_s : int, default 60
        Sliding-mean window for both trends.
    backend : {"numpy", "pallas", "auto"}
        ``"pallas"`` runs the device chain of
        :func:`trend_correlation_matrix` on the pair (one batched dispatch,
        agreeing with numpy within 1e-3); out-of-domain inputs fall back to
        the numpy path automatically.

    Returns
    -------
    float
        Pearson r in [-1, 1]; NaN when either series is empty or has zero
        trend variance.
    """
    qa = per_second_counts(a, backend=backend)
    qb = per_second_counts(b, backend=backend)
    if _resolve_backend(backend) == "pallas":
        from repro.kernels import ops
        try:
            return float(ops.trend_correlation_batched(
                [qa, qb], max(window_s, 1))[0, 1])
        except ops.PallasDomainError as err:
            ops.warn_host_fallback("trend_correlation", err)
    return trend_correlation_from_counts(qa, qb, window_s)


# ------------------------------------------------- S x S correlation matrix
def _corr_matrix_numpy(counts: Sequence[np.ndarray], window_s: int,
                       n_points: Optional[int]) -> np.ndarray:
    """Float64 host mirror of :func:`repro.kernels.ops.
    trend_correlation_batched`: same resample-to-common-grid convention,
    same NaN/clip/diagonal contract."""
    from repro.kernels.ops import _corr_from_gram
    trends = [sliding_mean(np.asarray(q, np.float64), window_s)
              for q in counts]
    S = len(trends)
    live = [s for s in range(S) if len(trends[s])]
    if not live:
        return np.full((S, S), np.nan)
    K = int(n_points) if n_points is not None else \
        min(len(trends[s]) for s in live)
    if K < 1:
        raise ValueError("n_points must be >= 1")
    grid = np.linspace(0.0, 1.0, K)
    z = np.stack([np.interp(grid, np.linspace(0.0, 1.0, len(trends[s])),
                            trends[s]) for s in live])
    z -= z.mean(axis=1, keepdims=True)
    return _corr_from_gram(z @ z.T, np.asarray(live), S)


def trend_correlation_matrix(counts: Sequence[np.ndarray],
                             window_s: int = 60, *,
                             n_points: Optional[int] = None,
                             backend: str = "auto") -> np.ndarray:
    """Pearson trend-correlation matrix for ALL S×S count-series pairs.

    The batched form of the Fig.-6 fidelity check: every series' sliding-
    mean trend is resampled onto a common uniform grid (``n_points``
    points, default the shortest non-empty series' length), mean-centered,
    and correlated against every other.

    Parameters
    ----------
    counts : sequence of 1-D integer arrays
        Per-second count series (e.g. ``StreamMetrics.counts`` rows from
        :func:`metrics_batched`), ragged lengths allowed.
    window_s : int, default 60
        Sliding-mean window applied to every series (must be >= 1).
    n_points : int, optional
        Common resampling grid size; defaults to the shortest non-empty
        series' length, which for two series reproduces the pairwise
        :func:`trend_correlation_from_counts` convention.
    backend : {"numpy", "pallas", "auto"}
        ``"pallas"`` runs counts → prefix-sum scan → trends → resample →
        centered S×S Gram through ONE batched device dispatch chain
        (:func:`repro.kernels.ops.trend_correlation_batched`) — no
        per-pair host loop and no host cumsum. ``"numpy"`` mirrors the
        convention in float64; the backends agree within 1e-3.

    Returns
    -------
    np.ndarray, float64, shape (S, S)
        Symmetric, clipped to [-1, 1], diagonal exactly 1 for series with
        non-zero trend variance; rows/columns of empty or zero-variance
        series are NaN.

    Raises
    ------
    ValueError
        If ``window_s < 1`` or ``n_points < 1``. Device-domain violations
        (totals ≥ 2³¹) do NOT raise here — they fall back to numpy.
    """
    if window_s < 1:
        raise ValueError("window_s must be >= 1")
    counts = [np.asarray(q).reshape(-1) for q in counts]
    if _resolve_backend(backend) == "pallas" and counts:
        from repro.kernels import ops
        try:
            return ops.trend_correlation_batched(counts, window_s, n_points)
        except ops.PallasDomainError as err:
            ops.warn_host_fallback("trend_correlation_matrix", err)
    return _corr_matrix_numpy(counts, window_s, n_points)
