"""NSA — Normalizing and Sampling Stream Data (paper Algorithm 1).

Semantics
---------
Given a bounded stream ``B`` with timestamps ``t`` spanning ``T`` seconds and
a user time range ``max`` (the paper's symbol; here ``max_range``):

1. **Normalize** (Min-Max, paper formula (1), ``min = 0``)::

       scale_stamp_i = floor( (t_i - t_min) / (t_max - t_min) * max_range )

   Min-Max is the only normalization preserving record order and relative
   spacing, which the paper requires ("so that the data is dependent on the
   time series").

2. **Sample** (systematic, per scale-stamp bucket): compression multiplies
   the per-second arrival rate by ``multiple = T / max_range``; sampling
   divides it back. Each bucket keeps ``len(bucket) / multiple`` records,
   chosen every-``multiple``-th ("setting a second as the distance"), so the
   simulated per-second rate matches the *original* per-second rate and
   Tables 1-3 volatility statistics are preserved.

   .. note:: the paper's pseudocode computes ``multiple = Len(B)/max``. With
      ``Len(B)`` = record count, the kept rate would be ``rate/avg_rate`` ≈ 1
      rec/s — contradicting Tables 1-3 where the simulated average equals the
      original per-second average (~25/s for SogouQ). ``Len(B)`` must denote
      the stream's *time length* (the tables' note: "original time range of
      stream data set is 86400s"), i.e. ``multiple = T / max`` — the
      "normalization multiple" of §3.2. We implement that reading; the
      pseudocode-literal reading is available as ``multiple_mode='records'``
      for comparison.

Implementations
---------------
- :func:`nsa_paper` — faithful per-record Python loop, the paper-written
  algorithm (the §Perf baseline; O(n) interpreted).
- :func:`nsa` — vectorized numpy (beyond-paper; same output bit-for-bit).
- :func:`nsa` with ``backend="pallas"`` — the device-resident fast path:
  normalize + keep mask (``ops.stream_sample``) and mask compaction
  (``ops.compact_mask``) run on device; only the O(max_range) per-bucket
  tables and the final column gather touch the host. Bit-identical to the
  numpy path (the kernel snaps its f32 buckets to exact f64 tables).
- :func:`nsa_batched` — S streams in ONE kernel dispatch
  (``ops.stream_sample_batched``) instead of S sequential ones.
- :func:`nsa_sweep` — the full (stream × max_range) scenario grid in ONE
  kernel dispatch: per-scenario bucket tables are padded to the sweep's
  maximum bucket count (masked tail buckets with zero keep budget) and
  every scenario's keep mask compacts through one batched scan, so the
  whole Tables 1-3 sweep costs one normalize→sample→mask→compact→gather
  chain instead of one per ``max_range``.

Backend selection rules
-----------------------
``backend`` on :func:`nsa` / :func:`nsa_batched` (and the passthrough knob
on ``Controller.simulate``/``Controller.run``) accepts:

- ``"auto"``  — the device path when JAX reports a TPU backend, else numpy.
  Off-TPU the Pallas kernels would run in ``interpret`` mode, which is
  correct but slower than vectorized numpy — so auto never picks it on CPU.
- ``"pallas"`` — force the device path (interpret mode off-TPU; this is what
  tests and CPU benchmarks use).
- ``"numpy"`` — force the host path.

Every backend produces bit-identical output for the same arguments.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.streamsim.preprocess import Stream

BACKENDS = ("auto", "numpy", "pallas")


def _resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend == "auto":
        from repro.kernels.ops import on_tpu
        return "pallas" if on_tpu() else "numpy"
    return backend


def scale_stamps(t: np.ndarray, max_range: int) -> np.ndarray:
    """Min-Max normalize timestamps into integer buckets [0, max_range).

    Paper formula (1) with min=0, floored to the containing simulated second.
    """
    t = np.asarray(t, dtype=np.float64)
    if len(t) == 0:
        return np.zeros(0, dtype=np.int64)
    t_min, t_max = float(t[0]), float(t[-1])
    span = t_max - t_min
    if span <= 0.0:
        return np.zeros(len(t), dtype=np.int64)
    ss = np.floor((t - t_min) / span * max_range).astype(np.int64)
    # the record at t_max lands exactly on max_range -> clamp into last bucket
    np.clip(ss, 0, max_range - 1, out=ss)
    return ss


def _multiple(stream_len_records: int, time_range_s: float, max_range: int,
              mode: str) -> float:
    if mode == "time":       # the reading consistent with Tables 1-3
        return max(time_range_s / max_range, 1.0)
    elif mode == "records":  # pseudocode-literal reading, kept for comparison
        return max(stream_len_records / max_range, 1.0)
    raise ValueError(f"multiple_mode must be 'time'|'records', got {mode!r}")


def systematic_keep_mask(ss: np.ndarray, max_range: int, multiple: float,
                         *, keep: str = "systematic") -> np.ndarray:
    """Per-record boolean keep mask implementing the per-bucket sampling.

    ``ss`` must be non-decreasing (it is, since Min-Max is monotone and the
    stream is chronological). Within bucket ``b`` with ``c`` records, keep
    ``k = round(c / multiple)`` records (>=1 if the bucket is non-empty):

    - ``keep='systematic'`` — Bresenham-even selection: record with in-bucket
      rank ``r`` survives iff ``(r*k) % c < k``; exactly ``k`` survive, evenly
      spaced (the paper text's systematic sampling).
    - ``keep='first'``      — keep ranks ``< k`` (the paper pseudocode's
      ``if i > rs then remove`` reading).
    """
    n = len(ss)
    if n == 0:
        return np.zeros(0, dtype=bool)
    counts = np.bincount(ss, minlength=max_range).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(n, dtype=np.int64) - starts[ss]
    c = counts[ss]
    k = np.rint(c / multiple).astype(np.int64)
    k = np.clip(k, 1, None)  # non-empty buckets keep at least one record
    if keep == "systematic":
        return (rank * k) % np.maximum(c, 1) < k
    elif keep == "first":
        return rank < k
    raise ValueError(f"keep must be 'systematic'|'first', got {keep!r}")


def nsa(stream: Stream, max_range: int, *, keep: str = "systematic",
        multiple_mode: str = "time", backend: str = "numpy") -> Stream:
    """Vectorized NSA (Algorithm 1): normalize + sample -> simulated stream Ds.

    Parameters
    ----------
    stream : Stream
        Preprocessed (chronological) original stream.
    max_range : int
        Target simulated time range in seconds (the paper's ``max``); must
        be positive.
    keep : {"systematic", "first"}
        In-bucket sampling rule — Bresenham-even systematic selection (the
        paper text) or keep-first-k (the pseudocode-literal reading). The
        device kernel only implements ``"systematic"``; ``"first"`` always
        takes the numpy path.
    multiple_mode : {"time", "records"}
        How the compression multiple is derived (see the module
        docstring's note on the paper's ``Len(B)`` ambiguity).
    backend : {"numpy", "pallas", "auto"}
        ``"pallas"`` runs normalize → keep-mask → compaction → gather
        device-resident (two fused Pallas dispatches + one XLA scatter);
        ``"auto"`` picks pallas on the TPU, numpy otherwise.

    Returns
    -------
    Stream
        The simulated stream: ``scale_stamp`` filled, records the
        systematic sample; per-second volatility statistics match the
        original's (paper §5.2). **Bit-identical across backends** — the
        kernel snaps its f32 buckets to exact f64 host tables.

    Raises
    ------
    ValueError
        If ``max_range <= 0`` or ``keep``/``multiple_mode`` is unknown.

    Notes
    -----
    Streams outside the device kernels' domain (int32 keep-rule overflow,
    ``max_range`` past the ±1-snap guarantee, on the TPU bucket tables
    wider than the kernel's SMEM budget) raise
    :class:`repro.kernels.ops.PallasDomainError` inside the ops layer;
    this function catches it, warns (:class:`repro.kernels.ops.
    HostFallbackWarning`) and falls back to the numpy path, so the
    bit-identity contract survives out-of-domain inputs.
    """
    if max_range <= 0:
        raise ValueError("max_range must be positive")
    m = _multiple(len(stream), stream.time_range, max_range, multiple_mode)
    if (_resolve_backend(backend) == "pallas" and keep == "systematic"
            and len(stream) > 0):
        from repro.kernels import ops
        try:
            return _nsa_pallas(stream, max_range, m)
        except ops.PallasDomainError as err:
            ops.warn_host_fallback(f"nsa({stream.name!r}, {max_range})",
                                   err)
    ss = scale_stamps(stream.t, max_range)
    mask = systematic_keep_mask(ss, max_range, m, keep=keep)
    return Stream(
        name=stream.name,
        t=stream.t[mask],
        payload={k: v[mask] for k, v in stream.payload.items()},
        scale_stamp=ss[mask],
    )


def _nsa_pallas(stream: Stream, max_range: int, multiple: float) -> Stream:
    """Device-resident NSA: normalize -> mask -> compact -> gather.

    The per-record work (bucketing, keep mask, prefix-sum compaction, index
    scatter) runs in two fused Pallas dispatches plus one XLA scatter; the
    host only builds the O(max_range) exact tables and fancy-indexes the
    payload columns (which may be float64/strings — not device-representable
    without loss) by the device-computed kept indices.
    """
    from repro.kernels import ops

    ss_dev, keep_dev = ops.stream_sample(stream.t, max_range, multiple)
    return _compact_gather(stream, ss_dev, keep_dev)


def _compact_gather(stream: Stream, ss_dev, keep_dev) -> Stream:
    """Shared tail of the device path: compact the keep mask to indices on
    device, gather scale stamps there (delivered as host int64 — the numpy
    path's dtype), and fancy-index the host columns once."""
    import jax.numpy as jnp
    from repro.kernels import ops

    idx_dev, total = ops.compact_mask(keep_dev)
    ss_kept = np.asarray(
        jnp.take(ss_dev, idx_dev[:total], mode="clip")).astype(np.int64)
    idx = np.asarray(idx_dev[:total])
    return Stream(
        name=stream.name,
        t=stream.t[idx],
        payload={k: v[idx] for k, v in stream.payload.items()},
        scale_stamp=ss_kept,
    )


def nsa_batched(streams: Dict[str, Stream], max_range: int, *,
                multiple_mode: str = "time", backend: str = "auto"
                ) -> Dict[str, Stream]:
    """NSA over many concurrent device streams — the IoT-realistic shape.

    Parameters
    ----------
    streams : dict of str -> Stream
        Named streams to compress together.
    max_range : int
        Shared simulated time range (positive).
    multiple_mode : {"time", "records"}
        As in :func:`nsa`.
    backend : {"auto", "numpy", "pallas"}
        On ``"pallas"`` all S keep masks come from ONE batched kernel
        dispatch (2-D grid over streams × record tiles) instead of S
        sequential ones; each stream is then compacted and gathered as in
        :func:`nsa`. Off-TPU ``"auto"`` falls back to per-stream numpy.

    Returns
    -------
    dict of str -> Stream
        **Bit-identical** to ``{k: nsa(s, max_range)}`` for every backend.

    Raises
    ------
    ValueError
        If ``max_range <= 0``.

    Notes
    -----
    Batches containing an empty stream, and batches where any member falls
    outside the device kernels' domain
    (:class:`repro.kernels.ops.PallasDomainError`), fall back to the
    per-stream numpy path wholesale — never silently wrong output.
    """
    if max_range <= 0:
        raise ValueError("max_range must be positive")
    resolved = _resolve_backend(backend)
    if resolved != "pallas" or not streams or \
            any(len(s) == 0 for s in streams.values()):
        return {name: nsa(s, max_range, multiple_mode=multiple_mode,
                          backend="numpy")
                for name, s in streams.items()}
    from repro.kernels import ops

    names = list(streams)
    ts = [streams[n].t for n in names]
    mults = [_multiple(len(streams[n]), streams[n].time_range, max_range,
                       multiple_mode) for n in names]
    try:
        ss_b, keep_b, lengths = ops.stream_sample_batched(ts, max_range,
                                                          mults)
        return {name: _compact_gather(streams[name], ss_b[s],
                                      keep_b[s, :lengths[s]])
                for s, name in enumerate(names)}
    except ops.PallasDomainError as err:
        ops.warn_host_fallback("nsa_batched", err)
        return {name: nsa(s, max_range, multiple_mode=multiple_mode,
                          backend="numpy")
                for name, s in streams.items()}


def nsa_sweep(streams: Dict[str, Stream], max_ranges: Sequence[int], *,
              pairs: Optional[Sequence[Tuple[str, int]]] = None,
              multiple_mode: str = "time", backend: str = "auto"
              ) -> Dict[Tuple[str, int], Stream]:
    """NSA over the full (stream × max_range) scenario grid — ONE dispatch.

    The Tables 1-3 sweep shape: every ``(name, max_range)`` scenario becomes
    one ROW of a single range-padded kernel launch. Rows simulated at a
    smaller ``max_range`` than the sweep's maximum get their bucket tables
    padded to the maximum with masked tail buckets (``counts = 0``, zero
    keep budget), and each row normalizes into its own bucket count carried
    as a kernel scalar — so mixing ``max_range = 1`` with ``max_range =
    3600`` in one launch is exact. All rows' keep masks then compact
    through ONE batched prefix-sum dispatch plus one XLA scatter
    (:func:`repro.kernels.ops.compact_mask_batched_device`).

    Parameters
    ----------
    streams : dict of str -> Stream
        Named source streams.
    max_ranges : sequence of int
        Simulated time ranges; with ``pairs=None`` the scenario grid is the
        cross product ``streams × max_ranges``.
    pairs : sequence of (str, int), optional
        Explicit scenario subset (e.g. only store-missing scenarios) —
        each entry names a stream and its ``max_range``. Overrides the
        cross product; ``max_ranges`` is ignored when given.
    multiple_mode : {"time", "records"}
        As in :func:`nsa`.
    backend : {"auto", "numpy", "pallas"}
        On ``"pallas"`` the whole grid is ONE ``stream_sample`` dispatch
        plus ONE batched compaction; ``"numpy"``/off-TPU ``"auto"`` run the
        per-scenario host path.

    Returns
    -------
    dict of (str, int) -> Stream
        One simulated stream per scenario, **bit-identical** to
        ``nsa(streams[name], max_range)`` — and therefore to the per-range
        :func:`nsa_batched` path — for every backend.

    Raises
    ------
    ValueError
        If any ``max_range`` is not positive.

    Notes
    -----
    Sweeps containing an empty stream, and sweeps where any scenario falls
    outside the device kernels' domain
    (:class:`repro.kernels.ops.PallasDomainError`), fall back to the
    per-scenario numpy path wholesale — never silently wrong output.
    """
    if pairs is None:
        pairs = [(name, mr) for name in streams for mr in max_ranges]
    pairs = [(name, int(mr)) for name, mr in pairs]
    if any(mr <= 0 for _, mr in pairs):
        raise ValueError("max_range must be positive")

    def _host() -> Dict[Tuple[str, int], Stream]:
        return {(name, mr): nsa(streams[name], mr,
                                multiple_mode=multiple_mode,
                                backend="numpy")
                for name, mr in pairs}

    resolved = _resolve_backend(backend)
    if resolved != "pallas" or not pairs or \
            any(len(streams[name]) == 0 for name, _ in pairs):
        return _host()
    from repro.kernels import ops
    try:
        ss_b, idx_b, totals, _ = nsa_sweep_device(
            streams, pairs, multiple_mode=multiple_mode)
    except ops.PallasDomainError as err:
        ops.warn_host_fallback("nsa_sweep", err)
        return _host()
    totals = np.asarray(totals, np.int64)
    idx_b = idx_b[:, :ops.kept_width(totals, idx_b.shape[1])]
    return materialize_sweep(streams, pairs, ops.gather_kept(ss_b, idx_b),
                             idx_b, totals)


def nsa_sweep_device(streams: Dict[str, Stream],
                     pairs: Sequence[Tuple[str, int]], *,
                     multiple_mode: str = "time", device=None):
    """The device leg of the range-padded sweep — NO host gather.

    Dispatches ONE ``stream_sample`` launch plus ONE batched compaction
    for the given scenario rows and returns device-resident handles
    without waiting for the device, so a caller (the sweep engine) can
    dispatch every shard's chain before reading any of them back. The
    caller then reads ``totals``, cuts ``idx`` to the kept width
    (:func:`repro.kernels.ops.kept_width`), gathers the kept scale stamps
    by that cut with :func:`repro.kernels.ops.gather_kept` and chains
    them straight into the fused metrics engine; the payload gather is
    deferred to :func:`materialize_sweep`.

    Parameters
    ----------
    streams, pairs, multiple_mode :
        As in :func:`nsa_sweep` (``pairs`` is required here — this is the
        plan-driven entry point). Streams must be non-empty.
    device : optional
        jax device the whole chain is committed to (one plan shard per
        device).

    Returns
    -------
    (ss, idx, totals, lengths)
        ``ss`` int32 ``(R, N)`` device — every record's scale stamp
        (``gather_kept(ss, idx)`` puts row ``r``'s kept stamps in its
        first ``totals[r]`` entries). ``idx`` int32 ``(R, N)`` device —
        kept-record indices, sentinel ``N`` past each row's total, so
        only its first ``kept_width(totals, N)`` columns hold any.
        ``totals`` int32 ``(R,)`` device (the O(R) kept counts; reading
        them waits for the compaction); ``lengths`` int64 ``(R,)`` host
        source lengths.

    Raises
    ------
    PallasDomainError
        When any scenario falls outside the kernels' exactness domain —
        callers fall back to the numpy path wholesale.
    """
    from repro.kernels import ops

    ts = [streams[name].t for name, _ in pairs]
    mults = [_multiple(len(streams[name]), streams[name].time_range, mr,
                       multiple_mode) for name, mr in pairs]
    ss_b, keep_b, lengths = ops.stream_sample_batched(
        ts, [mr for _, mr in pairs], mults, device=device)
    idx_b, totals = ops.compact_mask_batched_device(keep_b)
    return ss_b, idx_b, totals, lengths


def materialize_sweep(streams: Dict[str, Stream],
                      pairs: Sequence[Tuple[str, int]],
                      ss_kept, idx_b, totals) -> Dict[Tuple[str, int],
                                                      Stream]:
    """The single host pass of the device sweep: gather payload columns.

    Takes the handles of :func:`nsa_sweep_device`, moves the kept stamp /
    index matrices to host ONCE, and fancy-indexes each scenario's
    timestamp and payload columns (which may be float64/strings — not
    device-representable without loss). This is the only place a sweep's
    per-record data crosses to host.
    """
    ss_host = np.asarray(ss_kept).astype(np.int64)
    idx_host = np.asarray(idx_b)
    out = {}
    for r, (name, mr) in enumerate(pairs):
        src, total = streams[name], int(totals[r])
        idx = idx_host[r, :total]
        out[(name, mr)] = Stream(
            name=src.name,
            t=src.t[idx],
            payload={k: v[idx] for k, v in src.payload.items()},
            scale_stamp=ss_host[r, :total],
        )
    return out


@dataclasses.dataclass
class ChunkHandles:
    """Device handles for ONE chunk of a chunked sweep (see ChunkedNSA).

    ``ss_kept``/``idx``/``totals`` are device arrays — reading any of them
    forces a sync, which the pipeline defers until the NEXT chunk's
    dispatch is in flight. ``idx`` entries are LOCAL to the chunk's record
    slice; add ``rec_off[r]`` (host int64) to recover absolute record
    indices into the source stream.
    """
    ss_kept: object          # (R, K) int32 device — kept scale stamps
    idx: object              # (R, K) int32 device — local kept indices
    totals: object           # (R,)    int32 device — kept counts
    rec_off: np.ndarray      # (R,)    int64 host   — record slice offsets
    lo: int                  # chunk bucket range [lo, hi)
    hi: int


class ChunkedNSA:
    """Per-chunk device NSA over a scenario grid — the unbounded-stream form.

    Uploads each row's full-width bucket tables and each dataset's
    rebased f32 timestamps to the device ONCE (rows of one stream share
    its upload, :func:`repro.kernels.ops._nsa_row_inputs`), then serves
    the timeline chunk by chunk: ``chunk(lo, hi)`` runs the range-padded
    ``stream_sample`` kernel on just the record slice whose scale stamps
    land in ``[lo, hi)`` and compacts its keep mask — all
    device-resident, no host sync (totals stay on device; see
    :func:`repro.kernels.ops.compact_mask_batched_device`).

    Bit-exactness with the monolithic sweep: a chunk's records are a
    CONTIGUOUS slice ``[starts[lo], starts[hi])`` of the sorted stream
    (records never split a bucket), and the kernel is launched with the
    full-width tables rebased by the slice offset — so each record sees
    the same f32 timestamp, the same snapped bucket and the same
    in-bucket rank as in the monolithic launch, and the keep bits are
    bit-identical. Concatenating the chunks reproduces
    :func:`nsa_sweep_device` exactly.

    Parameters
    ----------
    streams : dict of str -> Stream
        Source streams (non-empty).
    pairs : sequence of (name, eff_range)
        Scenario rows; ``eff_range`` is the row's EFFECTIVE simulated
        range (``ScenarioSpec.span_s`` — ``max_range`` per simulated day).
    multiple_mode : {"time", "records"}
        As in :func:`nsa`.
    device : optional
        jax device everything is committed to.

    Raises
    ------
    PallasDomainError
        At construction, when any row falls outside the kernels'
        exactness domain — callers fall back to the host path before any
        chunk state exists.
    """

    def __init__(self, streams: Dict[str, Stream],
                 pairs: Sequence[Tuple[str, int]], *,
                 multiple_mode: str = "time", device=None):
        import jax
        import jax.numpy as jnp
        from repro.kernels import ops

        self.pairs = [(name, int(rng)) for name, rng in pairs]
        if not self.pairs:
            raise ValueError("need at least one scenario row")
        if any(rng <= 0 for _, rng in self.pairs):
            raise ValueError("ranges must be positive")
        ts = [np.asarray(streams[name].t, np.float64)
              for name, _ in self.pairs]
        if any(len(t) == 0 for t in ts):
            raise ValueError("chunked path requires non-empty streams")
        self.lengths = np.array([len(t) for t in ts], np.int64)
        self.width = max(rng for _, rng in self.pairs)
        R = len(self.pairs)
        self.N = max(int(-(-self.lengths.max() // ops.TILE) * ops.TILE),
                     ops.TILE)
        ops._check_metrics_domain(self.N)  # any chunk's kept width <= N
        mults = [_multiple(len(streams[name]), streams[name].time_range,
                           rng, multiple_mode)
                 for name, rng in self.pairs]

        def _dev(x):
            return jax.device_put(x, device) if device is not None \
                else jnp.asarray(x)

        self._dev = _dev
        self._t, starts_b, counts_b, k_b, scal_b = ops._nsa_row_inputs(
            ts, [rng for _, rng in self.pairs], mults, self.width, self.N,
            _dev)
        # host copy for slicing: col lo gives the first record of bucket
        # lo (tail buckets carry starts = n, so rows whose range ends
        # before the sweep's maximum contribute empty slices for free)
        self._starts_np = starts_b.astype(np.int64)
        # kept records through each bucket: a non-empty bucket keeps
        # exactly min(k, c) = k records, an empty one none — so a chunk's
        # kept width is known here, with no device sync
        self._kept_cum = np.zeros((R, self.width + 1), np.int64)
        np.cumsum(np.minimum(k_b, counts_b), axis=1,
                  out=self._kept_cum[:, 1:])

        self._starts = _dev(starts_b)
        self._counts = _dev(counts_b)
        self._ktab = _dev(k_b)
        self._scal = _dev(scal_b)

    def n_chunks(self, chunk_s: int) -> int:
        return -(-self.width // int(chunk_s))

    def chunk(self, lo: int, hi: int) -> ChunkHandles:
        """Dispatch NSA for absolute buckets ``[lo, hi)`` — async, no sync.

        The returned handles stay on device; the host reads them via
        :func:`materialize_sweep_chunk` one pipeline step later.
        """
        import jax.numpy as jnp
        from repro.kernels import ops

        lo, hi = int(lo), int(hi)
        if not 0 <= lo < hi <= self.width:
            raise ValueError(f"bad chunk range [{lo}, {hi}) for width "
                             f"{self.width}")
        a = self._starts_np[:, lo]
        b = self.lengths if hi >= self.width else self._starts_np[:, hi]
        m = b - a
        tile = ops.TILE
        Nc = max(int(-(-max(int(m.max()), 1) // tile) * tile), tile)
        a_dev = self._dev(a.astype(np.int32))
        # rebase the bucket tables by the slice offset: local rank ==
        # global rank, so the keep bits match the monolithic launch
        starts_reb = self._starts - a_dev[:, None]
        ss, keep = ops.stream_sample_launch(
            ops.slice_records(self._t, a_dev, Nc), starts_reb,
            self._counts, self._ktab, self._scal, self.width)
        j = jnp.arange(Nc, dtype=jnp.int32)[None, :]
        keep = keep.astype(bool) & \
            (j < self._dev(m.astype(np.int32))[:, None])
        idx, totals = ops.compact_mask_batched_device(keep)
        # only the first ``kept`` columns of each row hold kept records
        kept = int((self._kept_cum[:, hi] - self._kept_cum[:, lo]).max())
        idx = idx[:, :max(int(-(-kept // tile) * tile), tile)]
        return ChunkHandles(ss_kept=ops.gather_kept(ss, idx), idx=idx,
                            totals=totals, rec_off=a, lo=lo, hi=hi)


def materialize_sweep_chunk(streams: Dict[str, Stream],
                            pairs: Sequence[Tuple[str, int]],
                            handles: ChunkHandles,
                            totals: np.ndarray) -> List[Stream]:
    """Host gather for ONE chunk — the pipeline's only sync point.

    ``totals`` is the host copy of ``handles.totals`` (the caller reads
    it first so the device sync happens exactly once per chunk, after the
    next chunk's dispatch is already in flight). Returns one Stream per
    scenario row, in ``pairs`` order.
    """
    ss_host = np.asarray(handles.ss_kept).astype(np.int64)
    idx_host = np.asarray(handles.idx)
    out = []
    for r, (name, _) in enumerate(pairs):
        src, total = streams[name], int(totals[r])
        gi = idx_host[r, :total].astype(np.int64) + int(handles.rec_off[r])
        out.append(Stream(
            name=src.name,
            t=src.t[gi],
            payload={k: v[gi] for k, v in src.payload.items()},
            scale_stamp=ss_host[r, :total],
        ))
    return out


def nsa_paper(stream: Stream, max_range: int, *, keep: str = "systematic",
              multiple_mode: str = "time") -> Stream:
    """Paper-faithful per-record NSA: literal loops mirroring Algorithm 1.

    Bit-identical output to :func:`nsa`; kept as the §Perf baseline and as
    executable documentation of the paper's pseudocode.
    """
    if max_range <= 0:
        raise ValueError("max_range must be positive")
    n = len(stream)
    t = stream.t
    if n == 0:
        return Stream(stream.name, t[:0],
                      {k: v[:0] for k, v in stream.payload.items()},
                      np.zeros(0, dtype=np.int64))
    t_min, t_max = float(t[0]), float(t[-1])
    span = t_max - t_min
    # --- "Normalizing original stream data." (per-record loop) ---
    ss = np.empty(n, dtype=np.int64)
    for i in range(n):  # For s_i in B do
        if span <= 0.0:
            ss[i] = 0
        else:
            v = (t[i] - t_min) / span * max_range  # formula (1), min=0
            ss[i] = min(int(v), max_range - 1)
    # --- "Sampling normalized stream data." (per-bucket loop) ---
    m = _multiple(n, span, max_range, multiple_mode)
    keep_idx = []
    lo = 0
    for b in range(max_range):  # For i <- 0 to max do
        hi = lo
        while hi < n and ss[hi] == b:
            hi += 1
        c = hi - lo  # block = B[scale_stamp == i]
        if c > 0:
            k = max(int(round(c / m)), 1)  # rs = Len(block)/multiple
            for r in range(c):  # For s_i in block do
                if keep == "systematic":
                    if (r * k) % c < k:
                        keep_idx.append(lo + r)
                elif keep == "first":
                    if r < k:  # paper: "If i > rs then remove"
                        keep_idx.append(lo + r)
                else:
                    raise ValueError(f"bad keep {keep!r}")
        lo = hi
    idx = np.asarray(keep_idx, dtype=np.int64)
    return Stream(
        name=stream.name,
        t=t[idx],
        payload={k: v[idx] for k, v in stream.payload.items()},
        scale_stamp=ss[idx],
    )


def compression_factor(stream: Stream, max_range: int) -> float:
    """The task speedup the simulation buys: original range / simulated range.

    The paper's headline: one day into <=1 h  =>  >= 24x (§6).
    """
    return stream.time_range / float(max_range)


def expected_kept(stream: Stream, max_range: int) -> int:
    """Rough expected record count after NSA (for capacity planning)."""
    m = _multiple(len(stream), stream.time_range, max_range, "time")
    return int(math.ceil(len(stream) / m))
