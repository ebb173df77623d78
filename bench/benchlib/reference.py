"""Plain reference of the simulator's semantics, independent of the program.

Straight numpy, float64 throughout, written from the paper
(arXiv:2205.14244 §3-§5) and the program's documented semantics, importing
nothing of the program:

- POSD: find the time column, parse accurate-time strings to epoch
  seconds, undo the UserBehavior UTC+8 zone; a multi-day original is one
  preprocessed day per 86 400 s, each rebased onto its own day slot.
- NSA: Min-Max normalise into ``max_range`` buckets, keep
  ``k = max(1, rint(c / multiple))`` of each bucket's ``c`` records by the
  Bresenham-even rule, ``multiple = max(T / max_range, 1)``.
- statistics: per-second counts, their mean / variance / standard
  deviation; trend = 60 s zero-padded moving average (``np.convolve``);
  trend correlation = Pearson r after linear resampling to the shorter
  series; the fidelity matrix = the same over every original and sim of
  one ``max_range``.

``Arith`` carries the precision. ``F64`` is the reference. ``LOW`` is the
control of the correctness check: the same reference one precision step
down, the step a later change would be tempted to take — the NSA
normalisation in float32 (no exact float64 bucket tables) and the
statistics and trends in bfloat16 (every sum a pairwise sum rounded to
bfloat16 at each level).
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DAY = 86_400
TZ_OFFSETS = {"userbehavior": 8 * 3600.0}
TREND_WINDOW_S = 60


@dataclasses.dataclass(frozen=True)
class Arith:
    name: str
    nsa_dtype: type          # dtype of the normalisation arithmetic
    stat_dtype: Optional[object]   # None: exact float64 sums

    def round(self, x):
        if self.stat_dtype is None:
            return np.asarray(x, np.float64)
        return np.asarray(x).astype(self.stat_dtype).astype(np.float64)

    def sum(self, x, axis=-1):
        """Sum along ``axis``: float64, or pairwise with every partial
        rounded to the low precision."""
        x = self.round(np.asarray(x, np.float64))
        if self.stat_dtype is None:
            return x.sum(axis=axis)
        x = np.moveaxis(x, axis, -1)
        while x.shape[-1] > 1:
            if x.shape[-1] % 2:
                x = np.concatenate([x, np.zeros(x.shape[:-1] + (1,))], -1)
            x = self.round(x[..., 0::2] + x[..., 1::2])
        return x[..., 0]


F64 = Arith("float64", np.float64, None)


def _bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


LOW = Arith("float32 NSA / bfloat16 statistics", np.float32, _bf16())


@dataclasses.dataclass
class Sim:
    t: np.ndarray
    payload: Dict[str, np.ndarray]
    scale_stamp: np.ndarray

    def __len__(self):
        return len(self.t)


# ----------------------------------------------------------------- POSD
def _is_time(col: np.ndarray) -> bool:
    head = col[:64]
    if col.dtype.kind in "US":
        try:
            np.array(np.char.replace(head.astype(str), " ", "T"),
                      dtype="datetime64[s]")
            return True
        except ValueError:
            return False
    if col.dtype.kind in "if" and len(head):
        h = head.astype(np.float64)
        return bool(np.all((h > 6.0e8) & (h < 4.2e9)) and
                    np.all(np.diff(h) >= 0))
    return False


def posd(name: str, columns: Dict[str, np.ndarray]):
    """(t float64 epoch seconds, payload) of one raw day."""
    hinted = [c for c in columns
              if any(h in c.lower() for h in ("time", "timestamp", "ts",
                                              "date"))]
    order = hinted + [c for c in columns if c not in hinted]
    tcol = next((c for c in order if _is_time(columns[c])), None)
    if tcol is None:
        raise ValueError(f"{name}: no time column")
    col = columns[tcol]
    if col.dtype.kind in "US":
        t = np.array(np.char.replace(col.astype(str), " ", "T"),
                     dtype="datetime64[s]").astype(np.int64).astype(
                         np.float64)
    else:
        t = col.astype(np.float64)
    t = t - TZ_OFFSETS.get(name, 0.0)
    payload = {k: v for k, v in columns.items() if k != tcol}
    if len(t) > 1 and np.any(np.diff(t) < 0):
        o = np.argsort(t, kind="stable")
        t, payload = t[o], {k: v[o] for k, v in payload.items()}
    return t, payload


def original(name: str, raw_days: Sequence[Dict[str, np.ndarray]],
             duration_s: int = 0):
    """The preprocessed original: one day as parsed, or ``duration_s``
    seconds of days rebased onto consecutive day slots."""
    if not duration_s:
        return posd(name, raw_days[0])
    ts, pays = [], []
    for d, cols in enumerate(raw_days):
        t, p = posd(name, cols)
        ts.append(np.minimum(t - t[0], float(DAY)) + d * float(DAY))
        pays.append(p)
    t = np.concatenate(ts)
    keep = t < float(duration_s)
    payload = {k: np.concatenate([p[k] for p in pays])[keep]
               for k in pays[0]}
    return t[keep], payload


# ------------------------------------------------------------------ NSA
def nsa(t: np.ndarray, payload: Dict[str, np.ndarray], max_range: int,
        arith: Arith = F64) -> Sim:
    n = len(t)
    span = float(t[-1] - t[0])
    if span <= 0:
        ss = np.zeros(n, np.int64)
    else:
        dt = arith.nsa_dtype
        rel = (t - t[0]).astype(dt)
        ss = np.floor(rel / dt(span) * dt(max_range)).astype(np.int64)
        ss = np.clip(ss, 0, max_range - 1)
    multiple = max(span / max_range, 1.0)
    counts = np.bincount(ss, minlength=max_range)
    first = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(n) - first[ss]
    c = counts[ss]
    k = np.maximum(np.rint(c / multiple).astype(np.int64), 1)
    keep = (rank * k) % np.maximum(c, 1) < k
    return Sim(t[keep], {k2: v[keep] for k2, v in payload.items()},
               ss[keep])


# ----------------------------------------------------------- statistics
def counts_original(t: np.ndarray) -> np.ndarray:
    b = np.floor(t - t[0]).astype(np.int64)
    return np.bincount(b, minlength=int(b.max()) + 1)


def counts_sim(sim: Sim, span: int) -> np.ndarray:
    tr = max(int(span), int(sim.scale_stamp.max()) + 1 if len(sim) else 0)
    return np.bincount(sim.scale_stamp, minlength=tr)


def volatility(q: np.ndarray, arith: Arith = F64) -> Tuple[float, ...]:
    """(average, variance, standard deviation) of a count series."""
    n = len(q)
    avg = float(arith.round(arith.sum(q) / n))
    var = float(arith.round(arith.sum(arith.round(
        np.asarray(q, np.float64) ** 2)) / n - avg * avg))
    var = max(var, 0.0)
    return avg, var, float(np.sqrt(var))


def trend(q: np.ndarray, arith: Arith = F64) -> np.ndarray:
    w = max(min(TREND_WINDOW_S, len(q)), 1)
    if arith.stat_dtype is None:
        return np.convolve(np.asarray(q, np.float64), np.ones(w) / w,
                           mode="same")
    # each window's sum as a pairwise low-precision sum
    half = (w - 1) // 2
    pad = np.concatenate([np.zeros(w - half - 1), q, np.zeros(half)])
    win = np.lib.stride_tricks.sliding_window_view(pad, w)
    return arith.round(arith.sum(win) / w)


def _resample(x: np.ndarray, n: int) -> np.ndarray:
    return np.interp(np.linspace(0, 1, n), np.linspace(0, 1, len(x)), x)


def corr_matrix(series: Sequence[np.ndarray],
                arith: Arith = F64) -> np.ndarray:
    """Pearson matrix of the series' trends on the shortest one's grid."""
    trends = [trend(q, arith) for q in series]
    n = min(len(x) for x in trends)
    z = np.stack([_resample(x, n) for x in trends])
    if arith.stat_dtype is None:
        return np.corrcoef(z)
    z = arith.round(z - arith.sum(z)[:, None] / n)
    gram = arith.sum(z[:, None, :] * z[None, :, :])
    d = np.sqrt(np.diag(gram))
    return np.clip(gram / np.outer(d, d), -1.0, 1.0)


# -------------------------------------------------------------- digests
def digest(sim) -> Dict[str, int]:
    """CRC-32 of each column as delivered, in stream order, and the count
    (the same figure the benchmark's consumer folds bucket by bucket)."""
    out = {"records": int(len(sim.t)),
           "t": zlib.crc32(np.ascontiguousarray(sim.t))}
    for k in sorted(sim.payload):
        out[k] = zlib.crc32(np.ascontiguousarray(sim.payload[k]))
    return out


# ------------------------------------------------------------ the answer
@dataclasses.dataclass
class Expected:
    """What a sound run of one configuration must produce."""

    original_rows: Dict[str, int]
    sims: Dict[Tuple[str, int], Sim]
    digests: Dict[Tuple[str, int], Dict[str, int]]
    vol_original: Dict[str, Tuple[float, ...]]
    vol_sim: Dict[Tuple[str, int], Tuple[float, ...]]
    trend_corr: Dict[Tuple[str, int], float]
    #: max_range -> (labels, matrix)
    fidelity: Dict[int, Tuple[List[str], np.ndarray]]


def expected(cfg: Dict, raw_days: Dict[str, List[Dict[str, np.ndarray]]],
             arith: Arith = F64) -> Expected:
    """Run the configuration's whole grid through the reference."""
    days = int(cfg["days"])
    duration = days * DAY if cfg["chunk_s"] else 0
    origs = {d: original(d, raw_days[d], duration) for d in cfg["datasets"]}
    oq = {d: counts_original(origs[d][0]) for d in cfg["datasets"]}
    sims, digests, vol_sim, tcorr, sq = {}, {}, {}, {}, {}
    for d in cfg["datasets"]:
        t, payload = origs[d]
        for mr in cfg["max_ranges"]:
            span = int(mr) * days if duration else int(mr)
            s = nsa(t, payload, span, arith)
            sc = (d, int(mr))
            sims[sc], digests[sc] = s, digest(s)
            sq[sc] = counts_sim(s, span)
            vol_sim[sc] = volatility(sq[sc], arith)
            tcorr[sc] = float(corr_matrix([oq[d], sq[sc]], arith)[0, 1])
    fidelity = {}
    for mr in cfg["max_ranges"]:
        ds = list(cfg["datasets"])
        labels = [f"{d}/original" for d in ds] + [f"{d}/sim{mr}" for d in ds]
        fidelity[int(mr)] = (labels, corr_matrix(
            [oq[d] for d in ds] + [sq[(d, int(mr))] for d in ds], arith))
    return Expected(
        original_rows={d: len(origs[d][0]) for d in cfg["datasets"]},
        sims=sims, digests=digests,
        vol_original={d: volatility(oq[d], arith) for d in cfg["datasets"]},
        vol_sim=vol_sim, trend_corr=tcorr, fidelity=fidelity)
