"""The benchmark's own dataset generators: the traffic source of every cell.

A copy of the program's surrogate generators (SogouQ, Baidu-Map Traffic,
Taobao UserBehavior; arXiv:2205.14244 Tables 1-3), kept here so that a
change to the program's generators never moves the yardstick. The
calibration (mean rate, coefficient of variation, diurnal shape, burst
timescales) and the field layout are the program's.

One difference, on purpose: every seed gets the same set of arrivals in
another order. The per-second counts of day ``d`` of a dataset are drawn
once, from a fixed trace seed; ``--seed`` then reorders them (the 240 s
blocks inside each aligned 1 440 s window trade places, the first and last
window stay put), places every arrival inside its second and draws the
record contents (ids, coordinates, behaviours). So every seed has the same
record count per dataset-day, and every chunk of the chunked pipeline (whose
edges fall on multiples of 1 440 s of original time for all six ranges) has
the same count to within the records of its edge seconds: runs of different
seeds do the same work on the same padded device shapes, while NSA's bucket
contents, the kept records and every statistic differ from seed to seed.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import numpy as np

DAY = 86_400
#: UserBehavior timestamps are stored as a UTC+8 wall clock
USERBEHAVIOR_TZ_OFFSET = 8 * 3600
#: root of the fixed per-second counts (never the run's seed)
TRACE_SEED = 20220528
#: ``--seed`` reorders BLOCK_S-second blocks inside each aligned WINDOW_S
#: window: 1 440 s divides every chunk of 600 buckets at the six ranges
BLOCK_S, WINDOW_S = 240, 1440
_TAG = {"sogouq": 11, "traffic": 22, "userbehavior": 33}


@dataclasses.dataclass(frozen=True)
class Raw:
    """An unpreprocessed stream: named columns in arrival order."""

    name: str
    columns: Dict[str, np.ndarray]

    def __len__(self) -> int:
        return len(next(iter(self.columns.values())))


def _smooth_noise(seconds, scale_s, rng):
    knots = rng.standard_normal(int(DAY / scale_s) + 2)
    x = np.interp(seconds, np.arange(len(knots)) * scale_s, knots)
    return (x - x.mean()) / (x.std() + 1e-9)


def _intensity(rate, cv, rng):
    seconds = np.arange(DAY)
    t = seconds / DAY
    trend = (0.35
             + 0.45 * np.exp(-0.5 * ((t - 0.45) / 0.13) ** 2)
             + 0.65 * np.exp(-0.5 * ((t - 0.85) / 0.09) ** 2)
             - 0.25 * np.exp(-0.5 * ((t - 0.17) / 0.10) ** 2))
    shape = ((trend - trend.mean()) / (trend.std() + 1e-9)
             + 0.55 * _smooth_noise(seconds, 1800.0, rng)
             + 0.30 * _smooth_noise(seconds, 240.0, rng))
    z = (shape - shape.mean()) / (shape.std() + 1e-9)
    return rate * np.clip(1.0 + cv * z, 0.01, None)


def counts(name: str, scale: float, day: int) -> np.ndarray:
    """The fixed per-second counts of one dataset-day: a non-homogeneous
    Poisson process with the dataset's diurnal rate."""
    rate, cv = {"sogouq": (25.4, 0.60), "traffic": (21.5, 0.49),
                "userbehavior": (122.0, 0.55)}[name]
    rng = np.random.default_rng([TRACE_SEED, _TAG[name], int(day)])
    return rng.poisson(_intensity(rate * scale, cv, rng))


def reorder(per_second: np.ndarray, rng) -> np.ndarray:
    """``per_second`` with the blocks of each window but the first and the
    last put in an order drawn from ``rng``."""
    per = WINDOW_S // BLOCK_S
    w = per_second.reshape(DAY // WINDOW_S, per, BLOCK_S).copy()
    for i in range(1, w.shape[0] - 1):
        w[i] = w[i, rng.permutation(per)]
    return w.reshape(-1)


def arrivals(name: str, scale: float, day: int, seed: int) -> np.ndarray:
    """Sorted arrival offsets (seconds into the day) of one dataset-day:
    the fixed counts, reordered and placed inside their seconds by
    ``seed``."""
    rng = np.random.default_rng([int(seed) % 2 ** 63, _TAG[name], int(day),
                                 1])
    c = reorder(counts(name, scale, day), rng)
    sec = np.repeat(np.arange(DAY, dtype=np.float64), c)
    ts = sec + rng.random(sec.shape[0])
    ts.sort(kind="stable")
    return ts


def make_raw(name: str, scale: float, day: int, seed: int) -> Raw:
    """One day of dataset ``name``: the arrivals and contents of ``seed``."""
    ts = arrivals(name, scale, day, seed)
    n = len(ts)
    rng = np.random.default_rng([int(seed) % 2 ** 63, _TAG[name], int(day)])
    if name == "sogouq":
        times = np.datetime64("2008-06-01T00:00:00") + \
            ts.astype("timedelta64[s]")
        text = np.char.replace(np.datetime_as_string(times, unit="s"),
                               "T", " ")
        return Raw(name, {
            "access_time": text,
            "user_id": rng.integers(0, 2_000_000, n, dtype=np.int64),
            "query_hash": rng.integers(0, 2 ** 31, n, dtype=np.int64),
            "result_rank": rng.integers(1, 11, n, dtype=np.int32),
            "click_rank": rng.integers(1, 11, n, dtype=np.int32)})
    if name == "traffic":
        return Raw(name, {
            "query_ts": 1_491_004_800.0 + ts,
            "start_lat": rng.uniform(39.44, 41.06, n),
            "start_lon": rng.uniform(115.42, 117.51, n),
            "dest_lat": rng.uniform(39.44, 41.06, n),
            "dest_lon": rng.uniform(115.42, 117.51, n),
            "eta_s": rng.gamma(2.0, 900.0, n).astype(np.float32)})
    if name == "userbehavior":
        return Raw(name, {
            "user_id": rng.integers(1, 1_000_000, n, dtype=np.int64),
            "item_id": rng.integers(1, 4_000_000, n, dtype=np.int64),
            "category_id": rng.integers(1, 9_500, n, dtype=np.int64),
            "behavior_type": rng.choice(
                np.array([0, 1, 2, 3], np.int32), n,
                p=[0.89, 0.02, 0.06, 0.03]),
            "timestamp": (1_511_539_200 + ts +
                          USERBEHAVIOR_TZ_OFFSET).astype(np.int64)})
    raise KeyError(f"unknown dataset {name!r}")


class RawSource:
    """The raw days of one run, generated once and kept for the reference.

    The program asks for day ``d`` of a multi-day stream as seed
    ``seed + d``; :meth:`for_program` turns that back into the day, so the
    program and the reference read the same bytes.
    """

    def __init__(self, scale: float, seed: int):
        self.scale = float(scale)
        self.seed = int(seed)
        self.days: Dict[tuple, Raw] = {}
        self.seconds = 0.0

    def get(self, name: str, day: int) -> Raw:
        import time

        key = (name, int(day))
        if key not in self.days:
            t0 = time.perf_counter()
            self.days[key] = make_raw(name, self.scale, day,
                                      self.seed + int(day))
            self.seconds += time.perf_counter() - t0
        return self.days[key]

    def for_program(self, name: str, wrap: Callable) -> Callable:
        """A generator with the program's ``(scale, seed)`` signature that
        hands out this source's days, converted by ``wrap``."""
        def generate(scale: float = 1.0, seed: int = 0):
            if float(scale) != self.scale:
                raise ValueError(f"scale {scale} != the cell's {self.scale}")
            return wrap(self.get(name, int(seed) - self.seed))
        return generate
