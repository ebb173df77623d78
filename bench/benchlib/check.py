"""The comparison that decides ``correct``.

Each number below is compared with its limit from the configuration file
(``limits``); ``correct`` holds when every number is at or under its limit.

- ``streams_differ``: scenarios whose simulated stream, read back from the
  store after the window, is not bit-identical to the reference's (times,
  scale stamps, every payload column and its dtype). Limit 0.
- ``deliveries_differ``: (sweep, scenario) pairs whose consumer did not
  receive exactly the reference's records, in stream order, once each:
  record count, CRC-32 of every column as delivered, strictly increasing
  buckets. Limit 0.
- ``rows_differ``: reports whose original or simulated row count is not
  the reference's. Limit 0.
- ``stat_rel_err``: the widest relative gap (over ``max(1, |reference|)``)
  of any report's average, variance or standard deviation, original or
  simulated, in any sweep.
- ``corr_err``: the widest absolute gap of any report's trend correlation
  or any fidelity-matrix entry, in any sweep; a NaN where the reference
  has a number counts as infinite.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchlib.reference import Expected

NAMES = ("streams_differ", "deliveries_differ", "rows_differ",
         "stat_rel_err", "corr_err")


@dataclasses.dataclass
class Report:
    """The parts of one scenario report that are compared."""

    dataset: str
    max_range: int
    original_rows: int
    simulated_rows: int
    vol_original: Tuple[float, float, float]
    vol_sim: Tuple[float, float, float]
    trend_corr: float
    delivered: Dict            # consumer digest: records, crc per column
    ordered: bool


@dataclasses.dataclass
class Observed:
    #: one list of reports per sweep, and one fidelity map per sweep
    sweeps: List[List[Report]]
    fidelity: List[Dict[int, Tuple[List[str], np.ndarray]]]
    #: scenario -> the stream read back from the store (t, payload, ss)
    stored: Dict[Tuple[str, int], Optional[object]]


def _same(a, b) -> bool:
    if a is None:
        return False
    if a.t.dtype != b.t.dtype or not np.array_equal(a.t, b.t):
        return False
    if a.scale_stamp is None or not np.array_equal(
            np.asarray(a.scale_stamp, np.int64), b.scale_stamp):
        return False
    if set(a.payload) != set(b.payload):
        return False
    return all(a.payload[k].dtype == b.payload[k].dtype and
               np.array_equal(a.payload[k], b.payload[k])
               for k in b.payload)


def _rel(a: float, b: float) -> float:
    if math.isnan(a) or math.isnan(b):
        return 0.0 if math.isnan(a) and math.isnan(b) else math.inf
    return abs(a - b) / max(1.0, abs(b))


def _abs(a: float, b: float) -> float:
    if math.isnan(a) or math.isnan(b):
        return 0.0 if math.isnan(a) and math.isnan(b) else math.inf
    return abs(a - b)


def numbers(obs: Observed, ref: Expected) -> Dict[str, float]:
    streams = sum(not _same(obs.stored.get(sc), ref.sims[sc])
                  for sc in ref.sims)
    deliveries = rows = 0
    stat = corr = 0.0
    for reports in obs.sweeps:
        seen = {(r.dataset, r.max_range): r for r in reports}
        deliveries += sum(sc not in seen for sc in ref.sims)
        for sc, r in seen.items():
            want = ref.digests.get(sc)
            if want is None:
                deliveries += 1
                rows += 1
                continue
            if r.delivered != want or not r.ordered:
                deliveries += 1
            if (r.original_rows != ref.original_rows[sc[0]] or
                    r.simulated_rows != len(ref.sims[sc])):
                rows += 1
            for a, b in zip(r.vol_original, ref.vol_original[sc[0]]):
                stat = max(stat, _rel(a, b))
            for a, b in zip(r.vol_sim, ref.vol_sim[sc]):
                stat = max(stat, _rel(a, b))
            corr = max(corr, _abs(r.trend_corr, ref.trend_corr[sc]))
    for fid in obs.fidelity:
        for mr, (labels, want) in ref.fidelity.items():
            got = fid.get(mr)
            if got is None or list(got[0]) != list(labels):
                corr = math.inf
                continue
            g, w = np.asarray(got[1], np.float64), np.asarray(want)
            for a, b in zip(g.ravel(), w.ravel()):
                corr = max(corr, _abs(float(a), float(b)))
    return {"streams_differ": float(streams),
            "deliveries_differ": float(deliveries),
            "rows_differ": float(rows), "stat_rel_err": stat,
            "corr_err": corr}


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(values[k] <= float(limits[k]) for k in NAMES)


def lines(values: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"check {k}: {values[k]!r} limit {float(limits[k])!r}"
            for k in NAMES]


def as_json(values: Dict[str, float], limits: Dict[str, float]) -> Dict:
    def num(x):
        return x if math.isfinite(x) else str(x)
    return {k: {"value": num(values[k]), "limit": float(limits[k])}
            for k in NAMES}


def expected_observation(ref: Expected) -> Observed:
    """An observation made of a reference's own answers — how the control
    (the reference one precision down) is put in the program's place."""
    reports = [Report(d, mr, ref.original_rows[d], len(ref.sims[(d, mr)]),
                      ref.vol_original[d], ref.vol_sim[(d, mr)],
                      ref.trend_corr[(d, mr)], ref.digests[(d, mr)], True)
               for d, mr in ref.sims]
    return Observed(sweeps=[reports], fidelity=[dict(ref.fidelity)],
                    stored=dict(ref.sims))
