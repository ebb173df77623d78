#!/usr/bin/env python3
"""Chip smoke test: the paper's full-day Tables 1-3 sweep on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded sweep over four chips

One chip runs the main path through ``Controller.run_many``: three datasets
at full rate (``scale=1.0``, one day each: ~2.2 M sogouq, ~1.9 M traffic
and ~10.5 M userbehavior records) times six ``max_range`` values, 18
scenarios. POSD, the NSA kernels, the fused metrics engine, the trend /
fidelity chain, the store and the PSDA multi-queue replay into a consumer
that counts records all run. It does so twice, monolithic and through the
chunked pipeline (``chunk_s=3600``, the carry kernels), and checks both
against the same grid run with ``backend="numpy"``, the plain reference:

- stored simulated streams are bit-identical, and chunked equals monolithic;
- every consumer counted exactly its stream's records;
- volatility statistics, report trend correlations and the fidelity
  matrices agree within 1e-3;
- every report ran in device mode and ended with status ``ok``.

``--chips 4`` runs only the multi-device path: the same grid sharded over
four chips, compared with ``n_devices=1`` in the same process (reports and
stored streams equal), and checks that each shard's arrays sit on the chip
the plan gave it.

Everything runs in this one process, which holds the chip(s). Stores live
in a fresh temporary directory that is removed at the end. Earlier lines
report each phase's wall time, compile time and peak device memory for this
one cold run; they are not benchmark numbers. The last line of standard
output is ``{"ok": true, "device": {...}}``, printed only when every check
passed. Without a TPU, or on any failed check, the script exits nonzero and
prints no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

DATASETS = ("sogouq", "traffic", "userbehavior")
MAX_RANGES = (600, 1200, 1800, 2400, 3000, 3600)
CHUNK_S = 3600
SEED = 0
#: agreement bound for f32 device statistics vs the f64 reference
TOL = 1e-3


def count_records(queue) -> Dict:
    """The smoke's consumer: drain the queue and count what arrived."""
    n = 0
    for bucket in queue:
        n += len(bucket)
    return {"consumed_records": n}


@dataclasses.dataclass
class Sweep:
    """One ``run_many`` call and what it left behind."""
    label: str
    reports: list
    fidelity: list
    store: object
    seconds: float
    #: scenario -> (plan device slot, devices its arrays sat on)
    placement: dict


def run_sweep(label: str, root: str, *, scale: float, **kw) -> Sweep:
    """One ``Controller.run_many`` over the smoke grid in a fresh store."""
    from repro.streamsim import Controller

    ctrl = Controller(os.path.join(root, label))
    t0 = time.perf_counter()
    reports = ctrl.run_many(DATASETS, MAX_RANGES, count_records,
                            scale=scale, seed=SEED, **kw)
    return Sweep(label, reports, ctrl.last_fidelity, ctrl.store,
                 time.perf_counter() - t0, ctrl.last_placement)


def _store_key(report) -> str:
    return f"{report.dataset}__sim{report.max_range}"


def check_run(sweep: Sweep, mode: str) -> List[str]:
    """Per-report checks: status, mode, and that the consumer saw every
    record of the stream the store holds."""
    problems = []
    want = {(d, mr) for d in DATASETS for mr in MAX_RANGES}
    got = {(r.dataset, r.max_range) for r in sweep.reports}
    if got != want:
        problems.append(f"{sweep.label}: scenarios {sorted(got)} != grid")
    for r in sweep.reports:
        tag = f"{sweep.label} {r.dataset}/{r.max_range}"
        if r.status != "ok":
            problems.append(f"{tag}: status {r.status!r} ({r.failure})")
        if r.mode != mode:
            problems.append(f"{tag}: ran in {r.mode!r} mode, not {mode!r}")
        stored = len(sweep.store.get(_store_key(r)))
        seen = r.consumer_metrics.get("consumed_records")
        if not seen == r.simulated_rows == stored:
            problems.append(f"{tag}: consumer counted {seen}, report says "
                            f"{r.simulated_rows}, store holds {stored}")
    return problems


def _close(a: float, b: float, rel: bool) -> bool:
    if math.isnan(a) or math.isnan(b):
        return math.isnan(a) and math.isnan(b)
    scale = max(1.0, abs(a)) if rel else 1.0
    return abs(a - b) <= TOL * scale


def _same_stream(a, b) -> bool:
    import numpy as np

    cols = (("t", a.t, b.t), ("scale_stamp", a.scale_stamp, b.scale_stamp))
    if set(a.payload) != set(b.payload):
        return False
    cols += tuple((k, a.payload[k], b.payload[k]) for k in a.payload)
    return all(x.dtype == y.dtype and np.array_equal(x, y)
               for _, x, y in cols)


def compare(ref: Sweep, got: Sweep) -> List[str]:
    """Differences between two runs of the grid: stored streams must be
    bit-identical, row counts equal, statistics within ``TOL``."""
    problems = []
    by_sc = {(r.dataset, r.max_range): r for r in ref.reports}
    for r in got.reports:
        sc = (r.dataset, r.max_range)
        tag = f"{got.label} vs {ref.label} {sc[0]}/{sc[1]}"
        want = by_sc.get(sc)
        if want is None:
            problems.append(f"{tag}: missing from {ref.label}")
            continue
        for field in ("original_rows", "simulated_rows"):
            if getattr(r, field) != getattr(want, field):
                problems.append(f"{tag}: {field} {getattr(r, field)} != "
                                f"{getattr(want, field)}")
        for side in ("original_volatility", "simulated_volatility"):
            a, b = getattr(r, side), getattr(want, side)
            for f in ("average", "variance", "std_variance"):
                if not _close(getattr(a, f), getattr(b, f), rel=True):
                    problems.append(f"{tag}: {side}.{f} {getattr(a, f)} vs "
                                    f"{getattr(b, f)}")
        if not _close(r.trend_corr, want.trend_corr, rel=False):
            problems.append(f"{tag}: trend_corr {r.trend_corr} vs "
                            f"{want.trend_corr}")
        if not _same_stream(got.store.get(_store_key(r)),
                                        ref.store.get(_store_key(want))):
            problems.append(f"{tag}: stored streams differ")
    fid_ref = {f.max_range: f for f in ref.fidelity}
    for f in got.fidelity:
        tag = f"{got.label} vs {ref.label} fidelity@{f.max_range}"
        want = fid_ref.get(f.max_range)
        if want is None or want.labels != f.labels:
            problems.append(f"{tag}: labels differ")
            continue
        worst = max((abs(a - b) for ra, rb in zip(f.trend_corr,
                                                  want.trend_corr)
                     for a, b in zip(ra, rb) if not (a != a and b != b)),
                    default=0.0)
        nan_ok = all((a != a) == (b != b)
                     for ra, rb in zip(f.trend_corr, want.trend_corr)
                     for a, b in zip(ra, rb))
        if not nan_ok or not worst <= TOL:
            problems.append(f"{tag}: matrices differ by {worst}")
    if len(got.fidelity) != len(ref.fidelity):
        problems.append(f"{got.label}: {len(got.fidelity)} fidelity "
                        f"matrices, {ref.label} has {len(ref.fidelity)}")
    return problems


def one_chip(root: str, *, scale: float = 1.0,
             backend: str = "auto") -> Tuple[List[str], List[Sweep]]:
    """Monolithic and chunked device sweeps against the numpy reference."""
    mono = run_sweep("device", root, scale=scale, backend=backend)
    _phase(mono)
    chunked = run_sweep("chunked", root, scale=scale, backend=backend,
                        chunk_s=CHUNK_S)
    _phase(chunked)
    ref = run_sweep("numpy", root, scale=scale, backend="numpy")
    _phase(ref)
    problems = (check_run(mono, "device") + check_run(chunked, "device") +
                check_run(ref, "host") + compare(ref, mono) +
                compare(ref, chunked) + compare(mono, chunked))
    return problems, [mono, chunked, ref]


def shard_placement(sweep: Sweep, devices: Sequence) -> List[str]:
    """Each scenario's device arrays must sit on the device its shard's
    plan slot names, and the shards must spread over more than one
    device."""
    problems, used = [], set()
    if len(sweep.placement) != len(sweep.reports):
        problems.append(f"{sweep.label}: placement known for "
                        f"{len(sweep.placement)} of {len(sweep.reports)} "
                        "scenarios")
    for sc, (slot, on) in sorted(sweep.placement.items()):
        want = str(devices[slot % len(devices)])
        if on != [want]:
            problems.append(f"{sweep.label} {sc[0]}/{sc[1]}: arrays on {on}, "
                            f"not {want}")
        used.add(want)
    if len(used) < min(len(devices), 2):
        problems.append(f"shards used {len(used)} device(s) of "
                        f"{len(devices)}")
    return problems


def four_chips(root: str, devices: Sequence, *, scale: float = 1.0,
               backend: str = "auto") -> Tuple[List[str], List[Sweep]]:
    """The sharded sweep over ``devices`` against ``n_devices=1``."""
    sharded = run_sweep("sharded", root, scale=scale, backend=backend,
                        n_devices=len(devices))
    _phase(sharded)
    single = run_sweep("one_device", root, scale=scale, backend=backend,
                       n_devices=1)
    _phase(single)
    problems = (check_run(sharded, "device") + check_run(single, "device") +
                shard_placement(sharded, devices) +
                compare(single, sharded))
    return problems, [sharded, single]


_COMPILE_S = [0.0]


def _on_event(event: str, duration: float, **_kw) -> None:
    if event.endswith("backend_compile_duration"):
        _COMPILE_S[0] += duration


def _phase(sweep: Sweep) -> None:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    print(f"[cold run] {sweep.label}: {sweep.seconds:.1f} s wall, "
          f"{_COMPILE_S[0]:.1f} s compiling so far, peak device bytes so "
          f"far {peaks} of {[s.get('bytes_limit') for s in stats]}",
          flush=True)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: main path vs numpy; 4: sharded sweep only")
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro import compile_cache
    from repro.kernels.ops import HostFallbackWarning

    print(f"[cold run] compile cache: {compile_cache.enable()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    # a fallback to the host is a failure here, not a quiet detour
    warnings.simplefilter("error", HostFallbackWarning)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        if args.chips == 1:
            problems, _ = one_chip(root)
        else:
            problems, _ = four_chips(root, devices[:args.chips])
    for p in problems:
        print(f"chip_smoke: {p}", file=sys.stderr)
    if problems:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
