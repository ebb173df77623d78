"""Bench-regression smoke gate for the batched-sweep rows.

Reads ``BENCH_*.json`` files produced by ``benchmarks/run.py`` and fails
(exit 1) if any gated row misses its ratio against the path it replaced
(recorded as a ``*_us`` derived field on the row). Each gate is
``(baseline_field, max_ratio)``: the row passes while
``new <= baseline * max_ratio``, so ``1.0`` means "no slower than the
replaced path" and ``1 / 1.2`` means "at least 1.2x faster".

- ``PR4/sweep_single_dispatch_3x6`` vs ``per_range_path_us`` — the
  range-padded single launch must beat the per-range dispatch loop
  (guards range-padding overhead on small sweeps).
- ``PR5/sweep_sharded_4dev_8x6`` vs ``pr4_single_dispatch_us`` — the
  planner's size-grouped shards must beat the monolithic PR 4 launch
  (guards the padded-area win and the per-shard dispatch overhead).
- ``PR5/device_resident_report_64`` vs ``host_gather_path_us`` — the
  device-resident report chain must beat the host-gather + per-scenario
  loop it replaced.
- ``PR6/sweep_resume_3x4_k8`` vs ``restart_from_zero_us`` — resuming a
  killed checkpointed sweep (8 of 12 scenarios already marked done) must
  beat restarting it from zero (guards the marker-read overhead and any
  accidental re-replay of completed scenarios).
- ``PR7/chunked_pipeline_7day_8sc`` vs ``sequential_chunk_path_us`` at
  ratio ``1/1.2`` — the double-buffered cross-chunk-carry pipeline must
  be at least 1.2x faster than the naive sequential chunk loop it
  replaces for unbounded streams (block per chunk, recompute running
  stats from scratch each chunk).
- ``PR7/chunk_vs_monolith_1day`` vs ``monolithic_path_us`` at ratio
  ``1.05`` — running one day as a single day-sized chunk must cost at
  most 5% over the monolithic sweep path (guards chunking overhead in
  the degenerate single-chunk case).
- ``PR8/task_etl`` / ``PR8/task_windowed_stats`` /
  ``PR8/task_event_detect`` vs ``original_replay_us`` at ratio ``1/4``
  — each stream task's simulated replay must be at least 4x faster than
  replaying the original stream (the paper's >= 24x claim holds at
  full-day spans; the CI smoke runs a reduced span, so the gate floor
  is conservative). The fidelity half of the claim is enforced INSIDE
  ``bench_PR8.run`` (hard failure below ``FIDELITY_FLOOR``).
- ``PR8/task_serving`` vs ``original_replay_us`` at ratio ``1/2`` — the
  warm-engine serving load test must be at least 2x faster under the
  simulated arrival mix.
- ``PR9/service_failover_recovery`` vs ``restart_from_zero_us`` — a
  surviving sweep-service worker recovering a killed peer's sweep (8 of
  12 results already published, one expired lease reaped + requeued)
  must beat restarting the whole sweep from zero (guards the reap/claim
  marker overhead and any accidental re-execution of published
  scenarios).
- ``PR9/service_overhead`` vs ``direct_run_many_us`` at ratio ``1.15``
  — the full service path (election, queue/lease/result markers,
  heartbeat, count-row merge) must stay within 15% of the direct
  ``run_many`` it wraps when nothing fails.

Structural regressions (an accidental per-scenario dispatch loop, a
padding blowup, a host round-trip creeping back in) show up as
multiples, far outside benchmark noise.

Usage: ``python benchmarks/check_regression.py BENCH_PR4.json
[BENCH_PR5.json ...]`` — each file is checked against the gated rows it
is expected to carry (matched by the row prefix in the file name). A
named file that does not exist is a hard FAIL with a one-line message
(no traceback): a missing baseline means the gate silently stopped
gating, which is itself the regression.
"""

from __future__ import annotations

import json
import os
import re
import sys

#: gated row -> (derived field naming the replaced path's time, max ratio
#: of the new time over that baseline for the gate to pass)
GATES = {
    "PR4/sweep_single_dispatch_3x6": ("per_range_path_us", 1.0),
    "PR5/sweep_sharded_4dev_8x6": ("pr4_single_dispatch_us", 1.0),
    "PR5/device_resident_report_64": ("host_gather_path_us", 1.0),
    "PR6/sweep_resume_3x4_k8": ("restart_from_zero_us", 1.0),
    "PR7/chunked_pipeline_7day_8sc": ("sequential_chunk_path_us", 1 / 1.2),
    "PR7/chunk_vs_monolith_1day": ("monolithic_path_us", 1.05),
    "PR8/task_etl": ("original_replay_us", 1 / 4),
    "PR8/task_windowed_stats": ("original_replay_us", 1 / 4),
    "PR8/task_event_detect": ("original_replay_us", 1 / 4),
    "PR8/task_serving": ("original_replay_us", 1 / 2),
    "PR9/service_failover_recovery": ("restart_from_zero_us", 1.0),
    "PR9/service_overhead": ("direct_run_many_us", 1.15),
}


def _expected_rows(path: str):
    """The gated rows a file must carry, by its BENCH_<prefix>.json name."""
    stem = os.path.basename(path)
    m = re.match(r"BENCH_(\w+)\.json$", stem)
    prefix = (m.group(1) if m else "") + "/"
    return [name for name in GATES if name.startswith(prefix)]


def _check_row(rows, name: str, baseline_field: str,
               max_ratio: float, path: str) -> int:
    row = next((r for r in rows if r["name"].split("@")[0] == name), None)
    if row is None:
        print(f"FAIL: no {name} row found [read {path}]", file=sys.stderr)
        return 1
    m = re.search(rf"{baseline_field}=(\d+(?:\.\d+)?)", row["derived"])
    if m is None:
        print(f"FAIL: {row['name']} carries no {baseline_field} baseline "
              f"[read {path}]", file=sys.stderr)
        return 1
    new, baseline = float(row["us_per_call"]), float(m.group(1))
    ok = new <= baseline * max_ratio
    need = (f"needed <= {max_ratio:.2f}x of baseline" if max_ratio != 1.0
            else "needed no slower")
    print(f"{'OK' if ok else 'FAIL'}: {row['name']} = {new:.0f}us vs "
          f"replaced-path baseline {baseline:.0f}us "
          f"({baseline / max(new, 1e-9):.1f}x; {need}) [read {path}]")
    if not ok:
        print(f"{name} misses its gate against the path it replaces — "
              "structural regression", file=sys.stderr)
        return 1
    return 0


def check(paths) -> int:
    status = 0
    for path in paths:
        if not os.path.isfile(path):
            print(f"FAIL: benchmark file {path} is missing — the gated "
                  "rows it carries were never produced (run "
                  "`BENCH_QUICK=1 python benchmarks/run.py` first)",
                  file=sys.stderr)
            status |= 1
            continue
        with open(path) as f:
            rows = json.load(f)
        expected = _expected_rows(path)
        if not expected:
            print(f"note: no gated rows expected in {path}")
            continue
        for name in expected:
            field, max_ratio = GATES[name]
            status |= _check_row(rows, name, field, max_ratio, path)
    return status


if __name__ == "__main__":
    sys.exit(check(sys.argv[1:] or ["BENCH_PR4.json", "BENCH_PR5.json",
                                    "BENCH_PR6.json", "BENCH_PR7.json",
                                    "BENCH_PR8.json", "BENCH_PR9.json"]))
