"""PR 5 trajectory rows: sweep planner shards + device-resident reporting.

Two rows quantify what the plan/engine split buys over the PR 4
single-device composition:

- ``sweep_sharded_4dev_8x6`` — an 8-stream × 6-time-range grid (48
  scenarios) with heterogeneous stream sizes, the planner's target shape.
  NEW: ``plan_sweep`` partitions the grid into 4 size-grouped,
  cost-balanced shards and the engine runs each shard's
  normalize→sample→compact→metrics chain as one dispatch per stage,
  followed by the single ``materialize()`` host pass. OLD (the PR 4
  path): ONE monolithic ``nsa_sweep`` launch padded to the global maximum
  row length + the host-input batched metrics dispatch over the gathered
  scale stamps. The planner wins on *padded area*: a monolithic launch
  pads every row to the longest stream's tile count, while size-grouped
  shards pad only to their own maximum — less kernel work on real
  hardware, fewer interpret-mode grid steps on CPU. Gated by
  ``check_regression.py`` (the sharded path must never lose to the
  monolith it replaces).

- ``device_resident_report_64`` — 64 scenarios' report statistics
  (per-second histograms + volatility moments + per-scenario
  original↔simulated trend correlation). NEW: the fused metrics engine
  consumes the NSA chain's device-resident kept stamps directly
  (``stream_metrics_batched_device``) and ALL pairwise trend correlations
  come from one fused XLA chain (``trend_corr_pairwise``). OLD (PR 4):
  gather kept stamps to host, re-stack them into the host-input metrics
  dispatch, download the histograms, then run the per-scenario host
  sliding-mean/resample/Pearson loop. Also gated.

All rows are min-of-reps; reduced scales carry an ``@`` suffix so trend
tooling never mixes incommensurable sizes. Full scale is the TPU target —
off-TPU the Pallas legs run in interpret mode on both sides of each
comparison, so the structural difference (padded area, host round-trips,
per-scenario loops) is what the ratio measures.
"""

from __future__ import annotations

import os
import time
from typing import List

import numpy as np

from repro.kernels import ops
from repro.streamsim import make_stream, plan_sweep, preprocess
from repro.streamsim import engine as sweep_engine
from repro.streamsim.metrics import (per_second_counts,
                                     trend_correlation_from_counts)
from repro.streamsim.nsa import nsa_sweep

TIME_RANGES = (600, 1200, 1800, 2400, 3000, 3600)
QUICK = bool(int(os.environ.get("BENCH_QUICK", "0")))


class _NoStore:
    """Planner/engine store stub: nothing cached, nothing persisted."""

    def exists(self, key) -> bool:
        return False

    def __bool__(self) -> bool:
        return False


def _tmin(fn, reps=3):
    """(result, min-of-reps seconds) — min is robust to scheduler noise."""
    out, best = fn(), float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        r = fn()
        best = min(best, time.perf_counter() - t0)
        assert r == out, "non-deterministic benchmark result"
    return out, best


def _hetero_streams(n, base_scale, seed=3):
    """n streams with ~8x record-count spread — the planner's target
    shape (a monolithic launch pads everything to the biggest)."""
    names = ("sogouq", "traffic", "userbehavior")
    out = {}
    for i in range(n):
        sc = base_scale * (1 + (i % 4)) * (2 if i >= n // 2 else 1)
        s = preprocess(make_stream(names[i % 3], scale=sc, seed=seed + i))
        s.name = f"s{i}"
        out[f"s{i}"] = s
    return out


def run(csv: List[str]) -> None:
    if ops.on_tpu():
        base, tag = 0.05, ""
    else:
        base = 0.0001 if QUICK else 0.0002
        tag = f"@scale{base}"
    streams = _hetero_streams(8, base)
    reps = 2 if QUICK else 5
    row_counts = {k: len(v) for k, v in streams.items()}
    store = _NoStore()
    w_max = max(TIME_RANGES)

    # --- sharded plan/engine vs the PR 4 monolithic single dispatch -------
    def _sharded():
        plan = plan_sweep(store, list(streams), TIME_RANGES, row_counts,
                          n_devices=4, host_index=0, n_hosts=1)
        result = sweep_engine.execute_sweep(plan, streams, store,
                                            backend="pallas")
        sims = result.materialize(store=False)
        return sum(len(s) for s in sims.values())

    def _pr4_monolith():
        sims = nsa_sweep(streams, TIME_RANGES, backend="pallas")
        stamps = [sims[(n, mr)].scale_stamp
                  for n in streams for mr in TIME_RANGES]
        hist, _, _ = ops.stream_metrics_batched(stamps, w_max)
        hist.block_until_ready()
        return sum(len(s) for s in sims.values())

    got_new, dt_new = _tmin(_sharded, reps=reps)
    got_old, dt_old = _tmin(_pr4_monolith, reps=reps)
    assert got_new == got_old, "sharded and monolithic sweeps must " \
        f"produce identical simulated row totals ({got_new} vs {got_old})"
    plan = plan_sweep(store, list(streams), TIME_RANGES, row_counts,
                      n_devices=4, host_index=0, n_hosts=1)
    csv.append(
        f"PR5/sweep_sharded_4dev_8x6{tag},{dt_new*1e6:.0f},"
        f"scenarios=48;shards={len(plan.shards)};"
        f"padded_area={plan.padded_area()};"
        f"monolithic_area={plan.monolithic_area()};"
        f"pr4_single_dispatch_us={dt_old*1e6:.0f};"
        f"speedup={dt_old/max(dt_new, 1e-9):.1f}x")

    # --- device-resident report stats vs the PR 4 host-gather path -------
    # 64 scenarios as ONE engine shard: kept stamps stay on device
    import jax.numpy as jnp

    from repro.streamsim.nsa import nsa_sweep_device

    r_ranges = tuple(int(t) for t in np.linspace(75, 600, 8))
    r_streams = _hetero_streams(8, base * 2, seed=11)
    r_names = list(r_streams)
    r_pairs = [(n, mr) for n in r_streams for mr in r_ranges]
    ss_b, idx_b, totals, _ = nsa_sweep_device(r_streams, r_pairs)
    totals = np.asarray(totals, np.int64)
    ss_kept = ops.gather_kept(ss_b, idx_b)
    # compaction packs kept stamps to the front: the metrics dispatch (one
    # per path variant, identical shape — run in setup) reads only the
    # kept-width column slice, exactly as the engine does
    n_kept = int(-(-max(int(totals.max(initial=1)), 1)
                   // ops.TILE) * ops.TILE)
    ss_kept = ss_kept[:, :min(n_kept, ss_kept.shape[1])]
    r_w = max(r_ranges)
    hist, mom = ops.stream_metrics_batched_device(ss_kept, totals, r_w)
    hist.block_until_ready()
    lb = np.array([mr for _, mr in r_pairs], np.int64)
    om_counts = {n: per_second_counts(s) for n, s in r_streams.items()}
    la_u = np.array([len(om_counts[n]) for n in r_names], np.int64)
    a_index = np.array([r_names.index(n) for n, _ in r_pairs])
    qa_mat = np.zeros((len(r_names), int(la_u.max())), np.int32)
    for i, n in enumerate(r_names):
        qa_mat[i, :len(om_counts[n])] = om_counts[n]
    qa_dev = jnp.asarray(qa_mat)

    def _device_resident():
        # counts stay device-resident: one fused chain computes every
        # pair's trend correlation (each original's trend ONCE), and only
        # O(S) scalars ([Σq, Σq²] moments, P correlations) reach host
        corrs = ops.trend_corr_pairwise(qa_dev, la_u, hist, lb, 60,
                                        a_index=a_index)
        m = np.asarray(mom)
        return round(float(np.nansum(corrs) + m[:, 0].sum()), 3)

    def _pr4_host_gather():
        # the PR 4 report stage: histogram matrix gathered to host, then
        # the per-scenario sliding-mean/resample/Pearson loop (the
        # original's full-length trend recomputed for every scenario)
        counts = np.asarray(hist)
        corrs = [trend_correlation_from_counts(
            om_counts[n], counts[i, :mr])
            for i, (n, mr) in enumerate(r_pairs)]
        m = np.asarray(mom)
        return round(float(np.nansum(corrs) + m[:, 0].sum()), 3)

    got_d, dt_d = _tmin(_device_resident, reps=reps)
    got_h, dt_h = _tmin(_pr4_host_gather, reps=reps)
    assert abs(got_d - got_h) <= max(2e-3 * abs(got_h), 0.5), \
        f"report statistics diverged across paths ({got_d} vs {got_h})"
    csv.append(
        f"PR5/device_resident_report_64{tag},{dt_d*1e6:.0f},"
        f"scenarios={len(r_pairs)};"
        f"host_gather_path_us={dt_h*1e6:.0f};"
        f"speedup={dt_h/max(dt_d, 1e-9):.1f}x")
