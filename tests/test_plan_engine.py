"""Plan/engine layer tests: sweep planning, sharded execution, and the
device-residency contract.

Contracts under test:
- ``plan_sweep`` enumerates the grid in report order, resolves store-cache
  hits, and partitions missing scenarios into balanced, padding-aware
  shards (per host AND per device) without ever splitting a scenario;
- a sharded pallas sweep reports equivalently to the numpy path (NSA
  bit-identical rows, statistics within the documented 1e-3 tolerance)
  and costs exactly one NSA dispatch per shard;
- between NSA and metrics no per-scenario data crosses to host: the fused
  metrics engine consumes jax arrays, and the single ``materialize()``
  host pass happens strictly after every metrics dispatch;
- under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (run in a
  subprocess — the flag must precede jax initialization) the shards land
  on four REAL distinct devices and the 8×6 grid executes as ≤ 4 NSA
  dispatches.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.streamsim import (Controller, make_stream, plan_sweep,
                             preprocess)
from repro.streamsim.plan import ROW_TILE, ScenarioSpec, Shard


def _consumer(queue):
    return {"records_seen": sum(len(b) for b in queue)}


class _FakeStore:
    """exists() from a fixed key set — planner tests need no disk."""

    def __init__(self, keys=()):
        self.keys = set(keys)

    def exists(self, key):
        return key in self.keys


# ------------------------------------------------------------------ planner
class TestPlanSweep:
    ROWS = {"a": 10_000, "b": 9_000, "c": 900, "d": 800}

    def test_grid_order_and_cache_resolution(self):
        store = _FakeStore({"b__sim20"})
        plan = plan_sweep(store, ["a", "b"], [10, 20], self.ROWS,
                          n_devices=2, host_index=0, n_hosts=1)
        assert [s.scenario for s in plan.scenarios] == \
            [("a", 10), ("a", 20), ("b", 10), ("b", 20)]
        assert [s.scenario for s in plan.cached] == [("b", 20)]
        assert len(plan.missing) == 3
        # shards cover exactly the missing scenarios, none split/duplicated
        covered = sorted(s.scenario for sh in plan.shards for s in sh.specs)
        assert covered == sorted(s.scenario for s in plan.missing)

    def test_force_marks_everything_missing(self):
        store = _FakeStore({"a__sim10", "a__sim20"})
        plan = plan_sweep(store, ["a"], [10, 20], self.ROWS, force=True,
                          n_devices=1, host_index=0, n_hosts=1)
        assert not plan.cached and len(plan.missing) == 2

    def test_shards_group_similar_sizes_and_balance(self):
        # two big (10k/9k rows) + two small (900/800) streams: the
        # padding-aware partition must not mix a big with a small (that
        # pads the small to the big's width)
        plan = plan_sweep(_FakeStore(), list(self.ROWS), [60], self.ROWS,
                          n_devices=2, host_index=0, n_hosts=1)
        assert len(plan.shards) == 2
        groups = [sorted(s.dataset for s in sh.specs) for sh in plan.shards]
        assert ["a", "b"] in groups and ["c", "d"] in groups
        # planned area beats the monolithic single-launch padding
        assert plan.padded_area() < plan.monolithic_area()

    def test_more_devices_than_scenarios(self):
        plan = plan_sweep(_FakeStore(), ["a"], [60], self.ROWS,
                          n_devices=8, host_index=0, n_hosts=1)
        assert len(plan.shards) == 1
        assert plan.shards[0].specs[0].scenario == ("a", 60)

    def test_host_partition_is_a_disjoint_cover(self):
        plans = [plan_sweep(_FakeStore(), list(self.ROWS), [10, 20],
                            self.ROWS, n_devices=2, host_index=h,
                            n_hosts=3) for h in range(3)]
        per_host = [sorted(s.scenario for s in p.local_missing)
                    for p in plans]
        merged = sorted(sc for host in per_host for sc in host)
        assert merged == sorted(s.scenario for s in plans[0].missing)
        # strided slicing keeps host loads similar (within one scenario)
        sizes = [len(h) for h in per_host]
        assert max(sizes) - min(sizes) <= 1

    def test_shard_cost_properties(self):
        spec = ScenarioSpec("a", 60, 1.0, 0, rows=ROW_TILE + 1,
                            cached=False)
        sh = Shard(0, (spec,))
        assert sh.padded_rows == 2 * ROW_TILE
        assert sh.cost == 2 * ROW_TILE
        assert sh.max_range == 60

    def test_bad_inputs_rejected(self):
        with pytest.raises(ValueError):
            plan_sweep(_FakeStore(), ["a"], [0], self.ROWS,
                       n_devices=1, host_index=0, n_hosts=1)
        with pytest.raises(ValueError):
            plan_sweep(_FakeStore(), ["a"], [10], self.ROWS,
                       n_devices=1, host_index=2, n_hosts=2)


# ----------------------------------------------------------- sharded engine
def _hetero_streams(n=8, seed=3):
    """n streams of very different sizes (the planner's target shape)."""
    base = ["sogouq", "traffic", "userbehavior"]
    out = {}
    for i in range(n):
        scale = 0.0008 * (1 + (i % 4))
        s = preprocess(make_stream(base[i % 3], scale=scale, seed=seed + i))
        s.name = f"s{i}"
        out[f"s{i}"] = s
    return out


class TestShardedEngine:
    def test_sharded_pallas_equivalent_to_numpy(self, tmp_path):
        # 3 datasets x 4 ranges forced across 4 shards on however many
        # devices exist: rows must stay bit-identical to the numpy path,
        # statistics within the documented tolerance
        datasets = ["sogouq", "traffic", "userbehavior"]
        ranges = [10, 20, 40, 80]
        c = Controller(str(tmp_path / "sharded"))
        rep = c.run_many(datasets, ranges, _consumer, scale=0.002, seed=9,
                         backend="pallas", n_devices=4)
        ref_c = Controller(str(tmp_path / "ref"))
        ref = ref_c.run_many(datasets, ranges, _consumer, scale=0.002,
                             seed=9, backend="numpy")
        assert [(r.dataset, r.max_range) for r in rep] == \
            [(r.dataset, r.max_range) for r in ref]
        for a, b in zip(rep, ref):
            assert a.simulated_rows == b.simulated_rows
            assert a.consumer_metrics["records_seen"] == \
                b.consumer_metrics["records_seen"]
            assert a.trend_corr == pytest.approx(b.trend_corr, abs=1e-3)
            for f in ("average", "variance", "std_variance"):
                assert getattr(a.simulated_volatility, f) == pytest.approx(
                    getattr(b.simulated_volatility, f), rel=1e-3, abs=1e-6)
        # stored sims are the bit-identical NSA output
        for r in rep:
            a = c.store.get(f"{r.dataset}__sim{r.max_range}")
            b = ref_c.store.get(f"{r.dataset}__sim{r.max_range}")
            np.testing.assert_array_equal(a.t, b.t)
            np.testing.assert_array_equal(a.scale_stamp, b.scale_stamp)
        # fidelity matrices agree across backends too
        for fa, fb in zip(c.last_fidelity, ref_c.last_fidelity):
            np.testing.assert_allclose(np.asarray(fa.trend_corr),
                                       np.asarray(fb.trend_corr),
                                       atol=1e-3)

    def test_one_dispatch_per_shard(self, tmp_path, monkeypatch):
        # a 4-shard plan must cost exactly 4 NSA device dispatches —
        # one per shard, never one per scenario
        import repro.kernels.ops as ops_mod
        import repro.kernels.stream_sample as sskern

        dispatches = []
        real_kernel = sskern.stream_sample_pallas

        def counting_kernel(*args, **kwargs):
            dispatches.append(args[0].shape)
            return real_kernel(*args, **kwargs)

        monkeypatch.setattr(sskern, "stream_sample_pallas", counting_kernel)
        monkeypatch.setattr(ops_mod, "stream_sample_pallas", counting_kernel)

        datasets = ["sogouq", "traffic", "userbehavior"]
        ranges = [10, 20, 30, 40, 50, 60]
        c = Controller(str(tmp_path / "store"))
        reports = c.run_many(datasets, ranges, _consumer, scale=0.002,
                             seed=9, backend="pallas", n_devices=4)
        assert len(reports) == 18
        assert len(dispatches) == 4, \
            f"expected 4 NSA dispatches (one per shard), saw {dispatches}"
        assert sum(shape[0] for shape in dispatches) == 18, \
            "shards must cover all 18 scenarios exactly once"

    def test_no_host_transfer_between_nsa_and_metrics(self, tmp_path,
                                                      monkeypatch):
        # the device-residency contract: the fused metrics engine consumes
        # jax arrays straight from the NSA chain, and the single
        # materialize() host pass happens strictly AFTER every metrics
        # dispatch
        import jax

        import repro.kernels.ops as ops_mod
        import repro.streamsim.engine as engine_mod

        events = []
        real_metrics = ops_mod.stream_metrics_batched_device
        real_mat = engine_mod.materialize_sweep

        def checking_metrics(ss, totals, max_range):
            assert isinstance(ss, jax.Array), \
                f"metrics engine fed host data: {type(ss)}"
            events.append("metrics")
            return real_metrics(ss, totals, max_range)

        def tracking_materialize(*args, **kwargs):
            events.append("materialize")
            return real_mat(*args, **kwargs)

        monkeypatch.setattr(ops_mod, "stream_metrics_batched_device",
                            checking_metrics)
        monkeypatch.setattr(engine_mod, "materialize_sweep",
                            tracking_materialize)

        c = Controller(str(tmp_path / "store"))
        c.run_many(["sogouq", "traffic"], [20, 40], _consumer, scale=0.002,
                   seed=9, backend="pallas", n_devices=2)
        assert "metrics" in events and "materialize" in events
        first_mat = events.index("materialize")
        assert all(e != "metrics" for e in events[first_mat:]), \
            f"metrics dispatched after the host pass: {events}"

    def test_engine_direct_hetero_sweep(self, tmp_path):
        # the engine consumes arbitrary named streams (not just the
        # Controller's datasets): 8 heterogeneous streams x 2 ranges
        from repro.streamsim import engine
        from repro.streamsim.store import StreamStore

        originals = _hetero_streams(8)
        store = StreamStore(str(tmp_path / "store"))
        plan = plan_sweep(store, list(originals), [30, 60],
                          {k: len(v) for k, v in originals.items()},
                          n_devices=4, host_index=0, n_hosts=1)
        assert len(plan.shards) == 4
        result = engine.execute_sweep(plan, originals, store,
                                      backend="pallas")
        assert result.mode == "device"
        sims = result.materialize()
        from repro.streamsim import nsa
        for (name, mr), sim in sims.items():
            ref = nsa(originals[name], mr, backend="numpy")
            np.testing.assert_array_equal(sim.t, ref.t)
            np.testing.assert_array_equal(sim.scale_stamp, ref.scale_stamp)
        # sims were persisted by materialize
        assert store.exists("s0__sim30") and store.exists("s7__sim60")

    def test_domain_error_falls_back_to_host_mode(self, tmp_path):
        # a poisoned scenario (giant single bucket) must send the WHOLE
        # sweep to host mode, bit-identically — never silently wrong
        from repro.streamsim import engine
        from repro.streamsim.preprocess import Stream
        from repro.streamsim.store import StreamStore

        originals = {
            "burst": Stream("burst", np.full(100_000, 5.0),
                            {"x": np.arange(100_000)}),
            "ok": preprocess(make_stream("traffic", scale=0.002, seed=3)),
        }
        store = StreamStore(str(tmp_path / "store"))
        plan = plan_sweep(store, list(originals), [600],
                          {k: len(v) for k, v in originals.items()},
                          n_devices=2, host_index=0, n_hosts=1)
        result = engine.execute_sweep(plan, originals, store,
                                      backend="pallas")
        assert result.mode == "host"
        sims = result.materialize()
        from repro.streamsim import nsa
        for (name, mr), sim in sims.items():
            ref = nsa(originals[name], mr, backend="numpy")
            np.testing.assert_array_equal(sim.t, ref.t)


    def test_multi_host_slice_reports_and_partial_fidelity(self, tmp_path):
        # host 0 of 2 reports only its scenario slice, and fidelity rows
        # for its owned sims are emitted as partial matrices (labels
        # record the subset) instead of being silently dropped
        datasets, ranges = ["sogouq", "traffic"], [20, 40]
        c = Controller(str(tmp_path / "h0"))
        reports = c.run_many(datasets, ranges, _consumer, scale=0.002,
                             seed=9, backend="numpy", n_devices=2,
                             host_index=0, n_hosts=2)
        all_sc = {(d, mr) for d in datasets for mr in ranges}
        got = {(r.dataset, r.max_range) for r in reports}
        assert got and got < all_sc, "host 0 owns a strict subset"
        assert c.last_fidelity, "partial fidelity rows must be emitted"
        for fr in c.last_fidelity:
            m = np.asarray(fr.trend_corr)
            D = len(fr.labels) // 2
            assert 1 <= D <= len(datasets)
            assert m.shape == (2 * D, 2 * D)
            assert all(lb.endswith("/original") for lb in fr.labels[:D])
            assert all(f"/sim{fr.max_range}" in lb
                       for lb in fr.labels[D:])

    @pytest.mark.parametrize("n_devices", [1, 4])
    def test_gather_is_cut_to_the_kept_width(self, tmp_path, n_devices):
        # each shard keeps only its first K = TILE-rounded largest total
        # columns of the index plane, gathers the stamps by that cut, and
        # still yields the numpy path's streams and the full-width
        # gather's histograms and moments
        from repro.kernels import ops
        from repro.streamsim import engine, nsa
        from repro.streamsim.nsa import nsa_sweep_device
        from repro.streamsim.store import StreamStore

        originals = _kept_width_streams()
        store = StreamStore(str(tmp_path / "store"))
        plan = plan_sweep(store, list(originals), [60, 600, 3600],
                          {k: len(v) for k, v in originals.items()},
                          n_devices=n_devices, host_index=0, n_hosts=1)
        assert len(plan.shards) == n_devices
        result = engine.execute_sweep(plan, originals, store,
                                      backend="pallas")
        assert result.mode == "device"
        widest_not_first = False
        for sr in result.shard_results:
            ss, idx, totals, _ = nsa_sweep_device(originals, sr.pairs)
            np.testing.assert_array_equal(sr.totals, np.asarray(totals))
            K = -(-int(sr.totals.max()) // ops.TILE) * ops.TILE
            assert sr.idx.shape == sr.ss_kept.shape == (len(sr.pairs), K)
            assert K < idx.shape[1]
            widest_not_first |= int(np.argmax(sr.totals)) != 0
            # what a gather over the whole plane fed the metrics
            hist, mom = ops.stream_metrics_batched_device(
                ops.gather_kept(ss, idx)[:, :K], sr.totals,
                sr.shard.max_range)
            np.testing.assert_array_equal(np.asarray(sr.hist),
                                          np.asarray(hist))
            np.testing.assert_array_equal(
                sr.mom, np.asarray(mom, np.float64))
        assert widest_not_first
        _assert_streams_match_numpy(originals, result.materialize())

    def test_nsa_sweep_gathers_the_kept_width(self, monkeypatch):
        # the public one-dispatch sweep cuts its index plane the same way
        from repro.kernels import ops
        from repro.streamsim.nsa import nsa_sweep

        nsa_mod = sys.modules[nsa_sweep.__module__]
        originals = _kept_width_streams()
        seen = []
        real = nsa_mod.materialize_sweep

        def recording(streams, pairs, ss_kept, idx_b, totals):
            seen.append((ss_kept.shape, idx_b.shape, totals))
            return real(streams, pairs, ss_kept, idx_b, totals)

        monkeypatch.setattr(nsa_mod, "materialize_sweep", recording)
        sims = nsa_sweep(originals, [60, 600, 3600], backend="pallas")
        [(ss_shape, idx_shape, totals)] = seen
        K = -(-int(totals.max()) // ops.TILE) * ops.TILE
        N = -(-max(map(len, originals.values())) // ops.TILE) * ops.TILE
        assert ss_shape == idx_shape == (len(sims), K)
        assert K < N
        assert int(np.argmax(totals)) != 0
        _assert_streams_match_numpy(originals, sims)

    def test_materialize_persists_after_earlier_peek(self, tmp_path):
        # materialize(store=False) then materialize() must still persist
        from repro.streamsim import engine
        from repro.streamsim.store import StreamStore

        originals = {"s": preprocess(make_stream("traffic", scale=0.002,
                                                 seed=3))}
        store = StreamStore(str(tmp_path / "store"))
        plan = plan_sweep(store, ["s"], [30], {"s": len(originals["s"])},
                          n_devices=1, host_index=0, n_hosts=1)
        result = engine.execute_sweep(plan, originals, store,
                                      backend="pallas")
        result.materialize(store=False)
        assert not store.exists("s__sim30"), "peek must not persist"
        result.materialize()
        assert store.exists("s__sim30"), "later default call must persist"

    @pytest.mark.parametrize("n_devices", [1, 4])
    def test_leg_spans_and_shard_counters(self, tmp_path, n_devices):
        # one nsa.leg over every shard; per shard an nsa.shard holding its
        # nsa.tables; one totals read and one moments read for the sweep.
        # A 1-shard sweep thus opens nsa.leg, nsa.tables and
        # nsa.device_wait once each, as before the shards overlapped
        from repro import obs
        from repro.streamsim import engine
        from repro.streamsim.store import StreamStore

        originals = {d: preprocess(make_stream(d, scale=0.002, seed=3))
                     for d in ("sogouq", "traffic", "userbehavior")}
        store = StreamStore(str(tmp_path / "store"))
        plan = plan_sweep(store, list(originals), [30, 60],
                          {k: len(v) for k, v in originals.items()},
                          n_devices=n_devices, host_index=0, n_hosts=1)
        n = len(plan.shards)
        assert n == n_devices
        with obs.recording() as rec:
            result = engine.execute_sweep(plan, originals, store,
                                          backend="pallas")
        assert result.mode == "device"
        calls = rec.calls()
        assert {k: calls.get(k) for k in (
            "nsa.leg", "nsa.shard", "nsa.tables", "nsa.totals_wait",
            "nsa.device_wait")} == {
            "nsa.leg": 1, "nsa.shard": n, "nsa.tables": n,
            "nsa.totals_wait": 1, "nsa.device_wait": 1}
        parents = {name: parent for name, parent in rec.totals}
        assert parents["nsa.leg"] is None
        for inner in ("nsa.shard", "nsa.totals_wait", "nsa.device_wait"):
            assert parents[inner] == "nsa.leg"
        assert parents["nsa.tables"] == "nsa.shard"
        counts = rec.counts()
        assert counts["nsa.shard_rows"] == 6
        # the kernels' plane: each shard's longest source, TILE-padded
        from repro.kernels.ops import TILE
        assert counts["nsa.padded_cells"] == sum(
            len(sr.pairs) * -(-max(len(originals[d]) for d, _ in sr.pairs)
                              // TILE) * TILE
            for sr in result.shard_results)
        # the gather and the download touch only the kept width
        assert counts["nsa.gather_cells"] == sum(
            len(sr.pairs) * sr.ss_kept.shape[1]
            for sr in result.shard_results)
        assert counts["nsa.gather_cells"] < counts["nsa.padded_cells"]
        # every co-simulated scenario reports the one leg's seconds
        leg_s = rec.spans()["nsa.leg"]
        assert all(result.nsa_s[sc] == leg_s for sc in result.nsa_s)


def _kept_width_streams():
    """Three day-long sources of different lengths: swept at 60, 600 and
    3600 s their kept counts differ by row, and the largest stays far
    below the longest source."""
    return {d: preprocess(make_stream(d, scale=0.002, seed=3))
            for d in ("sogouq", "traffic", "userbehavior")}


def _assert_streams_match_numpy(originals, sims):
    from repro.streamsim import nsa

    assert sims
    for (name, mr), sim in sims.items():
        ref = nsa(originals[name], mr, backend="numpy")
        for got, want in [(sim.t, ref.t),
                          (sim.scale_stamp, ref.scale_stamp)] + \
                [(sim.payload[k], v) for k, v in ref.payload.items()]:
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
        assert sim.payload.keys() == ref.payload.keys()


# ----------------------------------------------------------- replay errors
def test_replay_many_chains_through_existing_causes():
    # a consumer exception that already carries its own __cause__ must not
    # make LATER failures unreachable: the next failure links to the
    # existing chain's tail
    from repro.streamsim import nsa
    from repro.streamsim.engine import replay_many

    s = preprocess(make_stream("traffic", scale=0.002, seed=5))
    sims = {("traffic", mr): nsa(s, mr) for mr in (5, 11)}

    def consumer(queue):
        buckets = list(queue)
        mr = buckets[-1].scale_stamp + 1 if buckets else 0
        if mr == 5:
            try:
                raise KeyError("inner")
            except KeyError as inner:
                raise ValueError("first") from inner
        raise OSError("second")

    with pytest.raises(RuntimeError) as ei:
        replay_many(sims, consumer, 64)
    chain, exc = [], ei.value.__cause__
    while exc is not None:
        chain.append(type(exc).__name__)
        exc = exc.__cause__
    assert chain == ["ValueError", "KeyError", "OSError"], chain


# ---------------------------------------------------- device-input ops layer
class TestDeviceInputOps:
    def test_stream_metrics_device_matches_host_input(self):
        import jax.numpy as jnp

        from repro.kernels import ops

        rng = np.random.default_rng(0)
        W = 90
        rows = [np.sort(rng.integers(0, W, n).astype(np.int32))
                for n in (700, 1, 2500)]
        N = max(len(r) for r in rows)
        # device layout: garbage (out-of-range stamps allowed) past totals
        ssb = np.full((3, N), W - 1, np.int32)
        for s, r in enumerate(rows):
            ssb[s, :len(r)] = r
        totals = np.array([len(r) for r in rows])
        hist_d, mom_d = ops.stream_metrics_batched_device(
            jnp.asarray(ssb), totals, W)
        hist_h, mom_h, _ = ops.stream_metrics_batched(rows, W)
        np.testing.assert_array_equal(np.asarray(hist_d),
                                      np.asarray(hist_h))
        np.testing.assert_allclose(np.asarray(mom_d), np.asarray(mom_h),
                                   rtol=1e-6)

    def test_stream_metrics_device_rejects_huge_rows(self):
        from repro.kernels import ops
        with pytest.raises(ValueError):
            ops.stream_metrics_batched_device(np.zeros((2, 8), np.int32),
                                              [8, 8], 0)

    def test_trend_corr_pairwise_matches_host_pairs(self):
        from repro.kernels import ops
        from repro.streamsim.metrics import trend_correlation_from_counts

        rng = np.random.default_rng(1)
        D, P = 3, 9
        la = np.array([400, 73, 1])
        qa = np.zeros((D, 400), np.int32)
        for d in range(D):
            qa[d, :la[d]] = rng.integers(0, 40, la[d])
        lb = np.array([60, 200, 400, 17, 1, 60, 90, 5, 300])
        a_index = np.array([0, 0, 0, 1, 1, 1, 2, 2, 2])
        qb = np.zeros((P, 400), np.int32)
        for p in range(P):
            qb[p, :lb[p]] = rng.integers(0, 40, lb[p])
        got = ops.trend_corr_pairwise(qa, la, qb, lb, 60, a_index=a_index)
        for p in range(P):
            exp = trend_correlation_from_counts(
                qa[a_index[p], :la[a_index[p]]], qb[p, :lb[p]])
            if np.isnan(exp):
                assert np.isnan(got[p])
            else:
                assert got[p] == pytest.approx(exp, abs=1e-3)

    def test_trend_corr_pairwise_empty_and_flat_are_nan(self):
        from repro.kernels import ops

        qa = np.array([[3, 3, 3, 3], [1, 2, 3, 4]], np.int32)
        qb = np.array([[1, 2, 3, 4], [0, 0, 0, 0]], np.int32)
        # pair 0: flat left trend (zero variance at window 1) -> NaN;
        # pair 1: empty right series (length 0) -> NaN
        r = ops.trend_corr_pairwise(qa, [4, 4], qb, [4, 0], 1)
        assert np.isnan(r).all()

    def test_trend_corr_pairwise_domain_guard(self):
        from repro.kernels import ops
        with pytest.raises(ops.PallasDomainError):
            ops.trend_corr_pairwise(np.ones((1, 4), np.int32), [4],
                                    np.ones((1, 4), np.int32), [4], 60,
                                    totals=[2 ** 31])

    def test_trend_correlation_batched_device_matches_host_input(self):
        import jax.numpy as jnp

        from repro.kernels import ops

        rng = np.random.default_rng(2)
        lens = [300, 120, 1, 300]
        qs = [rng.integers(0, 30, n) for n in lens]
        qmat = np.zeros((len(qs), max(lens)), np.int32)
        for s, q in enumerate(qs):
            qmat[s, :len(q)] = q
        got = ops.trend_correlation_batched_device(
            jnp.asarray(qmat), lens, 60,
            totals=[int(q.sum()) for q in qs])
        exp = ops.trend_correlation_batched(qs, 60)
        np.testing.assert_allclose(got, exp, atol=1e-6, equal_nan=True)


# ------------------------------------------------- forced 4-device topology
_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax
    assert jax.local_device_count() == 4, jax.local_device_count()

    import repro.kernels.ops as ops_mod
    import repro.kernels.stream_sample as sskern
    from repro.streamsim import Controller

    dispatch_devices = []
    real = sskern.stream_sample_pallas

    def counting(*args, **kwargs):
        dispatch_devices.append(tuple(args[0].devices())[0].id)
        return real(*args, **kwargs)

    sskern.stream_sample_pallas = counting
    ops_mod.stream_sample_pallas = counting

    def consumer(queue):
        return {"records_seen": sum(len(b) for b in queue)}

    datasets = ["sogouq", "traffic", "userbehavior"]
    ranges = [10, 20, 30, 40, 50, 60]
    c = Controller("@STORE@")
    reports = c.run_many(datasets, ranges, consumer, scale=0.002, seed=9,
                         backend="pallas")
    assert len(reports) == 18
    assert len(dispatch_devices) <= 4, dispatch_devices
    assert len(set(dispatch_devices)) == len(dispatch_devices), \\
        "each shard must land on its own device: " + repr(dispatch_devices)

    ref = Controller("@REF_STORE@")
    ref_reports = ref.run_many(datasets, ranges, consumer, scale=0.002,
                               seed=9, backend="numpy")
    for a, b in zip(reports, ref_reports):
        assert a.simulated_rows == b.simulated_rows
        assert abs(a.trend_corr - b.trend_corr) < 1e-3 or \\
            (a.trend_corr != a.trend_corr and b.trend_corr != b.trend_corr)
    print("OK devices=" + repr(sorted(set(dispatch_devices))))
""")


def test_sharded_sweep_on_four_forced_devices(tmp_path):
    """The acceptance shape: 4 forced host-platform devices, the grid
    executes as <= 4 NSA dispatches on 4 DISTINCT devices, reports match
    the single-process numpy path. Runs in a subprocess because
    ``XLA_FLAGS`` must be set before jax initializes."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    script = _SUBPROCESS_SCRIPT \
        .replace("@STORE@", str(tmp_path / "store")) \
        .replace("@REF_STORE@", str(tmp_path / "ref"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "OK devices=" in proc.stdout


# --------------------------- four forced devices: overlap and bit identity
_OVERLAP_SCRIPT = textwrap.dedent("""
    import json

    import numpy as np
    import jax
    assert jax.local_device_count() == 4, jax.local_device_count()

    import repro.kernels.ops as ops_mod
    from repro.streamsim import Controller

    # from the first compaction on: each compaction's device, each
    # metrics dispatch, and each batched host read of device results
    events = []
    real_compact = ops_mod.compact_mask_batched_device
    real_metrics = ops_mod.stream_metrics_batched_device
    real_get = jax.device_get

    def compact(mask):
        events.append(["compact", tuple(mask.devices())[0].id])
        return real_compact(mask)

    def metrics(*args, **kwargs):
        events.append(["metrics", None])
        return real_metrics(*args, **kwargs)

    def device_get(x):
        if events:
            events.append(["read", None])
        return real_get(x)

    ops_mod.compact_mask_batched_device = compact
    ops_mod.stream_metrics_batched_device = metrics
    jax.device_get = device_get

    def consumer(queue):
        return {"records_seen": sum(len(b) for b in queue)}

    datasets = ["sogouq", "traffic", "userbehavior"]
    ranges = [10, 20, 30, 40, 50, 60]
    kw = dict(scale=0.002, seed=9)
    runs = {"four": Controller("@STORE@/four"),
            "one": Controller("@STORE@/one"),
            "numpy": Controller("@STORE@/numpy")}
    runs["four"].run_many(datasets, ranges, consumer, backend="pallas",
                          **kw)
    sweep_events = list(events)
    shards = sorted({(slot, tuple(devs)) for slot, devs
                     in runs["four"].last_placement.values()})
    runs["one"].run_many(datasets, ranges, consumer, backend="pallas",
                         n_devices=1, **kw)
    runs["numpy"].run_many(datasets, ranges, consumer, backend="numpy",
                           **kw)

    def columns(s):
        cols = {"t": s.t, "scale_stamp": s.scale_stamp}
        cols.update({"payload." + k: v for k, v in s.payload.items()})
        return {k: (str(v.dtype), v.shape,
                    v.tolist() if v.dtype == object else v.tobytes().hex())
                for k, v in cols.items()}

    differ = []
    for d in datasets:
        for mr in ranges:
            key = f"{d}__sim{mr}"
            cols = {name: columns(c.store.get(key))
                    for name, c in runs.items()}
            for name in ("one", "numpy"):
                if cols["four"] != cols[name]:
                    differ.append([key, name])
    print(json.dumps({"events": sweep_events,
                      "shards": shards,
                      "differ": differ}))
""")


@pytest.fixture(scope="module")
def four_device_sweep(tmp_path_factory):
    """One child process with four forced host-platform devices: a
    4-shard pallas sweep (its compaction, metrics and read order
    recorded), a 1-shard pallas sweep and the numpy path of the same
    grid; returns the child's summary."""
    tmp = tmp_path_factory.mktemp("four_devices")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    script = _OVERLAP_SCRIPT.replace("@STORE@", str(tmp))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    import json
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_shard_streams_are_byte_identical_to_one_shard_and_numpy(
        four_device_sweep):
    shards = four_device_sweep["shards"]
    assert len(shards) == 4
    assert len({tuple(devs) for _, devs in shards}) == 4
    assert four_device_sweep["differ"] == []


def test_every_shard_is_dispatched_before_any_totals_are_read(
        four_device_sweep):
    events = [tuple(e) for e in four_device_sweep["events"]]
    compacts = [dev for kind, dev in events[:4] if kind == "compact"]
    assert len(compacts) == 4 and len(set(compacts)) == 4, events
    # then one read of every shard's totals, every shard's kept-stamp
    # gather and metrics, and one read of every shard's moments
    assert events[4:10] == [("read", None)] + [("metrics", None)] * 4 + \
        [("read", None)], events
