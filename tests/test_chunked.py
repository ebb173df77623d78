"""Chunked double-buffered pipeline tests (the unbounded-stream PR's
acceptance gates).

Contracts under test:

- **chunk-boundary equivalence**: for any chunk size — single-bucket,
  ragged last chunk, hour, day-sized single chunk — the chunked
  ``run_many`` reproduces the monolithic reports: simulated rows and
  stored streams bit-equal, statistics within the documented
  tolerances, on BOTH backends;
- **carry reset**: back-to-back chunked runs over the same plan report
  identically — no :class:`~repro.kernels.ops.ChunkCarry` state leaks
  across runs (and the second run exercises chunk-granular resume:
  existing chunk files are skipped, not rewritten);
- **device residency + double buffering**: the metrics carry consumes
  jax arrays straight from the chunk dispatch (no host transfer
  between chunks), and chunk ``k+1``'s NSA dispatch is issued BEFORE
  chunk ``k``'s host gather;
- **StreamStore chunk API**: atomic per-chunk append, transparent
  concatenated ``get``, resume skip of existing chunks, completeness
  check at finalize;
- **ChunkFeed**: bounded (high-watermark ≤ maxsize), blocking with no
  busy-wait on both sides; a stalled chunk iterator stalls the chunked
  replay walk without spinning, and fault injection over chunked
  replay preserves the delivery reconciliation identity
  ``delivered == emitted - dropped + duplicated``;
- **multi-day sweeps**: ``duration_s`` grows every scenario's span to
  ``max_range`` per day; chunk-size variants agree bit-exactly; host
  residency stays bounded (``feed_hwm_chunks <= 2``) over the 7-day
  8-scenario acceptance sweep;
- **regression gate**: ``benchmarks/check_regression.py`` fails with a
  clean one-line message (no traceback) on a missing baseline file and
  enforces per-row ratio gates.
"""

import importlib.util
import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.streamsim import (
    ChunkFeed,
    Controller,
    FaultPlan,
    FaultSpec,
    MultiQueueProducer,
    QueueGroup,
    RetryPolicy,
    StreamStore,
    VirtualClock,
    make_stream,
    nsa,
    plan_sweep,
    preprocess,
)
from repro.streamsim import engine
from repro.streamsim.plan import DAY_S
from repro.streamsim.preprocess import Stream

CHAOS = FaultSpec(drop_rate=0.2, duplicate_rate=0.15, reorder_rate=0.25,
                  reorder_window=3, delay_jitter_s=0.01)


def _consumer(queue):
    return {"records_seen": sum(len(b) for b in queue)}


def _reconciles(m):
    return m["buckets_in"] == (m["emitted_buckets"]
                               - m.get("fault_dropped", 0)
                               + m.get("fault_duplicated", 0))


def _mini_stream(name="traffic", scale=0.002, seed=9):
    return preprocess(make_stream(name, scale=scale, seed=seed))


def _slice(sim, lo, hi):
    a, b = np.searchsorted(sim.scale_stamp, [lo, hi])
    return Stream(name=sim.name, t=sim.t[a:b],
                  payload={k: v[a:b] for k, v in sim.payload.items()},
                  scale_stamp=sim.scale_stamp[a:b])


# ------------------------------------------------------------ store chunks
class TestStoreChunks:
    def _chunks(self, n=3, rows=30):
        rng = np.random.default_rng(3)
        t = np.sort(rng.uniform(0, 60, size=rows))
        ss = np.sort(rng.integers(0, 60, size=rows)).astype(np.int64)
        full = Stream(name="s", t=t, payload={"x": rng.normal(size=rows)},
                      scale_stamp=ss)
        edges = np.linspace(0, rows, n + 1).astype(int)
        parts = [Stream(name="s", t=t[a:b],
                        payload={"x": full.payload["x"][a:b]},
                        scale_stamp=ss[a:b])
                 for a, b in zip(edges[:-1], edges[1:])]
        return full, parts

    def test_append_finalize_get_roundtrip(self, tmp_path):
        store = StreamStore(tmp_path)
        full, parts = self._chunks()
        for i, p in enumerate(parts):
            assert store.append_chunk("k", i, p) is True
        assert not store.exists("k")     # invisible until finalized
        store.finalize_chunks("k", name="s", n_chunks=len(parts))
        assert store.exists("k")
        got = store.get("k")
        np.testing.assert_array_equal(got.t, full.t)
        np.testing.assert_array_equal(got.scale_stamp, full.scale_stamp)
        np.testing.assert_array_equal(got.payload["x"], full.payload["x"])
        man = store.manifest("k")
        assert man["chunks"] == len(parts) and man["rows"] == len(full)

    def test_append_chunk_resume_skips_existing(self, tmp_path):
        store = StreamStore(tmp_path)
        _, parts = self._chunks()
        assert store.append_chunk("k", 0, parts[0]) is True
        f = store._chunk_file(store._dir("k"), 0)
        before = f.stat().st_mtime_ns
        # the resume path: an existing chunk is NOT rewritten
        assert store.append_chunk("k", 0, parts[1]) is False
        assert f.stat().st_mtime_ns == before
        assert store.append_chunk("k", 0, parts[0], overwrite=True) is True
        assert store.has_chunk("k", 0) and not store.has_chunk("k", 1)
        assert store.list_chunks("k") == [0]

    def test_finalize_missing_chunk_raises(self, tmp_path):
        store = StreamStore(tmp_path)
        _, parts = self._chunks()
        store.append_chunk("k", 0, parts[0])
        store.append_chunk("k", 2, parts[2])
        with pytest.raises(ValueError, match="missing chunk"):
            store.finalize_chunks("k", name="s", n_chunks=3)
        assert not store.exists("k")     # key stays invisible

    def test_finalize_stats_matches_reread(self, tmp_path):
        # the runner's precomputed-stats path must write the same
        # manifest the re-read path assembles from the chunk files
        store = StreamStore(tmp_path)
        full, parts = self._chunks()
        for i, p in enumerate(parts):
            store.append_chunk("a", i, p)
            store.append_chunk("b", i, p)
        store.finalize_chunks("a", name="s", n_chunks=len(parts))
        store.finalize_chunks(
            "b", name="s", n_chunks=len(parts),
            stats={"rows": len(full), "nbytes": full.nbytes(),
                   "time_range_s": full.time_range})
        ma, mb = store.manifest("a"), store.manifest("b")
        for field in ("rows", "nbytes", "chunks"):
            assert ma[field] == mb[field]
        assert ma["time_range_s"] == pytest.approx(mb["time_range_s"])

    def test_delete_removes_chunk_files(self, tmp_path):
        store = StreamStore(tmp_path)
        _, parts = self._chunks()
        for i, p in enumerate(parts):
            store.append_chunk("k", i, p)
        store.finalize_chunks("k", name="s", n_chunks=len(parts))
        store.delete("k")
        assert not store.exists("k") and store.list_chunks("k") == []


# -------------------------------------------------------------- chunk feed
class TestChunkFeed:
    def _chunk(self, n=4):
        t = np.arange(float(n))
        return Stream(name="c", t=t, payload={"x": t.copy()},
                      scale_stamp=np.arange(n, dtype=np.int64))

    @pytest.mark.timeout(30)
    def test_bounded_put_blocks_until_get(self):
        feed = ChunkFeed(maxsize=2)
        feed.put(self._chunk())
        feed.put(self._chunk())
        with pytest.raises(TimeoutError):
            feed.put(self._chunk(), timeout=0.05)
        got = []
        th = threading.Thread(target=lambda: feed.put(self._chunk()),
                              daemon=True)
        th.start()
        got.append(feed.get())
        th.join(timeout=5)
        assert not th.is_alive()         # put unblocked by the get
        assert feed.stats()["feed_hwm_chunks"] <= 2

    @pytest.mark.timeout(30)
    def test_empty_get_blocks_then_drains_after_close(self):
        feed = ChunkFeed(maxsize=2)
        with pytest.raises(TimeoutError):
            feed.get(timeout=0.05)       # blocking wait, not a spin
        feed.put(self._chunk())
        feed.close()
        assert feed.get() is not None    # close still drains the queue
        assert feed.get() is None        # then signals end-of-timeline
        with pytest.raises(RuntimeError):
            feed.put(self._chunk())

    def test_maxsize_validated(self):
        with pytest.raises(ValueError):
            ChunkFeed(maxsize=0)


# ------------------------------------------------- chunk/monolith equality
def _assert_equivalent(rep, ref, store_a, store_b):
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
    for r in rep:
        sa = store_a.get(f"{r.dataset}__sim{r.max_range}")
        sb = store_b.get(f"{r.dataset}__sim{r.max_range}")
        np.testing.assert_array_equal(sa.t, sb.t)
        np.testing.assert_array_equal(sa.scale_stamp, sb.scale_stamp)


class TestChunkedEquivalence:
    DATASETS = ["sogouq", "traffic"]
    RANGES = [20, 45]                    # 45 % 7 != 0: ragged last chunk

    @pytest.mark.timeout(120)
    @pytest.mark.parametrize("backend", ["numpy", "pallas"])
    @pytest.mark.parametrize("chunk_s", [1, 7, 3600, 86400])
    def test_chunked_reproduces_monolithic(self, tmp_path, backend,
                                           chunk_s):
        c = Controller(str(tmp_path / "chunked"))
        rep = c.run_many(self.DATASETS, self.RANGES, _consumer,
                         scale=0.002, seed=9, backend=backend,
                         chunk_s=chunk_s)
        ref_c = Controller(str(tmp_path / "mono"))
        ref = ref_c.run_many(self.DATASETS, self.RANGES, _consumer,
                             scale=0.002, seed=9, backend=backend)
        _assert_equivalent(rep, ref, c.store, ref_c.store)
        # the bounded-residency stat rides on every chunked report
        for r in rep:
            assert r.consumer_metrics["feed_hwm_chunks"] <= 2

    @pytest.mark.timeout(120)
    def test_carry_resets_per_run_and_resume_skips_chunks(self, tmp_path):
        # two fresh runners over the SAME plan: run 2 recomputes device
        # work but must (a) start from a fresh carry — identical stats —
        # and (b) skip rewriting the chunk files run 1 left behind
        originals = {"traffic": _mini_stream()}
        store = StreamStore(str(tmp_path / "store"))
        plan = plan_sweep(store, ["traffic"], [20, 45],
                          {"traffic": len(originals["traffic"])},
                          scale=0.002, seed=9, n_devices=1, host_index=0,
                          n_hosts=1, chunk_s=7)
        r1 = engine.ChunkedSweepRunner(plan, originals, store,
                                       backend="pallas").run()
        key = plan.scenarios[0].store_key
        mtimes = {i: store._chunk_file(store._dir(key), i).stat().st_mtime_ns
                  for i in store.list_chunks(key)}
        r2 = engine.ChunkedSweepRunner(plan, originals, store,
                                       backend="pallas").run()
        for a, b in zip(r1.shard_results, r2.shard_results):
            np.testing.assert_array_equal(a.totals, b.totals)
            np.testing.assert_array_equal(np.asarray(a.hist),
                                          np.asarray(b.hist))
            np.testing.assert_array_equal(a.mom, b.mom)
        for i, m in mtimes.items():
            assert store._chunk_file(store._dir(key),
                                     i).stat().st_mtime_ns == m, \
                f"chunk {i} was rewritten on resume"

    @pytest.mark.timeout(120)
    def test_device_resident_and_double_buffered(self, tmp_path,
                                                 monkeypatch):
        # (a) the metrics carry consumes jax arrays straight from the
        # chunk dispatch — no host transfer between chunks; (b) chunk
        # k+1's NSA dispatch is issued BEFORE chunk k's host gather
        import jax

        import repro.kernels.ops as ops_mod
        import repro.streamsim.engine as engine_mod

        events = []
        real_sample = ops_mod.stream_sample_pallas
        real_metrics = ops_mod.stream_metrics_chunk
        real_mat = engine_mod.materialize_sweep_chunk

        def counting_sample(*args, **kwargs):
            events.append("sample")
            return real_sample(*args, **kwargs)

        def checking_metrics(carry, ss, totals, lo, hi):
            assert isinstance(ss, jax.Array), \
                f"chunk metrics fed host data: {type(ss)}"
            assert isinstance(totals, jax.Array), \
                f"chunk totals crossed to host early: {type(totals)}"
            events.append("metrics")
            return real_metrics(carry, ss, totals, lo, hi)

        def tracking_mat(*args, **kwargs):
            events.append("mat")
            return real_mat(*args, **kwargs)

        monkeypatch.setattr(ops_mod, "stream_sample_pallas",
                            counting_sample)
        monkeypatch.setattr(ops_mod, "stream_metrics_chunk",
                            checking_metrics)
        monkeypatch.setattr(engine_mod, "materialize_sweep_chunk",
                            tracking_mat)

        originals = {"traffic": _mini_stream()}
        store = StreamStore(str(tmp_path / "store"))
        plan = plan_sweep(store, ["traffic"], [30],
                          {"traffic": len(originals["traffic"])},
                          scale=0.002, seed=9, n_devices=1, host_index=0,
                          n_hosts=1, chunk_s=10)
        runner = engine.ChunkedSweepRunner(plan, originals, store,
                                           backend="pallas")
        assert runner.mode == "device"
        runner.run()
        n = plan.n_chunks
        assert events.count("sample") == n == events.count("metrics")
        assert events.count("mat") == n
        # double buffering: the i-th host gather happens only after the
        # (i+1)-th chunk's NSA dispatch (the last chunk has no successor)
        mat_seen = 0
        for j, e in enumerate(events):
            if e != "mat":
                continue
            samples_before = sum(x == "sample" for x in events[:j])
            if mat_seen < n - 1:
                assert samples_before >= mat_seen + 2, \
                    f"host gather {mat_seen} ran before dispatch " \
                    f"{mat_seen + 1}: {events}"
            mat_seen += 1


# ---------------------------------------------------------------- multi-day
class TestMultiDay:
    @pytest.mark.timeout(300)
    def test_7day_8sc_bounded_and_chunk_size_invariant(self, tmp_path):
        # the acceptance sweep: 7 days x 8 scenarios, two chunk sizes —
        # reports and stored streams must agree bit-exactly, and every
        # report must prove bounded residency (<= 2 chunks buffered)
        datasets = ["sogouq", "traffic"]
        ranges = [15, 30, 45, 60]
        dur = 7 * DAY_S
        reps = {}
        ctrls = {}
        for cs in (45, 150):
            c = Controller(str(tmp_path / f"c{cs}"))
            reps[cs] = c.run_many(datasets, ranges, _consumer, scale=0.001,
                                  seed=5, chunk_s=cs, duration_s=dur)
            ctrls[cs] = c
        for a, b in zip(reps[45], reps[150]):
            assert a.simulated_rows == b.simulated_rows
            assert a.consumer_metrics["records_seen"] == \
                b.consumer_metrics["records_seen"]
            assert a.consumer_metrics["feed_hwm_chunks"] <= 2
            assert b.consumer_metrics["feed_hwm_chunks"] <= 2
        for r in reps[45]:
            key = f"{r.dataset}__sim{r.max_range}__d{dur}"
            sa = ctrls[45].store.get(key)
            sb = ctrls[150].store.get(key)
            np.testing.assert_array_equal(sa.t, sb.t)
            np.testing.assert_array_equal(sa.scale_stamp, sb.scale_stamp)
            # the simulated timeline really spans all 7 days
            assert sa.scale_stamp[-1] >= 6 * r.max_range

    def test_duration_requires_chunking(self, tmp_path):
        c = Controller(str(tmp_path / "s"))
        with pytest.raises(ValueError, match="chunk_s"):
            c.run_many(["traffic"], [20], _consumer, scale=0.002,
                       duration_s=DAY_S)

    def test_chunked_rejects_rewind_features(self, tmp_path):
        # consumed chunks cannot rewind: scenario-grain retry/deadline
        # are monolithic-path features and must be rejected loudly
        c = Controller(str(tmp_path / "s"))
        with pytest.raises(ValueError):
            c.run_many(["traffic"], [20], _consumer, scale=0.002,
                       chunk_s=10, retry_policy=RetryPolicy(max_attempts=2))
        with pytest.raises(ValueError):
            c.run_many(["traffic"], [20], _consumer, scale=0.002,
                       chunk_s=10, consumer_deadline_s=5.0)


# ------------------------------------------------------------ chunked chaos
class TestChunkedFaults:
    @pytest.mark.timeout(120)
    def test_fault_injected_chunked_replay_reconciles(self, tmp_path):
        # the chunked walk must keep the delivery identity under chaos
        c = Controller(str(tmp_path / "s"))
        reports = c.run_many(["traffic"], [20, 40, 60], _consumer,
                             scale=0.002, seed=9, chunk_s=7,
                             fault_plan=FaultPlan(5, default=CHAOS))
        assert len(reports) == 3
        dropped = 0
        for r in reports:
            m = r.consumer_metrics
            assert _reconciles(m), f"{r.dataset} does not reconcile: {m}"
            assert m["records_seen"] == m["records_in"]
            dropped += m.get("fault_dropped", 0)
        assert dropped > 0               # the schedule actually fired

    @pytest.mark.timeout(60)
    def test_stalled_feed_blocks_walk_without_busy_wait(self):
        # round-locked walk: until EVERY scenario's chunk k lands, the
        # producer sleeps in Condition.wait — no records emitted, no CPU
        # burned — then completes normally once the stall resolves
        sim = nsa(_mini_stream(), 20)
        chunks = [_slice(sim, 0, 10), _slice(sim, 10, 20)]
        feeds = {"a": ChunkFeed(maxsize=2), "b": ChunkFeed(maxsize=2)}
        group = QueueGroup(feeds, maxsize=1_000_000)
        producer = MultiQueueProducer(feeds, group.queues,
                                      clock=VirtualClock())
        assert producer.chunked
        status = []
        th = threading.Thread(target=lambda: status.append(producer.run()),
                              daemon=True)
        th.start()
        for ch in chunks:
            feeds["a"].put(ch)
        feeds["a"].close()
        cpu0 = time.process_time()
        time.sleep(0.3)                  # feed "b" is stalled
        cpu_burn = time.process_time() - cpu0
        assert th.is_alive()             # walk is blocked, not finished
        assert group["a"].stats()["buckets_in"] == 0, \
            "round lock broken: scenario emitted before the sweep's round"
        assert cpu_burn < 0.2, \
            f"stalled walk burned {cpu_burn:.2f}s CPU — busy-wait"
        for ch in chunks:
            feeds["b"].put(ch)
        feeds["b"].close()
        th.join(timeout=10)
        assert not th.is_alive() and status == [0]
        for k in ("a", "b"):
            assert group[k].stats()["records_in"] == len(sim)


# ------------------------------------------------------ chunk record slice
def _clipped_gather(t, a, width):
    """The oracle: ``t[r, clip(a[r] + j, 0, N - 1)]``, one index a column."""
    j = np.arange(width)[None, :]
    return np.take_along_axis(t, np.clip(a[:, None] + j, 0, t.shape[1] - 1),
                              axis=1)


class TestSliceRecords:
    N = 40

    @pytest.mark.parametrize("starts,width", [
        ([0], 8),                        # R = 1, first window
        ([N - 1], 8),                    # the last record, then padding
        ([N], 8),                        # timeline over: all padding
        ([0, N - 1, N], 16),
        ([N - 5, 3, 17], 16),            # a + width > N, columns left
        ([0, 7, N], N),                  # width = N
        ([2, 0], N + 24),                # wider than the plane
    ])
    def test_matches_clipped_gather(self, starts, width):
        from repro.kernels import ops
        rng = np.random.default_rng(len(starts) * 100 + width)
        t = np.sort(rng.random((len(starts), self.N)), axis=1) \
            .astype(np.float32)
        a = np.array(starts, np.int32)
        got = np.asarray(ops.slice_records(t, a, width))
        np.testing.assert_array_equal(got, _clipped_gather(t, a, width))

    @pytest.mark.parametrize("rows", [1, 6])
    def test_lowers_to_copies_not_a_gather(self, rows):
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops
        text = ops.slice_records.lower(
            jax.ShapeDtypeStruct((rows, 4096), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.int32), 2048).as_text()
        assert "dynamic_slice" in text
        assert "gather" not in text


# -------------------------------------------------------- regression gate
def _load_check_regression():
    path = (Path(__file__).resolve().parent.parent / "benchmarks"
            / "check_regression.py")
    spec = importlib.util.spec_from_file_location("check_regression", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestRegressionGate:
    def test_missing_file_is_clean_one_line_failure(self, tmp_path,
                                                    capsys):
        cr = _load_check_regression()
        missing = tmp_path / "BENCH_PR7.json"
        assert cr.check([str(missing)]) == 1     # returns, never raises
        err = capsys.readouterr().err
        assert "missing" in err and str(missing) in err

    def _rows(self, name, us, derived):
        return [{"name": name, "us_per_call": us, "derived": derived}]

    def test_speedup_ratio_gate(self, tmp_path):
        cr = _load_check_regression()
        path = tmp_path / "BENCH_PR7.json"
        # 1.25x over the sequential loop: inside the >=1.2x gate
        ok = self._rows("PR7/chunked_pipeline_7day_8sc@scale0.002", 80.0,
                        "sequential_chunk_path_us=100")
        path.write_text(json.dumps(
            ok + self._rows("PR7/chunk_vs_monolith_1day", 100.0,
                            "monolithic_path_us=100")))
        assert cr.check([str(path)]) == 0
        # only 1.1x: misses the >=1.2x gate
        bad = self._rows("PR7/chunked_pipeline_7day_8sc@scale0.002", 91.0,
                         "sequential_chunk_path_us=100")
        path.write_text(json.dumps(
            bad + self._rows("PR7/chunk_vs_monolith_1day", 100.0,
                             "monolithic_path_us=100")))
        assert cr.check([str(path)]) == 1

    def test_overhead_ratio_gate(self, tmp_path):
        cr = _load_check_regression()
        path = tmp_path / "BENCH_PR7.json"
        fast = self._rows("PR7/chunked_pipeline_7day_8sc", 50.0,
                          "sequential_chunk_path_us=100")
        path.write_text(json.dumps(
            fast + self._rows("PR7/chunk_vs_monolith_1day", 104.0,
                              "monolithic_path_us=100")))
        assert cr.check([str(path)]) == 0        # 1.04x <= 1.05x
        path.write_text(json.dumps(
            fast + self._rows("PR7/chunk_vs_monolith_1day", 107.0,
                              "monolithic_path_us=100")))
        assert cr.check([str(path)]) == 1        # 1.07x > 1.05x
