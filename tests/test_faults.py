"""Chaos + resilience layer tests (the robustness PR's acceptance gates).

Contracts under test:

- seeded fault schedules are **deterministic**: same ``(seed, key)`` ⇒
  bit-identical schedule, across runs AND across the sequential-vs-merged
  producer walks (each scenario owns its RNG stream);
- a no-op :class:`FaultPlan` leaves the replay **bit-equal** to the
  fault-free pipeline (stats dict equality, not approximation);
- per-scenario delivery reconciles: ``delivered == emitted - dropped +
  duplicated``, under every fault mix;
- ``StreamQueue.close()`` wakes producers blocked in ``put()`` — on a
  full queue AND on the group byte budget — with ``RuntimeError("queue
  closed")`` instead of a hang;
- a wedged consumer surfaces as a *named* ``TimeoutError`` under
  ``consumer_deadline_s`` while sibling scenarios complete;
- transient injected consumer crashes heal via :class:`RetryPolicy`;
  persistent ones trip the :class:`CircuitBreaker` and degrade to
  ``status="partial"`` reports under ``on_failure="degrade"``;
- a sweep killed after k reports resumes via checkpoint markers with
  reports equal to an uninterrupted run.

Hang-prone tests carry ``@pytest.mark.timeout`` — enforced in CI's
chaos-smoke job via pytest-timeout (a no-op marker locally).
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

from repro.streamsim import (
    ByteBudget,
    CircuitBreaker,
    Controller,
    Deadline,
    EventDetectTask,
    FaultPlan,
    FaultSpec,
    MultiQueueProducer,
    Producer,
    QueueGroup,
    RetryPolicy,
    StreamQueue,
    StreamStore,
    SweepCheckpoint,
    VirtualClock,
    make_stream,
    nsa,
    preprocess,
)
from repro.streamsim import engine
from repro.streamsim.faults import InjectedConsumerCrash
from repro.streamsim.queue import Bucket

CHAOS = FaultSpec(drop_rate=0.2, duplicate_rate=0.15, reorder_rate=0.25,
                  reorder_window=3, delay_jitter_s=0.01, stall_rate=0.05,
                  stall_s=0.02)


def _sims(max_ranges=(20, 40, 60), scale=0.002, seed=9):
    s = preprocess(make_stream("traffic", scale=scale, seed=seed))
    return {("traffic", mr): nsa(s, mr) for mr in max_ranges}


def _bucket(stamp=0, n=4):
    t = np.arange(float(n))
    return Bucket(scale_stamp=stamp, t=t, payload={"x": t.copy()},
                  emit_time=0.0)


def _drain(queue):
    return {"records_seen": sum(len(b) for b in queue)}


def _reconciles(m):
    return m["buckets_in"] == (m["emitted_buckets"]
                               - m.get("fault_dropped", 0)
                               + m.get("fault_duplicated", 0))


# ------------------------------------------------------------- determinism
class TestScheduleDeterminism:
    def test_same_seed_same_schedule(self):
        a = FaultPlan(7, default=CHAOS).injector(("traffic", 40))
        b = FaultPlan(7, default=CHAOS).injector(("traffic", 40))
        for _ in range(500):
            assert a.draw() == b.draw()
        assert a.stats() == b.stats()

    def test_different_seed_or_key_differs(self):
        base = [FaultPlan(7, default=CHAOS).injector(("traffic", 40)).draw()
                for _ in range(200)]
        other_seed = FaultPlan(8, default=CHAOS).injector(("traffic", 40))
        other_key = FaultPlan(7, default=CHAOS).injector(("traffic", 60))
        assert [other_seed.draw() for _ in range(200)] != base
        assert [other_key.draw() for _ in range(200)] != base

    def test_drop_schedule_stable_under_other_rates(self):
        # fixed draw order: changing duplicate_rate must not shift WHICH
        # buckets the drop schedule selects
        def drops(spec):
            inj = FaultPlan(3, default=spec).injector("k")
            return [i for i in range(300) if inj.draw().drop]

        only_drop = FaultSpec(drop_rate=0.3)
        with_dups = FaultSpec(drop_rate=0.3, duplicate_rate=0.5,
                              reorder_rate=0.2)
        assert drops(only_drop) == drops(with_dups)

    def test_reset_rewinds_schedule(self):
        inj = FaultPlan(7, default=CHAOS).injector("k")
        first = [inj.draw() for _ in range(100)]
        inj.reset()
        assert [inj.draw() for _ in range(100)] == first
        assert inj.next_attempt() == 1
        inj.reset()
        assert inj.next_attempt() == 2   # attempts survive reset

    @pytest.mark.timeout(60)
    def test_merged_walk_matches_sequential_schedule(self):
        # per scenario, the interleaved MultiQueueProducer walk must apply
        # the EXACT schedule a sequential Producer replay applies
        sims = _sims()
        plan_a = FaultPlan(11, default=CHAOS)
        group = QueueGroup(sims, maxsize=1_000_000)
        mp = MultiQueueProducer(sims, group.queues, clock=VirtualClock(),
                                fault_plan=plan_a)
        assert mp.run() == 0
        for key, sim in sims.items():
            plan_b = FaultPlan(11, default=CHAOS)
            q_ref = StreamQueue(maxsize=1_000_000)
            p_ref = Producer(sim, q_ref, clock=VirtualClock(),
                             faults=plan_b.injector(key))
            assert p_ref.run() == 0
            got = [b.scale_stamp for b in group[key]]
            exp = [b.scale_stamp for b in q_ref]
            assert got == exp
            assert mp.stats(key) == p_ref.stats()
            assert group[key].stats() == q_ref.stats()


# ----------------------------------------------------------- noop == clean
class TestNoopBitEquality:
    @pytest.mark.timeout(60)
    def test_noop_plan_stats_bit_equal_to_fault_free(self):
        sims = _sims()
        clean, t1 = engine.replay_many(sims, _drain, 64)
        chaotic, t2 = engine.replay_many(sims, _drain, 64,
                                         fault_plan=FaultPlan(0))
        assert clean == chaotic

    def test_noop_spec_short_circuits(self):
        assert FaultSpec().is_noop
        assert not CHAOS.is_noop
        assert not FaultSpec(consumer_crash_attempts=(1,)).is_noop

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            FaultSpec(drop_rate=1.5)
        with pytest.raises(ValueError):
            FaultSpec(reorder_window=0)
        with pytest.raises(ValueError):
            FaultSpec(stall_s=-1.0)
        with pytest.raises(ValueError):
            FaultSpec(consumer_crash_attempts=(0,))


# ------------------------------------------------------------ reconciliation
class TestDeliveryReconciliation:
    @pytest.mark.timeout(60)
    def test_single_producer_reconciles(self):
        sims = _sims((60,))
        key = ("traffic", 60)
        plan = FaultPlan(5, default=CHAOS)
        q = StreamQueue(maxsize=1_000_000)
        p = Producer(sims[key], q, clock=VirtualClock(),
                     faults=plan.injector(key))
        assert p.run() == 0
        m = {**q.stats(), **p.stats()}
        assert m["fault_dropped"] > 0 and m["fault_duplicated"] > 0
        assert _reconciles(m)

    @pytest.mark.timeout(60)
    def test_replay_many_reconciles_every_scenario(self):
        sims = _sims()
        metrics, _ = engine.replay_many(sims, _drain, 64,
                                        fault_plan=FaultPlan(5,
                                                             default=CHAOS))
        for key, m in metrics.items():
            assert _reconciles(m), f"{key} does not reconcile: {m}"
            assert m["records_seen"] == m["records_in"]

    def test_reorder_is_loss_free_and_counted(self):
        sims = _sims((60,))
        key = ("traffic", 60)
        spec = FaultSpec(reorder_rate=1.0, reorder_window=2)
        q = StreamQueue(maxsize=1_000_000)
        p = Producer(sims[key], q, clock=VirtualClock(),
                     faults=FaultPlan(2, default=spec).injector(key))
        assert p.run() == 0
        m = {**q.stats(), **p.stats()}
        assert m["fault_reordered"] == m["emitted_buckets"]
        assert _reconciles(m)   # holds flush at close: never a drop
        # multiset of stamps preserved exactly (bounded loss-free reorder)
        got = [b.scale_stamp for b in q]
        assert sorted(got) == sorted(
            int(s) for s in np.unique(sims[key].scale_stamp))

    def test_reorder_actually_perturbs_order_within_window(self):
        # a mixed schedule (held buckets overtaken by inline successors)
        # must produce out-of-order delivery, displaced by <= window
        sims = _sims((60,))
        key = ("traffic", 60)
        spec = FaultSpec(reorder_rate=0.5, reorder_window=3)
        q = StreamQueue(maxsize=1_000_000)
        p = Producer(sims[key], q, clock=VirtualClock(),
                     faults=FaultPlan(2, default=spec).injector(key))
        assert p.run() == 0
        got = [b.scale_stamp for b in q]
        src = sorted(int(s) for s in np.unique(sims[key].scale_stamp))
        assert sorted(got) == src
        assert got != src, "reorder_rate=0.5 must perturb delivery order"
        # bounded: a bucket lands at most `window` emissions late
        for pos, stamp in enumerate(got):
            assert pos - src.index(stamp) <= spec.reorder_window


# ----------------------------------------------------- queue close semantics
class TestCloseWakesProducers:
    @pytest.mark.timeout(30)
    def test_close_wakes_put_blocked_on_full_queue(self):
        q = StreamQueue(maxsize=1)
        q.put(_bucket(0))
        caught = []

        def blocked_producer():
            try:
                q.put(_bucket(1))      # no timeout: blocks on backpressure
            except RuntimeError as e:
                caught.append(e)

        th = threading.Thread(target=blocked_producer, daemon=True)
        th.start()
        time.sleep(0.1)
        assert th.is_alive()           # parked in put()
        q.close()
        th.join(5.0)
        assert not th.is_alive(), "close() must wake a blocked put()"
        assert caught and "queue closed" in str(caught[0])

    @pytest.mark.timeout(30)
    def test_close_wakes_put_blocked_on_byte_budget(self):
        b = _bucket(0)
        group = QueueGroup(["a", "b"], maxsize=64,
                           max_bytes=int(b.nbytes() * 1.5))
        group["a"].put(_bucket(0))     # budget nearly exhausted
        caught = []

        def blocked_producer():
            try:
                group["b"].put(_bucket(1))   # blocks on the shared budget
            except RuntimeError as e:
                caught.append(e)

        th = threading.Thread(target=blocked_producer, daemon=True)
        th.start()
        time.sleep(0.1)
        assert th.is_alive()           # parked on the byte budget
        group["b"].close()
        th.join(5.0)
        assert not th.is_alive(), "close() must wake a budget-blocked put()"
        assert caught and "queue closed" in str(caught[0])


# ------------------------------------------------------------- byte budget
class TestByteBudget:
    @pytest.mark.timeout(30)
    def test_block_policy_is_shared_backpressure(self):
        b = _bucket()
        group = QueueGroup(["a"], maxsize=1000,
                           max_bytes=int(b.nbytes() * 1.5))
        n = 20

        def produce():
            for i in range(n):
                group["a"].put(_bucket(i))
            group["a"].close()

        th = threading.Thread(target=produce, daemon=True)
        th.start()
        got = list(group["a"])
        th.join(5.0)
        assert len(got) == n           # everything delivered, throttled
        assert group.budget_stats()["bytes_used"] == 0
        assert group.budget_stats()["dropped_retention"] == 0

    def test_drop_oldest_evicts_globally_oldest(self):
        b = _bucket()
        group = QueueGroup(["a", "b"], maxsize=1000,
                           max_bytes=int(b.nbytes() * 3.5),
                           retention_policy="drop_oldest")
        for i in range(3):
            group["a"].put(_bucket(i))
        for i in range(3):             # budget full: a's oldest evicted
            group["b"].put(_bucket(10 + i))
        bs = group.budget_stats()
        assert bs["dropped_retention"] > 0
        assert bs["bytes_used"] <= bs["max_bytes"]
        assert group["a"].dropped_retention > 0
        assert group["b"].dropped_retention == 0
        assert group["a"].stats()["dropped_retention"] == \
            group["a"].dropped_retention

    def test_oversized_bucket_admitted_alone(self):
        big = _bucket(0, n=1000)
        group = QueueGroup(["a"], maxsize=10,
                           max_bytes=max(1, big.nbytes() // 2))
        group["a"].put(big)            # empty group: admit over cap
        assert group["a"].get() is not None

    def test_budget_validation(self):
        with pytest.raises(ValueError):
            ByteBudget(0)
        with pytest.raises(ValueError):
            ByteBudget(100, policy="lifo")
        with pytest.raises(ValueError):
            QueueGroup(["a"], max_bytes=100, retention_policy="nope")

    @pytest.mark.timeout(60)
    def test_replay_many_under_byte_budget_delivers_everything(self):
        sims = _sims((20, 40))
        metrics, _ = engine.replay_many(sims, _drain, 64,
                                        max_bytes=1 << 16)
        for key, sim in sims.items():
            assert metrics[key]["records_seen"] == len(sim)
            assert metrics[key]["dropped_retention"] == 0


# --------------------------------------------------------- consumer deadline
class TestConsumerDeadline:
    @pytest.mark.timeout(60)
    def test_wedged_consumer_is_a_named_timeout(self):
        sims = _sims((20, 40))
        wedged_key = ("traffic", 40)

        def consumer(queue):
            buckets = list(queue)      # drain to EOS
            if buckets[-1].scale_stamp + 1 == 40:
                time.sleep(30)         # wedge well past the deadline
            return {"records_seen": sum(len(b) for b in buckets)}

        with pytest.raises(RuntimeError) as ei:
            engine.replay_many(sims, consumer, 64,
                               consumer_deadline_s=0.5)
        msg = str(ei.value)
        assert repr(wedged_key) in msg
        assert repr(("traffic", 20)) not in msg
        assert isinstance(ei.value.__cause__, TimeoutError)

    @pytest.mark.timeout(60)
    def test_wedged_consumer_degrades_and_siblings_complete(self):
        sims = _sims((20, 40))

        def consumer(queue):
            buckets = list(queue)
            if buckets[-1].scale_stamp + 1 == 40:
                time.sleep(30)
            return {"records_seen": sum(len(b) for b in buckets)}

        metrics, _ = engine.replay_many(sims, consumer, 64,
                                        consumer_deadline_s=0.5,
                                        on_failure="degrade")
        ok = metrics[("traffic", 20)]
        bad = metrics[("traffic", 40)]
        assert ok["records_seen"] == len(sims[("traffic", 20)])
        assert "degraded" not in ok
        assert bad["degraded"] and "TimeoutError" in bad["failed"]
        assert bad["attempts"] == 1

    def test_bad_on_failure_rejected(self):
        with pytest.raises(ValueError):
            engine.replay_many({}, _drain, 64, on_failure="ignore")


# ------------------------------------------------------------ retry/breaker
class TestRetryAndBreaker:
    RETRY = RetryPolicy(max_attempts=3, base_delay_s=0.001,
                        max_delay_s=0.002, seed=1)

    @pytest.mark.timeout(60)
    def test_transient_crash_heals_with_retry(self):
        sims = _sims((20, 40))
        flaky = ("traffic", 40)
        plan = FaultPlan(3, overrides={
            flaky: FaultSpec(consumer_crash_attempts=(1,))})
        metrics, _ = engine.replay_many(sims, _drain, 64, fault_plan=plan,
                                        retry_policy=self.RETRY)
        assert metrics[flaky]["records_seen"] == len(sims[flaky])
        assert metrics[flaky]["retries"] == 1
        assert "retries" not in metrics[("traffic", 20)]

    @pytest.mark.timeout(60)
    def test_persistent_crash_trips_breaker_and_degrades(self):
        sims = _sims((20, 40))
        broken = ("traffic", 40)
        plan = FaultPlan(3, overrides={
            broken: FaultSpec(consumer_crash_attempts=(1, 2, 3, 4, 5))})
        metrics, _ = engine.replay_many(sims, _drain, 64, fault_plan=plan,
                                        retry_policy=self.RETRY,
                                        breaker_threshold=3,
                                        on_failure="degrade")
        bad = metrics[broken]
        assert bad["degraded"]
        assert "InjectedConsumerCrash" in bad["failed"]
        assert bad["attempts"] == 3
        assert bad["breaker"] == "open"
        assert metrics[("traffic", 20)]["records_seen"] == \
            len(sims[("traffic", 20)])

    @pytest.mark.timeout(60)
    def test_persistent_crash_raises_by_default(self):
        sims = _sims((20,))
        plan = FaultPlan(3, default=FaultSpec(
            consumer_crash_attempts=(1, 2, 3)))
        with pytest.raises(RuntimeError) as ei:
            engine.replay_many(sims, _drain, 64, fault_plan=plan,
                               retry_policy=self.RETRY)
        assert isinstance(ei.value.__cause__, InjectedConsumerCrash)

    @pytest.mark.timeout(60)
    def test_retry_preserves_transport_schedule(self):
        # the retried replay must reconcile with the SAME drop/dup counts
        # as a clean one-shot replay of the same schedule (reset(), not a
        # new stream)
        sims = _sims((60,))
        key = ("traffic", 60)
        chaos_crash = dataclasses.replace(CHAOS,
                                          consumer_crash_attempts=(1,))
        metrics, _ = engine.replay_many(
            sims, _drain, 64,
            fault_plan=FaultPlan(5, overrides={key: chaos_crash}),
            retry_policy=self.RETRY)
        ref_q = StreamQueue(maxsize=1_000_000)
        ref_p = Producer(sims[key], ref_q, clock=VirtualClock(),
                         faults=FaultPlan(5, default=CHAOS).injector(key))
        assert ref_p.run() == 0
        m = metrics[key]
        assert _reconciles(m)
        for f in ("fault_dropped", "fault_duplicated", "fault_reordered"):
            assert m[f] == ref_p.stats()[f]

    def test_retry_policy_deterministic_and_capped(self):
        p = RetryPolicy(max_attempts=5, base_delay_s=0.1, max_delay_s=0.5,
                        multiplier=4.0, jitter=0.5, seed=42)
        assert p.delay(2, "k") == p.delay(2, "k")
        assert p.delay(2, "k") != p.delay(2, "other")
        for a in range(1, 5):
            assert p.delay(a, "k") <= 0.5 * 1.5
        assert len(p.delays("k")) == 4
        with pytest.raises(ValueError):
            p.delay(0)
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            RetryPolicy(jitter=2.0)

    def test_circuit_breaker_transitions(self):
        t = [0.0]
        br = CircuitBreaker(failure_threshold=2, recovery_s=10.0,
                            clock=lambda: t[0])
        assert br.allow()
        br.record_failure()
        assert br.state == "closed" and br.allow()
        br.record_failure()
        assert br.state == "open" and not br.allow()
        t[0] = 11.0                     # recovery window elapsed
        assert br.allow() and br.state == "half-open"
        br.record_failure()             # probe fails: re-open
        assert br.state == "open"
        t[0] = 22.0
        assert br.allow()
        br.record_success()             # probe heals: closed
        assert br.state == "closed" and br.allow()

    def test_deadline(self):
        t = [0.0]
        d = Deadline(2.0, clock=lambda: t[0])
        assert d.remaining() == pytest.approx(2.0) and not d.expired
        t[0] = 3.0
        assert d.remaining() == 0.0 and d.expired
        assert Deadline(None).remaining() is None
        assert not Deadline(None).expired


# --------------------------------------------------------- checkpoint/resume
class TestCheckpointResume:
    @staticmethod
    def _report_key_fields(r):
        d = dataclasses.asdict(r)
        for f in ("preprocess_s", "nsa_s", "produce_s", "spans", "counts"):
            d.pop(f)
        return d

    def test_store_markers_roundtrip(self, tmp_path):
        store = StreamStore(str(tmp_path / "store"))
        store.put_marker("sweep1", "report__traffic__40", {"x": 1})
        assert store.has_marker("sweep1", "report__traffic__40")
        assert store.get_marker("sweep1", "report__traffic__40") == {"x": 1}
        assert store.list_markers("sweep1") == ["report__traffic__40"]
        assert store.list_markers("other") == []
        store.clear_markers("sweep1")
        assert store.list_markers("sweep1") == []
        assert store.list() == []       # markers invisible to streams
        # nested namespaces (the sweep service's layout) are legal, but
        # empty or dot-prefixed segments stay out of the namespace
        store.put_marker("a/b", "n", {})
        assert store.list_markers("a/b") == ["n"]
        with pytest.raises(ValueError):
            store.put_marker("a//b", "n", {})
        with pytest.raises(ValueError):
            store.put_marker("a/.trash-x", "n", {})
        with pytest.raises(ValueError):
            store.put_marker("ok", "../n", {})

    def test_sweep_id_stable_and_config_sensitive(self, tmp_path):
        from repro.streamsim.plan import plan_sweep
        store = StreamStore(str(tmp_path / "store"))
        kw = dict(scale=1.0, seed=0, n_devices=1, host_index=0, n_hosts=1)
        a = plan_sweep(store, ["traffic"], [20, 40], {"traffic": 10}, **kw)
        b = plan_sweep(store, ["traffic"], [20, 40], {"traffic": 10}, **kw)
        c = plan_sweep(store, ["traffic"], [20, 60], {"traffic": 10}, **kw)
        d = plan_sweep(store, ["traffic"], [20, 40], {"traffic": 10},
                       pairs=[("traffic", 40)], **kw)
        assert a.sweep_id == b.sweep_id
        assert a.sweep_id != c.sweep_id
        assert a.sweep_id == d.sweep_id   # pairs resume: same namespace

    @pytest.mark.timeout(120)
    def test_kill_after_k_reports_resumes_equal(self, tmp_path,
                                                monkeypatch):
        datasets, max_ranges = ["traffic"], [20, 40, 60]
        kw = dict(scale=0.002, seed=9, checkpoint=True)

        ref = Controller(str(tmp_path / "ref"))
        ref_reports = ref.run_many(datasets, max_ranges, _drain, scale=0.002,
                                   seed=9)

        class SimulatedKill(BaseException):
            pass

        c = Controller(str(tmp_path / "store"))
        real_build = engine.build_report
        built = []

        def dying_build(*args, **kwargs):
            if len(built) == 2:        # kill after k=2 completed reports
                raise SimulatedKill()
            r = real_build(*args, **kwargs)
            built.append(r)
            return r

        monkeypatch.setattr(engine, "build_report", dying_build)
        with pytest.raises(SimulatedKill):
            c.run_many(datasets, max_ranges, _drain, **kw)
        monkeypatch.setattr(engine, "build_report", real_build)

        # exactly k report markers survived the kill
        markers_root = tmp_path / "store" / "_markers"
        sweep_dirs = list(markers_root.iterdir())
        assert len(sweep_dirs) == 1
        reports_marked = [p for p in sweep_dirs[0].iterdir()
                         if p.name.startswith("report__")]
        assert len(reports_marked) == 2

        resumed = c.run_many(datasets, max_ranges, _drain, **kw)
        assert len(resumed) == len(ref_reports) == 3
        for got, exp in zip(resumed, ref_reports):
            assert self._report_key_fields(got) == \
                self._report_key_fields(exp)
        # completed sweep clears its markers
        assert not any(markers_root.iterdir())

    @pytest.mark.timeout(120)
    def test_uninterrupted_checkpoint_run_equals_plain(self, tmp_path):
        datasets, max_ranges = ["traffic"], [20, 40]
        a = Controller(str(tmp_path / "plain")).run_many(
            datasets, max_ranges, _drain, scale=0.002, seed=9)
        b = Controller(str(tmp_path / "ckpt")).run_many(
            datasets, max_ranges, _drain, scale=0.002, seed=9,
            checkpoint=True)
        for got, exp in zip(b, a):
            assert self._report_key_fields(got) == \
                self._report_key_fields(exp)

    def test_checkpoint_marker_roundtrip_of_reports(self, tmp_path):
        store = StreamStore(str(tmp_path / "store"))
        ckpt = SweepCheckpoint(store, "s1")
        vol = engine.Volatility(average=1.0, variance=2.0,
                                std_variance=0.5, time_range=60)
        r = engine.SimulationReport(
            dataset="traffic", max_range=40, original_rows=100,
            simulated_rows=50, compression=2.0, original_volatility=vol,
            simulated_volatility=vol, trend_corr=0.9, preprocess_s=0.1,
            nsa_s=0.2, produce_s=0.3,
            consumer_metrics={"records_seen": 50}, status="partial",
            failure="RuntimeError('x')", attempts=2)
        ckpt.mark_report(r)
        assert ckpt.done_scenarios() == [("traffic", 40)]
        loaded = ckpt.load_reports()[("traffic", 40)]
        assert loaded == r
        ckpt.mark_materialized([("traffic", 40)])
        assert ckpt.materialized_scenarios() == [("traffic", 40)]
        ckpt.clear()
        assert ckpt.done_scenarios() == []


# ----------------------------------------------------- controller integration
class TestControllerResilience:
    @pytest.mark.timeout(120)
    def test_run_many_degrades_to_partial_report(self, tmp_path):
        broken = ("traffic", 40)
        plan = FaultPlan(3, overrides={
            broken: FaultSpec(consumer_crash_attempts=(1, 2, 3, 4, 5))})
        c = Controller(str(tmp_path / "store"))
        reports = c.run_many(
            ["traffic"], [20, 40], _drain, scale=0.002, seed=9,
            fault_plan=plan,
            retry_policy=RetryPolicy(max_attempts=2, base_delay_s=0.001),
            on_failure="degrade")
        by_sc = {(r.dataset, r.max_range): r for r in reports}
        assert by_sc[("traffic", 20)].status == "ok"
        assert by_sc[("traffic", 20)].failure is None
        bad = by_sc[broken]
        assert bad.status == "partial"
        assert "InjectedConsumerCrash" in bad.failure
        assert bad.attempts == 2
        # the partial report still carries real simulation statistics
        assert bad.simulated_rows > 0
        # and round-trips through the metrics repository JSON
        loaded = [m for m in c.load_metrics()
                  if m.get("status") == "partial"]
        assert len(loaded) == 1 and loaded[0]["max_range"] == 40

    @pytest.mark.timeout(120)
    def test_run_many_chaos_reports_reconcile(self, tmp_path):
        c = Controller(str(tmp_path / "store"))
        reports = c.run_many(
            ["traffic"], [20, 40], _drain, scale=0.002, seed=9,
            fault_plan=FaultPlan(5, default=CHAOS))
        for r in reports:
            assert r.status == "ok" and r.attempts == 1
            assert _reconciles(r.consumer_metrics)


# ----------------------------------------------------- stream-task chaos tier
class TestTaskChaosIntegration:
    """The task tier meets the chaos layer: :class:`EventDetectTask` run
    through ``replay_many`` under a non-noop :class:`FaultPlan` must (a)
    satisfy the delivery reconciliation identity ``buckets_in ==
    emitted - dropped + duplicated`` per scenario, and (b) keep its
    detections displaced by at most the reorder window under a loss-free
    bounded reorder — exactly zero displacement once the watermark buffer
    (``reorder_tolerance``) is sized to that window."""

    REORDER = FaultSpec(reorder_rate=1.0, reorder_window=4)

    @staticmethod
    def _detect_sims():
        # CUSUM needs bucket-count variation to alarm at all; the sliced
        # sogouq morning ramp gives it (traffic at tiny scale compresses
        # to a flat one-record-per-bucket series).
        if not hasattr(TestTaskChaosIntegration, "_cache"):
            from repro.streamsim import slice_stream
            s = slice_stream(
                preprocess(make_stream("sogouq", scale=0.3, seed=0)), 7200)
            TestTaskChaosIntegration._cache = {("sogouq", 100): nsa(s, 100)}
        return TestTaskChaosIntegration._cache

    @pytest.mark.timeout(120)
    def test_detect_reconciles_under_full_chaos(self):
        sims = _sims((40, 60))
        task = EventDetectTask(mode="threshold", threshold=2.0)
        metrics, _ = engine.replay_many(
            sims, task, 64, fault_plan=FaultPlan(11, default=CHAOS))
        for key, m in metrics.items():
            assert _reconciles(m), f"{key} does not reconcile: {m}"
            assert m["task"] == "event-detect"
            # every delivered bucket reached the task
            assert m["task_buckets"] == m["buckets_in"]

    @pytest.mark.timeout(120)
    def test_threshold_event_set_survives_bounded_reorder(self):
        # threshold events carry the triggering bucket's OWN stamp, so a
        # loss-free reorder leaves the event SET identical (stamp
        # displacement zero <= window) even with no watermark buffer.
        sims = _sims((60,))
        key = ("traffic", 60)
        base, _ = engine.replay_many(
            sims, EventDetectTask(mode="threshold", threshold=2.0), 64)
        chaos, _ = engine.replay_many(
            sims, EventDetectTask(mode="threshold", threshold=2.0), 64,
            fault_plan=FaultPlan(3, default=self.REORDER))
        assert chaos[key]["fault_reordered"] > 0
        assert _reconciles(chaos[key])
        assert sorted(chaos[key]["task_events"].tolist()) == \
            sorted(base[key]["task_events"].tolist())

    @pytest.mark.timeout(120)
    def test_cusum_displacement_bounded_by_reorder_window(self):
        # CUSUM is order-sensitive; with the watermark buffer sized to
        # the fault plan's reorder window the faulted event list is
        # bit-equal to the unfaulted one (displacement bound met at 0).
        sims = self._detect_sims()
        key = ("sogouq", 100)
        w = self.REORDER.reorder_window
        kw = dict(mode="cusum", drift=0.5, h=2.0, reorder_tolerance=w)
        base, _ = engine.replay_many(sims, EventDetectTask(**kw), 64)
        chaos, _ = engine.replay_many(
            sims, EventDetectTask(**kw), 64,
            fault_plan=FaultPlan(3, default=self.REORDER))
        assert chaos[key]["fault_reordered"] > 0
        assert base[key]["detect_events"] > 0   # non-vacuous comparison
        assert chaos[key]["task_events"].tolist() == \
            base[key]["task_events"].tolist()

    @pytest.mark.timeout(120)
    def test_cusum_without_watermark_stays_within_window(self):
        # even with NO watermark buffer, every faulted detection sits
        # within the reorder window of some unfaulted detection: the
        # bounded-displacement half of the acceptance gate.
        sims = self._detect_sims()
        key = ("sogouq", 100)
        w = self.REORDER.reorder_window
        kw = dict(mode="cusum", drift=0.5, h=2.0)
        base, _ = engine.replay_many(sims, EventDetectTask(**kw), 64)
        chaos, _ = engine.replay_many(
            sims, EventDetectTask(**kw), 64,
            fault_plan=FaultPlan(3, default=self.REORDER))
        ref = base[key]["task_events"]
        assert len(ref) > 0
        for stamp in chaos[key]["task_events"]:
            assert np.abs(ref - stamp).min() <= w, \
                f"event at {stamp} displaced beyond window {w}: {ref}"
