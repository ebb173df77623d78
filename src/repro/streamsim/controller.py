"""Controller — the user-side core component (paper §4).

The paper's controller has three functions, mirrored 1:1 here:
  (1) control the producer to load + simulate a user-defined time range;
  (2) collect physical/workload metrics of the stream processing system;
  (3) manage metrics of different stream data for viewing.

The paper collects metrics over the SPS's REST API into a "metrics
repository"; here the consumers (training/serving loops) expose a metrics
callback and the repository is a JSON directory.

Since the plan/engine split, the controller is a THIN driver: ``run`` and
``run_many`` build a :class:`~repro.streamsim.plan.SweepPlan` and hand it
to the sweep engine (:mod:`repro.streamsim.engine`), which owns all NSA /
metrics / fidelity / replay orchestration. What remains here is the
paper-side surface: the store, the metrics repository, and the
per-dataset preprocessing timer.
"""

from __future__ import annotations

import itertools
import json
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.streamsim import engine
from repro.streamsim.datasets import make_stream
# Report dataclasses live in the engine's report layer now; re-exported
# here because the controller is their historical import location.
from repro.streamsim.engine import FidelityReport, SimulationReport  # noqa: F401
from repro.streamsim.faults import FaultPlan
from repro.streamsim.nsa import _resolve_backend, nsa
from repro.streamsim.plan import DAY_S, plan_sweep
from repro.streamsim.preprocess import Stream, preprocess
from repro.streamsim.queue import StreamQueue
from repro.streamsim.resilience import RetryPolicy, SweepCheckpoint
from repro.streamsim.store import StreamStore


class Controller:
    def __init__(self, store_dir: str, metrics_dir: Optional[str] = None):
        self.store = StreamStore(store_dir)
        self.metrics_dir = Path(metrics_dir or (Path(store_dir) / "_metrics"))
        self.metrics_dir.mkdir(parents=True, exist_ok=True)
        self.fidelity_dir = self.metrics_dir / "fidelity"
        self._metrics_seq = itertools.count()
        #: the per-sweep S×S fidelity matrices from the latest
        #: :meth:`run_many` call (also persisted under ``fidelity_dir``)
        self.last_fidelity: List[FidelityReport] = []
        #: where the latest monolithic :meth:`run_many` ran each scenario
        #: it computed in device mode (see :meth:`repro.streamsim.engine.
        #: DeviceSweepResult.placement`)
        self.last_placement: Dict = {}

    # ----------------------------------------------------- (1) simulate/run
    def prepare(self, dataset: str, *, scale: float = 1.0, seed: int = 0,
                force: bool = False) -> Stream:
        """POSD once, persist (preprocessing is a one-time job — paper §3.1)."""
        key = f"{dataset}__orig"
        if self.store.exists(key) and not force:
            return self.store.get(key)
        with obs.span("controller.posd"):
            stream = preprocess(make_stream(dataset, scale=scale, seed=seed))
        self.store.put(key, stream, {"scale": scale, "seed": seed})
        return stream

    def simulate(self, dataset: str, max_range: int, *, scale: float = 1.0,
                 seed: int = 0, force: bool = False,
                 backend: str = "auto") -> Stream:
        """NSA once per (dataset, max_range), persist (paper §3.2: stored
        'because repeated normalizing and sampling operations are not
        performed').

        ``backend`` selects the NSA implementation ("auto" picks the
        device-resident Pallas path on TPU, numpy otherwise — see
        :mod:`repro.streamsim.nsa`); every backend is bit-identical, so the
        store cache is backend-agnostic.
        """
        key = f"{dataset}__sim{max_range}"
        if self.store.exists(key) and not force:
            return self.store.get(key)
        original = self.prepare(dataset, scale=scale, seed=seed, force=force)
        sim = nsa(original, max_range, backend=backend)
        self.store.put(key, sim, {"max_range": max_range})
        return sim

    def _prepare_all(self, datasets: Sequence[str], scale: float,
                     seed: int, duration_s: int = 0) -> tuple:
        """POSD every dataset, timing each (matching ``run``'s reports) with
        its ``controller.prepare`` span.

        ``duration_s > 0`` prepares the MULTI-DAY original instead: one
        preprocessed day per 86 400 s of duration (day ``d`` generated
        with ``seed + d``, so days carry distinct traffic), each day
        rebased onto ``[d*86400, (d+1)*86400)`` so the diurnal cycle
        stays aligned across days, concatenated and trimmed to
        ``duration_s``. Cached under ``<dataset>__orig__d<duration>``.
        """
        originals, t_pre = {}, {}
        for d in datasets:
            with obs.span("controller.prepare") as sp:
                if duration_s > 0:
                    originals[d] = self._prepare_multiday(d, scale, seed,
                                                          duration_s)
                else:
                    originals[d] = self.prepare(d, scale=scale, seed=seed)
            t_pre[d] = sp.seconds
        return originals, t_pre

    def _prepare_multiday(self, dataset: str, scale: float, seed: int,
                          duration_s: int) -> Stream:
        key = f"{dataset}__orig__d{duration_s}"
        if self.store.exists(key):
            return self.store.get(key)
        with obs.span("controller.posd"):
            n_days = -(-int(duration_s) // DAY_S)
            ts, payloads = [], []
            for day in range(n_days):
                raw = make_stream(dataset, scale=scale, seed=seed + day)
                st = preprocess(raw)
                # rebase the day onto its slot; clip a (pathological) day
                # running past 86 400 s to the slot boundary so the
                # concatenation stays chronological
                t_day = np.minimum(st.t - st.t[0], float(DAY_S))
                ts.append(t_day + day * float(DAY_S))
                payloads.append(st.payload)
            t = np.concatenate(ts)
            cols = payloads[0].keys()
            payload = {c: np.concatenate([p[c] for p in payloads])
                       for c in cols}
            keep = t < float(duration_s)     # trim the partial last day
            stream = Stream(name=dataset, t=t[keep],
                            payload={c: v[keep] for c, v in payload.items()},
                            scale_stamp=None)
        self.store.put(key, stream, {"scale": scale, "seed": seed,
                                     "duration_s": int(duration_s)})
        return stream

    def run(self, dataset: str, max_range: int,
            consumer: Callable[[StreamQueue], Dict], *,
            scale: float = 1.0, seed: int = 0,
            queue_size: int = 64, backend: str = "auto"
            ) -> SimulationReport:
        """Full pipeline: POSD -> NSA -> PSDA -> consumer (the SPS task).

        A thin driver: the scenario becomes a one-cell
        :class:`~repro.streamsim.plan.SweepPlan` executed by the sweep
        engine; the consumer drains the queue on the CALLING thread (no
        thread-safety requirement, unlike :meth:`run_many`).

        Parameters
        ----------
        dataset : str
            Dataset name (see :func:`repro.streamsim.datasets.make_stream`).
        max_range : int
            Simulated time range for NSA.
        consumer : callable
            Drains the queue and returns its own metrics dict (function
            (2): collecting workload metrics of the SPS).
        scale, seed :
            Synthetic-dataset shape parameters (store-cache keyed).
        queue_size : int, default 64
            Bounded-queue capacity; the producer honours backpressure.
        backend : {"auto", "numpy", "pallas"}
            Passed through to NSA and the metrics engine. NSA output is
            bit-identical across backends; metric statistics agree within
            the documented 1e-3 tolerance; out-of-domain inputs fall back
            to numpy automatically.

        Returns
        -------
        SimulationReport
            All report statistics come from the engine's batched metrics
            pass, so each stream is read once instead of once per
            statistic. The report is also persisted as JSON (function (3):
            the metrics repository).

        Raises
        ------
        RuntimeError
            If the producer reports a non-zero fault status.
        """
        originals, t_pre = self._prepare_all([dataset], scale, seed)
        plan = plan_sweep(self.store, [dataset], [max_range],
                          {dataset: len(originals[dataset])},
                          scale=scale, seed=seed, n_hosts=1, host_index=0,
                          n_devices=1)
        result = engine.execute_sweep(plan, originals, self.store,
                                      backend=backend)
        sim = result.materialize()[(dataset, max_range)]
        consumer_metrics, t_prod = engine.replay_one(sim, consumer,
                                                     queue_size)
        report = engine.build_report(result, (dataset, max_range),
                                     t_pre[dataset], t_prod,
                                     consumer_metrics)
        self.save_metrics(report)
        return report

    def run_many(self, datasets: Sequence[str], max_ranges: Sequence[int],
                 consumer: Callable[[StreamQueue], Dict], *,
                 scale: float = 1.0, seed: int = 0, queue_size: int = 64,
                 backend: str = "auto", fidelity_window_s: int = 60,
                 n_devices: Optional[int] = None,
                 host_index: Optional[int] = None,
                 n_hosts: Optional[int] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 breaker_threshold: int = 3,
                 consumer_deadline_s: Optional[float] = None,
                 on_failure: str = "raise",
                 max_bytes: Optional[int] = None,
                 retention_policy: str = "block",
                 checkpoint: bool = False,
                 chunk_s: int = 0,
                 duration_s: int = 0,
                 service: bool = False,
                 lease_ttl_s: float = 60.0,
                 service_poll_s: float = 0.2,
                 lease_batch: int = 1,
                 worker_id: Optional[str] = None,
                 service_deadline_s: Optional[float] = None
                 ) -> List[SimulationReport]:
        """The Tables 1-3 scenario sweep (datasets × time ranges), planned
        and executed by the sweep engine.

        A thin driver over :func:`repro.streamsim.plan.plan_sweep` +
        :func:`repro.streamsim.engine.execute_sweep` +
        :func:`repro.streamsim.engine.run_sweep`: the plan resolves
        store-cache hits and partitions the store-missing scenarios into
        per-device (and, under ``jax.distributed``, per-host) shards with
        range-padded row counts balanced across shards; the engine then
        runs each shard's normalize→sample→compact→metrics chain as ONE
        dispatch per kernel stage on that shard's device, keeps kept-index
        sets and per-second counts device-resident until a single
        ``materialize()`` host pass, and replays every scenario through
        ONE multi-queue virtual-time loop.

        Parameters
        ----------
        datasets : sequence of str
            Dataset names (see :func:`repro.streamsim.datasets.make_stream`).
        max_ranges : sequence of int
            Simulated time ranges — the sweep grid is their cross product
            with ``datasets``.
        consumer : callable
            Drains the queue per scenario and returns its metrics dict (the
            SPS-side workload). Scenario consumers run CONCURRENTLY (one
            thread per scenario — the batched replay's shared backpressure
            requires it), so a consumer shared across scenarios must be
            thread-safe.
        scale, seed, queue_size :
            As in :meth:`run`.
        backend : {"auto", "numpy", "pallas"}
            Passed through to the engine; ``"numpy"`` (and ``"auto"`` off
            TPU) reproduces the sequential per-scenario reports bit-equal,
            ``"pallas"`` keeps the whole reporting chain device-resident
            (statistics within the documented 1e-3 tolerance).
        fidelity_window_s : int, default 60
            Sliding-mean window for the per-sweep fidelity matrices.
        n_devices, host_index, n_hosts : int, optional
            Plan-partition overrides (default: this process's jax
            topology — see :func:`repro.streamsim.plan.plan_sweep`). In a
            multi-host run every host builds the same plan and reports
            only its own scenario slice into the shared repository.
        fault_plan : FaultPlan, optional
            Seeded per-scenario chaos schedule (drops / duplicates /
            reorders / jitter / stalls / consumer crashes) injected into
            the replay — see :mod:`repro.streamsim.faults`.
        retry_policy, breaker_threshold, consumer_deadline_s, on_failure :
            The replay resilience knobs, passed through to
            :func:`repro.streamsim.engine.replay_many`: solo retries with
            capped exponential backoff, a per-scenario circuit breaker,
            a consumer deadline that surfaces a wedged consumer as a
            named scenario failure instead of hanging ``join()`` forever,
            and ``on_failure="degrade"`` to turn terminal failures into
            ``status="partial"`` reports instead of raising.
        max_bytes, retention_policy :
            Optional shared byte budget across the sweep's queues (broker
            retention — ``"block"`` or ``"drop_oldest"``); see
            :class:`repro.streamsim.queue.ByteBudget`.
        checkpoint : bool, default False
            Persist per-scenario completion markers through the stream
            store (namespace: :attr:`~repro.streamsim.plan.SweepPlan.
            sweep_id`). A killed sweep re-invoked with the same arguments
            resumes from the last completed scenario: finished scenarios'
            reports load from their markers, only the remainder is
            re-simulated/replayed, and the markers are cleared once the
            whole sweep completes. (Resume re-plans only the remaining
            scenarios, so its fidelity matrices cover the resumed subset;
            single-host sweeps are the intended scope.)
        chunk_s : int, default 0
            ``> 0`` routes the sweep through the chunked double-buffered
            pipeline (:class:`repro.streamsim.engine.ChunkedSweepRunner`
            + :func:`repro.streamsim.engine.run_sweep_chunked`): each
            scenario's timeline is computed, persisted and replayed in
            ``chunk_s``-second chunks with cross-chunk carry state
            device-resident, so host residency stays bounded (at most 2
            chunks per scenario buffered — the ``feed_hwm_chunks`` stat
            in each report's ``consumer_metrics`` proves it) while the
            reports compose to the monolithic answer. ``chunk_s`` does
            NOT enter the store cache key — chunked and monolithic runs
            share simulated streams. The chunked path does not support
            ``retry_policy``/``consumer_deadline_s`` (a consumed chunk
            cannot be rewound); ``on_failure="degrade"`` still applies.
        duration_s : int, default 0
            ``> 0`` simulates a MULTI-DAY source: one preprocessed day
            per 86 400 s (see :meth:`_prepare_all`), every scenario's
            effective simulated range growing to ``max_range`` per day
            (``ScenarioSpec.span_s``), preserving the per-day
            compression ratio. Requires ``chunk_s > 0`` (multi-day runs
            exist to be streamed, not held whole).
        service : bool, default False
            Run the sweep through the fault-tolerant lease-based sweep
            service (:mod:`repro.streamsim.service`) instead of static
            host partitioning: scenarios are published to a durable work
            queue in the store, any number of participants (this process
            plus every other ``run_many(service=True)`` pointed at the
            same store and sweep config) lease, execute, and publish
            them, expired leases of dead workers are requeued (and
            quarantined as ``status="poisoned"`` after
            ``breaker_threshold`` worker deaths on one scenario), and
            EVERY participant returns the full grid's merged reports
            plus the cross-host-merged full S×S fidelity matrix on
            :attr:`last_fidelity`. Incompatible with ``chunk_s`` and
            ``checkpoint`` (the service's queue IS the checkpoint).
        lease_ttl_s, service_poll_s, lease_batch, worker_id,
        service_deadline_s :
            Service knobs: lease time-to-live (must comfortably exceed
            one scenario batch's runtime — heartbeats renew it while the
            worker lives), idle poll interval, scenarios leased per
            claim, this participant's stable id (defaults to
            host-pid-nonce), and an overall give-up deadline.

        Returns
        -------
        list of SimulationReport
            One per (dataset, max_range) scenario, in ``for dataset: for
            max_range`` order, each equivalent to the per-scenario
            :meth:`run` report (``nsa_s`` holds the sweep's shared NSA wall
            time for scenarios simulated together and ``produce_s`` the
            shared replay-loop wall time; ``nsa_s`` is 0.0 for store cache
            hits). Every report also carries the call's span totals and
            counters (``spans``, ``counts``; see :mod:`repro.obs`).

        Notes
        -----
        As a side product, each sweep's full S×S trend-correlation matrix
        over [originals..., sims@max_range...] — the Fig.-6 fidelity
        check — is computed from ONE batched dispatch chain per
        ``max_range`` (consuming the engine's device-resident count rows
        on the pallas backend), saved as JSON under ``fidelity_dir``, and
        exposed on :attr:`last_fidelity`.
        """
        if duration_s and not chunk_s:
            raise ValueError(
                "duration_s requires chunk_s > 0 — multi-day sweeps run "
                "through the chunked pipeline")
        if chunk_s and (retry_policy is not None or
                        consumer_deadline_s is not None):
            raise ValueError(
                "retry_policy/consumer_deadline_s are monolithic-replay "
                "features; the chunked pipeline cannot rewind a "
                "scenario's consumed chunks")
        if service and (chunk_s or checkpoint):
            raise ValueError(
                "service mode is incompatible with chunk_s/checkpoint — "
                "the service's durable work queue is its own checkpoint "
                "and leases are scenario-granular")
        with obs.recording() as rec:
            originals, t_pre = self._prepare_all(datasets, scale, seed,
                                                 duration_s)
            if _resolve_backend(backend) == "numpy":
                # host mode ignores the partition; don't let the topology
                # defaults force a jax runtime initialization on the pure
                # numpy path
                n_devices = 1 if n_devices is None else n_devices
                host_index = 0 if host_index is None else host_index
                n_hosts = 1 if n_hosts is None else n_hosts
            row_counts = {d: len(originals[d]) for d in datasets}
            if service:
                return self._run_service(
                    datasets, max_ranges, originals, t_pre, consumer,
                    scale=scale, seed=seed, queue_size=queue_size,
                    backend=backend, fidelity_window_s=fidelity_window_s,
                    n_devices=n_devices, host_index=host_index,
                    n_hosts=n_hosts, fault_plan=fault_plan,
                    retry_policy=retry_policy,
                    breaker_threshold=breaker_threshold,
                    consumer_deadline_s=consumer_deadline_s,
                    on_failure=on_failure, max_bytes=max_bytes,
                    retention_policy=retention_policy,
                    lease_ttl_s=lease_ttl_s, service_poll_s=service_poll_s,
                    lease_batch=lease_batch, worker_id=worker_id,
                    service_deadline_s=service_deadline_s, recorder=rec)
            with obs.span("plan.sweep"):
                plan = plan_sweep(self.store, datasets, max_ranges,
                                  row_counts, scale=scale, seed=seed,
                                  n_devices=n_devices, host_index=host_index,
                                  n_hosts=n_hosts, chunk_s=chunk_s,
                                  duration_s=duration_s)
            ckpt: Optional[SweepCheckpoint] = None
            prior: Dict = {}
            grid = [s.scenario for s in plan.scenarios]
            if plan.n_hosts > 1:
                local = {s.scenario for s in plan.local_missing} | \
                    {s.scenario for s in plan.cached}
                grid = [sc for sc in grid if sc in local]
            if checkpoint:
                ckpt = SweepCheckpoint(self.store, plan.sweep_id)
                done = set(ckpt.done_scenarios()) & set(grid)
                if done:
                    # resume: completed scenarios' reports come straight
                    # from their markers; only the remainder is planned
                    # and run
                    prior = {sc: r for sc, r in ckpt.load_reports().items()
                             if sc in done}
                    remaining = [sc for sc in grid if sc not in done]
                    plan = None
                    if remaining:
                        with obs.span("plan.sweep"):
                            plan = plan_sweep(
                                self.store, datasets, max_ranges,
                                row_counts, scale=scale, seed=seed,
                                pairs=remaining, n_devices=n_devices,
                                host_index=host_index, n_hosts=n_hosts,
                                chunk_s=chunk_s, duration_s=duration_s)
            new_reports: List[SimulationReport] = []
            self.last_placement = {}
            if plan is not None:
                if chunk_s:
                    runner = engine.ChunkedSweepRunner(
                        plan, originals, self.store, backend=backend,
                        checkpoint=ckpt)
                    new_reports, fidelity = engine.run_sweep_chunked(
                        runner, consumer, queue_size=queue_size,
                        fidelity_window_s=fidelity_window_s, t_pre=t_pre,
                        fault_plan=fault_plan, on_failure=on_failure,
                        max_bytes=max_bytes,
                        retention_policy=retention_policy, checkpoint=ckpt)
                else:
                    result = engine.execute_sweep(
                        plan, originals, self.store, backend=backend,
                        checkpoint=ckpt)
                    self.last_placement = result.placement()
                    new_reports, fidelity = engine.run_sweep(
                        result, consumer, queue_size=queue_size,
                        fidelity_window_s=fidelity_window_s, t_pre=t_pre,
                        fault_plan=fault_plan, retry_policy=retry_policy,
                        breaker_threshold=breaker_threshold,
                        consumer_deadline_s=consumer_deadline_s,
                        on_failure=on_failure, max_bytes=max_bytes,
                        retention_policy=retention_policy, checkpoint=ckpt)
                if not chunk_s and plan.n_hosts > 1:
                    # PR 5 gap closed: publish this host's exact count
                    # rows into the shared store and, once every host's
                    # rows are there, replace the partial per-host
                    # matrices with the merged FULL S×S matrix (the last
                    # host to finish — and any later re-run — sees the
                    # complete artifact)
                    merged = self._publish_and_merge_fidelity(
                        result, plan, fidelity_window_s)
                    if merged is not None:
                        fidelity = merged
                self.last_fidelity = fidelity
                with obs.span("engine.report"):
                    for fr in fidelity:
                        self.save_fidelity(fr)
            by_sc = dict(prior)
            by_sc.update({(r.dataset, r.max_range): r for r in new_reports})
            reports = [by_sc[sc] for sc in grid]
            _carry_totals(reports, rec)
            for report in reports:
                self.save_metrics(report)
            if ckpt is not None:
                ckpt.clear()     # sweep complete: the next run starts fresh
            return reports

    def _run_service(self, datasets, max_ranges, originals, t_pre,
                     consumer, *, scale, seed, queue_size, backend,
                     fidelity_window_s, n_devices, host_index, n_hosts,
                     fault_plan, retry_policy, breaker_threshold,
                     consumer_deadline_s, on_failure, max_bytes,
                     retention_policy, lease_ttl_s, service_poll_s,
                     lease_batch, worker_id, service_deadline_s,
                     recorder: obs.Recorder) -> List[SimulationReport]:
        """The ``run_many(service=True)`` leg: one participant of the
        lease-based sweep service. Every participant gets the full
        grid's merged reports back; only the reports THIS worker
        computed land in its local metrics repository (the shared store
        carried them to every peer already)."""
        from repro.streamsim.service import run_service_sweep

        if n_hosts is None or host_index is None or n_devices is None:
            from repro.distributed import process_topology
            pidx, pcount, local = process_topology()
            n_hosts = pcount if n_hosts is None else n_hosts
            host_index = pidx if host_index is None else host_index
            n_devices = local if n_devices is None else n_devices
        if worker_id is None:
            import os
            worker_id = f"host{host_index}-{os.getpid()}"
        reports, fidelity, mine = run_service_sweep(
            self.store, datasets, max_ranges, originals, consumer,
            scale=scale, seed=seed, t_pre=t_pre, queue_size=queue_size,
            backend=backend, fidelity_window_s=fidelity_window_s,
            n_devices=n_devices, lease_ttl_s=lease_ttl_s,
            poll_s=service_poll_s, lease_batch=lease_batch,
            breaker_threshold=breaker_threshold, worker_id=worker_id,
            n_participants=n_hosts, deadline_s=service_deadline_s,
            fault_plan=fault_plan, retry_policy=retry_policy,
            consumer_deadline_s=consumer_deadline_s,
            on_failure=on_failure, max_bytes=max_bytes,
            retention_policy=retention_policy)
        self.last_fidelity = fidelity
        with obs.span("engine.report"):
            for fr in fidelity:
                self.save_fidelity(fr)
        from repro.streamsim.service import scenario_marker
        _carry_totals(reports, recorder)
        own = set(mine)
        for report in reports:
            if scenario_marker(report.dataset, report.max_range) in own:
                self.save_metrics(report)
        return reports

    def _publish_and_merge_fidelity(self, result, plan, window_s):
        """Cross-host fidelity merge for STATIC multi-host sweeps.

        Publishes this host's exact per-scenario count rows (plus the
        per-dataset original rows) under the host-independent
        ``sweep_group_id`` namespace, then attempts the same count-row
        merge the sweep service uses. Returns the merged full-grid
        :class:`FidelityReport` list, or None while peers' rows are
        still missing (the caller keeps its partial per-host matrices —
        exactly the pre-PR 9 behavior — until the last host closes the
        sweep)."""
        from repro.streamsim.service import (merge_fidelity, pack_counts,
                                             scenario_marker)

        gid = plan.sweep_group_id
        ns = f"{gid}/fidelity"
        worker = f"host{plan.host_index}"
        for (d, mr), row in result.count_rows().items():
            name = f"sim__{scenario_marker(d, mr)}"
            # first-writer-wins: rows are deterministic (within backend
            # tolerance), and keeping the first writer preserves true
            # provenance — a later host re-reporting a cache hit must
            # not claim the row it never computed
            if not self.store.has_marker(ns, name):
                self.store.put_marker(ns, name,
                                      {"counts": pack_counts(row),
                                       "worker": worker})
        for d in plan.datasets:
            name = f"orig__{d}"
            if not self.store.has_marker(ns, name):
                self.store.put_marker(ns, name, {
                    "counts": pack_counts(result.om[d].counts),
                    "worker": worker})
        merged = merge_fidelity(self.store, gid, plan.datasets,
                                plan.max_ranges, window_s=window_s)
        D = len(plan.datasets)
        complete = len(merged) == len(plan.max_ranges) and \
            all(len(fr.labels) == 2 * D for fr in merged)
        return merged if complete else None

    # -------------------------------------------------- (3) metrics manager
    def _unique_path(self, directory: Path, stem: str) -> Path:
        """ms stamp + a monotonic per-controller sequence number: two
        artifacts landing in the same millisecond (routine under
        ``run_many``) must not overwrite each other; the existence loop
        covers other controllers writing the same directory."""
        path = directory / f"{stem}_{next(self._metrics_seq):06d}.json"
        while path.exists():
            path = directory / f"{stem}_{next(self._metrics_seq):06d}.json"
        return path

    def save_metrics(self, report: SimulationReport) -> Path:
        stem = (f"{report.dataset}_max{report.max_range}_"
                f"{int(time.time() * 1e3)}")
        path = self._unique_path(self.metrics_dir, stem)
        with open(path, "w") as f:
            json.dump(report.to_json(), f, indent=2, default=_np_default)
        return path

    def save_fidelity(self, report: FidelityReport) -> Path:
        """Persist one sweep's S×S fidelity matrix under ``fidelity_dir``
        (kept out of ``metrics_dir`` proper so :meth:`list_metrics` keeps
        its one-file-per-scenario contract)."""
        self.fidelity_dir.mkdir(parents=True, exist_ok=True)
        stem = f"fidelity_max{report.max_range}_{int(time.time() * 1e3)}"
        path = self._unique_path(self.fidelity_dir, stem)
        with open(path, "w") as f:
            json.dump(report.to_json(), f, indent=2, default=_np_default)
        return path

    def list_fidelity(self) -> List[Path]:
        return sorted(self.fidelity_dir.glob("*.json"))

    def load_fidelity(self) -> List[Dict]:
        out = []
        for p in self.list_fidelity():
            with open(p) as f:
                out.append(json.load(f))
        return out

    def list_metrics(self) -> List[Path]:
        return sorted(self.metrics_dir.glob("*.json"))

    def load_metrics(self) -> List[Dict]:
        out = []
        for p in self.list_metrics():
            with open(p) as f:
                out.append(json.load(f))
        return out


def _carry_totals(reports: List[SimulationReport],
                  rec: obs.Recorder) -> None:
    """Give every report of a ``run_many`` call the call's span totals
    and counters, as every report of a sweep carries the shared
    ``nsa_s``."""
    spans, counts = rec.spans(), rec.counts()
    for r in reports:
        r.spans, r.counts = dict(spans), dict(counts)


def _np_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON serializable: {type(o)}")
