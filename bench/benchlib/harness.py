"""One run of one cell: set-up, the measured window, the check, the result.

The system under test is the simulator's public entry,
``Controller.run_many``, driven in a closed loop of whole sweeps:

1. set-up (``setup_s``): the persistent compile cache, the cell's raw
   streams from ``--seed`` (the benchmark's own generators, handed to the
   program under its own dataset names), and the traffic mix's warm-up
   sweeps, which POSD the originals into the store and compile every shape
   the window uses;
2. the window: whole sweeps back to back until ``--seconds`` have passed;
   the sweep in flight then finishes and counts. Between sweeps the
   traffic mix's reset runs, timed apart;
3. the check: after the window and the device-memory reading, the plain
   reference (``benchlib.reference``) recomputes the grid and
   ``benchlib.check`` compares every sweep's reports, deliveries and
   fidelity matrices and the stored streams with it.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import warnings
import zlib
from typing import Callable, Dict, List, Optional

from benchlib import check, reference, spec, trace as trace_mod
from benchlib.generators import RawSource


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class Consumer:
    """The benchmark's stand-in for a user's stream job: per scenario it
    counts the records it receives, notes when the first one arrived, folds
    every column into a running CRC-32 in delivery order and checks that
    buckets arrive in increasing order. Each call owns its own state, so
    the concurrent scenario threads share nothing."""

    name = "bench_consumer"

    def __call__(self, queue) -> Dict:
        n, first, last_stamp, ordered = 0, None, -1, True
        crc: Dict[str, int] = {}
        for bucket in queue:
            if first is None:
                first = time.perf_counter()
            n += len(bucket)
            if bucket.scale_stamp <= last_stamp:
                ordered = False
            last_stamp = bucket.scale_stamp
            crc["t"] = zlib.crc32(bucket.t, crc.get("t", 0))
            for k, v in bucket.payload.items():
                crc[k] = zlib.crc32(v, crc.get(k, 0))
        return {"consumed_records": n, "first_record_at": first,
                "ordered": ordered, "crc": crc}


@dataclasses.dataclass
class Sweep:
    seconds: float
    first_record_s: float
    reports: list
    fidelity: Dict


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (``bench/metrics/*.py``)."""

    cell: spec.Cell
    setup_s: float
    sweeps: List[Sweep]
    resets_s: List[float]
    trace: Optional[trace_mod.TraceSummary]
    device_kind: str


def _report_row(r) -> check.Report:
    cm = r.consumer_metrics
    delivered = {"records": int(cm.get("consumed_records", -1)),
                 **{k: int(v) for k, v in cm.get("crc", {}).items()}}
    ov, sv = r.original_volatility, r.simulated_volatility
    return check.Report(
        r.dataset, int(r.max_range), int(r.original_rows),
        int(r.simulated_rows),
        (ov.average, ov.variance, ov.std_variance),
        (sv.average, sv.variance, sv.std_variance),
        float(r.trend_corr), delivered, bool(cm.get("ordered", False)))


def failed_reports(reports, want_mode: str) -> int:
    return sum(r.status != "ok" or r.mode != want_mode or
               r.consumer_metrics.get("consumed_records") != r.simulated_rows
               for r in reports)


def _written_bytes() -> Optional[int]:
    """Bytes this process has caused to be written to storage (Linux)."""
    try:
        with open("/proc/self/io") as f:
            fields = dict(line.split(": ") for line in f.read().splitlines())
        return int(fields["write_bytes"])
    except (OSError, KeyError, ValueError):
        return None


class CompileCounter:
    """Counts XLA compilations through ``jax.monitoring``."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            with self._lock:
                self.count += 1
                self.seconds += duration


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, backend: Optional[str] = None,
             trace_dir: Optional[str] = None,
             before_sweeps: Optional[Callable] = None) -> Dict:
    """Run one cell once and return the result line's object.

    ``backend`` overrides the configuration's (tests run the Pallas
    kernels in interpret mode on the CPU); ``before_sweeps`` is called with
    the controller before the first sweep (tests plant faults there).
    """
    import jax

    from repro import compile_cache
    from repro.kernels.ops import HostFallbackWarning
    from repro.streamsim import Controller, datasets as prog_datasets
    from repro.streamsim.plan import plan_sweep

    cfg = cell.config
    traffic = cell.traffic
    log(f"compile cache: {compile_cache.enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    warnings.simplefilter("error", HostFallbackWarning)
    device = jax.devices()[0]
    jax_start_s = time.perf_counter() - t_start

    backend = backend or cfg["backend"]
    days = int(cfg["days"])
    kw = dict(scale=float(cfg["scale"]), seed=int(seed), backend=backend)
    if cfg["chunk_s"]:
        kw.update(chunk_s=int(cfg["chunk_s"]), duration_s=days * 86_400)
    datasets, max_ranges = list(cfg["datasets"]), list(cfg["max_ranges"])

    source = RawSource(cfg["scale"], seed)

    def to_program(raw):
        return prog_datasets.RawStream(
            raw.name, {k: v.copy() for k, v in raw.columns.items()})

    saved = dict(prog_datasets.DATASETS)
    root = tempfile.mkdtemp(prefix="bench_store_")
    try:
        for d in datasets:
            prog_datasets.DATASETS[d] = source.for_program(d, to_program)
        ctrl = Controller(os.path.join(root, "store"))
        if before_sweeps is not None:
            before_sweeps(ctrl)
        plan = plan_sweep(ctrl.store, datasets, max_ranges,
                          {d: 1 for d in datasets}, scale=kw["scale"],
                          seed=kw["seed"], n_devices=1, host_index=0,
                          n_hosts=1, chunk_s=kw.get("chunk_s", 0),
                          duration_s=kw.get("duration_s", 0))
        sim_keys = [s.store_key for s in plan.scenarios]
        consumer = Consumer()

        def sweep() -> Sweep:
            t0 = time.perf_counter()
            reports = ctrl.run_many(datasets, max_ranges, consumer, **kw)
            dt = time.perf_counter() - t0
            firsts = [r.consumer_metrics.get("first_record_at")
                      for r in reports]
            firsts = [f for f in firsts if f is not None]
            fr = (min(firsts) - t0) if firsts else math.nan
            fid = {int(f.max_range): (list(f.labels),
                                      [list(row) for row in f.trend_corr])
                   for f in ctrl.last_fidelity}
            return Sweep(dt, fr, reports, fid)

        def reset() -> float:
            t0 = time.perf_counter()
            if not traffic["keep_simulated"]:
                for key in sim_keys:
                    ctrl.store.delete(key)
            return time.perf_counter() - t0

        # ---- set-up: warm-up sweeps, each followed by the mix's reset
        c0 = compiles.seconds
        warm = []
        for _ in range(int(traffic["warmup_sweeps"])):
            warm.append(sweep())
            reset()
        pre_s = sum(r.preprocess_s for r in warm[0].reports
                    if r.max_range == max_ranges[0])
        setup_s = time.perf_counter() - t_start
        log(f"setup: {setup_s:.3f} s = jax start {jax_start_s:.3f} + "
            f"generation {source.seconds:.3f} + program POSD "
            f"{max(pre_s - source.seconds, 0.0):.3f} + compile "
            f"{compiles.seconds - c0:.3f} + rest; warm-up sweeps "
            + ", ".join(f"{w.seconds:.3f} s" for w in warm))

        # ---- the window
        n_compiles = compiles.count
        sweeps: List[Sweep] = []
        resets: List[float] = []
        if trace:
            own_dir = trace_dir is None
            trace_dir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
            # no Python tracer: it records every call of the host's
            # replay loop, slowing it and swelling the trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_end = time.perf_counter() + float(seconds)
        while True:
            with jax.profiler.TraceAnnotation("sweep"):
                sweeps.append(sweep())
            if time.perf_counter() >= t_end:
                break
            with jax.profiler.TraceAnnotation("reset"):
                resets.append(reset())
        summary = None
        if trace:
            jax.profiler.stop_trace()
            summary = trace_mod.reduce(trace_mod.find_xplane(trace_dir))
            if own_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        window_compiles = compiles.count - n_compiles
        for i, s in enumerate(sweeps):
            log(f"sweep {i}: {s.seconds:.6f} s, first record "
                f"{s.first_record_s:.6f} s")
        log(f"resets: {len(resets)}, {sum(resets):.6f} s in all")
        log(f"compilations inside the window: {window_compiles}")
        for line in trace_mod.summary_lines(summary):
            log(line)
        stats = device.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))

        # ---- the check, after the window and the memory reading
        want_mode = "device" if backend != "numpy" else "host"
        attempted = sum(len(s.reports) for s in sweeps)
        failed = sum(failed_reports(s.reports, want_mode) for s in sweeps)
        stored = {}
        for s, key in zip(plan.scenarios, sim_keys):
            stored[s.scenario] = (ctrl.store.get(key)
                                  if ctrl.store.exists(key) else None)
        obs = check.Observed(
            sweeps=[[_report_row(r) for r in s.reports] for s in sweeps],
            fidelity=[{mr: (lab, m) for mr, (lab, m) in s.fidelity.items()}
                      for s in sweeps],
            stored=stored)
        del ctrl
        gc.collect()
        t_ref = time.perf_counter()
        raw_days = {d: [source.get(d, i).columns for i in range(days)]
                    for d in datasets}
        ref = reference.expected(cfg, raw_days)
        values = check.numbers(obs, ref)
        log(f"reference and comparison: {time.perf_counter() - t_ref:.3f} s")
    finally:
        prog_datasets.DATASETS.clear()
        prog_datasets.DATASETS.update(saved)
        shutil.rmtree(root, ignore_errors=True)

    limits = cfg["limits"]
    run = Run(cell=cell, setup_s=setup_s, sweeps=sweeps, resets_s=resets,
              trace=summary, device_kind=device.device_kind)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = spec.reader(m["name"])(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read in {cell.name}, "
                "left out of the result")
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    devinfo = {"platform": device.platform, "kind": device.device_kind,
               "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": check.verdict(values, limits),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": devinfo}
    if summary is not None:
        devinfo["busy_s"] = summary.busy_s
        devinfo["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_modules(10)],
            "idle_gaps": [[n, s] for n, s in summary.gaps[:10]]}
    log(f"bytes written by this process: {_written_bytes()}")
    result["checks"] = check.as_json(values, limits)
    for line in check.lines(values, limits):
        log(line)
    return result
