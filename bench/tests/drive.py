#!/usr/bin/env python3
"""Drive one benchmark run at a tiny scale, optionally with a planted fault.

    python bench/tests/drive.py --workload t123_day_sweep --fault alter

Skips the harness's look for a TPU, shrinks the configuration to
``--scale``, plants the named fault in the timed path and prints the run's
result object, extended with the store keys the traffic mix deleted, as
the last line. On the CPU the Pallas kernels run in interpret mode; the
tests run this in a child process so that no patch, warning filter or JAX
setting leaks. On a chip, ``--trace-dir`` records a small trace.

Faults (each breaks the path under the window, never the check):

- ``alter``: one simulated record's timestamp changed where NSA produces
  it (the sweep's host gather, or the chunk gather on the chunked path);
- ``half``: every scenario replays only the first half of its stream;
- ``unchanged``: a step returns its state unchanged — the store keeps no
  simulated stream (``put_many`` / ``append_chunk`` write nothing);
- ``stale_carry``: on the chunked path the metrics carry never advances;
- ``none``: no fault.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def _altered(stream):
    from repro.streamsim.preprocess import Stream

    t = stream.t.copy()
    if len(t):
        t[len(t) // 2] += 1.0
    return Stream(stream.name, t, stream.payload, stream.scale_stamp)


def _half(stream):
    from repro.streamsim.preprocess import Stream

    n = len(stream) // 2
    return Stream(stream.name, stream.t[:n],
                  {k: v[:n] for k, v in stream.payload.items()},
                  None if stream.scale_stamp is None
                  else stream.scale_stamp[:n])


def plant(fault: str) -> None:
    from repro.kernels import ops
    from repro.streamsim import engine, store

    if fault == "alter":
        mono, chunk = engine.materialize_sweep, engine.materialize_sweep_chunk

        def materialize_sweep(*a, **k):
            out = mono(*a, **k)
            first = next(iter(out))
            out[first] = _altered(out[first])
            return out

        def materialize_sweep_chunk(*a, **k):
            out = chunk(*a, **k)
            out[0] = _altered(out[0])
            return out

        engine.materialize_sweep = materialize_sweep
        engine.materialize_sweep_chunk = materialize_sweep_chunk
    elif fault == "half":
        replay, feed = engine.replay_many, engine.ChunkedSweepRunner._feed_chunk

        def replay_many(sims, *a, **k):
            return replay({sc: _half(s) for sc, s in sims.items()}, *a, **k)

        def _feed_chunk(self, feeds, spec, k, chunk):
            return feed(self, feeds, spec, k, _half(chunk))

        engine.replay_many = replay_many
        engine.ChunkedSweepRunner._feed_chunk = _feed_chunk
    elif fault == "unchanged":
        store.StreamStore.put_many = lambda self, items, extra=None: None
        store.StreamStore.append_chunk = \
            lambda self, key, idx, stream, overwrite=False: True
        store.StreamStore.finalize_chunks = lambda self, *a, **k: None
    elif fault == "stale_carry":
        ops.stream_metrics_chunk = \
            lambda carry, ss, valid, lo, hi: \
            dataclasses.replace(carry, next_lo=int(hi))
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--fault", default="none")
    p.add_argument("--backend", default="pallas")
    p.add_argument("--scale", type=float, default=0.002)
    p.add_argument("--seed", type=int, default=2 ** 31 + 11)
    p.add_argument("--seconds", type=float, default=0.5)
    p.add_argument("--trace-dir", default=None,
                   help="trace the window and keep the trace here")
    args = p.parse_args(argv)
    t_start = time.perf_counter()

    from benchlib import harness, spec

    cell = spec.load_cell(args.workload)
    cell.config = dict(cell.config, scale=args.scale)
    plant(args.fault)
    deleted = []

    def watch(ctrl):
        delete = ctrl.store.delete

        def recording_delete(key):
            deleted.append(key)
            return delete(key)
        ctrl.store.delete = recording_delete

    res = harness.run_cell(cell, args.seed, args.seconds,
                           args.trace_dir is not None, t_start=t_start,
                           backend=args.backend, trace_dir=args.trace_dir,
                           before_sweeps=watch)
    res["deleted_keys"] = deleted
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
