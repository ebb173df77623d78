#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload t123_day_sweep --seed 7 --seconds 30 \
        --trace 0

Run it from the repository root, on a machine whose JAX sees the TPU
chips the cell asks for (``BENCHMARK.json``). ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window. The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: every number
compared with its limit); standard error ends with the same comparisons.
Without a TPU, or with fewer chips than the cell needs, it prints no result
and exits 1.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from benchlib import harness, spec

    cell = spec.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chip(s), JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
