#!/usr/bin/env python3
"""The control of the correctness check: the reference one precision down.

    python3 bench/control.py --workload t123_day_sweep --seeds 1 2 3

For each seed it generates the cell's raw streams, computes the float64
reference and the control (``benchlib.reference.LOW``: NSA normalised in
float32, statistics and trends in bfloat16), puts the control's answers in
the program's place and prints every number the check compares, beside its
limit. The control has to come out as not correct: that is what shows the
comparison can fail. The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def control_numbers(cfg, seed):
    from benchlib import check, reference
    from benchlib.generators import RawSource

    src = RawSource(cfg["scale"], seed)
    raw = {d: [src.get(d, i).columns for i in range(int(cfg["days"]))]
           for d in cfg["datasets"]}
    ref = reference.expected(cfg, raw)
    low = reference.expected(cfg, raw, reference.LOW)
    obs = check.expected_observation(low)
    return check.numbers(obs, ref)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--scale", type=float, default=None,
                   help="override the configuration's scale (tests)")
    args = p.parse_args(argv)
    from benchlib import check, spec

    cell = spec.load_cell(args.workload)
    cfg = dict(cell.config)
    if args.scale is not None:
        cfg["scale"] = args.scale
    worst = {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        values = control_numbers(cfg, seed)
        ok = check.verdict(values, cfg["limits"])
        print(json.dumps({"seed": seed, "correct": ok, "numbers": values,
                          "seconds": time.perf_counter() - t0}), flush=True)
        for k, v in values.items():
            worst[k] = min(worst.get(k, v), v)
    print(json.dumps({"workload": args.workload,
                      "smallest_control_reading": worst,
                      "limits": cfg["limits"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
