"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  bench_volatility  -> Tables 1-3 (volatility at the six time ranges)
  bench_network     -> Fig. 6   (bytes into the SPS, trend correlation)
  bench_efficiency  -> Fig. 7 / Table 4 + the >=24x headline (§6)
  bench_kernels     -> Pallas kernel micro-benchmarks

Alongside the CSV, every module's rows are written machine-readable to
``BENCH_<module>.json`` (set ``BENCH_OUT_DIR`` to redirect; default CWD) so
the per-PR perf trajectory can be tracked by tooling instead of CSV scraping.

``BENCH_QUICK=1`` switches every module to CI-smoke scales (small synthetic
streams, reduced kernel shapes); reduced rows carry an ``@shape`` suffix in
their name so trend tooling never mixes them with full-scale rows. CI runs
the quick mode on every PR and uploads the JSON as workflow artifacts.
"""

import json
import os
import sys


def _parse_row(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_efficiency, bench_kernels, bench_network,
                            bench_PR4, bench_PR5, bench_PR6, bench_PR7,
                            bench_PR8, bench_PR9, bench_PR10,
                            bench_volatility)
    out_dir = os.environ.get("BENCH_OUT_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    csv = ["name,us_per_call,derived"]
    for mod in (bench_volatility, bench_network, bench_efficiency,
                bench_kernels, bench_PR4, bench_PR5, bench_PR6, bench_PR7,
                bench_PR8, bench_PR9, bench_PR10):
        print(f"# running {mod.__name__} ...", file=sys.stderr, flush=True)
        start = len(csv)
        mod.run(csv)
        suffix = mod.__name__.split(".")[-1].replace("bench_", "")
        path = os.path.join(out_dir, f"BENCH_{suffix}.json")
        with open(path, "w") as f:
            json.dump([_parse_row(r) for r in csv[start:]], f, indent=2)
        print(f"# wrote {path}", file=sys.stderr, flush=True)
    print("\n".join(csv))


if __name__ == '__main__':
    main()
