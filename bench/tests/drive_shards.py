#!/usr/bin/env python3
"""Drive one tiny benchmark run (``drive.py``) and report how its sweeps
were sharded.

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python bench/tests/drive_shards.py --workload t123_day_sweep_4chip

Takes ``drive.py``'s arguments. The last line of standard output is the
run's result object, extended with ``shards`` (per plan shard of the last
sweep: its device slot and the devices its arrays sit on) and ``counts``
(the last sweep's program counters).
"""

import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import drive  # noqa: E402  (puts bench/ and src/ on the path)
from benchlib import harness  # noqa: E402


def main(argv=None) -> int:
    seen = []
    run_cell = harness.run_cell

    def watching(*args, before_sweeps=None, **kwargs):
        def watch(ctrl):
            run_many = ctrl.run_many

            def recording(*a, **k):
                reports = run_many(*a, **k)
                seen.append((ctrl.last_placement, reports[0].counts))
                return reports
            ctrl.run_many = recording
            if before_sweeps is not None:
                before_sweeps(ctrl)
        return run_cell(*args, before_sweeps=watch, **kwargs)

    harness.run_cell = watching
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            drive.main(argv)
    finally:
        harness.run_cell = run_cell
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    placement, counts = seen[-1]
    res["shards"] = sorted({(slot, tuple(devs))
                            for slot, devs in placement.values()})
    res["counts"] = counts
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
