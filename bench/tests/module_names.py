#!/usr/bin/env python3
"""Print, as one JSON list, the XLA modules a tiny sweep runs.

    JAX_PLATFORMS=cpu python bench/tests/module_names.py

Runs a small monolithic sweep and a small chunked one through
``Controller.run_many`` under the JAX profiler and lists the ``hlo_module``
of every executed operation. A module carries its jitted function's name
(``jit_<function>``) on every backend, so the metric readers' name tables
can be checked against it without a chip.
"""

import glob
import json
import os
import shutil
import sys
import tempfile
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def consumer(queue):
    return {"consumed_records": sum(len(b) for b in queue)}


def main() -> int:
    import jax
    from jax._src.profiler import ProfileData

    from repro.streamsim import Controller

    root = tempfile.mkdtemp(prefix="bench_modules_")
    ctrl = Controller(os.path.join(root, "store"))
    kw = dict(scale=0.002, seed=5, backend="pallas")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(root, "trace"),
                             profiler_options=opts)
    ctrl.run_many(["sogouq", "userbehavior"], [600, 3600], consumer, **kw)
    ctrl.run_many(["userbehavior"], [600, 3600], consumer, chunk_s=3600,
                  duration_s=2 * 86_400, **kw)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(root, "trace", "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    module = dict(e.stats).get("hlo_module")
                if module:
                    names.add(str(module))
    shutil.rmtree(root, ignore_errors=True)
    print(json.dumps(sorted(names)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
