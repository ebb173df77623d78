#!/usr/bin/env python3
"""Drive one tiny benchmark run (``drive.py``) and read the cell's
program-span metrics from it.

    JAX_PLATFORMS=cpu python bench/tests/drive_spans.py \
        --workload ub_stream_chunked

Takes ``drive.py``'s arguments. The run reports its end-to-end metrics
as usual; the ``Run`` its readers were handed is kept, and every
``program_span`` metric that ``BENCHMARK.json`` lists for the cell is
read from it. The last line of standard output is one JSON object,
metric name -> value (null where the reader found nothing).
"""

import argparse
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import drive  # noqa: E402  (puts bench/ and src/ on the path)
from benchlib import spec  # noqa: E402


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--workload", required=True)
    workload = p.parse_known_args(argv)[0].workload

    runs = []
    reader = spec.reader

    def keeping(name, root=spec.ROOT):
        read = reader(name, root)

        def read_and_keep(run):
            runs.append(run)
            return read(run)
        return read_and_keep

    spec.reader = keeping
    try:
        with contextlib.redirect_stdout(io.StringIO()):
            drive.main(argv)
    finally:
        spec.reader = reader
    cell = spec.load_cell(workload)
    print(json.dumps({m["name"]: reader(m["name"])(runs[-1])
                      for m in cell.per_layer
                      if m["source"] == "program_span"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
