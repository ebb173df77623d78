"""The program-span metrics read from a real run: every cell driven on
the CPU at a tiny scale, each span reader it lists reading a positive
number."""

import json

import pytest

from benchlib import spec


def span_metrics(workload):
    return [m["name"] for m in spec.load_benchmark()["per_layer"]
            if m["source"] == "program_span"
            and workload in m.get("workloads", [workload])]


@pytest.mark.parametrize("workload", [
    w["name"] for w in spec.load_benchmark()["workloads"]])
def test_span_readers_read_the_run(child, workload):
    rc, out, err = child("bench/tests/drive_spans.py", "--workload",
                         workload)
    assert rc == 0, err[-4000:]
    values = json.loads(out.strip().splitlines()[-1])
    want = span_metrics(workload)
    assert want and set(values) == set(want)
    for name in want:
        assert values[name] is not None and values[name] > 0, (name, values)
