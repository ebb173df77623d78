"""The paper grid's four-chip cell, driven end to end on the CPU at a tiny
scale with four forced host-platform devices: one plan shard per device,
the check passing, and failing under a planted fault."""

import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(TESTS))
CELL = "t123_day_sweep_4chip"


@pytest.fixture(scope="module")
def drive_shards(tmp_path_factory):
    """One run of the cell (``bench/tests/drive_shards.py``) in a child
    process that sees four CPU devices; returns its result object."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        "--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1")
    env["OMP_NUM_THREADS"] = "1"

    def run(fault):
        p = subprocess.run(
            [sys.executable, "bench/tests/drive_shards.py", "--workload",
             CELL, "--fault", fault], cwd=ROOT, env=env,
            capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-4000:]
        return json.loads(p.stdout.strip().splitlines()[-1])
    return run


def test_one_shard_per_device_and_the_check_passes(drive_shards):
    res = drive_shards("none")
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] % 18 == 0
    assert res["device"]["count"] == 4
    slots = [slot for slot, _ in res["shards"]]
    devices = [tuple(devs) for _, devs in res["shards"]]
    assert sorted(slots) == [0, 1, 2, 3]
    assert len(set(devices)) == 4 and all(len(d) == 1 for d in devices)
    assert res["counts"]["nsa.shard_rows"] == 18
    assert 0 < res["counts"]["nsa.padded_cells"]


def test_altered_record_makes_the_run_incorrect(drive_shards):
    res = drive_shards("alter")
    assert res["correct"] is False
    c = res["checks"]["streams_differ"]
    assert c["value"] > c["limit"]
