"""The paper-grid cell simulated anew each sweep, driven end to end on the
CPU at a tiny scale: the fresh mix's sweep loop, its counts, and the check
failing under every fault the cell can have."""

import pytest

from benchlib import spec


def sims_of(workload):
    cfg = spec.load_cell(workload).config
    return {f"{d}__sim{mr}" for d in cfg["datasets"]
            for mr in cfg["max_ranges"]}


def test_fresh_loop_deletes_only_simulated_keys(drive):
    res = drive("t123_day_sweep", "none", "--seconds", "2")
    assert res["correct"] is True
    deleted = res["deleted_keys"]
    want = sims_of("t123_day_sweep")
    assert set(deleted) == want
    assert not any("orig" in k for k in deleted)
    # the warm-up's reset, then one between every two window sweeps
    assert len(deleted) % len(want) == 0 and len(deleted) >= len(want)
    n_sweeps = res["attempted"] // 18
    assert len(deleted) == len(want) * n_sweeps
    assert res["failed"] == 0
    assert res["metrics"]["first_record_s"]["value"] > 0
    assert res["metrics"]["sweep_rec_per_s"]["value"] > 0
    assert set(res["metrics"]) == {"setup_s", "sweep_rec_per_s",
                                   "first_record_s"}


@pytest.mark.parametrize("fault,caught_by", [
    ("alter", "streams_differ"),
    ("half", "deliveries_differ"),
    ("unchanged", "streams_differ"),
])
def test_fault_makes_the_run_incorrect(drive, fault, caught_by):
    res = drive("t123_day_sweep", fault)
    assert res["correct"] is False
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"]
