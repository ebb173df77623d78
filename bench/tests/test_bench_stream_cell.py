"""The chunked multi-day cell driven end to end on the CPU at a tiny
scale, and the check failing under every fault it can have."""

import pytest

from benchlib import spec


def test_chunked_loop_counts_and_deletes(drive):
    res = drive("ub_stream_chunked", "none", "--seconds", "1")
    assert res["correct"] is True
    cfg = spec.load_cell("ub_stream_chunked").config
    d = cfg["days"] * 86_400
    want = {f"userbehavior__sim{mr}__d{d}" for mr in cfg["max_ranges"]}
    assert set(res["deleted_keys"]) == want
    assert res["attempted"] % len(want) == 0 and res["failed"] == 0
    assert res["metrics"]["first_record_s"]["value"] > 0


@pytest.mark.parametrize("fault,caught_by", [
    ("alter", "streams_differ"),
    ("half", "deliveries_differ"),
    ("unchanged", "streams_differ"),
    ("stale_carry", "stat_rel_err"),
])
def test_fault_makes_the_run_incorrect(drive, fault, caught_by):
    res = drive("ub_stream_chunked", fault)
    assert res["correct"] is False
    c = res["checks"][caught_by]
    assert c["value"] == "inf" or c["value"] > c["limit"]
