"""The paper-grid cell replayed from the store, driven end to end on the
CPU at a tiny scale: the cached mix's sweep loop, its counts, and the
check failing under every fault the cell can have."""

import pytest


def test_cached_loop_keeps_every_key(drive):
    res = drive("t123_day_replay", "none")
    assert res["correct"] is True
    assert res["deleted_keys"] == []
    assert res["attempted"] % 18 == 0 and res["failed"] == 0
    assert res["metrics"]["first_record_s"]["value"] > 0


@pytest.mark.parametrize("fault,caught_by", [
    ("alter", "streams_differ"),
    ("half", "deliveries_differ"),
    ("unchanged", "streams_differ"),
])
def test_fault_makes_the_run_incorrect(drive, fault, caught_by):
    res = drive("t123_day_replay", fault)
    assert res["correct"] is False
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"]
