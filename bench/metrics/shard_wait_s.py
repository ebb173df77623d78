"""shard_wait_s: host seconds per sweep blocked on the slowest chip once
every shard is dispatched — the program's ``nsa.totals_wait`` (every
shard's kept totals) plus ``nsa.device_wait`` (every shard's moments),
averaged over the window's sweeps. None where the program does not open
``nsa.totals_wait``: ``nsa.device_wait`` alone then times a different
wait."""

from benchlib import spans


def read(run):
    totals = spans.per_sweep_s(run, "nsa.totals_wait")
    if totals is None:
        return None
    return totals + (spans.per_sweep_s(run, "nsa.device_wait") or 0.0)
