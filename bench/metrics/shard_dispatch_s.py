"""shard_dispatch_s: host seconds per sweep spent getting every plan
shard's device chain under way (its NSA tables, uploads and dispatch) —
the program's ``nsa.shard`` spans, summed over the shards and averaged
over the window's sweeps."""

from benchlib import spans


def read(run):
    return spans.per_sweep_s(run, "nsa.shard")
