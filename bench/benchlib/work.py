"""Bytes and operations each kernel's work needs, from the valid records.

These count the work the algorithm requires, whatever implements it:
padding (the 18 -> 24 row tile, the record-tile tail, tables padded to the
sweep's widest range) and the histogram's one-hot compares are not counted,
so they show as a lower roofline share.
"""

from __future__ import annotations

from typing import Sequence

#: stream_sample per record: read its f32 timestamp (4 B), write its int32
#: scale stamp (4 B) and one keep byte; ~8 elementwise operations
SAMPLE_BYTES_PER_RECORD = 4 + 4 + 1
SAMPLE_OPS_PER_RECORD = 8
#: per table entry: exact bucket start, count and keep budget, int32 each
SAMPLE_BYTES_PER_BUCKET = 3 * 4


def stream_sample(records: Sequence[int], ranges: Sequence[int]):
    """(bytes, ops) of NSA's normalise-and-keep over rows with ``records``
    valid records, each bucketed into its own ``ranges`` seconds."""
    n = sum(int(r) for r in records)
    b = n * SAMPLE_BYTES_PER_RECORD + \
        sum(int(r) for r in ranges) * SAMPLE_BYTES_PER_BUCKET
    return float(b), float(n * SAMPLE_OPS_PER_RECORD)


def stream_metrics(records: Sequence[int], widths: Sequence[int]):
    """(bytes, ops) of the per-second histogram and its two moments: read
    each valid int32 stamp once, write each series' int32 counts over its
    own width and two moments; one add per record, two per bucket."""
    n = sum(int(r) for r in records)
    w = sum(int(x) for x in widths)
    b = 4 * n + 4 * w + 8 * len(widths)
    return float(b), float(n + 2 * w)
