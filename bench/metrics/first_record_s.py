"""first_record_s: from a sweep's call to the first record any consumer
receives, averaged over every sweep of the window (host clock)."""

import math


def read(run):
    vals = [s.first_record_s for s in run.sweeps
            if not math.isnan(s.first_record_s)]
    return sum(vals) / len(vals) if vals else None
