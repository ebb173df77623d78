"""stats_s: seconds per sweep in the fidelity matrices and the report
statistics, the host-input metrics of originals and cache hits included,
averaged over the window's sweeps — the program's ``engine.stats`` span."""

from benchlib import spans


def read(run):
    return spans.per_sweep_s(run, "engine.stats")
