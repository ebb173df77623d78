"""Small helpers the metric readers share."""

from __future__ import annotations


def simulated_reports(run):
    """Every report, over all sweeps, whose scenario was simulated in that
    sweep (not a store cache hit)."""
    return [r for s in run.sweeps for r in s.reports if r.nsa_s > 0]


def mean_of_sweep_max(run, field: str):
    """Per sweep the largest value of a shared report field (the program
    gives every co-simulated scenario the sweep's total), averaged over
    the sweeps where it is non-zero; None where it is zero throughout."""
    vals = [max(getattr(r, field) for r in s.reports)
            for s in run.sweeps if s.reports]
    vals = [v for v in vals if v > 0]
    return sum(vals) / len(vals) if vals else None
