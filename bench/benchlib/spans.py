"""Read the program's own spans (``SimulationReport.spans``).

Every report of a ``Controller.run_many`` call carries the call's seconds
per span name, so one report per sweep gives the sweep's totals. A
program without spans gives nothing, and the reader then returns None.
"""

from __future__ import annotations


def per_sweep_s(run, name: str):
    """Seconds of span ``name`` per sweep, averaged over the window's
    sweeps (a sweep that never opened it counts 0); None where no sweep
    opened it."""
    totals = [getattr(s.reports[0], "spans", None) or {}
              for s in run.sweeps if s.reports]
    if not any(name in t for t in totals):
        return None
    return sum(t.get(name, 0.0) for t in totals) / len(totals)
