"""materialize_s: seconds per sweep in the host gather of the simulated
streams' columns (whole streams, or chunk by chunk), averaged over the
window's sweeps — the program's ``engine.materialize`` span."""

from benchlib import spans


def read(run):
    return spans.per_sweep_s(run, "engine.materialize")
