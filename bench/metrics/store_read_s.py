"""store_read_s: seconds per sweep loading streams from the store
(originals, cache-hit sims), averaged over the window's sweeps — the
program's ``store.read`` span."""

from benchlib import spans


def read(run):
    return spans.per_sweep_s(run, "store.read")
