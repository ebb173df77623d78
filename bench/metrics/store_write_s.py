"""store_write_s: seconds per sweep writing simulated streams, chunk files
and manifests to the store, averaged over the window's sweeps — the
program's ``store.write`` span."""

from benchlib import spans


def read(run):
    return spans.per_sweep_s(run, "store.write")
