"""chunk_device_wait_s: seconds per sweep the chunk loop waits for each
chunk's kept totals on the device, averaged over the window's sweeps — the
program's ``chunk.device_wait`` span."""

from benchlib import spans


def read(run):
    return spans.per_sweep_s(run, "chunk.device_wait")
