"""chunk_pipeline_s: the chunked pipeline's wall time per sweep — the
program's SimulationReport.nsa_s on the chunked path, which spans the
whole chunk loop, host legs included — averaged over the sweeps."""

from benchlib import readings


def read(run):
    if not run.cell.config["chunk_s"]:
        return None
    return readings.mean_of_sweep_max(run, "nsa_s")
