"""nsa_leg_s: the monolithic engine's device leg per sweep — the program's
own SimulationReport.nsa_s (host clock around NSA -> metrics, ending in a
device read), averaged over the sweeps that simulated."""

from benchlib import readings


def read(run):
    if run.cell.config["chunk_s"]:
        return None
    return readings.mean_of_sweep_max(run, "nsa_s")
