"""replay_s: the merged PSDA replay loop's wall time per sweep, consumers
included — the program's SimulationReport.produce_s, averaged."""

from benchlib import readings


def read(run):
    return readings.mean_of_sweep_max(run, "produce_s")
