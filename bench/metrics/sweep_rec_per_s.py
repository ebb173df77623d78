"""sweep_rec_per_s: source records simulated per second — every completed
scenario's original_rows over all sweeps of the window, over the summed
wall time of those sweeps (host clock)."""


def read(run):
    records = sum(r.original_rows for s in run.sweeps for r in s.reports
                  if r.status == "ok")
    seconds = sum(s.seconds for s in run.sweeps)
    return records / seconds if seconds > 0 else None
