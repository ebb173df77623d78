"""device_idle_share: 100 x (1 - device busy / traced window), busy being
the union of the intervals in which a device operation ran."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
