"""setup_s: process start to the first timed sweep (host clock)."""


def read(run):
    return run.setup_s
