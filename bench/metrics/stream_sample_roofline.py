"""stream_sample_roofline: the NSA normalise-and-keep kernel's share of its
roofline — the least time its required bytes and operations take at the
chip's peaks, over the kernel's device time in the trace. Every scenario
row the sweeps simulated reads its whole original once."""

from benchlib import peaks, readings, work

KERNELS = (r"stream_sample_pallas",)


def read(run):
    if run.trace is None or run.trace.kernel_calls(KERNELS) == 0:
        return None
    records, ranges = [], []
    for rep in readings.simulated_reports(run):
        records.append(rep.original_rows)
        ranges.append(rep.simulated_volatility.time_range)
    if not records:
        return None
    b, ops = work.stream_sample(records, ranges)
    least = peaks.least_time_s(b, ops, run.device_kind)
    return 100.0 * least / run.trace.kernel_s(KERNELS)
