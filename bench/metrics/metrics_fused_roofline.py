"""metrics_fused_roofline: the fused histogram + moments kernel's share of
its roofline (all variants: batched, device-input and chunk-carry). Each
sweep counts every simulated stream's kept records over its own range and
every original's records over its own seconds."""

from benchlib import peaks, work

KERNELS = (r"stream_metrics_pallas", r"stream_metrics_carry_pallas")


def read(run):
    if run.trace is None or run.trace.kernel_calls(KERNELS) == 0:
        return None
    records, widths = [], []
    for s in run.sweeps:
        seen = set()
        for r in s.reports:
            records.append(r.simulated_rows)
            widths.append(r.simulated_volatility.time_range)
            if r.dataset not in seen:
                seen.add(r.dataset)
                records.append(r.original_rows)
                widths.append(r.original_volatility.time_range)
    b, ops = work.stream_metrics(records, widths)
    least = peaks.least_time_s(b, ops, run.device_kind)
    return 100.0 * least / run.trace.kernel_s(KERNELS)
