"""compaction_device_s: device seconds per sweep of NSA's compaction — the
prefix-scan kernel, the XLA scatter of kept indices and the kept-stamp
gather — summed from the trace."""

KERNELS = (r"compact_positions_batched_pallas", r"_scatter_kept",
           r"gather_kept")


def read(run):
    if run.trace is None or run.trace.kernel_calls(KERNELS) == 0:
        return None
    return run.trace.kernel_s(KERNELS) / len(run.sweeps)
