"""The block-ELL aggregation kernel's device time over the device's busy
time in the traced window, on each device plane, then the mean over the
planes: what ``spmm_abft.busy_share`` reads on one chip."""
from bench import trace_reduce

OP = "spmm_abft_kernel"            # the kernel's op in the device trace


def read(run):
    if run.trace is None:
        return None
    window = run.trace.window("bench.window")
    shares = []
    for events in run.trace.devices.values():
        clipped = trace_reduce.clip(events, window)
        busy = sum(b - a for a, b in trace_reduce.union(clipped))
        kernel = sum(b - a for a, b in trace_reduce.clip(
            [e for e in events if e.name == OP], window))
        if busy <= 0 or kernel <= 0:
            return None
        shares.append(kernel / busy)
    return 100.0 * sum(shares) / len(shares)
