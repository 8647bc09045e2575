"""The block-ELL aggregation kernel's device time over the device's busy time
in the traced window."""
from bench import trace_reduce

OP = "spmm_abft_kernel"            # the kernel's op in the device trace


def read(run):
    if run.trace is None:
        return None
    window = run.trace.window("bench.window")
    ns, _ = trace_reduce.op_ns(run.trace, window, OP)
    busy = trace_reduce.busy_ns(run.trace, window)
    if ns <= 0 or busy <= 0:
        return None
    return 100.0 * ns / busy
