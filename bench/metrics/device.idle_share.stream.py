"""Share of the traced window in which the device ran no operation: 1 minus
the union of the device-op intervals over the window."""
from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    return trace_reduce.idle_share(run.trace)
