"""Device time the check adds outside the kernel: 1 - busy time of the
unchecked forwards (``mode="none"``) over that of as many checked ones.
``mode="none"`` still runs the eq.-5 column inside the kernel, so this is
not the whole cost of the check."""
from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    try:
        none = run.trace.window("bench.window.none")
    except KeyError:
        return None
    checked = trace_reduce.busy_ns(run.trace, run.trace.window("bench.window"))
    unchecked = trace_reduce.busy_ns(run.trace, none)
    if checked <= 0:
        return None
    return 100.0 * (1.0 - unchecked / checked)
