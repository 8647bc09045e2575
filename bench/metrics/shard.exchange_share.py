"""Share of the chips' busy time spent in collective operations (the
all-gather of each layer's X and x_r, the psum of the check partials): on
each device plane, the union of its collective ops' intervals over its
busy time in the traced window, then the mean over the planes.  Nothing
to read on one plane, or where no collective ran."""
from bench import trace_reduce

# the HLO names of the collectives, as the device trace gives them
# (``all-gather``, ``all-reduce``; ``-start``/``-done`` when asynchronous)
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def _ns(events, window):
    return sum(b - a for a, b in trace_reduce.union(
        trace_reduce.clip(events, window)))


def read(run):
    if run.trace is None or len(run.trace.devices) < 2:
        return None
    window = run.trace.window("bench.window")
    shares = []
    for events in run.trace.devices.values():
        busy = _ns(events, window)
        if busy <= 0:
            return None
        shared = [e for e in events
                  if any(c in e.name for c in COLLECTIVES)]
        shares.append(_ns(shared, window) / busy)
    if not any(shares):
        return None
    return 100.0 * sum(shares) / len(shares)
