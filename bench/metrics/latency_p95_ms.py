"""95th percentile over every request due in the window, each timed from when
it was due until its verdict reached the caller."""
import numpy as np


def read(run):
    lat = run.ctx.samples.get("latency_s")
    if not lat:
        return None
    return 1e3 * float(np.percentile(lat, 95))
