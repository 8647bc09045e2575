"""Median of dispatch time minus enqueue time over the window's served
requests (the engine's own request time stamps)."""
import numpy as np


def read(run):
    waits = run.ctx.samples.get("queue_wait_s")
    if not waits:
        return None
    return 1e3 * float(np.median(waits))
