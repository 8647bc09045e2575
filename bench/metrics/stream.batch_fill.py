"""Graphs served in the window over the graph slots its batches offered
(batches dispatched times slots per batch)."""


def read(run):
    c = run.ctx.counters
    batches = c.get("window_batches")
    if not batches or not c.get("n_slots"):
        return None
    return 100.0 * c["window_served"] / (batches * c["n_slots"])
