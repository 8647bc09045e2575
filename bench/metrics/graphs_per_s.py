"""Graphs whose verdict arrived in the window, over the window's seconds."""


def read(run):
    done = run.ctx.counters.get("completed_in_window")
    if done is None or run.ctx.window_s <= 0:
        return None
    return done / run.ctx.window_s
