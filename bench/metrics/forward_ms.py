"""Window seconds per guarded forward completed, each timed until its logits
are ready and its verdict is on the host."""


def read(run):
    n = run.ctx.counters.get("forwards")
    if not n:
        return None
    return 1e3 * run.ctx.window_s / n
