"""The whole forward's share of its chips' peak: the paper's Table II model
operations per forward (check work not counted) times forwards per second
of the traced window, over the cell's chips times one chip's peak
FLOP/s."""
from bench import cost


def read(run):
    g = run.config.get("graph")
    forwards = run.ctx.counters.get("forwards")
    if g is None or not forwards or run.ctx.window_s <= 0:
        return None
    ops = cost.true_ops(g["nodes"], g["undirected_edges"], g["feature_nnz"],
                        run.config["layer_dims"])
    rate = forwards / run.ctx.window_s
    return 100.0 * ops * rate / (run.ctx.chips * run.peak["flops_per_s"])
