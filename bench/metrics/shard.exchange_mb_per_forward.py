"""Megabytes (1e6 B) gathered across chips a forward: the ``bytes`` of the
``gcn.exchange`` spans (each layer's X and x_r, as every chip holds them
after the gather) in the traced window, over the window's forwards."""
from bench import program_spans


def read(run):
    spans = program_spans.in_window(run)
    forwards = run.ctx.counters.get("forwards")
    if not spans or not forwards:
        return None
    found = program_spans.named(spans, "gcn.exchange")
    if not found:
        return None
    return sum(s.id("bytes", 0) for s in found) / 1e6 / forwards
