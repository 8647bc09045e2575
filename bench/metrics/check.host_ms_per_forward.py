"""Host milliseconds a checked forward spends on the check: the
``gcn.check_column`` (eq.-5 column), ``gcn.corners`` (eq.-6 corner
reduction) and ``gcn.summarize`` (checks to one report) spans in the traced
window, over the window's forwards."""
from bench import program_spans

CHECK = ("gcn.check_column", "gcn.corners", "gcn.summarize")


def read(run):
    spans = program_spans.in_window(run)
    forwards = run.ctx.counters.get("forwards")
    if not spans or not forwards:
        return None
    found = [s for s in spans if s.name in CHECK]
    if not found:
        return None
    return 1e-6 * sum(s.ns for s in found) / forwards
