"""Host milliseconds of one checked forward inside the program: the mean
duration of the ``gcn.forward`` spans (``gcn_apply``: the layers and the
report) in the traced window.  It times the host's dispatch, and any wait
the host makes for the device inside it, not the device work itself.
Nothing to read where their count is not the window's forward count."""
from bench import program_spans


def read(run):
    spans = program_spans.in_window(run)
    forwards = run.ctx.counters.get("forwards")
    if not spans or not forwards:
        return None
    found = program_spans.named(spans, "gcn.forward")
    if len(found) != forwards:
        return None
    return 1e-6 * sum(s.ns for s in found) / forwards
