"""Share of the traced window's ``stream.seal`` spans that the flush
deadline sealed (``cause=deadline``), not a full bin or a drain."""
from bench import program_spans


def read(run):
    spans = program_spans.in_window(run)
    if not spans:
        return None
    seals = program_spans.named(spans, "stream.seal")
    if not seals:
        return None
    late = sum(s.id("cause") == "deadline" for s in seals)
    return 100.0 * late / len(seals)
