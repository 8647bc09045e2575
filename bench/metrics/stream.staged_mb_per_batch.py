"""Megabytes (1e6 B) staged to the device a batch: the ``bytes`` of the
``stream.stage`` spans in the traced window (the step's operands and the
copy kept for the guard's replay), over the ``stream.dispatch`` spans
there."""
from bench import program_spans


def read(run):
    spans = program_spans.in_window(run)
    if not spans:
        return None
    batches = len(program_spans.named(spans, "stream.dispatch"))
    if not batches:
        return None
    staged = sum(s.id("bytes", 0)
                 for s in program_spans.named(spans, "stream.stage"))
    return staged / 1e6 / batches
