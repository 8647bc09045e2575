"""Host milliseconds of serving-loop work a batch: the time the
``stream.seal`` spans (pack, stage, dispatch, the previous batch's
adjudication) and the ``stream.materialize`` spans (the deferred
device-to-host flush) cover in the traced window, nested spans once, over
the ``stream.dispatch`` spans there."""
from bench import program_spans


def read(run):
    spans = program_spans.in_window(run)
    if not spans:
        return None
    batches = len(program_spans.named(spans, "stream.dispatch"))
    if not batches:
        return None
    work = [s for s in spans
            if s.name in ("stream.seal", "stream.materialize")]
    return 1e-6 * program_spans.union_ns(work) / batches
