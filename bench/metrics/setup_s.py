"""Seconds from process start to the first timed request: data, layout,
staging, compile-cache load and warm-up."""


def read(run):
    return run.setup_time
