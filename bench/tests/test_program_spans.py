"""The readers of the program's spans (``bench/program_spans.py`` and the
five metrics on it), on tiny traced runs recorded here on the CPU, on a
chip trace older than the spans, and on a hand-made trace.

``harness.measure(trace=True)`` wants a device plane, which a CPU trace
lacks, so the traced runs drive the cell's driver and build the ``Run``
by hand."""
import pathlib
import shutil

import pytest

from bench import drivers, harness, program_spans, trace_reduce
from conftest import tiny

DATA = pathlib.Path(__file__).parent / "data"
FULL = ("engine.host_ms_per_forward", "check.host_ms_per_forward")
STREAM = ("stream.host_ms_per_batch", "stream.deadline_seal_share",
          "stream.staged_mb_per_batch")


def _read(run, names):
    return {n: harness.load_reader(n)(run) for n in names}


def _traced(bench, peak, workload, tmp_path, seconds):
    config, traffic = tiny(bench, workload)
    ctx = drivers.Ctx(config=config, traffic=traffic, seed=2**31 + 5,
                      seconds=seconds, trace_dir=str(tmp_path),
                      require_compiled=lambda interpret: None)
    harness.load_driver(traffic["driver"])(ctx)
    trace = trace_reduce.load(trace_reduce.find_trace_file(str(tmp_path)))
    return harness.Run(config=config, peak=peak, ctx=ctx, setup_time=0.0,
                       trace=trace)


def test_bench_lists_the_readers(bench):
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in FULL:
        assert per_layer[name]["workloads"] == ["pubmed.full", "cora.full",
                                                "pubmed.full_clustered"]
    for name in STREAM:
        assert per_layer[name]["workloads"] == ["pubmed.stream"]


def test_full_graph_run(bench, peak, tmp_path):
    run = _traced(bench, peak, "cora.full", tmp_path, 0.3)
    assert all(v <= limit for _, v, limit in run.ctx.checks)
    spans = program_spans.in_window(run)
    forwards = run.ctx.counters["forwards"]
    assert len(program_spans.named(spans, "gcn.forward")) == forwards
    assert {s.id("mode") for s in program_spans.named(spans, "gcn.forward")
            } == {"fused"}                  # the unchecked ones lie outside
    got = _read(run, FULL + STREAM)
    assert got["engine.host_ms_per_forward"] > 0
    assert got["check.host_ms_per_forward"] > 0
    assert all(got[n] is None for n in STREAM)
    # the spans end with the window
    t0, t1 = run.trace.window("bench.window")
    assert all(t0 <= s.start and s.end <= t1 for s in spans)


def test_stream_run(bench, peak, tmp_path):
    run = _traced(bench, peak, "pubmed.stream", tmp_path, 1.5)
    assert all(v <= limit for _, v, limit in run.ctx.checks)
    counters = run.ctx.counters
    spans = program_spans.in_window(run)
    dispatches = program_spans.named(spans, "stream.dispatch")
    assert len(dispatches) == counters["window_batches"] > 0
    got = _read(run, FULL + STREAM)
    assert all(got[n] is None for n in FULL)
    assert got["stream.host_ms_per_batch"] > 0
    assert 0 <= got["stream.deadline_seal_share"] <= 100
    stages = program_spans.named(spans, "stream.stage")
    assert {s.id("purpose") for s in stages} == {"step", "replay"}
    assert got["stream.staged_mb_per_batch"] == pytest.approx(
        sum(s.id("bytes") for s in stages) / 1e6 / len(dispatches))


def test_nothing_to_read_without_program_spans(bench, peak, tmp_path):
    """A chip trace of the program before it had spans."""
    shutil.copy(DATA / "cora_full.xplane.pb", tmp_path)
    ctx = drivers.Ctx(config={}, traffic={}, seed=0, seconds=0.05,
                      trace_dir=str(tmp_path),
                      require_compiled=lambda interpret: None)
    ctx.counters.update(forwards=2, window_batches=2)
    run = harness.Run(config={}, peak=peak, ctx=ctx, setup_time=0.0,
                      trace=trace_reduce.load(str(tmp_path /
                                                  "cora_full.xplane.pb")))
    assert program_spans.in_window(run) is None
    assert _read(run, FULL + STREAM) == {n: None for n in FULL + STREAM}
    run.trace = None
    assert _read(run, FULL + STREAM) == {n: None for n in FULL + STREAM}


def test_idle_by_program_span_names_the_innermost():
    ev = trace_reduce.Event
    trace = trace_reduce.Trace(
        devices={"/device:TPU:0": [ev("k", 10, 20), ev("k", 60, 70)]},
        spans=[ev("bench.window", 0, 100), ev("bench.pump", 30, 80)])
    spans = [program_spans.Span("stream.seal", 30, 60, ()),
             program_spans.Span("stream.pack", 32, 45, ())]
    by = dict(program_spans.idle_by_program_span(trace, spans, (0, 100)))
    # gaps: 0-10 (window), 20-60 (middle 40: pack), 70-100 (middle 85)
    assert by == pytest.approx({"bench.window": 40e-9,
                                "stream.pack": 40e-9})
    pieces = program_spans.innermost(spans)
    assert pieces == [(30, 32, "stream.seal"), (32, 45, "stream.pack"),
                      (45, 60, "stream.seal")]
    assert program_spans.union_ns(spans) == 30
