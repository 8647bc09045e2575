"""The trace reduction, checked on a small trace recorded on a TPU v5 lite
chip: a ``--trace 1`` run of ``cora.full`` with a 0.05 s window (two checked
forwards, then two unchecked ones)."""
import pathlib

import pytest

from bench import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data" / "cora_full.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(DATA))


def test_planes_and_spans(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    names = [s.name for s in trace.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.window.none") == 1
    assert names.count("bench.forward") == 4
    assert names.count("bench.verdict") == 4


def test_op_names_are_short(trace):
    names = {e.name for evs in trace.devices.values() for e in evs}
    assert "spmm_abft_kernel" in names
    assert all(" = " not in n and not n.startswith("%") for n in names)
    assert tr.op_name("%spmm_abft_kernel.1 = (f32[8]) custom-call(%a)") \
        == "spmm_abft_kernel"


def test_busy_kernel_and_idle_add_up(trace):
    w = trace.window("bench.window")
    length = w[1] - w[0]
    busy = tr.busy_ns(trace, w)
    kernel, count = tr.op_ns(trace, w, "spmm_abft_kernel")
    assert count == 4                    # two layers x two forwards
    assert 0 < kernel <= busy < length
    idle = sum(b - a for a, b in tr.idle_gaps(trace, w))
    assert idle == pytest.approx(length - busy)
    assert tr.idle_share(trace) == pytest.approx(100.0 * idle / length)
    by_span = tr.idle_by_span(trace, w)
    assert sum(s for _, s in by_span) == pytest.approx(idle * 1e-9)
    assert by_span[0][0] == "bench.forward"
    top = tr.top_ops(trace, w)
    assert top[0][0] == "spmm_abft_kernel"
    assert top[0][1] == pytest.approx(kernel * 1e-9)


def test_union_and_clip():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    evs = [tr.Event("a", 0, 10), tr.Event("b", 8, 20)]
    assert tr.clip(evs, (5, 15)) == [(5, 10), (8, 15)]


def test_window_missing_raises(trace):
    with pytest.raises(KeyError):
        trace.window("bench.nothing")
