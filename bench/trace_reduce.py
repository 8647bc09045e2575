"""From a JAX profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* Device operations: the events of each device plane's ``XLA Ops`` line,
  named by their HLO instruction without its ``%`` and numeric suffix
  (``%spmm_abft_kernel.1 = ... custom-call(...)`` is ``spmm_abft_kernel``).
  Busy time is the union of their intervals; idle is the rest of a window.
* Kernel time by stable name: the summed device duration of the op events
  of that name (Pallas names its custom call after the kernel's jitted
  function).
* Host spans: the benchmark's own ``bench.*`` ``TraceAnnotation`` events.
  A window is the interval of one such span, and each idle gap on the
  device is attributed to the span the host was in at its middle.

All times are nanoseconds on the trace's own clock, on which the profiler
places host and device events alike.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]      # device plane -> op events
    spans: List[Event]                   # host bench.* spans

    def window(self, name: str) -> Interval:
        """The interval of the first host span called ``name``."""
        for s in self.spans:
            if s.name == name:
                return s.start, s.end
        raise KeyError(f"no span {name!r} in the trace")


def find_trace_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(text: str) -> str:
    """``%name.N = <shape> op(...)`` -> ``name``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        Event(op_name(e.name), e.start_ns,
                              e.start_ns + e.duration_ns)
                        for e in line.events)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
    for evs in devices.values():
        evs.sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return Trace(devices=devices, spans=spans)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(events: Sequence[Event], window: Interval) -> List[Interval]:
    t0, t1 = window
    return [(max(e.start, t0), min(e.end, t1)) for e in events
            if e.end > t0 and e.start < t1]


def busy_ns(trace: Trace, window: Interval) -> float:
    """Device-busy nanoseconds in the window, averaged over the devices."""
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    total = sum(sum(b - a for a, b in union(clip(evs, window)))
                for evs in trace.devices.values())
    return total / len(trace.devices)


def idle_share(trace: Trace, span: str = SPAN_PREFIX + "window") -> float:
    """Percent of the span's interval in which the devices ran no op."""
    t0, t1 = trace.window(span)
    return 100.0 * (1.0 - busy_ns(trace, (t0, t1)) / (t1 - t0))


def op_ns(trace: Trace, window: Interval, name: str) -> Tuple[float, int]:
    """(device nanoseconds, event count) of the ops called ``name``, summed
    over the devices, in the window."""
    total, count = 0.0, 0
    for evs in trace.devices.values():
        for e in evs:
            if e.name == name and e.end > window[0] and e.start < window[1]:
                total += min(e.end, window[1]) - max(e.start, window[0])
                count += 1
    return total, count


def top_ops(trace: Trace, window: Interval, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` device ops that took most time in the window, in seconds."""
    by: Dict[str, float] = collections.defaultdict(float)
    for evs in trace.devices.values():
        for e in evs:
            if e.end > window[0] and e.start < window[1]:
                by[e.name] += min(e.end, window[1]) - max(e.start, window[0])
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns * 1e-9) for name, ns in top]


def idle_gaps(trace: Trace, window: Interval) -> List[Interval]:
    """Intervals of the window in which the first device ran no op."""
    plane = sorted(trace.devices)[0]
    busy = union(clip(trace.devices[plane], window))
    gaps, t = [], window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < window[1]:
        gaps.append((t, window[1]))
    return gaps


def idle_by_span(trace: Trace, window: Interval, k: int = 10
                 ) -> List[Tuple[str, float]]:
    """Idle seconds of the window, summed by what the host was doing at
    each gap's middle, the ``k`` largest.  The benchmark's call spans do not
    nest in one another, only in its ``bench.window*`` spans: a gap's
    middle lies in at most one call span, else in a window span."""
    spans = [s for s in trace.spans
             if s.end > window[0] and s.start < window[1]]
    outer = [s for s in spans if s.name.startswith(SPAN_PREFIX + "window")]
    calls = [s for s in spans if not s.name.startswith(SPAN_PREFIX + "window")]
    starts = [s.start for s in calls]
    by: Dict[str, float] = collections.defaultdict(float)
    for a, b in idle_gaps(trace, window):
        t = (a + b) / 2
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and calls[i].end > t:
            name = calls[i].name
        else:
            name = next((s.name for s in outer if s.start <= t < s.end),
                        "outside any span")
        by[name] += b - a
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns * 1e-9) for name, ns in top]
