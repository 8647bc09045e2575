"""The program's own spans in a ``--trace 1`` run: the ``gcn.*``,
``stream.*`` and ``guard.*`` ``TraceAnnotation`` events that
``repro.runtime.spans`` records, with the ids each carries (``batch``,
``cause``, ``purpose``, ``bytes``, ...).

They sit on the host planes of the same ``.xplane.pb`` that
``bench/trace_reduce.py`` reads, on the clock of the device ops.  The
readers of ``bench/metrics`` take them from :func:`in_window`: the spans
that start inside the run's first ``bench.window`` span, clipped to its
end.  A program without these spans (one older than them) gives nothing
to read, and each reader then returns ``None``.

:func:`idle_by_program_span` puts the device's idle time down to the
innermost program span the host was in; no metric reads it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce

PREFIXES = ("gcn.", "stream.", "guard.")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float                      # ns, the trace's clock
    end: float
    ids: Tuple[Tuple[str, object], ...]

    def id(self, key: str, default=None):
        return dict(self.ids).get(key, default)

    @property
    def ns(self) -> float:
        return self.end - self.start


@functools.lru_cache(maxsize=2)
def load(path: str) -> Tuple[Span, ...]:
    """Every program span on the host planes of the trace, by start."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    tuple(sorted(dict(e.stats).items()))))
    return tuple(sorted(out, key=lambda s: s.start))


def in_window(run) -> Optional[List[Span]]:
    """The program spans that start in the run's ``bench.window``, clipped
    to its end; ``None`` for an untraced run or a trace without them."""
    if run.trace is None or not run.ctx.trace_dir:
        return None
    try:
        path = trace_reduce.find_trace_file(run.ctx.trace_dir)
        t0, t1 = run.trace.window(trace_reduce.SPAN_PREFIX + "window")
    except (FileNotFoundError, KeyError):
        return None
    spans = [dataclasses.replace(s, end=min(s.end, t1))
             for s in load(path) if t0 <= s.start < t1]
    return spans or None


def named(spans: Sequence[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def union_ns(spans: Sequence[Span]) -> float:
    """Nanoseconds covered by the spans, nested or overlapping ones once."""
    return sum(b - a for a, b in trace_reduce.union(
        [(s.start, s.end) for s in spans]))


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The spans' time cut into pieces, each named for the innermost span
    that covers it (spans of one thread nest)."""
    edges = sorted([(s.start, 1, i) for i, s in enumerate(spans)]
                   + [(s.end, 0, i) for i, s in enumerate(spans)])
    out: List[Tuple[float, float, str]] = []
    stack: List[int] = []
    t = None
    for at, opening, i in edges:
        if stack and t is not None and at > t:
            out.append((t, at, spans[stack[-1]].name))
        if opening:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
        t = at
    return out


def idle_by_program_span(trace, spans: Sequence[Span],
                         window: trace_reduce.Interval, k: int = 12
                         ) -> List[Tuple[str, float]]:
    """Idle seconds of the window on the first device, summed by the
    innermost program span the host was in at each gap's middle, else by
    the benchmark span (``bench.*``) it was in; the ``k`` largest."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    # the benchmark's call spans do not nest in one another
    calls = [s for s in trace.spans
             if not s.name.startswith(trace_reduce.SPAN_PREFIX + "window")]
    call_starts = [s.start for s in calls]
    by: Dict[str, float] = collections.defaultdict(float)
    for a, b in trace_reduce.idle_gaps(trace, window):
        t = (a + b) / 2
        i = bisect.bisect_right(starts, t) - 1
        j = bisect.bisect_right(call_starts, t) - 1
        if i >= 0 and pieces[i][1] > t:
            name = pieces[i][2]
        elif j >= 0 and calls[j].end > t:
            name = calls[j].name
        else:
            name = trace_reduce.SPAN_PREFIX + "window"
        by[name] += b - a
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns * 1e-9) for name, ns in top]
