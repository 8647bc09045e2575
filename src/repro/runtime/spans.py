"""Host spans at the program's layer boundaries, on the profiler's clock.

``span(name, **ids)`` is a :class:`jax.profiler.TraceAnnotation`.  The
profiler records it only while a trace is active (``jax.profiler.trace``),
with its ids as the event's stats and its nesting kept on the thread's
line, on the same clock as the device ops; with no trace active, entering
and leaving one costs about a microsecond.  So the profiler is the span
store: nothing is kept, switched or written here.

Spans wrap host code only.  None adds a transfer or a host read: a span
times the dispatch it wraps, not the device work behind it.  In code that
a jitted caller traces (the packed serve step runs ``gcn_forward``), a span
records the trace, once per compile, and nothing per call.

The ids join the spans of one batch: ``stream.seal``, ``stream.dispatch``
and ``stream.resolve`` carry the batch's dispatch sequence number.
"""
from __future__ import annotations

import jax

# the whole vocabulary: a name outside it is refused, so readers, tests and
# PERF.md point at one list
SPANS = (
    # engine (engine/api.py, engine/backends.py, engine/sharded.py,
    # kernels/spmm_abft/ops.py)
    "gcn.forward",          # gcn_apply: the forward and its report; mode
    "gcn.layer",            # one layer of the loop, or the network hook; layer
    "gcn.combine",          # X = H W
    "gcn.check_column",     # the eq.-5 column x_r = H w_r
    "gcn.aggregate",        # the aggregation (and a fused layer's kernel)
    "gcn.corners",          # the eq.-6 corner reduction after the kernel
    "gcn.summarize",        # checks -> one report
    "gcn.stage",            # one shard's rows to its devices; shard, bytes
    "gcn.exchange",         # a sharded layer's gather of X, x_r; layer, bytes
    # serving loop (engine/streaming.py)
    "stream.seal",          # one bin sealed; batch, cause, graphs
    "stream.pack",          # pack_graphs of the sealed bin
    "stream.stage",         # packed_step_args; purpose, bytes
    "stream.dispatch",      # the async step call; batch, kind
    "stream.resolve",       # adjudication of the in-flight batch; batch
    "stream.materialize",   # the deferred device->host flush; batches
    "stream.selfcheck",     # a check-path self-check that runs
    # guard (runtime/abft_guard.py)
    "guard.adjudicate",     # ABFTGuard.adjudicate
    "guard.sync",           # its first host read of the graph flags
    "guard.retry",          # one re-execution of a repair tier; tier
)
_NAMES = frozenset(SPANS)


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """The span ``name`` (one of :data:`SPANS`), tagged with ``ids``."""
    if name not in _NAMES:
        raise ValueError(f"span {name!r} is not in the vocabulary")
    return jax.profiler.TraceAnnotation(name, **ids)
