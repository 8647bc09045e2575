"""Check-sink tagging: a trace-time marker that makes ABFT coverage
statically verifiable.

``abftlint``'s coverage pass (``repro.analysis.coverage``) proves that
every matmul in a traced step flows into an eq. 4-6 checksum comparison.
"Flows into a comparison" must be a property of the *jaxpr*, not of the
Python source, so the comparison site needs a recognizable footprint in
the trace.  This module provides it:

* :data:`check_sink_p` — an identity primitive ``abft_check_sink`` whose
  equation marks "these values are being consumed by a checksum
  comparison".  It carries the check's declared ``granularity`` as a
  static parameter, so the analysis can report per-site granularity.
* :func:`tag_check` — routes a Check's (predicted, actual) pair through
  the primitive.  Called by ``Check.diff`` / ``Check.elementwise`` (the
  two reduction cores every report path funnels through) **only while
  tagging is enabled**.
* :func:`check_tagging` — the enabling context manager.  The lint traces
  under it; production traces never see the primitive, so runtime jaxprs,
  compiles, and numerics are bit-for-bit unchanged by this module.

The primitive is a full citizen anyway (impl, abstract eval, lowering,
batching, JVP/transpose are all identity), so a trace taken under
tagging still *executes* correctly — the verifier's own fixtures rely on
that, and a train step traced through ``jax.value_and_grad`` needs the
differentiation rules.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator, Tuple

import jax
from jax.extend import core as jax_core
from jax.interpreters import ad, batching, mlir

Array = jax.Array

CHECK_SINK = "abft_check_sink"

_state = threading.local()


def tagging_enabled() -> bool:
    return getattr(_state, "tagging", False)


@contextlib.contextmanager
def check_tagging() -> Iterator[None]:
    """Enable check-sink tagging for traces taken inside the block.

    Nesting is fine; tagging is thread-local, so a lint trace on one
    thread never perturbs a serving trace on another.
    """
    prev = tagging_enabled()
    _state.tagging = True
    try:
        yield
    finally:
        _state.tagging = prev


check_sink_p = jax_core.Primitive(CHECK_SINK)
check_sink_p.multiple_results = True


@check_sink_p.def_impl
def _check_sink_impl(*args, granularity):
    del granularity
    return list(args)


@check_sink_p.def_abstract_eval
def _check_sink_abstract(*avals, granularity):
    del granularity
    return list(avals)


mlir.register_lowering(check_sink_p,
                       lambda ctx, *args, granularity: list(args))


def _check_sink_batch(args, dims, *, granularity):
    return check_sink_p.bind(*args, granularity=granularity), dims


batching.primitive_batchers[check_sink_p] = _check_sink_batch


def _check_sink_jvp(primals, tangents, *, granularity):
    out = check_sink_p.bind(*primals, granularity=granularity)
    # tangents pass through untagged: the coverage property belongs to the
    # primal check comparison, and instantiating symbolic-zero tangents
    # just to re-tag them would change the trace shape
    tans = [ad.instantiate_zeros(t) if isinstance(t, ad.Zero) else t
            for t in tangents]
    return out, tans


ad.primitive_jvps[check_sink_p] = _check_sink_jvp


def _check_sink_transpose(cts, *args, granularity):
    del granularity, args
    return list(cts)


ad.primitive_transposes[check_sink_p] = _check_sink_transpose


def tag_check(predicted: Array, actual: Array, granularity: str
              ) -> Tuple[Array, Array]:
    """Identity on (predicted, actual); emits the ``abft_check_sink``
    equation when tagging is enabled (see module docstring)."""
    if not tagging_enabled():
        return predicted, actual
    p, a = check_sink_p.bind(predicted, actual, granularity=granularity)
    return p, a
