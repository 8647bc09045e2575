"""The load generators, one file each.  A traffic file names one under
``driver`` and gives its parameters; a new mix is a new data file, and a new
kind of load is a new file ``bench/drivers/<driver>.py`` whose ``run(ctx)``
the harness loads by path (``harness.load_driver``).  A name that starts
with ``_`` is a private helper, never a driver.

Every driver builds its inputs from the seed, warms up every shape its
window uses (set-up), measures for ``ctx.seconds`` (the window), and then
checks what the window produced against the plain reference.  In a traced
run the window runs under the profiler instead, with the spans below.
This module holds what they share: the :class:`Ctx` the harness hands
them, the spans, the profiler, the weights and the memory peak.

Spans (``jax.profiler.TraceAnnotation``) around the calls into the program:
``bench.window``, ``bench.window.none``, ``bench.forward``,
``bench.verdict``, ``bench.submit``, ``bench.pump``, ``bench.take_results``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass
class Ctx:
    """What the harness hands a driver, and what the driver hands back."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace_dir: Optional[str]          # set: run the window under the profiler
    require_compiled: Callable[[bool], None]
    chips: int = 1                    # the cell's; a mesh: jax.devices()[:chips]
    clock: Callable[[], float] = time.perf_counter
    # filled by the driver
    setup_end: Optional[float] = None
    window_s: float = 0.0
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    samples: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    checks: List[tuple] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: Optional[int] = None
    kernel_calls: List[dict] = dataclasses.field(default_factory=list)

    def check(self, name: str, value: float, limit: float) -> None:
        """A number compared, with its limit: correct while value <= limit."""
        self.checks.append((name, float(value), float(limit)))


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def profiled(ctx: Ctx):
    """The profiler around a traced window, or nothing."""
    import contextlib

    import jax
    if ctx.trace_dir is None:
        return contextlib.nullcontext()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return jax.profiler.trace(ctx.trace_dir, profiler_options=opts)


def make_weights(seed: int, dims) -> List[Any]:
    """Glorot-uniform float32 weights, made on the device in one jitted
    call from the seed."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(dims) - 1)
        out = []
        for k, fin, fout in zip(keys, dims[:-1], dims[1:]):
            scale = jnp.sqrt(6.0 / (fin + fout))
            out.append(jax.random.uniform(k, (fin, fout), jnp.float32,
                                          -scale, scale))
        return out
    ws = init(jax.random.PRNGKey(seed))
    jax.block_until_ready(ws)
    return ws


def device_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
