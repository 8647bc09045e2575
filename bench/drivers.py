"""The load generators.  A traffic file names one of them under ``driver``
and gives its parameters; a new mix is a new data file, not new code.

Every driver builds its inputs from the seed, warms up every shape its
window uses (set-up), measures for ``ctx.seconds`` (the window), and then
checks what the window produced against the plain reference.  In a traced
run the window runs under the profiler instead, with the spans below.

* ``full_graph``: one caller, closed loop, back-to-back ``gcn_apply`` on a
  whole graph, rotating through feature matrices that sit on the device.
* ``stream_open``: open-loop arrivals of small request graphs into
  ``StreamingEngine`` at a fixed rate; each request is timed from when it
  was due until its verdict reaches the caller.
* ``stream_closed``: the same requests, a fixed number kept outstanding.

Spans (``jax.profiler.TraceAnnotation``) around the calls into the program:
``bench.window``, ``bench.window.none``, ``bench.forward``,
``bench.verdict``, ``bench.submit``, ``bench.pump``, ``bench.take_results``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import graphs
from bench.references import gcn as ref_gcn


@dataclasses.dataclass
class Ctx:
    """What the harness hands a driver, and what the driver hands back."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace_dir: Optional[str]          # set: run the window under the profiler
    require_compiled: Callable[[bool], None]
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


# ---------------------------------------------------------------------------
# full_graph
# ---------------------------------------------------------------------------

def full_graph(ctx: Ctx) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.abft import ABFTConfig
    from repro.engine import Graph, fold_w_r, gcn_apply, make_backend
    from repro.kernels.spmm_abft.layout import coo_to_block_ell

    config, traffic = ctx.config, ctx.traffic
    dims = config["layer_dims"]
    block = config["block"]
    n_sets = traffic["feature_sets"]
    whole = graphs.make_graph(config, ctx.seed, n_sets)
    s = whole.s
    bell = coo_to_block_ell(s.row, s.col, s.data, s.shape, block, block)
    abft = ABFTConfig(mode="fused")
    weights = make_weights(ctx.seed, dims)
    params = fold_w_r({"layers": [{"w": w} for w in weights]}, abft)
    bk = make_backend(bell, abft, backend="block_ell")
    ctx.require_compiled(bk.interpret)
    feats = [jnp.asarray(f.todense()) for f in whole.features]
    jax.block_until_ready(feats)
    inputs = [Graph(s=bell, h0=h) for h in feats]

    def forward(i: int, cfg=abft):
        with span("bench.forward"):
            logits, report = gcn_apply(params, inputs[i % n_sets], cfg,
                                       backend=bk)
            logits.block_until_ready()
        with span("bench.verdict"):
            flag, max_rel = jax.device_get((report.flag, report.max_rel))
        return logits, bool(flag), float(max_rel)

    for i in range(n_sets + traffic["warm_forwards"]):
        forward(i)
    cfg_none = ABFTConfig(mode="none")
    if ctx.trace_dir is not None:           # the unchecked program: traced
        for i in range(traffic["warm_forwards"]):  # runs only
            forward(i, cfg_none)
    ctx.setup_end = ctx.clock()

    last: Dict[int, Any] = {}
    flagged = 0
    max_rels = []
    with profiled(ctx):
        with span("bench.window"):
            t0 = ctx.clock()
            n = 0
            while True:
                logits, flag, max_rel = forward(n)
                last[n % n_sets] = logits
                flagged += flag
                max_rels.append(max_rel)
                n += 1
                t1 = ctx.clock()
                if t1 - t0 >= ctx.seconds:
                    break
        if ctx.trace_dir is not None:
            with span("bench.window.none"):
                for i in range(n):
                    forward(i, cfg_none)
    ctx.window_s = t1 - t0
    ctx.memory_peak_bytes = device_peak_bytes()
    ctx.counters["forwards"] = n
    ctx.attempted = n
    ctx.failed = flagged
    # what one forward's kernel calls stage and compute, for the cost model
    ctx.kernel_calls = [{"kernel": "spmm_abft",
                         "vals_shape": tuple(bk.vals.shape),
                         "vals_itemsize": bk.vals.dtype.itemsize,
                         "cols_shape": tuple(bk.cols.shape),
                         "cols_itemsize": bk.cols.dtype.itemsize,
                         "out_width": g} for g in dims[1:]]

    # -- correctness: after the window, nothing of it timed --------------
    outs = {i: np.asarray(v) for i, v in last.items()}
    del bk, last
    delta = config["check"]["inject_delta"]
    bad = make_backend(bell, abft, backend="block_ell",
                       inject=(len(dims) - 2, bell.n_block_rows // 2, 0, delta))
    _, rep_bad = gcn_apply(params, inputs[0], abft, backend=bad)
    inject_flag, inject_rel = jax.device_get((rep_bad.flag, rep_bad.max_rel))
    del bad

    w_host = [np.asarray(w) for w in weights]
    agg = ref_gcn.coo_aggregate(s.row, s.col, s.data, s.shape[0], "highest")
    gap = 0.0
    for i, out in outs.items():
        ref = ref_gcn.forward(agg, whole.features[i].todense(), w_host,
                              "highest")
        gap = max(gap, ref_gcn.gap(out, ref))
    limits = config["check"]
    ctx.check("logit_gap", gap, limits["logit_gap"])
    ctx.check("clean_flags", flagged, 0)
    ctx.check("fault_missed", 0 if inject_flag else 1, 0)
    ctx.counters.update(clean_max_rel=max(max_rels),
                        fault_max_rel=float(inject_rel))


# ---------------------------------------------------------------------------
# streams
# ---------------------------------------------------------------------------

class _Stream:
    """Set-up and checks shared by the two stream drivers."""

    def __init__(self, ctx: Ctx):
        import jax

        from repro.core.abft import ABFTConfig
        from repro.engine import StreamingEngine, plan_rungs

        config, traffic = ctx.config, ctx.traffic
        self.ctx = ctx
        dims = config["layer_dims"]
        self.dims = dims
        gspec = traffic["graphs"]
        server = traffic["server"]
        self.pool = graphs.request_pool(gspec, dims[0], traffic["pool"],
                                        ctx.seed)
        profile = graphs.request_pool(gspec, dims[0],
                                      traffic["profile_graphs"],
                                      traffic["profile_seed"])
        self.rungs = plan_rungs(profile, n_slots=server["n_slots"],
                                block=config["block"])
        self.abft = ABFTConfig(mode="fused")
        self.weights = make_weights(ctx.seed, dims)
        params = {"layers": [{"w": w} for w in self.weights]}
        self.engine = StreamingEngine(
            params, self.abft, self.rungs,
            queue_capacity=server["queue_capacity"],
            flush_deadline=server["flush_deadline_s"])
        ctx.require_compiled(self.engine.interpret)
        self.engine.warmup()
        self.order = np.random.default_rng(
            np.random.SeedSequence([0x5EED, ctx.seed])).permutation(
                len(self.pool))
        self.next = 0
        self.meta: Dict[int, tuple] = {}      # rid -> (due, pool index)
        self.recv: Dict[int, float] = {}
        self.results: Dict[int, Any] = {}
        jax.block_until_ready(self.weights)

    def take(self, drain: bool = False) -> int:
        """Collect finished verdicts (``drain``: first seal and resolve
        everything the engine holds)."""
        with span("bench.take_results"):
            done = (self.engine.drain() if drain
                    else self.engine.take_results())
        t = self.ctx.clock()
        for r in done:
            self.recv[r.rid] = t
            self.results[r.rid] = r
        return len(done)

    def submit(self, due: float) -> None:
        i = int(self.order[self.next % len(self.order)])
        self.next += 1
        with span("bench.submit"):
            rid = self.engine.submit(*self.pool[i])
        self.meta[rid] = (due, i)

    def warm(self, count: int) -> None:
        """Drive ``count`` requests through, so every path the window takes
        has run once, then forget them."""
        for _ in range(count):
            self.submit(self.ctx.clock())
            self.engine.pump()
            self.take()
        self.take(drain=True)
        self.meta.clear()
        self.recv.clear()
        self.results.clear()

    def close(self) -> None:
        """Collect every verdict still owed after the window, then check."""
        import jax

        from repro.engine import pack_graphs
        from repro.engine.streaming import make_packed_serve_step, \
            packed_step_args

        ctx, engine = self.ctx, self.engine
        time.sleep(engine.flush_deadline or 0.0)
        engine.pump()
        self.take()
        self.take(drain=True)
        ctx.memory_peak_bytes = device_peak_bytes()

        rids = list(self.meta)
        served = [rid for rid in rids
                  if rid in self.results
                  and self.results[rid].status == "served"]
        ctx.attempted = len(rids)
        ctx.failed = len(rids) - len(served)
        flagged = sum(bool(self.results[r].flag) for r in served)

        # an accumulator fault in one packed batch of the timed rung shape
        rung = self.rungs.rungs[0]
        items = [self.pool[i] for i in range(rung.n_slots)]
        pb = pack_graphs(items, block=self.rungs.block, n_slots=rung.n_slots,
                         stripe_multiple=self.rungs.stripe_multiple,
                         width_multiple=self.rungs.width_multiple,
                         stripe_cap=rung.stripe_cap, width_cap=rung.width_cap)
        delta = ctx.config["check"]["inject_delta"]
        step = make_packed_serve_step(engine.params, self.abft, rung.n_slots,
                                      block_g=self.rungs.block,
                                      inject=(len(self.dims) - 2, 0, 0, delta))
        _, metrics = step(*packed_step_args(pb))
        gflags = np.asarray(jax.device_get(metrics["abft_graph_flags"]))

        w_host = [np.asarray(w) for w in self.weights]
        refs: Dict[int, np.ndarray] = {}
        worst = 0.0
        for rid in served:
            i = self.meta[rid][1]
            if i not in refs:
                s, h0 = self.pool[i]
                refs[i] = ref_gcn.forward(
                    ref_gcn.dense_aggregate(s, "highest"), h0, w_host,
                    "highest")
            out = self.results[rid].logits
            worst = max(worst, float(np.abs(out - refs[i]).max()))
        scale = max((float(np.abs(r).max()) for r in refs.values()),
                    default=1.0)
        limits = ctx.config["check"]
        ctx.check("logit_gap", worst / scale, limits["logit_gap"])
        ctx.check("clean_flags", flagged + engine.guard.flags, 0)
        ctx.check("unanswered", ctx.failed, 0)
        ctx.check("fault_missed", 0 if gflags[0] else 1, 0)
        ctx.counters.update(
            served=len(served), compared=len(served),
            compiles=engine.compile_count,
            rung_table_size=len(self.rungs),
            degrades=engine.degrades)

    def latencies(self, rids) -> List[float]:
        return [self.recv[r] - self.meta[r][0] for r in rids
                if r in self.recv and self.results[r].status == "served"]

    def engine_counts(self) -> Dict[str, int]:
        e = self.engine
        return {"batches": e.batches_dispatched, "served": e.served,
                "rejected": e.rejected}


def stream_open(ctx: Ctx) -> None:
    traffic = ctx.traffic
    st = _Stream(ctx)
    rate = float(traffic["rate_per_s"])
    st.warm(traffic["warm_requests"])
    count = int(np.ceil(rate * ctx.seconds))
    gaps = graphs.exponential_gaps(count, rate)
    gaps = np.random.default_rng(
        np.random.SeedSequence([0xA11, ctx.seed])).permutation(gaps)
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    ctx.setup_end = ctx.clock()
    engine = st.engine
    before = st.engine_counts()
    late: List[float] = []
    with profiled(ctx):
        with span("bench.window"):
            t0 = ctx.clock()
            k = 0
            seen = t0                      # the generator's last look
            while True:
                now = ctx.clock()
                if now - t0 >= ctx.seconds:
                    break
                seen = now
                while (k < count and t0 + offsets[k] <= now
                       and ctx.clock() - t0 < ctx.seconds):
                    due = t0 + offsets[k]
                    st.submit(due)
                    late.append(ctx.clock() - due)
                    k += 1
                with span("bench.pump"):
                    engine.pump()
                st.take()
                nxt = t0 + offsets[k] if k < count else t0 + ctx.seconds
                wait = min(nxt, ctx.clock() + 5e-4) - ctx.clock()
                if wait > 0:
                    time.sleep(wait)
            t1 = ctx.clock()
        after = st.engine_counts()
    ctx.window_s = t1 - t0
    st.close()
    # requests the generator found due but could not offer before the
    # window closed count as attempted and failed
    unoffered = max(int(np.searchsorted(offsets, seen - t0, side="right"))
                    - k, 0)
    ctx.attempted += unoffered
    ctx.failed += unoffered
    rids = list(st.meta)
    ctx.samples["latency_s"] = st.latencies(rids)
    ctx.samples["queue_wait_s"] = [
        st.results[r].t_dispatch - st.results[r].t_enqueue for r in rids
        if r in st.results and st.results[r].t_dispatch is not None]
    ctx.samples["generator_late_s"] = late
    ctx.counters.update(offered=k, unoffered=unoffered,
                        offered_rate=k / ctx.window_s,
                        latency_p50_ms=1e3 * float(
                            np.median(ctx.samples["latency_s"] or [0.0])),
                        generator_late_p95_ms=1e3 * float(
                            np.percentile(late or [0.0], 95)),
                        n_slots=st.rungs.rungs[0].n_slots,
                        **{f"window_{key}": after[key] - before[key]
                           for key in after})


def stream_closed(ctx: Ctx) -> None:
    traffic = ctx.traffic
    st = _Stream(ctx)
    outstanding_cap = int(traffic["outstanding"])
    st.warm(traffic["warm_requests"])
    ctx.setup_end = ctx.clock()
    engine = st.engine
    before = st.engine_counts()
    in_flight = 0
    done_in_window = 0
    with profiled(ctx):
        with span("bench.window"):
            t0 = ctx.clock()
            while True:
                while (in_flight < outstanding_cap
                       and ctx.clock() - t0 < ctx.seconds):
                    st.submit(ctx.clock())
                    in_flight += 1
                with span("bench.pump"):
                    engine.pump()
                got = st.take()
                t1 = ctx.clock()
                in_flight -= got
                done_in_window += got
                if t1 - t0 >= ctx.seconds:
                    break
        after = st.engine_counts()
    ctx.window_s = t1 - t0
    st.close()
    ctx.counters.update(completed_in_window=done_in_window,
                        n_slots=st.rungs.rungs[0].n_slots,
                        **{f"window_{key}": after[key] - before[key]
                           for key in after})


DRIVERS = {"full_graph": full_graph, "stream_open": stream_open,
           "stream_closed": stream_closed}
