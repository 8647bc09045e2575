"""Open-loop arrivals of small request graphs into ``StreamingEngine`` at
a fixed rate; each request is timed from when it was due until its verdict
reaches the caller."""
from __future__ import annotations

import time
from typing import List

import numpy as np

from bench import graphs
from bench.drivers import Ctx, profiled, span
from bench.drivers._stream import Stream


def run(ctx: Ctx) -> None:
    traffic = ctx.traffic
    st = Stream(ctx)
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
