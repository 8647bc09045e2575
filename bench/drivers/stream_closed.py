"""Closed loop: the stream's requests, a fixed number kept outstanding."""
from __future__ import annotations

from bench.drivers import Ctx, profiled, span
from bench.drivers._stream import Stream


def run(ctx: Ctx) -> None:
    traffic = ctx.traffic
    st = Stream(ctx)
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
