"""Set-up and checks shared by the stream drivers."""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from bench import graphs
from bench.drivers import Ctx, device_peak_bytes, make_weights, span
from bench.references import gcn as ref_gcn


class Stream:
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
