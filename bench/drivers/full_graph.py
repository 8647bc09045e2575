"""One caller, closed loop, back-to-back ``gcn_apply`` on a whole graph,
rotating through feature matrices that sit on the device."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from bench import graphs
from bench.drivers import Ctx, device_peak_bytes, make_weights, profiled, span
from bench.references import gcn as ref_gcn


def run(ctx: Ctx) -> None:
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
