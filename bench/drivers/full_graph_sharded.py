"""One caller, closed loop, back-to-back ``gcn_apply`` on a whole graph
whose row stripes are split over a mesh of the cell's chips, rotating
through feature matrices held with their rows.

The tile table is planned from the COO on the host and built and staged
one shard at a time; each feature matrix is staged the same way, its rows
padded to the stripes'.  So no chip holds more than its share, and only
each layer's X and x_r cross chips.  Spans as ``full_graph``."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from bench import graphs
from bench.drivers import Ctx, device_peak_bytes, make_weights, profiled, span
from bench.references import gcn as ref_gcn


def _rows(coo: graphs.Coo, lo: int, hi: int) -> np.ndarray:
    """Dense rows ``[lo, hi)`` of a COO matrix; rows past its last are
    zero."""
    out = np.zeros((hi - lo, coo.shape[1]), np.float32)
    keep = (coo.row >= lo) & (coo.row < hi)
    np.add.at(out, (coo.row[keep] - lo, coo.col[keep]), coo.data[keep])
    return out


def run(ctx: Ctx) -> None:
    # the program's sharded staging first: a program without it stops
    # here, before any large allocation
    from repro.engine import (Graph, Partition, fold_w_r, gcn_apply,
                              make_backend, stage_rows)
    from repro.kernels.spmm_abft.layout import plan_block_ell

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.abft import ABFTConfig
    from repro.launch.mesh import make_graph_mesh

    config, traffic = ctx.config, ctx.traffic
    dims = config["layer_dims"]
    block = config["block"]
    n_sets = traffic["feature_sets"]
    part = Partition(make_graph_mesh(ctx.chips), "graph")
    whole = graphs.make_graph(config, ctx.seed, n_sets)
    s = whole.s
    n = s.shape[0]
    plan = plan_block_ell(s.row, s.col, s.data, s.shape, block, block)
    abft = ABFTConfig(mode="fused")
    weights = make_weights(ctx.seed, dims)
    params = jax.device_put(
        fold_w_r({"layers": [{"w": w} for w in weights]}, abft),
        NamedSharding(part.mesh, PartitionSpec()))
    bk = make_backend(plan, abft, backend="block_ell", partition=part)
    ctx.require_compiled(bk.interpret)
    rows = bk.vals.shape[0] * block
    feats = [stage_rows(part, rows, lambda lo, hi, f=f: _rows(f, lo, hi))
             for f in whole.features]
    inputs = [Graph(s=plan, h0=h) for h in feats]

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
            count = 0
            while True:
                logits, flag, max_rel = forward(count)
                last[count % n_sets] = logits
                flagged += flag
                max_rels.append(max_rel)
                count += 1
                t1 = ctx.clock()
                if t1 - t0 >= ctx.seconds:
                    break
        if ctx.trace_dir is not None:
            with span("bench.window.none"):
                for i in range(count):
                    forward(i, cfg_none)
    ctx.window_s = t1 - t0
    ctx.memory_peak_bytes = device_peak_bytes()
    ctx.counters["forwards"] = count
    ctx.counters["staged_tile_bytes_max"] = max(bk.staged_bytes.values())
    ctx.attempted = count
    ctx.failed = flagged
    # what one forward's kernel calls stage and compute, one call a shard
    # a layer, each over that shard's stripes
    local = bk.vals.shape[0] // part.n_shards
    ctx.kernel_calls = [{"kernel": "spmm_abft",
                         "vals_shape": (local,) + tuple(bk.vals.shape[1:]),
                         "vals_itemsize": bk.vals.dtype.itemsize,
                         "cols_shape": (local,) + tuple(bk.cols.shape[1:]),
                         "cols_itemsize": bk.cols.dtype.itemsize,
                         "out_width": g}
                        for g in dims[1:] for _ in range(part.n_shards)]

    # -- correctness: after the window, nothing of it timed --------------
    outs = {i: np.asarray(v)[:n] for i, v in last.items()}
    del bk, last
    delta = config["check"]["inject_delta"]
    bad = make_backend(plan, abft, backend="block_ell", partition=part,
                       inject=(len(dims) - 2, plan.n_block_rows // 2, 0,
                               delta))
    _, rep_bad = gcn_apply(params, inputs[0], abft, backend=bad)
    inject_flag, inject_rel = jax.device_get((rep_bad.flag, rep_bad.max_rel))
    del bad

    w_host = [np.asarray(w) for w in weights]
    agg = ref_gcn.coo_aggregate(s.row, s.col, s.data, n, "highest")
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
