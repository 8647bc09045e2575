"""Closed-batch multi-graph GCN serving driver (benchmark mode).

This driver materializes a whole stream, packs it once, and replays the
batches — the right harness for apples-to-apples throughput benchmarks
(``benchmarks/serve_backends.py``), where arrival timing must not pollute
the measurement.  For continuous traffic use the streaming server
(``repro.launch.serve_stream`` / ``engine.streaming.StreamingEngine``):
bounded request queue, online packing into canonical rung shapes, p50/p99
latency accounting, and backpressure.  Both run the SAME machinery —
``engine.streaming.PackedRunner``'s jitted steps, retry ladders, and the
``ABFTGuard`` escalation ladder — this module is a thin client of it.

Variable-size graphs batch one of two ways:

* ``--backend dense``      — bucketed zero-padding into [B, N, N] dense
  batches (one compile per bucket), O(B·N²·F) per bucket regardless of
  sparsity;
* ``--backend block_ell``  — block-diagonal packing into ONE block-ELL
  system per batch (``engine.batching.pack_graphs``): each graph pads only
  to the block size, aggregation runs through the spmm_abft Pallas kernel,
  and the fused epilogue segment-sums the per-stripe checksum partials into
  *per-graph* eq.-6 corners — serving cost scales with nnz, not N².

Both paths run under ``ABFTGuard.run_step_graphs``: the step emits a
per-graph verdict vector, so a flagged batch retries *only the flagged
graphs* (a small re-batch) instead of replaying the whole bucket; a
persistently flagged step falls back to restore->replay->verify.  With
``--check-granularity stripe`` (block_ell backend) the packed epilogue
keeps its per-row-stripe corners and the guard gains the surgical tier:
a flagged stripe's rows are gathered, re-executed through the fused
kernel, spliced, and re-verified (``engine.localize``) before any graph is
re-packed — the retry-escalation ladder is stripe -> graph -> whole-step
restore.  Per-layer ``w_r`` is folded once at weight-load time
(``engine.fold_w_r``), not recomputed per step.  Reports graphs/sec over
the sustained phase plus the stream-order per-graph verdicts.

    PYTHONPATH=src python -m repro.launch.serve_gcn --graphs 64 --batch 8 \
        --backend block_ell --block 32 --abft fused \
        --check-granularity stripe
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.abft import ABFTConfig
from repro.core.gcn import init_gcn
from repro.engine import GraphBatch, PackedGraphs, fold_w_r, \
    make_batches, make_packed_batches, synth_graph_stream
from repro.engine.streaming import (
    PackedRunner,
    dense_retry_fn,
    make_serve_step,
    packed_step_args,
)
from repro.kernels.runtime import use_compile_cache
from repro.runtime import ABFTGuard

Batch = Union[GraphBatch, PackedGraphs]


def serve(batches: Sequence[Batch], params, cfg: ABFTConfig,
          guard: Optional[ABFTGuard] = None, verbose: bool = True, *,
          block_g: int = 128, fused_layer: bool = False,
          fused_network: bool = False, vmem_budget: Optional[int] = None,
          granularity: str = "graph"):
    """Run every batch through the guarded jitted step; returns stats.

    Dispatches per batch type (GraphBatch -> dense, PackedGraphs -> packed
    block-ELL); both report per-graph verdicts, assembled into stream order
    via each batch's ``indices``.  Retries re-pack at each batch's own
    block size (``PackedGraphs.block``).  ``fused_layer=True`` selects the
    single-pass gcn_fused kernel on the packed path (dense path unaffected);
    ``fused_network=True`` tries the whole-network kernel first — every
    layer in ONE HBM traversal with activations resident in VMEM, falling
    back to the per-layer ladder when the depth-wide working set exceeds
    ``vmem_budget``.  ``granularity="stripe"`` (packed batches only) keeps
    per-stripe check corners and arms the guard's surgical retry tier;
    ``"slot"`` keeps per-(stripe, slot) telescoped corners and adds the
    slot-surgical rung below it — the escalation ladder becomes
    slot -> stripe -> graph -> whole-step restore.
    """
    if granularity not in ("graph", "stripe", "slot"):
        raise ValueError(f"serve granularity {granularity!r} not in "
                         f"('graph', 'stripe', 'slot')")
    guard = guard if guard is not None else ABFTGuard()
    params = fold_w_r(params, cfg)
    dense_step = None
    packed = PackedRunner(params, cfg, block_g, fused_layer, granularity,
                          fused_network=fused_network,
                          vmem_budget=vmem_budget)
    fusion = {"fused_hits": 0, "fused_fallbacks": 0,
              "network_hits": 0, "network_fallbacks": 0}

    def run_one(b: Batch, warm: bool):
        nonlocal dense_step
        stripe_retry = slot_retry = None
        if isinstance(b, PackedGraphs):
            step, args = packed.step_for(b), packed_step_args(b)
            retry = packed.retry_fn(b)
            if granularity in ("stripe", "slot"):
                stripe_retry = packed.stripe_retry_fn(b)
            if granularity == "slot":
                slot_retry = packed.slot_retry_fn(b)
            if not warm:
                for key, n in packed.fusion_counts(b).items():
                    fusion[key] += n
        else:
            if granularity != "graph":
                raise ValueError("dense batches have no row-stripes; "
                                 "--check-granularity stripe/slot needs "
                                 "--backend block_ell")
            if dense_step is None:
                dense_step = make_serve_step(params, cfg)
            step = dense_step
            args = (jnp.asarray(b.s), jnp.asarray(b.h0))
            retry = dense_retry_fn(dense_step, b)
        if warm:
            out, metrics = step(*args)
        else:
            out, metrics = guard.run_step_graphs(
                step, retry, *args, stripe_retry_fn=stripe_retry,
                slot_retry_fn=slot_retry)
        jax.block_until_ready(metrics["abft_graph_flags"])
        return out, metrics

    # warmup compiles per distinct shape (excluded from the timed phase)
    shapes = {}
    for b in batches:
        key = (b.s.shape, b.h0.shape) if isinstance(b, GraphBatch) \
            else (b.bell.values.shape, b.h0.shape, b.n_slots)
        shapes.setdefault(key, b)
    for b in shapes.values():
        jax.block_until_ready(run_one(b, warm=True)[0])  # abftlint: sync-ok (benchmark timing barrier)

    n_graphs = 0
    n_stream = sum(b.n_graphs for b in batches)
    graph_flags = np.zeros(n_stream, bool)
    graph_max_rel = np.zeros(n_stream, np.float32)
    t0 = time.perf_counter()
    for b in batches:
        logits, metrics = run_one(b, warm=False)
        jax.block_until_ready(logits)  # abftlint: sync-ok (benchmark timing barrier)
        n_graphs += b.n_graphs
        if b.indices is not None:
            live = b.indices >= 0
            graph_flags[b.indices[live]] = \
                np.asarray(metrics["abft_graph_flags"])[live]  # abftlint: sync-ok (benchmark result collection)
            graph_max_rel[b.indices[live]] = \
                np.asarray(metrics["abft_graph_max_rel"])[live]  # abftlint: sync-ok
    dt = time.perf_counter() - t0
    gps = n_graphs / max(dt, 1e-9)
    kind = "packed block_ell" if any(isinstance(b, PackedGraphs)
                                     for b in batches) else "dense"
    if fused_network and kind != "dense":
        kind += " (fused-network)"
    elif fused_layer and kind != "dense":
        kind += " (fused-layer)"
    if granularity == "stripe":
        kind += " [stripe corners]"
    elif granularity == "slot":
        kind += " [slot corners]"
    if verbose:
        print(f"served {n_graphs} graphs in {len(batches)} {kind} batches "
              f"({len(shapes)} shapes) in {dt*1e3:.1f} ms "
              f"-> {gps:.1f} graphs/sec")
        print(f"guard: steps={guard.steps} flags={guard.flags} "
              f"retries={guard.retries} graph_retries={guard.graph_retries} "
              f"stripe_retries={guard.stripe_retries} "
              f"slot_retries={guard.slot_retries} "
              f"recomputed_rows={guard.recomputed_rows} "
              f"flag_rate={guard.flag_rate:.4f} "
              f"evict={guard.should_evict()}")
        tiers = guard.repair_tiers()
        print(f"repair tiers: slot={tiers['slot']} "
              f"stripe={tiers['stripe']} graph={tiers['graph']} "
              f"restore={tiers['restore']} "
              f"persistent={tiers['persistent_escalations']} "
              f"suspect={tiers['suspect']}")
        if fusion["network_hits"] or fusion["network_fallbacks"] \
                or fusion["fused_hits"] or fusion["fused_fallbacks"]:
            print(f"fusion: network_hits={fusion['network_hits']} "
                  f"network_fallbacks={fusion['network_fallbacks']} "
                  f"fused_hits={fusion['fused_hits']} "
                  f"fused_fallbacks={fusion['fused_fallbacks']}")
    return {"graphs": n_graphs, "batches": len(batches), "seconds": dt,
            "graphs_per_sec": gps, "flags": guard.flags,
            "graph_retries": guard.graph_retries,
            "stripe_retries": guard.stripe_retries,
            "slot_retries": guard.slot_retries,
            "recomputed_rows": guard.recomputed_rows,
            "repair_tiers": guard.repair_tiers(),
            "graph_flags": graph_flags, "graph_max_rel": graph_max_rel,
            **fusion}


def main(argv: Optional[Sequence[str]] = None) -> dict:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--backend", default="dense",
                    choices=["dense", "block_ell"],
                    help="dense bucketed padding, or block-diagonal packed "
                         "block-ELL on the Pallas kernel path")
    ap.add_argument("--buckets", default="64,128",
                    help="comma list of node-count buckets (dense backend)")
    ap.add_argument("--block", type=int, default=32,
                    help="square block size of the packed block-ELL layout "
                         "(block_ell backend; use 128 on TPU)")
    ap.add_argument("--nodes", default="24,120",
                    help="lo,hi node-count range of the synthetic stream")
    ap.add_argument("--feat", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--classes", type=int, default=7)
    ap.add_argument("--abft", default="fused",
                    choices=["none", "split", "fused"])
    ap.add_argument("--fused-layer", action="store_true",
                    help="run each packed layer through the single-pass "
                         "gcn_fused kernel (combination + aggregation + "
                         "check in one HBM traversal; block_ell backend)")
    ap.add_argument("--fused-network", action="store_true",
                    help="run the WHOLE network through one kernel sweep "
                         "(activations ping-pong in VMEM, one HBM "
                         "traversal end-to-end; falls back to the "
                         "per-layer ladder when the depth-wide working "
                         "set exceeds the VMEM budget; block_ell backend)")
    ap.add_argument("--vmem-budget", type=int, default=None,
                    help="override the fused-kernel VMEM budget in bytes "
                         "(default: kernels.gcn_fused FUSED_VMEM_BUDGET)")
    ap.add_argument("--check-granularity", default="graph",
                    choices=["graph", "stripe", "slot"],
                    help="fault attribution: per packed graph (default), "
                         "per row-stripe, or per (stripe, slot) tile "
                         "column — stripe/slot arm the guard's surgical "
                         "retry tiers (block_ell backend)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.check_granularity != "graph" and args.backend != "block_ell":
        ap.error(f"--check-granularity {args.check_granularity} needs "
                 f"--backend block_ell (dense batches have no row-stripes)")
    if args.fused_network and args.backend != "block_ell":
        ap.error("--fused-network needs --backend block_ell")

    buckets = [int(b) for b in args.buckets.split(",")]
    n_lo, n_hi = (int(v) for v in args.nodes.split(","))
    cfg = ABFTConfig(mode=args.abft, threshold=1e-3, relative=True)
    print(f"=== serve_gcn: {args.graphs} graphs, batch {args.batch}, "
          f"backend={args.backend}, abft={args.abft} "
          f"({jax.default_backend()}) ===")

    stream = synth_graph_stream(args.graphs, n_lo=n_lo, n_hi=n_hi,
                                feat=args.feat, seed=args.seed)
    if args.backend == "block_ell":
        batches: List[Batch] = make_packed_batches(
            stream, args.batch, block=args.block,
            stripe_multiple=4, width_multiple=4)
    else:
        batches = make_batches(stream, args.batch, buckets)
    params = init_gcn(jax.random.PRNGKey(args.seed),
                      (args.feat, args.hidden, args.classes))
    return serve(batches, params, cfg, fused_layer=args.fused_layer,
                 fused_network=args.fused_network,
                 vmem_budget=args.vmem_budget,
                 granularity=args.check_granularity)


if __name__ == "__main__":
    main()
