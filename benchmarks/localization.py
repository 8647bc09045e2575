"""Fault-localization economics: how many rows must be re-executed to
recover from one detected fault, per recovery tier?

For each mix, a packed block-ELL batch runs the single-pass fused layer at
``granularity="slot"`` while the kernel's accumulator fault-injection
hook (``inject=(layer, stripe, slot, delta)``) perturbs one accumulator
element — one experiment per (layer, stripe, slot) point.  Detection is
asserted to be *exact* twice over: the injected (stripe, slot) telescoped
corner — and only it — flags, and the derived stripe corner agrees.  The
four tiers of the guard's escalation ladder are then costed in re-executed
rows (row x layer re-executions), the slot and stripe tiers from the SAME
injected metrics (slot-granularity reports carry both):

  * **slot**    — the sub-stripe surgical repair
    (``engine.localize.surgical_slot_retry``): the flagged stripe's rows
    at the flagged layer, then only the downstream stripes whose cols
    table references a row the splice actually CHANGED (ReLU masking and
    0·x=0 prune the rest).  Bit-for-bit equal to a clean run.
  * **stripe**  — the stripe-surgical repair (``engine.localize``): the
    flagged stripe's rows at the flagged layer, plus every stripe whose
    cols table references the repaired rows downstream, changed or not.
    Also asserted bit-for-bit.
  * **graph**   — PR 3's per-graph retry: every LOGICAL row of the flagged
    graph (its n_nodes, not its padded stripe rows), at every layer — the
    same basis ``PackedRunner.retry_fn`` reports in
    ``abft_rows_recomputed``, asserted equal here once per mix.
  * **step**    — whole-step replay (restore tier): every padded row of
    the batch, at every layer.

Writes ``BENCH_localization.json`` with the recomputed-rows fractions
(tier rows / step rows); the strict ordering slot < stripe < graph < step
is asserted per mix.  CPU runs the kernel in interpret mode — the row
counts are exact either way, only wall-clock is pessimistic.

    PYTHONPATH=src python -m benchmarks.localization --graphs 6
"""
from __future__ import annotations

import argparse
import json
from typing import List, Optional, Sequence

MIXES = (
    # name, node range, block — n_lo >= 2*block so every graph spans >= 2
    # stripes (single-stripe graphs make stripe and graph retry coincide)
    ("small", (32, 64), 16),
    ("wide", (48, 120), 16),
)


def run_mix(name: str, nodes, block: int, *, graphs: int, feat: int,
            hidden: int, classes: int, seed: int, stride: int,
            delta: float) -> dict:
    import jax
    import numpy as np

    from repro.core.abft import ABFTConfig
    from repro.core.gcn import init_gcn
    from repro.engine import fold_w_r, pack_graphs, synth_graph_stream
    from repro.engine.localize import surgical_slot_retry, \
        surgical_stripe_retry
    from repro.engine.streaming import (PackedRunner, make_packed_serve_step,
                                        packed_step_args)

    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)
    stream = synth_graph_stream(graphs, n_lo=nodes[0], n_hi=nodes[1],
                                feat=feat, seed=seed)
    pb = pack_graphs(stream, block=block, stripe_multiple=4,
                     width_multiple=2)
    params = fold_w_r(init_gcn(jax.random.PRNGKey(seed),
                               (feat, hidden, classes)), cfg)
    n_layers = len(params["layers"])
    args = packed_step_args(pb)
    nbm = pb.bell.n_block_rows
    width = pb.bell.width
    bm = pb.block
    stripe_graph = np.asarray(pb.stripe_graph)
    stripes_of = {g: int((stripe_graph == g).sum())
                  for g in range(pb.n_slots)}
    step_rows_once = nbm * bm * n_layers

    clean_step = make_packed_serve_step(params, cfg, pb.n_slots,
                                        block_g=block, fused_layer=True,
                                        granularity="slot")
    logits_clean, m_clean = clean_step(*args)
    assert not bool(np.asarray(m_clean["abft_graph_flags"]).any()), \
        "clean packed run flagged — raise the threshold or reseed"
    logits_clean = np.asarray(logits_clean)

    # same-basis guard: the graph-tier rows this benchmark charges must be
    # the rows the engine's own retry accounting reports, or the
    # stripe-vs-graph fractions silently compare different units
    runner = PackedRunner(params, cfg, block, fused_layer=True,
                          granularity="slot")
    _, m_retry = runner.retry_fn(pb)(logits_clean, [0])
    assert int(m_retry["abft_rows_recomputed"]) == \
        int(pb.n_nodes[0]) * n_layers, \
        (name, int(m_retry["abft_rows_recomputed"]),
         int(pb.n_nodes[0]) * n_layers,
         "engine retry accounting is not on the logical-rows basis")

    real_stripes = [s for s in range(nbm) if stripe_graph[s] < pb.n_slots
                    and stripes_of[int(stripe_graph[s])] > 0][::stride]
    rows = {"slot": 0, "stripe": 0, "graph": 0, "step": 0}
    n_inj = 0
    for layer in range(n_layers):
        for stripe in real_stripes:
            for slot in (0, width - 1):
                inj_step = make_packed_serve_step(
                    params, cfg, pb.n_slots, block_g=block,
                    fused_layer=True, granularity="slot",
                    inject=(layer, stripe, slot, delta))
                out_bad, m_bad = inj_step(*args)
                slf = np.asarray(m_bad["abft_slot_flags"])
                sf = np.asarray(m_bad["abft_stripe_flags"])
                gf = np.asarray(m_bad["abft_graph_flags"])
                slot_hits = np.argwhere(slf)
                assert slot_hits.shape == (1, 3) and \
                    tuple(slot_hits[0]) == (layer, stripe, slot), \
                    (name, layer, stripe, slot, slot_hits.tolist())
                flagged = np.argwhere(sf)
                assert flagged.shape == (1, 2) and \
                    tuple(flagged[0]) == (layer, stripe), \
                    (name, layer, stripe, slot, flagged.tolist())
                victim = int(stripe_graph[stripe])
                assert gf.sum() == 1 and gf[victim], (name, layer, stripe)
                # slot and stripe tiers costed from the SAME injected
                # metrics — slot-granularity reports carry both ladders
                rep_sl, sub_sl = surgical_slot_retry(
                    pb, params, cfg, out_bad, m_bad, block_g=block)
                assert not sub_sl["abft_graph_flags"].any(), \
                    (name, layer, stripe, slot)
                assert np.array_equal(rep_sl, logits_clean), \
                    (name, layer, stripe, slot, "slot splice not bit-exact")
                repaired, sub = surgical_stripe_retry(
                    pb, params, cfg, out_bad, m_bad, block_g=block)
                assert not sub["abft_graph_flags"].any(), \
                    (name, layer, stripe, slot)
                assert np.array_equal(repaired, logits_clean), \
                    (name, layer, stripe, slot, "splice not bit-exact")
                assert int(sub_sl["abft_rows_recomputed"]) <= \
                    int(sub["abft_rows_recomputed"]), \
                    (name, layer, stripe, slot, "slot reach exceeds stripe")
                rows["slot"] += int(sub_sl["abft_rows_recomputed"])
                rows["stripe"] += int(sub["abft_rows_recomputed"])
                rows["graph"] += int(pb.n_nodes[victim]) * n_layers
                rows["step"] += step_rows_once
                n_inj += 1
    frac = {k: v / max(rows["step"], 1) for k, v in rows.items()}
    assert rows["slot"] < rows["stripe"] < rows["graph"] < rows["step"], \
        (name, rows)
    return {"mix": name, "nodes": list(nodes), "block": block,
            "stripes": nbm, "graphs": pb.n_graphs, "layers": n_layers,
            "injections": n_inj, "rows": rows, "rows_fraction": frac}


def main(argv: Optional[Sequence[str]] = None) -> List[dict]:
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", type=int, default=6)
    ap.add_argument("--feat", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=8)
    ap.add_argument("--classes", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stride", type=int, default=1,
                    help="inject at every stride-th stripe (sweep thinning "
                         "for CI; 1 = every real stripe)")
    ap.add_argument("--delta", type=float, default=64.0,
                    help="accumulator perturbation magnitude")
    ap.add_argument("--json", default="BENCH_localization.json",
                    help="write machine-readable results here ('' disables)")
    args = ap.parse_args(argv)

    print(f"=== localization: {args.graphs} graphs/mix, stride "
          f"{args.stride} ({jax.default_backend()}) ===")
    print(f"{'mix':>8} {'inj':>5} {'slot rows':>10} {'stripe rows':>12} "
          f"{'graph rows':>12} {'step rows':>12}  fraction sl/s/g/step")
    results = []
    for name, nodes, block in MIXES:
        r = run_mix(name, nodes, block, graphs=args.graphs, feat=args.feat,
                    hidden=args.hidden, classes=args.classes,
                    seed=args.seed, stride=args.stride, delta=args.delta)
        results.append(r)
        f = r["rows_fraction"]
        print(f"{name:>8} {r['injections']:>5} {r['rows']['slot']:>10} "
              f"{r['rows']['stripe']:>12} "
              f"{r['rows']['graph']:>12} {r['rows']['step']:>12}  "
              f"{f['slot']:.3f}/{f['stripe']:.3f}/{f['graph']:.3f}/1.000")
    if args.json:
        rec = {"bench": "localization",
               "device_backend": jax.default_backend(),
               "config": {"graphs": args.graphs, "feat": args.feat,
                          "hidden": args.hidden, "classes": args.classes,
                          "seed": args.seed, "stride": args.stride,
                          "delta": args.delta},
               "mixes": results}
        with open(args.json, "w") as fh:
            json.dump(rec, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json}")
    return results


if __name__ == "__main__":
    main()
