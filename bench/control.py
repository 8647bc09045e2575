#!/usr/bin/env python3
"""Readings for the limit on ``logit_gap``: the control, run on the chip.

    python3 bench/control.py --workload pubmed.full --seeds 1,2,3

The control is the plain reference put in the program's place and computed
one precision step below what the configuration states: float32 products
at ``Precision.HIGH`` (three bfloat16 passes) where the program runs
``HIGHEST``.  For each seed it builds the cell's own inputs at the cell's
own size (the whole graph and its feature matrices, or the request pool),
computes the reference's logits in float32 on the host, the control's on
the chip (``jnp.matmul(..., precision=HIGH)``, dense adjacency) and the
same scheme emulated on the host (``precision="high"`` of the reference
module, which the CPU test uses), and prints the gaps as ``logit_gap``
measures them.  The limit has to lie below the smallest of these.

The benchmark's own runs never run this; the program's side of the reading
(the lower one) is the ``logit_gap`` that every run prints.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def chip_forward(jnp, jax, s_dense, h0, weights):
    high = jax.lax.Precision.HIGH
    h = jnp.asarray(h0)
    s = jnp.asarray(s_dense)
    for i, w in enumerate(weights):
        h = jnp.matmul(s, jnp.matmul(h, jnp.asarray(w), precision=high),
                       precision=high)
        if i < len(weights) - 1:
            h = jnp.maximum(h, 0.0)
    return jax.device_get(h)


def readings(config: dict, traffic: dict, seed: int, jax) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from bench import drivers, graphs
    from bench.references import gcn as ref

    dims = config["layer_dims"]
    weights = [np.asarray(w) for w in drivers.make_weights(seed, dims)]
    if traffic["driver"] == "full_graph":
        whole = graphs.make_graph(config, seed, traffic["feature_sets"])
        cases = [(whole.s, f.todense()) for f in whole.features]
    else:
        cases = graphs.request_pool(traffic["graphs"], dims[0],
                                    traffic["pool"], seed)
    scale = 0.0
    errs = {"chip_high": 0.0, "emulated_high": 0.0}
    for s, h0 in cases:
        if isinstance(s, graphs.Coo):
            agg_hi = ref.coo_aggregate(s.row, s.col, s.data, s.shape[0],
                                       "highest")
            agg_lo = ref.coo_aggregate(s.row, s.col, s.data, s.shape[0],
                                       "high")
            dense = s.todense()
        else:
            agg_hi = ref.dense_aggregate(s, "highest")
            agg_lo = ref.dense_aggregate(s, "high")
            dense = s
        r = ref.forward(agg_hi, h0, weights, "highest")
        scale = max(scale, float(np.abs(r).max()))
        chip = chip_forward(jnp, jax, dense, h0, weights)
        emu = ref.forward(agg_lo, h0, weights, "high")
        errs["chip_high"] = max(errs["chip_high"],
                                float(np.abs(chip - r).max()))
        errs["emulated_high"] = max(errs["emulated_high"],
                                    float(np.abs(emu - r).max()))
    return {k: e / scale for k, e in errs.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.run import NoChip, chip_or_fail, setup_jax

    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload, "workload")
    conf = harness.find(bench["configs"], cell["config"], "configuration")
    config = harness.load_json(harness.ROOT / conf["file"])
    traffic = harness.load_json(
        harness.BENCH / "traffic" / f"{cell['traffic']}.json")
    jax = setup_jax()
    try:
        chip_or_fail(jax, cell["chips"],
                     harness.load_json(harness.BENCH / "peaks.json"))
    except NoChip as err:
        print(f"control: {err}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = {"workload": args.workload, "seed": seed,
               "limit": config["check"]["logit_gap"],
               **readings(config, traffic, seed, jax)}
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
