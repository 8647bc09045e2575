#!/usr/bin/env python3
"""Find the knee of an open-loop stream cell: offer each rate in turn and
report whether completions kept up and whether the backlog grew.

    python3 bench/sweep.py --workload pubmed.stream --seed 1 --seconds 5 \\
        --rates 500,1000,2000

One process drives every rate (run it on the chip, not in the benchmark's
runs).  A rate is sustained when the generator offered at least 97 % of
what the rate asks for in the window, every request was answered with no
rejection, at least 97 % of the offered ones inside the window, and the
second half of the window's latencies has a 95th percentile no worse than
1.5 times the first half's (no growing backlog).  The cell's
``rate_per_s`` is then set by hand to about four fifths of the highest
sustained rate.
"""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import drivers, harness
    from bench.run import NoChip, chip_or_fail, require_compiled, setup_jax

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
        print(f"sweep: {err}", file=sys.stderr)
        return 2
    for rate in (float(r) for r in args.rates.split(",")):
        tr = copy.deepcopy(traffic)
        tr["rate_per_s"] = rate
        ctx = drivers.Ctx(config=config, traffic=tr, seed=args.seed,
                          seconds=args.seconds, trace_dir=None,
                          require_compiled=require_compiled)
        drivers.DRIVERS[tr["driver"]](ctx)
        lat = np.asarray(ctx.samples["latency_s"]) * 1e3
        half = len(lat) // 2
        late = np.asarray(ctx.samples["generator_late_s"]) * 1e3
        rec = {"rate": rate, "offered": ctx.counters["offered"],
               "answered": len(lat), "failed": ctx.failed,
               "served_in_window": ctx.counters["window_served"],
               "batches": ctx.counters["window_batches"],
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "p99_ms": float(np.percentile(lat, 99)),
               "p95_first_half_ms": float(np.percentile(lat[:half], 95)),
               "p95_second_half_ms": float(np.percentile(lat[half:], 95)),
               "generator_late_p95_ms": float(np.percentile(late, 95)),
               "checks": {n: v for n, v, _ in ctx.checks}}
        rec["sustained"] = bool(
            ctx.failed == 0
            and rec["offered"] >= 0.97 * rate * args.seconds
            and rec["served_in_window"] >= 0.97 * rec["offered"]
            and rec["p95_second_half_ms"] <= 1.5 * rec["p95_first_half_ms"])
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
