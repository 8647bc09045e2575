#!/usr/bin/env python3
"""Smoke test of the checked GCN path on a TPU, compiled, in one process.

    python3 chip_smoke.py             # one chip: phases (a)-(c)
    python3 chip_smoke.py --chips 4   # four chips: the sharded path only

(a) startup: a TPU must be the default backend; every kernel call below
    passes ``interpret=False``, so no environment variable or backend probe
    can turn this run into an interpret-mode one.
(b) full-graph forward: full Cora and full PubMed (published sizes, seeded
    synthetic graphs, random weights) through the block-ELL engine, two-pass
    (``spmm_abft``) and fused-layer (``gcn_fused``), in ``mode="fused"`` and
    ``mode="none"``; logits against a float32 numpy reference of
    S relu(S H W1) W2 built from the dataset's COO; the clean check must
    not flag and one injected accumulator fault must.
(c) streaming server: 32 synthetic requests at PubMed widths through
    ``StreamingEngine(fused_network=True)`` (the ``gcn_network`` kernel),
    all served with no guard flag, degrade or failover.
(d) ``--chips 4``: full PubMed stripe-sharded over a 4-chip mesh at
    ``layer`` and ``stripe`` granularity, against the same forward on one
    chip of that host.

Each phase prints one JSON line; any failed check raises and the exit code
is non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.kernels.runtime import use_compile_cache  # noqa: E402

BLOCK = 128
SEED = 0
INJECT_DELTA = 4.0          # one accumulator upset, far above f32 noise
LOGIT_RTOL = 1e-4           # of max |reference logit|: f32 sums, not bf16
CORNER_RTOL = 1e-6          # sharded vs one-chip corner: 1000x under the
                            # 1e-3 check threshold, so no verdict can differ


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record, sort_keys=True), flush=True)


def timed(fn):
    """(result, seconds) with the result's device work included."""
    t = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t


# ---------------------------------------------------------------------------
# references: plain numpy float32, independent of the engine
# ---------------------------------------------------------------------------

def reference_logits(aggregate, h0, weights):
    """S relu(S H W1) W2 (any depth); ``aggregate`` applies S — the COO
    scatter-add of a dataset, or a dense matmul."""
    import numpy as np
    h = h0
    for i, w in enumerate(weights):
        h = aggregate(h @ w)
        if i < len(weights) - 1:
            h = np.maximum(h, 0.0)
    return h


def max_err(a, ref) -> float:
    import numpy as np
    return float(np.abs(np.asarray(a) - ref).max())


# ---------------------------------------------------------------------------
# (b) full-graph forward
# ---------------------------------------------------------------------------

def load_graph(name: str):
    import numpy as np

    from repro.core.datasets import make_dataset
    from repro.core.gcn import init_gcn

    ds = make_dataset(name, seed=SEED)
    params = init_gcn(jax.random.PRNGKey(SEED), ds.stats.layer_dims)
    weights = [np.asarray(layer["w"]) for layer in params["layers"]]
    h0 = ds.features.todense()
    return ds, params, h0, reference_logits(ds.s.matmul_dense, h0, weights)


def forward_phase(name: str) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.abft import ABFTConfig
    from repro.engine import Graph, gcn_apply, make_backend
    from repro.kernels.spmm_abft.layout import coo_to_block_ell

    t = time.perf_counter()
    ds, params, h0, ref = load_graph(name)
    coo = ds.s
    bell = coo_to_block_ell(coo.row, coo.col, coo.data, coo.shape, BLOCK,
                            BLOCK)
    graph = Graph(s=bell, h0=jnp.asarray(h0))
    setup_s = time.perf_counter() - t
    n_layers = len(params["layers"])
    scale = float(np.abs(ref).max())
    record = {"phase": "forward", "graph": name, "nodes": ds.stats.nodes,
              "dims": list(ds.stats.layer_dims), "stripes": bell.n_block_rows,
              "ell_width": bell.width, "setup_s": setup_s,
              "max_abs_ref": scale, "runs": []}
    inject = (n_layers - 1, bell.n_block_rows // 2, 0, INJECT_DELTA)

    for fused_layer in (False, True):
        logits_by_mode = {}
        for mode in ("fused", "none"):
            cfg = ABFTConfig(mode=mode)
            bk = make_backend(bell, cfg, backend="block_ell", block_g=BLOCK,
                              interpret=False, fused_layer=fused_layer)
            check(bk.interpret is False, "backend resolved to interpret mode")
            (logits, report), first_s = timed(
                lambda: gcn_apply(params, graph, cfg, backend=bk))
            # the whole-layer hook counts its decisions for one forward
            fused, fallbacks = bk.fused_hits, bk.fused_fallbacks
            (logits, report), step_s = timed(
                lambda: gcn_apply(params, graph, cfg, backend=bk))
            err = max_err(logits, ref)
            logits_by_mode[mode] = np.asarray(logits)
            run = {"fused_layer": fused_layer, "mode": mode,
                   "kernels": {"gcn_fused": fused,
                               "spmm_abft": n_layers - fused},
                   "fused_fallbacks": fallbacks,
                   "first_call_s": first_s, "step_s": step_s,
                   "max_abs_err": err, "flag": bool(report.flag),
                   "max_rel": float(report.max_rel)}
            check(err <= LOGIT_RTOL * scale,
                  f"{name} {run}: logits off the float32 reference")
            check(not run["flag"], f"{name} {run}: clean check flagged")
            check(run["kernels"]["gcn_fused" if fused_layer
                                 else "spmm_abft"] == n_layers,
                  f"{name} {run}: wrong kernel ran")
            if mode == "fused":
                cfg_bad = ABFTConfig(mode="fused")
                bad = make_backend(bell, cfg_bad, backend="block_ell",
                                   block_g=BLOCK, interpret=False,
                                   fused_layer=fused_layer, inject=inject)
                (_, rep_bad), _ = timed(
                    lambda: gcn_apply(params, graph, cfg_bad, backend=bad))
                run["inject"] = list(inject)
                run["inject_flag"] = bool(rep_bad.flag)
                run["inject_max_rel"] = float(rep_bad.max_rel)
                check(run["inject_flag"],
                      f"{name} {run}: injected fault not flagged")
            record["runs"].append(run)
        record.setdefault("none_equals_fused", []).append(bool(
            np.array_equal(logits_by_mode["fused"], logits_by_mode["none"])))
    emit(record)
    return record


# ---------------------------------------------------------------------------
# (c) streaming server
# ---------------------------------------------------------------------------

def stream_phase() -> dict:
    import numpy as np

    from repro.core.abft import ABFTConfig
    from repro.core.datasets import STATS
    from repro.core.gcn import init_gcn
    from repro.engine import StreamingEngine, plan_rungs, synth_graph_stream

    dims = STATS["pubmed"].layer_dims
    stream = synth_graph_stream(32, n_lo=24, n_hi=120, feat=dims[0],
                                seed=SEED)
    rungs = plan_rungs(stream, n_slots=8, block=BLOCK)
    params = init_gcn(jax.random.PRNGKey(SEED), dims)
    engine = StreamingEngine(params, ABFTConfig(mode="fused"), rungs,
                             fused_network=True, keep_logits=True,
                             interpret=False)
    check(engine.interpret is False, "stream resolved to interpret mode")
    t = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t
    t = time.perf_counter()
    results = []
    for s, h0 in stream:
        engine.submit(s, h0)
        results.extend(engine.take_results())
    results.extend(engine.drain())
    serve_s = time.perf_counter() - t
    stats = engine.stats(results)

    weights = [np.asarray(layer["w"]) for layer in params["layers"]]
    by_rid = {r.rid: r for r in results}
    refs = [reference_logits(s.__matmul__, h0, weights) for s, h0 in stream]
    scale = max(float(np.abs(r).max()) for r in refs)
    err = max(max_err(by_rid[i].logits, r) for i, r in enumerate(refs))
    keys = ("submitted", "served", "guard_flags", "degrades", "failovers",
            "degrade_level", "active_backend", "compiles", "rung_table_size",
            "batches", "network_hits", "network_fallbacks", "fused_hits",
            "fused_fallbacks", "latency_p50_ms", "latency_p99_ms",
            "graphs_per_sec")
    record = {"phase": "stream", "dims": list(dims), "block": BLOCK,
              "warmup_s": warmup_s, "serve_s": serve_s, "max_abs_err": err,
              "max_abs_ref": scale,
              **{k: stats[k] for k in keys}}
    check(stats["served"] == stats["submitted"] == len(stream),
          f"stream {record}: not every request served")
    check(stats["guard_flags"] == 0, f"stream {record}: clean batch flagged")
    check(stats["degrades"] == 0 and stats["failovers"] == 0,
          f"stream {record}: backend degraded")
    check(stats["degrade_level"] == 0
          and stats["active_backend"] == "fused-network",
          f"stream {record}: not on the level-0 backend")
    check(stats["compiles"] <= stats["rung_table_size"],
          f"stream {record}: compiles exceed the rung table")
    check(stats["network_hits"] == stats["batches"] >= 1,
          f"stream {record}: gcn_network did not run every batch")
    check(err <= LOGIT_RTOL * scale,
          f"stream {record}: logits off the reference")
    emit(record)
    return record


# ---------------------------------------------------------------------------
# (d) four chips: the stripe-sharded path
# ---------------------------------------------------------------------------

def compare_corners(one_checks, checks, nbm: int) -> list:
    """Per layer: the sharded stripe corners against one chip's.  The
    sharded system pads stripes up to a multiple of the mesh; those pad
    corners must be exactly zero."""
    import numpy as np
    out = []
    for c1, c4 in zip(one_checks, checks):
        p1, a1 = np.asarray(c1.predicted), np.asarray(c1.actual)
        p4, a4 = np.asarray(c4.predicted), np.asarray(c4.actual)
        diff = np.maximum(np.abs(p4[:nbm] - p1), np.abs(a4[:nbm] - a1))
        out.append({
            "predicted_bitwise": bool(np.array_equal(p4[:nbm], p1)),
            "actual_bitwise": bool(np.array_equal(a4[:nbm], a1)),
            "max_abs_diff": float(diff.max()),
            # in units of the check's own relative scale
            "rel_diff": float((diff / np.maximum(1.0, np.abs(a1))).max()),
            "pad_zero": bool(not p4[nbm:].any() and not a4[nbm:].any())})
    return out


def sharded_phase(n_chips: int) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.abft import ABFTConfig, summarize
    from repro.engine import Graph, Partition, gcn_forward, make_backend
    from repro.kernels.spmm_abft.layout import coo_to_block_ell
    from repro.launch.mesh import make_graph_mesh

    check(len(jax.devices()) >= n_chips,
          f"need {n_chips} chips, have {len(jax.devices())}")
    ds, params, h0, ref = load_graph("pubmed")
    coo = ds.s
    bell = coo_to_block_ell(coo.row, coo.col, coo.data, coo.shape, BLOCK,
                            BLOCK)
    graph = Graph(s=bell, h0=jnp.asarray(h0))
    partition = Partition(make_graph_mesh(n_chips))
    cfg = ABFTConfig(mode="fused")
    scale = float(np.abs(ref).max())
    record = {"phase": "sharded", "graph": "pubmed", "chips": n_chips,
              "stripes": bell.n_block_rows, "runs": []}
    for fused_layer in (False, True):
        for granularity in ("layer", "stripe"):
            def backend(part):
                return make_backend(bell, cfg, backend="block_ell",
                                    partition=part, block_g=BLOCK,
                                    interpret=False, fused_layer=fused_layer,
                                    granularity=granularity)
            one_bk, bk = backend(None), backend(partition)
            (one_logits, one_checks), one_s = timed(
                lambda: gcn_forward(params, graph, cfg, backend=one_bk))
            (logits, checks), sharded_s = timed(
                lambda: gcn_forward(params, graph, cfg, backend=bk))
            # each chip holds its own slab of the stripes
            tile_devices = len(bk.vals.sharding.device_set)
            shard_stripes = {s.data.shape[0]
                             for s in bk.vals.addressable_shards}
            nbm = bell.n_block_rows
            corners = (compare_corners(one_checks, checks, nbm)
                       if granularity == "stripe" else None)
            run = {"fused_layer": fused_layer, "granularity": granularity,
                   "one_chip_s": one_s, "sharded_s": sharded_s,
                   "tile_devices": tile_devices,
                   "stripes_per_chip": sorted(shard_stripes),
                   "max_abs_diff_vs_one_chip":
                       max_err(logits, np.asarray(one_logits)),
                   "max_abs_err_vs_reference": max_err(logits, ref),
                   "flag": bool(summarize(checks, cfg).flag),
                   "one_chip_flag": bool(summarize(one_checks, cfg).flag),
                   "stripe_corners": corners}
            check(np.allclose(np.asarray(logits), np.asarray(one_logits),
                              rtol=1e-5, atol=1e-6 * scale),
                  f"sharded {run}: logits differ from one chip")
            check(run["max_abs_err_vs_reference"] <= LOGIT_RTOL * scale,
                  f"sharded {run}: logits off the float32 reference")
            check(not run["flag"] and not run["one_chip_flag"],
                  f"sharded {run}: clean check flagged")
            check(corners is None or all(
                      c["rel_diff"] <= CORNER_RTOL and c["pad_zero"]
                      for c in corners),
                  f"sharded {run}: stripe corners differ from one chip")
            check(tile_devices == n_chips and len(shard_stripes) == 1
                  and n_chips * shard_stripes.pop() >= nbm,
                  f"sharded {run}: stripes not spread over the mesh")
            record["runs"].append(run)
    emit(record)
    return record


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the stripe-sharded path on a "
                         "four-chip mesh against one chip")
    args = ap.parse_args(argv)

    # (a) startup: a TPU or nothing
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: default backend is {backend!r}, not 'tpu'; "
              f"nothing was run", file=sys.stderr)
        return 2
    devices = jax.devices()
    emit({"phase": "startup", "backend": backend,
          "device_kind": devices[0].device_kind, "devices": len(devices)})

    if args.chips == 4:
        sharded_phase(4)
    else:
        forward_phase("cora")
        forward_phase("pubmed")
        stream_phase()

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
