"""``abftlint`` CLI: run the static-analysis passes over a traced step.

    PYTHONPATH=src python -m repro.analysis.lint --step gcn-serve \
        --granularity slot --fused-network

Steps (each builds a tiny synthetic instance of the real serving path —
shapes matter to a trace, values don't):

* ``gcn-serve``    — the packed block-ELL serve step
  (``make_packed_serve_step``), exactly what ``launch/serve_gcn.py``
  dispatches;
* ``gcn-stream``   — the same step at every rung of a ``plan_rungs``
  shape menu, plus the rung-table VMEM lint *before* anything compiles;
* ``gcn-forward``  — the engine forward (``--backend dense|bcoo``);
* ``gcn-train``    — a jitted ``value_and_grad`` GCN train step (the
  backward pass's dot_generals are expected-unchecked: ABFT covers the
  forward products, which is the paper's scope — so this step reports
  them rather than gating on them);
* ``lm-prefill`` / ``lm-decode`` — the guarded LM serving steps
  (``engine/lm.py``'s checked-op factories, what ``launch/serve_lm.py``
  dispatches): folded-``w_r`` dense checks + the fused attention chain
  check + per-op verdict vectors.  These default to ``--mode fused``
  and gate on zero unchecked matmuls — ROADMAP item 2 is done; the old
  unguarded baseline manifest is still available via ``--mode none``
  (with ``--expect-unchecked``);
* ``gat-serve``    — the guarded GAT serve step (``engine/gat.py``):
  the attention-weighted aggregation's eq. 4–6 chain corner per layer.

Passes (``--passes coverage,vmem,syncs``; default all that apply):
coverage traces the step under check tagging and verifies every
dot_general / matmul-shaped pallas_call reaches an eq. 4-6 comparison;
vmem statically prices every traced pallas_call's BlockSpecs and (for
gcn-stream) every rung against the budget; syncs AST-lints
``src/repro/engine`` + ``src/repro/launch``.

Exit status: 0 clean, 1 findings, 2 usage/build error.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import List, Optional

STEPS = ("gcn-serve", "gcn-stream", "gcn-forward", "gcn-train",
         "lm-prefill", "lm-decode", "gat-serve")
PASSES = ("coverage", "vmem", "syncs")


def _synth_graphs(n_graphs: int, nodes: int, feat: int, seed: int = 0):
    import numpy as np
    rng = np.random.default_rng(seed)
    graphs = []
    for _ in range(n_graphs):
        s = (rng.random((nodes, nodes)) < 0.3).astype(np.float32)
        s += np.eye(nodes, dtype=np.float32)
        graphs.append((s, rng.random((nodes, feat)).astype(np.float32)))
    return graphs


def _gcn_params(dims, seed: int = 0):
    import jax

    from repro.core.gcn import init_gcn
    return init_gcn(jax.random.PRNGKey(seed), dims)


def _trace(fn, *args):
    import jax

    from repro.core.marker import check_tagging
    with check_tagging():
        return jax.make_jaxpr(fn)(*args)


def _packed_step_trace(args, granularity: str):
    """(closed_jaxpr, pb, dims) for the packed GCN serve step."""
    from repro.engine.api import fold_w_r
    from repro.engine.batching import pack_graphs
    from repro.engine.streaming import make_packed_serve_step, \
        packed_step_args

    from repro.core.abft import ABFTConfig

    dims = [args.feat, args.hidden, args.classes]
    cfg = ABFTConfig(mode=args.mode)
    params = fold_w_r(_gcn_params(dims), cfg)
    graphs = _synth_graphs(args.graphs, args.nodes, args.feat)
    pb = pack_graphs(graphs, block=args.block, n_slots=args.graphs)
    step = make_packed_serve_step(
        params, cfg, pb.n_slots, granularity=granularity,
        fused_layer=args.fused_layer, fused_network=args.fused_network,
        vmem_budget=args.vmem_budget)
    closed = _trace(step, *packed_step_args(pb))
    return closed, pb, dims


def _build_traces(args) -> List[tuple]:
    """[(name, closed_jaxpr)] for the requested step, plus any extra
    findings produced while building (rung-table lint)."""
    import jax
    import jax.numpy as jnp

    from repro.core.abft import ABFTConfig

    step, gran = args.step, args.granularity
    if step == "gcn-serve":
        closed, _pb, _dims = _packed_step_trace(args, gran)
        return [(f"gcn-serve/{gran}", closed)], []

    if step == "gcn-stream":
        import numpy as np

        from repro.analysis.vmem import lint_rung_table
        from repro.engine.batching import pack_graphs
        from repro.engine.streaming import make_packed_serve_step, \
            packed_step_args, plan_rungs

        dims = [args.feat, args.hidden, args.classes]
        cfg = ABFTConfig(mode=args.mode)
        from repro.engine.api import fold_w_r
        params = fold_w_r(_gcn_params(dims), cfg)
        graphs = _synth_graphs(max(args.graphs, 4), args.nodes, args.feat)
        rungs = plan_rungs(graphs, n_slots=4, block=args.block)
        # VMEM lint FIRST — an over-budget rung is rejected before any
        # rung shape is traced, let alone compiled
        verdicts = lint_rung_table(
            rungs, dims, block=args.block,
            budget=args.vmem_budget or _default_budget(),
            fused_network=args.fused_network)
        extra = [f"rung {v.stripe_cap}x{v.width_cap}x{v.n_slots}: "
                 f"{(v.network_bytes or v.layer_bytes)} bytes over budget "
                 f"{v.budget}" for v in verdicts if not v.fits]
        traces = []
        for r in rungs.rungs:
            pb = pack_graphs(graphs[:1], block=rungs.block,
                             n_slots=r.n_slots,
                             stripe_cap=r.stripe_cap, width_cap=r.width_cap,
                             stripe_multiple=rungs.stripe_multiple,
                             width_multiple=rungs.width_multiple)
            s = make_packed_serve_step(
                params, cfg, pb.n_slots, granularity=gran,
                fused_layer=args.fused_layer,
                fused_network=args.fused_network,
                vmem_budget=args.vmem_budget)
            traces.append((
                f"gcn-stream/rung{r.stripe_cap}x{r.width_cap}/{gran}",
                _trace(s, *packed_step_args(pb))))
        return traces, extra

    if step == "gcn-forward":
        from repro.core.abft import summarize
        from repro.engine import Graph, gcn_forward

        dims = [args.feat, args.hidden, args.classes]
        cfg = ABFTConfig(mode=args.mode)
        params = _gcn_params(dims)
        g = _synth_graphs(1, args.nodes, args.feat)[0]
        s, h0 = jnp.asarray(g[0]), jnp.asarray(g[1])
        if args.backend == "bcoo":
            from jax.experimental import sparse as jsparse
            s = jsparse.BCOO.fromdense(s)

        def fwd(h0):
            logits, checks = gcn_forward(params, Graph(s=s, h0=h0), cfg,
                                         backend=args.backend)
            rep = summarize(checks, cfg)
            return logits, rep.flag, rep.max_rel

        return [(f"gcn-forward/{args.backend}", _trace(jax.jit(fwd), h0))], []

    if step == "gcn-train":
        from repro.core.abft import ABFTConfig
        from repro.engine import Graph, gcn_loss

        dims = [args.feat, args.hidden, args.classes]
        cfg = ABFTConfig(mode=args.mode)
        params = _gcn_params(dims)
        g = _synth_graphs(1, args.nodes, args.feat)[0]
        s, h0 = jnp.asarray(g[0]), jnp.asarray(g[1])
        import numpy as np
        labels = jnp.asarray(
            np.arange(args.nodes) % args.classes, jnp.int32)

        def train(params, h0):
            (loss, rep), grads = jax.value_and_grad(
                lambda p: gcn_loss(p, Graph(s, h0), labels, None, cfg),
                has_aux=True)(params)
            new = jax.tree.map(lambda p, g: p - 1e-2 * g, params, grads)
            return loss, rep.flag, new

        return [("gcn-train", _trace(jax.jit(train), params, h0))], []

    if step in ("lm-prefill", "lm-decode"):
        import numpy as np

        from repro.configs import get_config, smoke_config
        from repro.engine.lm import (
            fold_lm_w_r,
            make_guarded_decode_step,
            make_guarded_prefill_step,
        )
        from repro.models.transformer import init_model

        cfg = smoke_config(get_config(args.arch))
        abft = ABFTConfig(mode=args.mode)
        params = fold_lm_w_r(init_model(cfg, jax.random.PRNGKey(0)),
                             cfg, abft)
        rng = np.random.default_rng(0)
        prompt, cache_len = 8, 16
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(2, prompt)), jnp.int32)}
        if cfg.family == "encdec":
            batch["src_embeds"] = jnp.asarray(
                rng.normal(size=(2, prompt, cfg.d_model)), jnp.float32)
        # trace the string-free jitted cores (.traceable): the host-side
        # wrappers attach the static op-id tuple, which is not a JAX type
        prefill = make_guarded_prefill_step(cfg, abft, cache_len).traceable
        inj = jnp.float32(0.0)
        if step == "lm-prefill":
            return [(f"lm-prefill/{cfg.name}",
                     _trace(prefill, params, batch, inj))], []
        (_logits, states), _m = jax.eval_shape(prefill, params, batch, inj)
        states = jax.tree.map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype), states)
        tok = jnp.zeros((2, 1), jnp.int32)
        pos = jnp.asarray(prompt, jnp.int32)
        fn = make_guarded_decode_step(cfg, abft).traceable
        return [(f"lm-decode/{cfg.name}",
                 _trace(fn, params, states, tok, pos, inj))], []

    if step == "gat-serve":
        from repro.engine.gat import (
            fold_gat_w_r,
            init_gat,
            make_gat_serve_step,
        )

        cfg = ABFTConfig(mode=args.mode)
        dims = (args.feat, args.hidden, args.hidden, args.classes)
        params = fold_gat_w_r(init_gat(jax.random.PRNGKey(0), dims), cfg)
        g = _synth_graphs(1, args.nodes, args.feat)[0]
        adj, h0 = jnp.asarray(g[0]), jnp.asarray(g[1])
        fn = make_gat_serve_step(cfg).traceable
        return [("gat-serve", _trace(fn, params, h0, adj,
                                     jnp.asarray(-1, jnp.int32),
                                     jnp.float32(0.0)))], []

    raise SystemExit(2)


def _default_budget() -> int:
    from repro.analysis.vmem import FUSED_VMEM_BUDGET
    return FUSED_VMEM_BUDGET


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="abftlint: static ABFT coverage / VMEM / sync analysis")
    ap.add_argument("--step", choices=STEPS, default="gcn-serve")
    ap.add_argument("--granularity", default="graph",
                    choices=["layer", "graph", "stripe", "slot"])
    ap.add_argument("--backend", default="dense",
                    choices=["dense", "bcoo", "block_ell"],
                    help="gcn-forward engine backend")
    ap.add_argument("--mode", default=None,
                    choices=["none", "split", "fused"],
                    help="ABFT mode for the traced step; default fused "
                         "everywhere (lm-* now trace the guarded engine "
                         "steps; --mode none recovers the historical "
                         "unguarded baseline manifest)")
    ap.add_argument("--arch", default="gemma-2b",
                    help="lm-* architecture (smoke-sized)")
    ap.add_argument("--fused-layer", action="store_true")
    ap.add_argument("--fused-network", action="store_true")
    ap.add_argument("--graphs", type=int, default=3)
    ap.add_argument("--nodes", type=int, default=24)
    ap.add_argument("--block", type=int, default=8)
    ap.add_argument("--feat", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=8)
    ap.add_argument("--classes", type=int, default=3)
    ap.add_argument("--vmem-budget", type=int, default=None)
    ap.add_argument("--passes", default="coverage,vmem,syncs",
                    help="comma list of: coverage,vmem,syncs")
    ap.add_argument("--manifest", type=Path, default=None,
                    help="write the coverage manifest(s) as JSON")
    ap.add_argument("--expect-unchecked", action="store_true",
                    help="invert the coverage gate: succeed when unchecked "
                         "matmuls exist (the historical lm-* --mode none "
                         "baseline manifest; the guarded lanes gate on "
                         "zero unchecked)")
    ap.add_argument("--verbose", "-v", action="store_true")
    args = ap.parse_args(argv)
    if args.mode is None:
        args.mode = "fused"

    passes = [p.strip() for p in args.passes.split(",") if p.strip()]
    bad = [p for p in passes if p not in PASSES]
    if bad:
        print(f"abftlint: unknown pass(es) {bad}; choose from {PASSES}",
              file=sys.stderr)
        return 2
    if args.backend == "block_ell" and args.step == "gcn-forward":
        print("abftlint: --backend block_ell is exercised via --step "
              "gcn-serve (the packed path); gcn-forward takes dense|bcoo",
              file=sys.stderr)
        return 2

    failures = 0
    manifests = []

    need_trace = "coverage" in passes or "vmem" in passes
    traces, extra = _build_traces(args) if need_trace else ([], [])
    for msg in extra:
        print(f"[vmem] RUNG OVER BUDGET: {msg}")
        failures += 1

    if "coverage" in passes:
        from repro.analysis.coverage import analyze_jaxpr, format_report
        for name, closed in traces:
            m = analyze_jaxpr(closed, step=name)
            manifests.append(m)
            print(format_report(m, verbose=args.verbose))
            if args.expect_unchecked:
                if m.n_unchecked == 0:
                    print(f"[coverage] {name}: expected unchecked matmuls "
                          f"but found none — remove --expect-unchecked "
                          f"(this path is now fully covered)")
                    failures += 1
            elif m.n_unchecked:
                failures += 1

    if "vmem" in passes:
        from repro.analysis.vmem import jaxpr_vmem_report
        budget = args.vmem_budget or _default_budget()
        for name, closed in traces:
            for est in jaxpr_vmem_report(closed, budget=budget):
                status = "ok" if est.fits else "OVER BUDGET"
                print(f"[vmem] {name}: {est.name} grid={est.grid} "
                      f"blocks={est.block_bytes}B scratch="
                      f"{est.scratch_bytes}B total={est.total_bytes}B "
                      f"/ {est.budget}B {status}")
                if not est.fits:
                    failures += 1

    if "syncs" in passes:
        from repro.analysis.syncs import scan_tree
        root = Path(__file__).resolve().parents[3]
        findings = scan_tree(root)
        for f in findings:
            try:
                print(f"[syncs] {Path(f.path).relative_to(root)}:{f.line}:"
                      f"{f.col}: [{f.rule}] {f.message}")
            except ValueError:
                print(f"[syncs] {f}")
        print(f"[syncs] {len(findings)} finding(s) over engine/ + launch/")
        failures += len(findings)

    if args.manifest is not None:
        payload = [m.to_dict() for m in manifests]
        args.manifest.write_text(json.dumps(
            payload[0] if len(payload) == 1 else payload, indent=2) + "\n")
        print(f"[coverage] manifest -> {args.manifest}")

    if failures:
        print(f"abftlint: {failures} failure(s)")
        return 1
    print("abftlint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
