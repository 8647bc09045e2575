"""abftlint (ISSUE 8 tentpole): the static-analysis subsystem's own tests.

Acceptance properties:
  (a) falsifiability — a fixture with a deliberately unchecked
      ``dot_general`` is flagged with this file's provenance, and
      injecting an unchecked matmul into the (clean) GCN forward flips
      its manifest from 0 unchecked to non-zero;
  (b) the GCN fused-network serve step verifies 100% coverage at slot
      granularity;
  (c) golden manifest parity across dense | bcoo | block_ell backends
      (every backend fully covered, same sink structure dense vs bcoo);
  (d) the marker primitive is inert: tagging changes no numerics and is
      OFF by default, so production traces carry zero sinks;
  (e) the static VMEM checker and the runtime fused_* fallback
      predicates are the SAME objects (shared-model identity), and an
      over-budget RungTable is rejected by ``assert_rung_table_fits``
      at lint time, before anything compiles;
  (f) every syncs-lint rule fires on a minimal fixture, suppression
      comments silence them, and the repo's own engine/ + launch/ trees
      sweep clean;
  (g) CLI smoke: ``--step gcn-serve --granularity slot`` exits 0 with a
      valid manifest; the unguarded LM-style trace exits non-zero.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.coverage import analyze_jaxpr, analyze_step, iter_eqns
from repro.analysis.syncs import scan_source, scan_tree
from repro.analysis.vmem import (
    FUSED_VMEM_BUDGET,
    assert_rung_table_fits,
    jaxpr_vmem_report,
    lint_rung_table,
)
from repro.core.abft import (ABFTConfig, check_matmul, report_traces,
                             summarize)
from repro.core.gcn import init_gcn
from repro.core.marker import CHECK_SINK, check_tagging, tagging_enabled
from repro.engine import Graph, gcn_forward
from repro.engine.api import fold_w_r
from repro.engine.batching import pack_graphs
from repro.engine.streaming import (
    Rung,
    RungTable,
    make_packed_serve_step,
    packed_step_args,
)

CFG = ABFTConfig(mode="fused")
REPO = Path(__file__).resolve().parents[1]


def _graph(nodes=12, feat=6, seed=0):
    rng = np.random.default_rng(seed)
    s = (rng.random((nodes, nodes)) < 0.4).astype(np.float32)
    s += np.eye(nodes, dtype=np.float32)
    h0 = rng.random((nodes, feat)).astype(np.float32)
    return s, h0


def _params(dims, seed=0):
    return init_gcn(jax.random.PRNGKey(seed), dims)


# ---------------------------------------------------------------------------
# (a) falsifiability
# ---------------------------------------------------------------------------

class TestFalsifiability:
    def test_unchecked_dot_general_is_flagged_with_provenance(self):
        w1 = jnp.ones((6, 5))
        w2 = jnp.ones((5, 4))

        def fixture(x):
            y1 = x @ w1
            c = check_matmul(x, w1, y1, CFG)      # checked product
            y2 = y1 @ w2                          # deliberately unchecked
            rep = summarize([c], CFG)
            return y2, rep.flag

        m = analyze_step(fixture, jnp.ones((3, 6)), step="fixture")
        assert m.n_sinks >= 1
        assert m.n_unchecked == 1
        assert m.n_checked >= 1
        site = m.unchecked_ops[0]
        assert site.kind == "dot_general"
        # provenance points at THIS file's y2 line
        assert "test_abftlint.py" in site.provenance

    def test_fully_checked_fixture_is_clean(self):
        w = jnp.ones((6, 5))

        def fixture(x):
            y = x @ w
            rep = summarize([check_matmul(x, w, y, CFG)], CFG)
            return y, rep.flag

        m = analyze_step(fixture, jnp.ones((3, 6)))
        assert m.n_unchecked == 0 and m.n_checked >= 1
        assert m.coverage == 1.0

    def test_injected_unchecked_matmul_flips_gcn_manifest(self):
        dims = [6, 8, 3]
        params = _params(dims)
        s, h0 = _graph(feat=dims[0])
        s, h0 = jnp.asarray(s), jnp.asarray(h0)
        w_x = jnp.ones((dims[-1], 7))

        def clean(h0):
            logits, checks = gcn_forward(params, Graph(s=s, h0=h0), CFG)
            rep = summarize(checks, CFG)
            return logits, rep.flag

        def injected(h0):
            logits, flag = clean(h0)
            return logits @ w_x, flag             # unchecked extra product

        m0 = analyze_step(clean, h0, step="gcn-clean")
        m1 = analyze_step(injected, h0, step="gcn-injected")
        assert m0.n_unchecked == 0 and m0.n_checked >= 4
        assert m1.n_unchecked == 1                # the verifier is falsifiable
        assert m1.n_checked == m0.n_checked

    def test_detection_survives_jit(self):
        w1, w2 = jnp.ones((6, 5)), jnp.ones((5, 4))

        def fixture(x):
            y1 = x @ w1
            rep = summarize([check_matmul(x, w1, y1, CFG)], CFG)
            return y1 @ w2, rep.flag

        m = analyze_step(jax.jit(fixture), jnp.ones((3, 6)))
        assert m.n_unchecked == 1
        assert "jit" in m.unchecked_ops[0].path


# ---------------------------------------------------------------------------
# (b) GCN fused-network slot coverage; (c) backend manifest parity
# ---------------------------------------------------------------------------

def _packed_manifest(granularity, *, fused_layer=False, fused_network=False,
                     dims=(8, 8, 3), n_graphs=3, nodes=16, block=8):
    params = fold_w_r(_params(list(dims)), CFG)
    graphs = [_graph(nodes, dims[0], seed=i) for i in range(n_graphs)]
    pb = pack_graphs(graphs, block=block, n_slots=n_graphs)
    step = make_packed_serve_step(params, CFG, pb.n_slots,
                                  granularity=granularity,
                                  fused_layer=fused_layer,
                                  fused_network=fused_network)
    with check_tagging():
        closed = jax.make_jaxpr(step)(*packed_step_args(pb))
    return analyze_jaxpr(closed, step=f"packed/{granularity}"), closed


class TestGCNCoverage:
    def test_fused_network_full_slot_coverage(self):
        m, _ = _packed_manifest("slot", fused_network=True)
        assert m.n_unchecked == 0
        assert m.n_checked >= 1
        assert m.coverage == 1.0
        assert "slot" in m.sink_granularities
        # the fused-network pallas kernel itself is a checked matmul site
        assert any(s.kind == "pallas_call" for s in m.checked_ops)

    @pytest.mark.parametrize("granularity", ["graph", "stripe", "slot"])
    def test_packed_serve_clean_at_every_granularity(self, granularity):
        m, _ = _packed_manifest(granularity)
        assert m.n_unchecked == 0
        # the two-pass path derives slot verdicts from stripe-granularity
        # check corners, so the traced sinks report stripe for slot too
        want = "stripe" if granularity == "slot" else granularity
        assert want in m.sink_granularities

    def test_manifest_parity_across_backends(self):
        dims = [6, 8, 3]
        params = _params(dims)
        s_np, h0_np = _graph(feat=dims[0])
        manifests = {}
        for backend in ("dense", "bcoo"):
            s = jnp.asarray(s_np)
            if backend == "bcoo":
                from jax.experimental import sparse as jsparse
                s = jsparse.BCOO.fromdense(s)

            def fwd(h0, s=s, backend=backend):
                logits, checks = gcn_forward(params, Graph(s=s, h0=h0), CFG,
                                             backend=backend)
                rep = summarize(checks, CFG)
                return logits, rep.flag, rep.max_rel

            manifests[backend] = analyze_step(fwd, jnp.asarray(h0_np),
                                              step=backend)
        m_ell, _ = _packed_manifest("graph")
        manifests["block_ell"] = m_ell

        # golden parity: every backend fully covered...
        for backend, m in manifests.items():
            assert m.n_unchecked == 0, (backend, m.to_dict())
            assert m.coverage == 1.0
        # ...and the dense/bcoo engines share one check structure (site
        # counts differ: dense aggregation is itself a dot_general, the
        # BCOO spmm is not)
        assert manifests["dense"].n_sinks == manifests["bcoo"].n_sinks
        assert manifests["dense"].sink_granularities == \
            manifests["bcoo"].sink_granularities

    def test_unguarded_trace_reports_everything_unchecked(self):
        # mode=none -> no sinks -> every matmul listed (the LM-lane shape)
        off = ABFTConfig(mode="none")
        params = _params([6, 8, 3])
        s, h0 = map(jnp.asarray, _graph(feat=6))

        def fwd(h0):
            logits, checks = gcn_forward(params, Graph(s=s, h0=h0), off)
            return logits

        m = analyze_step(fwd, h0)
        assert m.n_sinks == 0
        assert m.n_checked == 0
        assert m.n_unchecked >= 4
        assert all(s.provenance for s in m.unchecked_ops)


# ---------------------------------------------------------------------------
# (d) marker inertness
# ---------------------------------------------------------------------------

class TestMarkerInertness:
    def test_tagging_off_by_default(self):
        assert not tagging_enabled()
        w = jnp.ones((6, 5))

        def fixture(x):
            y = x @ w
            rep = summarize([check_matmul(x, w, y, CFG)], CFG)
            return y, rep.flag

        closed = jax.make_jaxpr(fixture)(jnp.ones((3, 6)))
        m = analyze_jaxpr(closed)
        assert m.n_sinks == 0  # production traces carry no marker

    def test_sink_survives_an_untagged_warm_call(self):
        # summarize runs as a compiled body with a trace cache; a lint
        # trace after an untagged call on the same check structure must
        # still see the check-sink (its own config, so the warm call below
        # is this structure's first trace in the process)
        cfg = ABFTConfig(mode="fused", threshold=3.25e-3)
        w = jnp.ones((6, 5))

        def fixture(x):
            y = x @ w
            rep = summarize([check_matmul(x, w, y, cfg)], cfg)
            return y, rep.flag, rep.max_rel

        x = jnp.ones((3, 6))
        before = report_traces()
        fixture(x)                                  # eager, untagged
        assert report_traces() == before + 1

        with check_tagging():
            closed = jax.make_jaxpr(fixture)(x)
        sinks = [path for eqn, path in iter_eqns(closed)
                 if eqn.primitive.name == CHECK_SINK]
        assert sinks == ["/jit"]
        m = analyze_jaxpr(closed)
        assert (m.n_sinks, m.n_unchecked) == (1, 0)
        assert m.n_checked >= 1
        assert m.coverage == 1.0
        assert m.sink_granularities == ("layer",)
        # ...and the tagged trace does not leak into production traces
        # (a fresh wrapper: make_jaxpr caches its own trace of ``fixture``)
        untagged = jax.make_jaxpr(lambda x: fixture(x))(x)
        assert analyze_jaxpr(untagged).n_sinks == 0

    def test_tagging_changes_no_numerics(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.random((4, 6)), jnp.float32)
        w = jnp.asarray(rng.random((6, 5)), jnp.float32)

        def fixture(x):
            y = x @ w
            rep = summarize([check_matmul(x, w, y, CFG)], CFG)
            return y, rep.max_rel

        y0, r0 = fixture(x)
        with check_tagging():
            y1, r1 = jax.jit(fixture)(x)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
        np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))

    def test_tagging_transparent_to_grad(self):
        w = jnp.ones((6, 5))

        def loss(x):
            y = x @ w
            rep = summarize([check_matmul(x, w, y, CFG)], CFG)
            return y.sum() + 0.0 * rep.max_rel

        x = jnp.ones((3, 6))
        g0 = jax.grad(loss)(x)
        with check_tagging():
            g1 = jax.grad(loss)(x)
        np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))


# ---------------------------------------------------------------------------
# (e) VMEM: shared identity + lint-time rung rejection + static estimates
# ---------------------------------------------------------------------------

class TestVmem:
    def test_runtime_and_static_checker_are_the_same_objects(self):
        from repro.analysis import vmem
        from repro.kernels.gcn_fused import ops as fused_ops
        assert fused_ops.fused_layer_fits is vmem.fused_layer_fits
        assert fused_ops.fused_network_fits is vmem.fused_network_fits
        assert fused_ops.fused_vmem_bytes is vmem.fused_vmem_bytes
        assert fused_ops.network_vmem_bytes is vmem.network_vmem_bytes
        assert fused_ops.FUSED_VMEM_BUDGET is vmem.FUSED_VMEM_BUDGET

    def test_over_budget_rung_table_rejected_before_compile(self):
        table = RungTable(rungs=(Rung(4, 4, 2), Rung(64, 64, 4)),
                          block=8, stripe_multiple=4, width_multiple=4)
        dims = [128, 256, 64]
        # a tiny budget must reject, naming the rung, without compiling
        with pytest.raises(ValueError, match="rung"):
            assert_rung_table_fits(table, dims, block=8, budget=4096)
        # the real budget admits this menu; verdicts carry both tiers
        verdicts = assert_rung_table_fits(table, dims, block=8,
                                          budget=FUSED_VMEM_BUDGET)
        assert len(verdicts) == 2
        assert all(v.fits and v.layer_fits for v in verdicts)

    def test_lint_rung_table_network_tier(self):
        table = RungTable(rungs=(Rung(2, 4, 2),), block=8,
                          stripe_multiple=4, width_multiple=4)
        v, = lint_rung_table(table, [8, 8, 3], block=8,
                             budget=FUSED_VMEM_BUDGET, fused_network=True)
        assert v.network_bytes is not None and v.network_fits
        assert v.rows == 2 * 8

    def test_static_pallas_estimates_from_trace(self):
        m, closed = _packed_manifest("slot", fused_network=True)
        ests = jaxpr_vmem_report(closed, budget=FUSED_VMEM_BUDGET)
        assert len(ests) >= 1
        for e in ests:
            assert e.total_bytes > 0
            assert e.fits


# ---------------------------------------------------------------------------
# (f) syncs lint rules
# ---------------------------------------------------------------------------

SYNC_SNIPPETS = {
    "implicit-sync-in-loop": "for r in batch:\n    x = float(vals[r])\n",
    "backend-query-in-loop":
        "import jax\nwhile run:\n    b = jax.default_backend()\n",
    "jit-in-loop": "import jax\nfor s in steps:\n    f = jax.jit(step)\n",
    "pack-without-caps": "pb = pack_graphs(graphs, block=8)\n",
    "mutable-default": "def f(x, acc=[]):\n    return acc\n",
    "fold-in-loop": "for s in steps:\n    p = fold_w_r(params, cfg)\n",
}


class TestSyncsLint:
    @pytest.mark.parametrize("rule", sorted(SYNC_SNIPPETS))
    def test_rule_fires(self, rule):
        findings = scan_source(SYNC_SNIPPETS[rule], path=f"<{rule}>")
        assert any(f.rule == rule for f in findings), findings

    @pytest.mark.parametrize("tag", ["ok", "sync-ok",
                                     "implicit-sync-in-loop-ok"])
    def test_suppression(self, tag):
        src = ("for r in batch:\n"
               f"    x = float(vals[r])  # abftlint: {tag}\n")
        assert scan_source(src) == []

    def test_suppression_is_rule_scoped(self):
        # a fold-in-loop tag must NOT silence a sync finding
        src = ("for r in batch:\n"
               "    x = float(vals[r])  # abftlint: fold-ok\n")
        assert [f.rule for f in scan_source(src)] == \
            ["implicit-sync-in-loop"]

    def test_sync_methods_and_numpy_copies(self):
        src = ("import numpy as np\n"
               "for r in batch:\n"
               "    a = out.block_until_ready()\n"
               "    b = np.asarray(out)\n"
               "    c = vals.item()\n")
        rules = [f.rule for f in scan_source(src)]
        assert rules == ["implicit-sync-in-loop"] * 3

    def test_constants_and_top_level_calls_are_fine(self):
        src = ("x = float(vals[0])\n"            # not in a loop
               "for r in batch:\n"
               "    y = int(8)\n")               # constant operand
        assert scan_source(src) == []

    def test_repo_dispatch_layers_sweep_clean(self):
        findings = scan_tree(REPO)
        assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# (g) CLI
# ---------------------------------------------------------------------------

class TestCLI:
    def test_gcn_serve_slot_exits_zero_with_manifest(self, tmp_path, capsys):
        from repro.analysis.lint import main
        manifest = tmp_path / "gcn-serve.json"
        rc = main(["--step", "gcn-serve", "--granularity", "slot",
                   "--graphs", "2", "--nodes", "12",
                   "--manifest", str(manifest)])
        out = capsys.readouterr().out
        assert rc == 0, out
        payload = json.loads(manifest.read_text())
        assert payload["n_unchecked"] == 0
        assert payload["n_checked"] >= 1
        assert payload["sink_granularities"]
        assert "abftlint: clean" in out

    def test_unguarded_step_exits_nonzero_with_provenance(self, capsys):
        # --mode none is the LM-lane shape: no sinks, every matmul listed
        from repro.analysis.lint import main
        rc = main(["--step", "gcn-serve", "--mode", "none",
                   "--graphs", "2", "--nodes", "12", "--passes", "coverage"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "UNCHECKED" in out and ".py:" in out

    def test_expect_unchecked_inverts_the_gate(self, capsys):
        from repro.analysis.lint import main
        rc = main(["--step", "gcn-serve", "--mode", "none",
                   "--graphs", "2", "--nodes", "12",
                   "--passes", "coverage", "--expect-unchecked"])
        assert rc == 0
        rc = main(["--step", "gcn-serve", "--granularity", "slot",
                   "--graphs", "2", "--nodes", "12",
                   "--passes", "coverage", "--expect-unchecked"])
        assert rc == 1  # fully covered -> the inverted gate must fail

    def test_gcn_stream_rung_lint_runs_before_traces(self, capsys):
        from repro.analysis.lint import main
        rc = main(["--step", "gcn-stream", "--granularity", "stripe",
                   "--passes", "coverage,vmem"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "rung" in out.lower()

    def test_bad_pass_is_usage_error(self):
        from repro.analysis.lint import main
        assert main(["--passes", "nope"]) == 2
