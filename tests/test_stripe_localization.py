"""Stripe-granular fault localization + surgical retry (ISSUE 5 tentpole)
and the guard/fold correctness fixes that ride along.

Acceptance properties:
  (a) granularity plumbing: stripe corners sum (per graph / in total) to
      exactly the coarser corners, clean streams never flag at any
      granularity, and unsupported (backend, granularity) pairs raise;
  (b) fault-injection sweep: a single accumulator fault injected at every
      (layer, stripe, slot) of a packed batch flags exactly ONE stripe of
      exactly ONE graph, and the surgical retry's spliced output matches a
      clean run bit-for-bit;
  (c) guard escalation ladder: the stripe tier runs first and its repair
      is adopted; an unverifiable repair escalates to the per-graph tier
      and then to restore->replay; retry/rows accounting is exact;
  (d) satellite fixes: a folded w_r whose dtype no longer matches
      cfg.dtype raises (no silent stale-precision checks); a retry_fn
      returning full-batch-aligned vectors raises instead of being
      misattributed; guard.retries counts re-executions performed in BOTH
      run_step and run_step_graphs;
  (e) serve_gcn --check-granularity stripe serves with per-graph verdicts
      identical to graph granularity, and the sharded stripe path
      concatenates per-shard corners into the single-device vector.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abft import (
    ABFTConfig,
    Check,
    per_graph_report,
    per_stripe_report,
)
from repro.core.gcn import init_gcn
from repro.engine import (
    Graph,
    fold_w_r,
    gcn_forward,
    make_backend,
    pack_graphs,
    synth_graph_stream,
)
from repro.engine.localize import surgical_stripe_retry
from repro.engine.streaming import make_packed_serve_step, packed_step_args
from repro.runtime import ABFTGuard, GuardConfig


def _stream(n_graphs=3, seed=1, feat=8, n_lo=32, n_hi=64):
    return synth_graph_stream(n_graphs, n_lo=n_lo, n_hi=n_hi, feat=feat,
                              seed=seed)


def _cfg(**kw):
    return ABFTConfig(mode="fused", threshold=1e-3, relative=True, **kw)


# ---------------------------------------------------------------------------
# (a) granularity plumbing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused_layer", [False, True])
def test_stripe_corners_sum_to_graph_corners(fused_layer):
    stream = _stream(3)
    pb = pack_graphs(stream, block=16, stripe_multiple=4)
    params = init_gcn(jax.random.PRNGKey(0), (8, 8, 3))
    cfg = _cfg()
    g = Graph(s=pb, h0=jnp.asarray(pb.h0))

    bk_s = make_backend(pb, cfg, granularity="stripe",
                        fused_layer=fused_layer)
    logits_s, checks_s = gcn_forward(params, g, cfg, backend=bk_s)
    bk_g = make_backend(pb, cfg, fused_layer=fused_layer)
    logits_g, checks_g = gcn_forward(params, g, cfg, backend=bk_g)

    np.testing.assert_array_equal(np.asarray(logits_s), np.asarray(logits_g))
    nbm = pb.bell.n_block_rows
    seg = np.asarray(pb.stripe_graph)
    for c_s, c_g in zip(checks_s, checks_g):
        assert c_s.granularity == "stripe"
        assert c_g.granularity == "graph"
        assert c_s.actual.shape == (nbm,)
        for field in ("predicted", "actual"):
            per_graph = np.zeros(pb.n_slots + 1, np.float64)
            np.add.at(per_graph, seg, np.asarray(getattr(c_s, field),
                                                 np.float64))
            np.testing.assert_allclose(per_graph[:pb.n_slots],
                                       np.asarray(getattr(c_g, field)),
                                       rtol=1e-5, atol=1e-5)
    # clean stream: no stripe flags, and the segment-reduced per-graph
    # verdicts agree with the native graph-granularity report
    sflags, _ = per_stripe_report(checks_s, cfg, nbm)
    assert not bool(np.asarray(sflags).any())
    gf_s, _ = per_graph_report(checks_s, cfg, pb.n_slots,
                               segments=jnp.asarray(pb.stripe_graph))
    gf_g, _ = per_graph_report(checks_g, cfg, pb.n_slots)
    np.testing.assert_array_equal(np.asarray(gf_s), np.asarray(gf_g))


def test_split_mode_emits_stripe_corners_for_both_checks():
    stream = _stream(2, seed=3)
    pb = pack_graphs(stream, block=16)
    params = init_gcn(jax.random.PRNGKey(3), (8, 8, 3))
    cfg = ABFTConfig(mode="split", threshold=1e-3, relative=True)
    bk = make_backend(pb, cfg, granularity="stripe")
    _, checks = gcn_forward(params, Graph(s=pb, h0=jnp.asarray(pb.h0)),
                            cfg, backend=bk)
    assert len(checks) == 4                       # 2 layers x 2 checks
    nbm = pb.bell.n_block_rows
    assert all(c.actual.shape == (nbm,) for c in checks)
    sflags, _ = per_stripe_report(checks, cfg, nbm)
    assert sflags.shape == (4, nbm)
    assert not bool(np.asarray(sflags).any())


def test_unsupported_granularities_raise():
    stream = _stream(1)
    s, h0 = stream[0]
    cfg = _cfg()
    with pytest.raises(ValueError, match="block_ell kernel"):
        make_backend(jnp.asarray(s), cfg, backend="dense",
                     granularity="stripe")
    pb = pack_graphs(stream, block=16)
    with pytest.raises(ValueError, match="granularity"):
        make_backend(pb, cfg, granularity="layer")  # packed: graph|stripe
    with pytest.raises(ValueError, match="not in"):
        make_backend(pb, cfg, granularity="bogus")
    scalar = Check(predicted=jnp.float32(1.0), actual=jnp.float32(1.0))
    with pytest.raises(ValueError, match="stripe-granular"):
        per_stripe_report([scalar], cfg, 4)


def test_inject_validates_tuple_shape():
    # the hook now exists on BOTH kernels (fused and two-pass), so inject
    # no longer requires fused_layer — but a malformed tuple still raises
    pb = pack_graphs(_stream(1), block=16)
    with pytest.raises(ValueError, match="layer, stripe, slot, delta"):
        make_backend(pb, _cfg(), granularity="stripe", inject=(0, 0, 1.0))


def test_inject_fires_on_two_pass_path():
    """The accumulator hook on the two-pass spmm kernel: a fused_layer=False
    step must detect the injected fault at the right (layer, stripe) —
    VMEM-fallback layers stay injectable."""
    pb = pack_graphs(_stream(2, seed=11), block=16)
    cfg = _cfg()
    params = fold_w_r(init_gcn(jax.random.PRNGKey(11), (8, 8, 3)), cfg)
    step = make_packed_serve_step(params, cfg, pb.n_slots, block_g=16,
                                  granularity="stripe",
                                  inject=(1, 0, 0, 64.0))
    _, m = step(*packed_step_args(pb))
    sf = np.asarray(m["abft_stripe_flags"])
    assert sf.sum() == 1 and sf[1, 0], np.argwhere(sf).tolist()


def test_per_graph_report_dispatches_on_granularity_not_shape():
    """A batch whose stripe count equals its slot count must NOT read
    stripe corners as per-graph verdicts: the fault would be attributed to
    the wrong graph and the corrupted one adopted as verified."""
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=False)
    # 4 stripes, 4 slots; stripe 1 belongs to graph 0 (graphs own 2,1,1)
    seg = jnp.asarray(np.array([0, 0, 1, 2], np.int32))
    stripe_chk = Check(predicted=jnp.asarray([0.0, 9.0, 0.0, 0.0]),
                       actual=jnp.zeros(4), granularity="stripe")
    flags, _ = per_graph_report([stripe_chk], cfg, 4, segments=seg)
    np.testing.assert_array_equal(np.asarray(flags),
                                  [True, False, False, False])
    # without the segments map a stripe check is unattributable — raise,
    # never shape-match it into the per-graph branch
    with pytest.raises(ValueError, match="per-graph"):
        per_graph_report([stripe_chk], cfg, 4)


# ---------------------------------------------------------------------------
# (b) the fault sweep: exact localization + bit-for-bit surgical repair
# ---------------------------------------------------------------------------

def test_fault_sweep_localizes_and_repairs_bit_for_bit():
    """Inject a single accumulator fault at EVERY (layer, stripe, slot) of
    a packed batch: exactly one stripe of exactly one graph flags, and the
    surgical retry's spliced output equals a clean run bit-for-bit."""
    stream = _stream(2, seed=5, n_lo=20, n_hi=40)
    pb = pack_graphs(stream, block=16)
    cfg = _cfg()
    params = fold_w_r(init_gcn(jax.random.PRNGKey(5), (8, 8, 3)), cfg)
    args = packed_step_args(pb)

    clean_step = make_packed_serve_step(params, cfg, pb.n_slots,
                                        block_g=16, fused_layer=True,
                                        granularity="stripe")
    logits_clean, m_clean = clean_step(*args)
    assert not bool(np.asarray(m_clean["abft_graph_flags"]).any())
    logits_clean = np.asarray(logits_clean)

    nbm, width = pb.bell.n_block_rows, pb.bell.width
    stripe_graph = np.asarray(pb.stripe_graph)
    n_layers = len(params["layers"])
    real = [s for s in range(nbm) if stripe_graph[s] < pb.n_slots]
    assert len(real) >= 3 and width >= 2
    last_layer_rows = []
    for layer in range(n_layers):
        for stripe in real:
            for slot in range(width):
                step = make_packed_serve_step(
                    params, cfg, pb.n_slots, block_g=16, fused_layer=True,
                    granularity="stripe",
                    inject=(layer, stripe, slot, 64.0))
                out_bad, m_bad = step(*args)
                sf = np.asarray(m_bad["abft_stripe_flags"])
                gf = np.asarray(m_bad["abft_graph_flags"])
                # exactly one stripe of exactly one graph flags, at the
                # injected (layer, stripe) — downstream layers see the
                # corruption CONSISTENTLY (their x_r is computed from the
                # same corrupted H), so their corners stay clean
                assert sf.sum() == 1 and sf[layer, stripe], \
                    (layer, stripe, slot, np.argwhere(sf).tolist())
                victim = int(stripe_graph[stripe])
                assert gf.sum() == 1 and gf[victim]
                repaired, sub = surgical_stripe_retry(
                    pb, params, cfg, out_bad, m_bad, block_g=16)
                assert not sub["abft_graph_flags"].any()
                assert np.array_equal(repaired, logits_clean), \
                    (layer, stripe, slot)
                assert sub["abft_rows_recomputed"] >= pb.block
                if layer == n_layers - 1:
                    last_layer_rows.append(sub["abft_rows_recomputed"])
    # a final-layer fault needs exactly one stripe re-executed
    assert all(r == pb.block for r in last_layer_rows)


def test_surgical_rows_strictly_below_graph_retry():
    """Every injection must cost the surgical tier strictly fewer
    re-executed rows than re-running the owning graph at every layer."""
    stream = _stream(2, seed=7, n_lo=36, n_hi=60)   # >= 2 stripes per graph
    pb = pack_graphs(stream, block=16)
    cfg = _cfg()
    params = fold_w_r(init_gcn(jax.random.PRNGKey(7), (8, 8, 3)), cfg)
    args = packed_step_args(pb)
    stripe_graph = np.asarray(pb.stripe_graph)
    n_layers = len(params["layers"])
    for layer in range(n_layers):
        for stripe in (0, int(np.argwhere(stripe_graph == 1)[0, 0])):
            step = make_packed_serve_step(
                params, cfg, pb.n_slots, block_g=16, fused_layer=True,
                granularity="stripe", inject=(layer, stripe, 0, 64.0))
            out_bad, m_bad = step(*args)
            _, sub = surgical_stripe_retry(pb, params, cfg, out_bad, m_bad,
                                           block_g=16)
            victim = int(stripe_graph[stripe])
            graph_rows = int((stripe_graph == victim).sum()) * pb.block \
                * n_layers
            assert 0 < sub["abft_rows_recomputed"] < graph_rows, \
                (layer, stripe, sub["abft_rows_recomputed"], graph_rows)


# ---------------------------------------------------------------------------
# (c) guard escalation ladder
# ---------------------------------------------------------------------------

def _metrics(flag, gflags=None, sflags=None):
    m = {"abft_flag": flag, "abft_max_rel": 1.0 if flag else 0.0}
    if gflags is not None:
        m["abft_graph_flags"] = np.asarray(gflags, bool)
        m["abft_graph_max_rel"] = np.where(m["abft_graph_flags"], 1.0,
                                           0.0).astype(np.float32)
    if sflags is not None:
        m["abft_stripe_flags"] = np.asarray(sflags, bool)
    return m


def test_guard_stripe_tier_runs_first_and_adopts():
    calls = []

    def step():
        return np.zeros(3), _metrics(True, [False, True, False],
                                     [[False, True, False, False]])

    def sretry(out, metrics):
        calls.append("stripe")
        out = out.copy()
        out[1] = 5.0
        return out, {"abft_graph_flags": np.zeros(3, bool),
                     "abft_graph_max_rel": np.asarray([0, 1e-7, 0],
                                                      np.float32),
                     "abft_rows_recomputed": 16,
                     "abft_stripes_recomputed": 1}

    def retry(out, idx):
        calls.append("graph")
        return out, _metrics(False, np.zeros(len(idx), bool))

    g = ABFTGuard(GuardConfig(max_retries=2))
    out, m = g.run_step_graphs(step, retry, stripe_retry_fn=sretry)
    assert calls == ["stripe"]                     # graph tier never ran
    np.testing.assert_array_equal(out, [0.0, 5.0, 0.0])
    assert bool(m["abft_flag"]) is False
    assert not np.asarray(m["abft_stripe_flags"]).any()   # cleared on adopt
    assert "abft_stripe_max_rel" not in m   # discarded run's divergences
    assert float(m["abft_max_rel"]) < 1e-3
    assert g.retries == 1 and g.stripe_retries == 1
    assert g.recomputed_rows == 16 and g.graph_retries == 0


def test_guard_zero_work_escalation_counts_no_retry():
    """A surgical tier that bails before re-executing anything performed
    zero re-executions — guard.retries must not count the intent."""
    def step():
        return np.zeros(2), _metrics(True, [True, False], [[True, False]])

    def sretry(out, metrics):
        return out, {"abft_graph_flags": np.asarray([True, False]),
                     "abft_rows_recomputed": 0,
                     "abft_stripes_recomputed": 0}

    def retry(out, idx):
        return out, _metrics(False, np.zeros(len(idx), bool))

    g = ABFTGuard(GuardConfig(max_retries=2))
    g.run_step_graphs(step, retry, stripe_retry_fn=sretry)
    # only the graph-tier re-execution counted
    assert g.retries == 1 and g.stripe_retries == 0
    assert g.graph_retries == 1


def test_guard_stripe_tier_escalates_to_graph_then_restore():
    fault = {"on": True}
    calls = []

    def step():
        f = fault["on"]
        return np.zeros(2), _metrics(f, [f, False], [[f, False]])

    def sretry(out, metrics):
        calls.append("stripe")
        m = dict(metrics)
        return out, {"abft_graph_flags":
                     np.asarray(m["abft_graph_flags"], bool),
                     "abft_rows_recomputed": 16,
                     "abft_stripes_recomputed": 1}

    def retry(out, idx):
        calls.append("graph")
        return out, _metrics(True, [True] * len(idx))

    def restore():
        calls.append("restore")
        fault["on"] = False

    g = ABFTGuard(GuardConfig(max_retries=1), restore_fn=restore)
    out, m = g.run_step_graphs(step, retry, stripe_retry_fn=sretry)
    assert calls == ["stripe", "graph", "restore"]
    assert bool(np.asarray(m["abft_flag"]).any()) is False
    # accounting: one surgical attempt + one graph retry, both performed
    assert g.retries == 2 and g.stripe_retries == 1 and g.graph_retries == 1
    assert g.restores == 1


def test_guard_validates_retry_fn_shapes():
    def step():
        return np.zeros(4), _metrics(True, [False, True, False, True])

    def bad_retry(out, idx):
        # full-batch-aligned vector: would be misattributed if truncated
        return out, _metrics(False, np.zeros(4, bool))

    g = ABFTGuard(GuardConfig(max_retries=1))
    with pytest.raises(ValueError, match="aligned to"):
        g.run_step_graphs(step, bad_retry)

    def bad_rel_retry(out, idx):
        m = _metrics(False, np.zeros(len(idx), bool))
        m["abft_graph_max_rel"] = np.zeros(4, np.float32)     # full batch
        return out, m

    g2 = ABFTGuard(GuardConfig(max_retries=1))
    with pytest.raises(ValueError, match="abft_graph_max_rel"):
        g2.run_step_graphs(step, bad_rel_retry)

    def bad_sretry(out, metrics):
        return out, {"abft_graph_flags": np.zeros(1, bool)}   # wrong shape

    def step_s():
        return np.zeros(2), _metrics(True, [True, False], [[True, False]])

    g3 = ABFTGuard(GuardConfig(max_retries=1))
    with pytest.raises(ValueError, match="FULL batch"):
        g3.run_step_graphs(step_s, bad_retry, stripe_retry_fn=bad_sretry)


def test_guard_retries_count_reexecutions_in_both_paths():
    """satellite: guard.retries means re-executions PERFORMED, identically
    for run_step and run_step_graphs."""
    # run_step: flagged twice, clean on the 3rd execution -> 2 re-executions
    n_calls = {"n": 0}

    def step(state):
        n_calls["n"] += 1
        return state, _metrics(n_calls["n"] < 3)

    g = ABFTGuard(GuardConfig(max_retries=2))
    g.run_step(step, 0)
    assert n_calls["n"] == 3
    assert g.retries == n_calls["n"] - 1          # first call is not a retry

    # run_step: flagged at the final attempt -> every re-execution counted,
    # the restore replay counted under restores, not retries
    g2 = ABFTGuard(GuardConfig(max_retries=2),
                   restore_fn=lambda: None)
    n2 = {"n": 0}

    def step2(state):
        n2["n"] += 1
        return state, _metrics(n2["n"] < 4)       # heals only on replay

    g2.run_step(step2, 0)
    assert n2["n"] == 4
    assert g2.retries == 2 and g2.restores == 1

    # run_step_graphs: one partial re-execution
    def gstep():
        return np.zeros(2), _metrics(True, [True, False])

    def gretry(out, idx):
        return out, _metrics(False, np.zeros(len(idx), bool))

    g3 = ABFTGuard(GuardConfig(max_retries=2))
    g3.run_step_graphs(gstep, gretry)
    assert g3.retries == 1 and g3.graph_retries == 1


# ---------------------------------------------------------------------------
# (d) folded w_r dtype validation (satellite)
# ---------------------------------------------------------------------------

def test_stale_w_r_dtype_raises():
    stream = _stream(1, seed=9)
    s, h0 = stream[0]
    params = init_gcn(jax.random.PRNGKey(9), (8, 8, 3))
    cfg16 = ABFTConfig(mode="fused", dtype=jnp.float16)
    folded16 = fold_w_r(params, cfg16)
    assert folded16["layers"][0]["w_r"].dtype == jnp.float16
    g = Graph(s=jnp.asarray(s), h0=jnp.asarray(h0))
    # consuming the f16 fold under an f32 config must raise, not silently
    # run the checks at the stale precision
    with pytest.raises(ValueError, match="fold_w_r"):
        gcn_forward(params | {"layers": folded16["layers"]}, g, _cfg())
    # re-folding at the new dtype heals it
    refolded = fold_w_r(params, _cfg())
    logits, _ = gcn_forward(refolded, g, _cfg())
    ref, _ = gcn_forward(params, g, _cfg())
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))


def test_w_r_dtype_respects_x64_canonicalization():
    # a requested f64 checksum realizes as f32 when x64 is disabled; the
    # validation must compare realized dtypes, not requested ones
    stream = _stream(1, seed=11)
    s, h0 = stream[0]
    params = init_gcn(jax.random.PRNGKey(11), (8, 8, 3))
    cfg64 = ABFTConfig(mode="fused", dtype=jnp.float64)
    folded = fold_w_r(params, cfg64)
    g = Graph(s=jnp.asarray(s), h0=jnp.asarray(h0))
    logits, _ = gcn_forward(folded, g, cfg64)     # must not raise
    assert np.asarray(logits).shape == (s.shape[0], 3)


# ---------------------------------------------------------------------------
# (e) serving + sharding at stripe granularity
# ---------------------------------------------------------------------------

def test_serve_stripe_granularity_matches_graph():
    from repro.engine import make_batches, make_packed_batches
    from repro.launch.serve_gcn import serve

    stream = _stream(8, seed=4, feat=12, n_lo=16, n_hi=60)
    params = init_gcn(jax.random.PRNGKey(4), (12, 8, 3))
    cfg = _cfg()
    batches = make_packed_batches(stream, 4, block=16, stripe_multiple=4,
                                  width_multiple=2)
    by_graph = serve(batches, params, cfg, verbose=False)
    by_stripe = serve(batches, params, cfg, verbose=False,
                      granularity="stripe")
    fused_stripe = serve(batches, params, cfg, verbose=False,
                         granularity="stripe", fused_layer=True)
    assert by_graph["graphs"] == by_stripe["graphs"] == 8
    np.testing.assert_array_equal(by_graph["graph_flags"],
                                  by_stripe["graph_flags"])
    # stripe rel divergences normalize by per-stripe scales, so the values
    # differ from graph granularity only at the f32 rounding floor
    np.testing.assert_allclose(by_graph["graph_max_rel"],
                               by_stripe["graph_max_rel"], atol=1e-5)
    np.testing.assert_array_equal(by_graph["graph_flags"],
                                  fused_stripe["graph_flags"])
    # dense batches cannot do stripes
    with pytest.raises(ValueError, match="row-stripes"):
        serve(make_batches(stream, 4, [64]), params, cfg, verbose=False,
              granularity="stripe")


def test_serve_gcn_driver_stripe_smoke(capsys, monkeypatch):
    from repro.launch.serve_gcn import main
    # keep the test process off the persistent compile cache
    monkeypatch.setattr("repro.launch.serve_gcn.use_compile_cache",
                        lambda: None)

    stats = main(["--graphs", "6", "--batch", "3", "--backend", "block_ell",
                  "--block", "16", "--nodes", "16,48", "--feat", "8",
                  "--hidden", "8", "--classes", "3",
                  "--check-granularity", "stripe", "--fused-layer"])
    assert stats["graphs"] == 6
    assert stats["flags"] == 0 and not stats["graph_flags"].any()
    assert stats["stripe_retries"] == 0 and stats["recomputed_rows"] == 0
    assert "[stripe corners]" in capsys.readouterr().out


def test_sharded_stripe_corners_concatenate():
    """Stripe granularity composes with the stripe-sharded path: per-shard
    partials concatenate (not psum) into exactly the single-device
    per-stripe corners.  Runs on however many host devices exist (1 is
    fine — shard_map still exercises the concat out_specs)."""
    from repro.engine import Partition
    from repro.kernels.spmm_abft import dense_to_block_ell
    from repro.launch.mesh import make_graph_mesh

    stream = _stream(1, seed=13, n_lo=60, n_hi=60)
    s, h0 = stream[0]
    bell = dense_to_block_ell(s, block_m=16, block_k=16)
    cfg = _cfg()
    n_dev = len(jax.devices())
    part = Partition(make_graph_mesh(n_dev), "graph")
    h0 = jnp.asarray(h0)
    w = np.random.default_rng(13).normal(0, 0.3, (8, 8)).astype(np.float32)
    x = h0 @ jnp.asarray(w)
    x_r = h0 @ jnp.asarray(w.sum(axis=1))

    bk_1 = make_backend(bell, cfg, backend="block_ell", block_g=16,
                        granularity="stripe")
    out_1, chk_1 = bk_1.aggregate(x, x_r)
    bk_n = make_backend(bell, cfg, backend="block_ell", block_g=16,
                        granularity="stripe", partition=part)
    out_n, chk_n = bk_n.aggregate(x, x_r)
    assert chk_n.granularity == "stripe"
    nbm_padded = bk_n.vals.shape[0]
    assert chk_n.actual.shape == (nbm_padded,)
    np.testing.assert_allclose(np.asarray(out_n), np.asarray(out_1),
                               atol=1e-5)
    nbm = bell.n_block_rows
    np.testing.assert_allclose(np.asarray(chk_n.actual)[:nbm],
                               np.asarray(chk_1.actual), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(chk_n.predicted)[:nbm],
                               np.asarray(chk_1.predicted), rtol=1e-6)
    # padding stripes (shard-divisibility) compare 0 = 0
    assert np.abs(np.asarray(chk_n.actual)[nbm:]).max(initial=0.0) == 0.0
