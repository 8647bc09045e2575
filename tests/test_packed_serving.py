"""Block-diagonal packed block-ELL serving (ISSUE 3 tentpole) + the
guard/batching correctness fixes that ride along.

Acceptance properties:
  (a) the packer builds exactly diag(S_1, …, S_G): per-graph diagonal
      blocks reproduce each S, everything off the diagonal is zero, and H0
      rows land at each graph's padded offset;
  (b) packed engine parity: per-graph logit rows match the single-graph
      dense engine (atol 1e-4) and clean streams never flag;
  (c) per-graph check isolation: a bit flip in one packed graph's
      combination output diverges ONLY that graph's check corner;
  (d) ABFTGuard restore path: restore is followed by a replayed, re-verified
      step (bounded by max_restores; raises rather than adopting flagged
      state), and run_step_graphs retries only the flagged graphs;
  (e) batching keeps input dtypes (f64 streams stay f64, bf16 stays bf16)
      and mixed feature dims fail fast with the offending graph named;
  (f) the w_r fold (engine.fold_w_r) is bitwise-parity with the per-step
      row_checksum recompute;
  (g) serve_gcn --backend block_ell serves a mixed-size stream with
      per-graph verdicts matching the dense backend graph-for-graph.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abft import ABFTConfig, per_graph_report
from repro.core.fault import flip_bit_f32
from repro.core.gcn import init_gcn
from repro.engine import (
    Graph,
    fold_w_r,
    gcn_apply,
    gcn_forward,
    make_backend,
    make_batches,
    make_packed_batches,
    pack_graphs,
    pad_graph,
    synth_graph_stream,
)
from repro.runtime import ABFTGuard, GuardConfig, GuardRefused


def _stream(n_graphs=3, seed=1, feat=8, n_lo=20, n_hi=70):
    return synth_graph_stream(n_graphs, n_lo=n_lo, n_hi=n_hi, feat=feat,
                              seed=seed)


# ---------------------------------------------------------------------------
# (a) the packer builds the block-diagonal system
# ---------------------------------------------------------------------------

def test_pack_graphs_is_block_diagonal():
    stream = _stream(3)
    pb = pack_graphs(stream, block=16, stripe_multiple=4, width_multiple=2)
    dense = pb.bell.todense()
    assert pb.bell.n_block_rows % 4 == 0          # stripe residue padded
    assert pb.bell.width % 2 == 0
    off_diag = dense.copy()
    for g, (s, h0) in enumerate(stream):
        o, n = pb.row_offsets[g], pb.n_nodes[g]
        assert o % 16 == 0 and n == s.shape[0]
        np.testing.assert_allclose(dense[o:o + n, o:o + n], s, atol=1e-6)
        np.testing.assert_allclose(pb.h0[o:o + n], h0, atol=0)
        off_diag[o:o + n, o:o + n] = 0.0
    assert np.abs(off_diag).max() == 0.0          # nothing off the diagonal
    # stripe segments: contiguous per graph, padding in overflow segment
    per_graph_stripes = [int((pb.stripe_graph == g).sum())
                        for g in range(pb.n_slots)]
    assert sum(per_graph_stripes) + int(
        (pb.stripe_graph == pb.n_slots).sum()) == pb.bell.n_block_rows
    for g, (s, _) in enumerate(stream):
        assert per_graph_stripes[g] == -(-s.shape[0] // 16)


def test_pack_graphs_empty_slots_pad_to_n_slots():
    stream = _stream(2)
    pb = pack_graphs(stream, block=16, n_slots=4)
    assert pb.n_slots == 4 and pb.n_graphs == 2
    assert (pb.n_nodes[2:] == 0).all()
    # empty slots own no stripes, so their check corner is 0 = 0
    assert not np.isin([2, 3], pb.stripe_graph).any()


# ---------------------------------------------------------------------------
# (b) packed engine parity vs the per-graph dense engine
# ---------------------------------------------------------------------------

def test_packed_parity_vs_dense_per_graph():
    stream = _stream(4, seed=3)
    pb = pack_graphs(stream, block=16, stripe_multiple=4)
    params = init_gcn(jax.random.PRNGKey(0), (8, 8, 3))
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)

    logits, checks = gcn_forward(params, Graph(s=pb, h0=jnp.asarray(pb.h0)),
                                 cfg)
    assert all(c.predicted.shape == (pb.n_slots,) for c in checks)
    flags, rels = per_graph_report(checks, cfg, pb.n_slots)
    assert not bool(np.asarray(flags).any())
    for g, (s, h0) in enumerate(stream):
        ref, rep = gcn_apply(params, Graph(s=jnp.asarray(s),
                                           h0=jnp.asarray(h0)), cfg)
        assert not bool(rep.flag)
        o, n = pb.row_offsets[g], pb.n_nodes[g]
        np.testing.assert_allclose(np.asarray(logits[o:o + n]),
                                   np.asarray(ref), atol=1e-4, rtol=1e-4,
                                   err_msg=f"graph {g}")
        # padded rows between graphs are exactly zero
        pad_rows = np.asarray(logits[o + n:o + (-(-n // 16)) * 16])
        assert np.abs(pad_rows).max(initial=0.0) == 0.0


def test_packed_split_mode_emits_per_graph_checks():
    """Split mode (eq. 2–3) on the packed path: BOTH checks segment per
    graph — the combination check must not collapse to one scalar that
    would smear a single graph's fault over the whole batch."""
    stream = _stream(3, seed=7)
    pb = pack_graphs(stream, block=16)
    params = init_gcn(jax.random.PRNGKey(7), (8, 8, 3))
    cfg = ABFTConfig(mode="split", threshold=1e-3, relative=True)

    logits, checks = gcn_forward(params, Graph(s=pb, h0=jnp.asarray(pb.h0)),
                                 cfg)
    assert len(checks) == 4                       # 2 layers x 2 checks
    assert all(c.predicted.shape == (pb.n_slots,) for c in checks)
    flags, _ = per_graph_report(checks, cfg, pb.n_slots)
    assert not bool(np.asarray(flags).any())
    for g, (s, h0) in enumerate(stream):
        ref, rep = gcn_apply(params, Graph(s=jnp.asarray(s),
                                           h0=jnp.asarray(h0)), cfg)
        assert not bool(rep.flag)
        o, n = pb.row_offsets[g], pb.n_nodes[g]
        np.testing.assert_allclose(np.asarray(logits[o:o + n]),
                                   np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_per_graph_report_rejects_unattributable_checks():
    from repro.core.abft import Check

    cfg = ABFTConfig(mode="fused", threshold=1e-3)
    scalar = Check(predicted=jnp.float32(1.0), actual=jnp.float32(1.0))
    with pytest.raises(ValueError, match="batched checks"):
        per_graph_report([scalar], cfg, 4)


# ---------------------------------------------------------------------------
# (c) a fault in one packed graph flags only that graph's corner
# ---------------------------------------------------------------------------

def test_packed_fault_flags_only_that_graph():
    tau = 1e-4
    stream = _stream(3, seed=5, feat=16, n_lo=30, n_hi=80)
    pb = pack_graphs(stream, block=16)
    w = init_gcn(jax.random.PRNGKey(5), (16, 12, 4))["layers"][0]["w"]
    cfg = ABFTConfig(mode="fused", threshold=tau, relative=False)
    bk = make_backend(pb, cfg)

    h = jnp.asarray(pb.h0)
    x = h @ w
    x_r = h @ w.sum(axis=1)                       # independent eq.-5 path
    _, chk = bk.aggregate(x, x_r)
    diffs = np.abs(np.asarray(chk.predicted) - np.asarray(chk.actual))
    assert chk.predicted.shape == (3,)
    assert (diffs < tau / 4).all()

    victim = 1
    o, n = pb.row_offsets[victim], pb.n_nodes[victim]
    x_np = np.asarray(x).copy()
    band = x_np[o:o + n]
    i, j = np.argwhere(np.abs(band) >= 1e-2)[5]
    x_np[o + i, j] = flip_bit_f32(np.float32(x_np[o + i, j]), 27)
    _, chk_bad = bk.aggregate(jnp.asarray(x_np), x_r)
    diffs = np.abs(np.asarray(chk_bad.predicted) - np.asarray(chk_bad.actual))
    assert diffs[victim] > tau                    # the victim flags ...
    others = np.delete(diffs, victim)
    assert (others < tau / 4).all()               # ... and only the victim


# ---------------------------------------------------------------------------
# (d) guard: restore->replay->verify + per-graph retry
# ---------------------------------------------------------------------------

def _metrics(flag, gflags=None):
    m = {"abft_flag": flag, "abft_max_rel": 1.0 if flag else 0.0}
    if gflags is not None:
        m["abft_graph_flags"] = np.asarray(gflags, bool)
    return m


def test_guard_restore_then_verify():
    fault = {"on": True}

    def step(state):
        return state + 1, _metrics(fault["on"])

    def restore():
        fault["on"] = False                       # checkpoint reload heals

    g = ABFTGuard(GuardConfig(max_retries=1), restore_fn=restore)
    out, m = g.run_step(step, 10)
    # the adopted output comes from the verified replay, with clean metrics
    assert out == 11
    assert bool(m["abft_flag"]) is False
    assert g.restores == 1 and g.flags == 1


def test_guard_restore_bounded_and_raises_unverified():
    def always_bad(state):
        return state, _metrics(True)

    g = ABFTGuard(GuardConfig(max_retries=0, max_restores=2),
                  restore_fn=lambda: None)
    with pytest.raises(GuardRefused, match="still flagged after 2"):
        g.run_step(always_bad, 0)
    assert g.restores == 2

    g2 = ABFTGuard(GuardConfig(max_retries=0))    # no restore_fn at all
    with pytest.raises(GuardRefused, match="no restore_fn"):
        g2.run_step(always_bad, 0)


def test_guard_per_graph_retry_retries_only_flagged():
    retried = []

    def step():
        m = _metrics(True, [False, True, False, True])
        m["abft_graph_max_rel"] = np.asarray([0.0, 0.3, 0.0, 0.2],
                                             np.float32)
        m["abft_max_rel"] = 0.3
        return np.zeros(4), m

    def retry(out, idx):
        retried.append(list(idx))
        out = out.copy()
        out[idx] = 7.0
        return out, _metrics(False, np.zeros(len(idx), bool)) | {
            "abft_graph_max_rel": np.full(len(idx), 1e-7, np.float32)}

    g = ABFTGuard(GuardConfig(max_retries=2))
    out, m = g.run_step_graphs(step, retry)
    assert retried == [[1, 3]]                    # only the flagged graphs
    np.testing.assert_array_equal(out, [0.0, 7.0, 0.0, 7.0])
    assert bool(m["abft_flag"]) is False
    assert not m["abft_graph_flags"].any()
    # metrics reflect the ADOPTED executions, not the failed attempt
    assert float(m["abft_max_rel"]) < 1e-3
    assert float(np.asarray(m["abft_graph_max_rel"]).max()) < 1e-3
    assert g.graph_retries == 2 and g.retries == 1 and g.flags == 1


def test_guard_per_graph_retry_narrows_then_restores():
    fault = {"on": True}

    def step():
        flag = fault["on"]
        return np.zeros(3), _metrics(flag, [flag, flag, False])

    def retry(out, idx):
        # graph 0 heals on retry; graph 1 is persistent
        return out, _metrics(True, [i == 1 for i in idx])

    def restore():
        fault["on"] = False

    g = ABFTGuard(GuardConfig(max_retries=2), restore_fn=restore)
    out, m = g.run_step_graphs(step, retry)
    # retries narrowed to graph 1, still flagged -> restore + full replay
    assert g.restores == 1
    assert bool(np.asarray(m["abft_flag"]).any()) is False


def test_guard_restore_returning_state_is_adopted_for_replay():
    # the train.py convention: restore_fn returns the checkpointed state,
    # and the replay must run FROM it, not from the in-memory state
    seen = []

    def step(state):
        seen.append(state)
        return state * 2, _metrics(state != 100)

    g = ABFTGuard(GuardConfig(max_retries=0), restore_fn=lambda: 100)
    out, m = g.run_step(step, 3)
    assert seen == [3, 100]                       # replay got restored state
    assert out == 200 and bool(m["abft_flag"]) is False
    assert g.restores == 1


def test_guard_graphs_restore_never_splices_state_into_data_args():
    # serving steps take DATA operands; a state-returning restore_fn must
    # not replace the batch adjacency on the run_step_graphs restore path
    fault = {"on": True}
    seen = []

    def step(data):
        seen.append(data)
        return np.zeros(2), _metrics(fault["on"], [fault["on"], False])

    def restore():
        fault["on"] = False
        return {"params": "ckpt"}                 # state-returning restore

    def retry(out, idx):
        return out, _metrics(True, [True] * len(idx))

    g = ABFTGuard(GuardConfig(max_retries=1), restore_fn=restore)
    out, m = g.run_step_graphs(step, retry, "batch-0")
    assert seen == ["batch-0", "batch-0"]         # replay kept the data arg
    assert bool(np.asarray(m["abft_flag"]).any()) is False


def test_guard_graphs_drops_unreconstructable_max_rel():
    # step emits abft_max_rel but no per-graph max_rel: after a clean
    # retry the stale flagged value must not ride under a clean flag
    def step():
        return np.zeros(2), _metrics(True, [True, False])  # max_rel = 1.0

    def retry(out, idx):
        return out, _metrics(False, [False] * len(idx))

    g = ABFTGuard(GuardConfig(max_retries=1))
    out, m = g.run_step_graphs(step, retry)
    assert bool(m["abft_flag"]) is False
    assert "abft_max_rel" not in m


def test_pack_graphs_records_quantization_for_retries():
    pb = pack_graphs(_stream(2), block=16, stripe_multiple=4,
                     width_multiple=2)
    assert pb.stripe_multiple == 4 and pb.width_multiple == 2


# ---------------------------------------------------------------------------
# (e) batching dtype preservation + mixed-feat validation
# ---------------------------------------------------------------------------

def test_pad_graph_preserves_dtype():
    s = np.eye(5, dtype=np.float64)
    h = np.ones((5, 3), np.float16)
    sp, hp = pad_graph(s, h, 8)
    assert sp.dtype == np.float64 and hp.dtype == np.float16
    assert sp.shape == (8, 8) and hp.shape == (8, 3)


def test_make_batches_preserves_and_promotes_dtype():
    rng = np.random.default_rng(0)

    def graph(n, s_dt, h_dt):
        return (np.eye(n, dtype=s_dt),
                rng.normal(size=(n, 4)).astype(h_dt))

    # uniform f64 stays f64 (reference streams)
    batches = make_batches([graph(10, np.float64, np.float64)], 2, [16])
    assert batches[0].s.dtype == np.float64
    assert batches[0].h0.dtype == np.float64
    # bf16 features survive batching
    bf16 = jnp.bfloat16.dtype
    batches = make_batches([graph(10, np.float32, bf16)], 2, [16])
    assert batches[0].h0.dtype == bf16
    # mixed f32/f64 in one bucket promotes (no silent downcast)
    batches = make_batches([graph(10, np.float32, np.float32),
                            graph(12, np.float64, np.float64)], 2, [16])
    assert batches[0].s.dtype == np.float64
    assert batches[0].h0.dtype == np.float64


def test_mixed_feature_dims_raise_up_front():
    rng = np.random.default_rng(0)
    good = (np.eye(10, dtype=np.float32),
            rng.normal(size=(10, 4)).astype(np.float32))
    bad = (np.eye(12, dtype=np.float32),
           rng.normal(size=(12, 6)).astype(np.float32))
    with pytest.raises(ValueError, match="graph 1 has feature dim 6"):
        make_batches([good, bad], 2, [16])
    with pytest.raises(ValueError, match="graph 1 has feature dim 6"):
        pack_graphs([good, bad], block=16)


# ---------------------------------------------------------------------------
# (f) the offline w_r fold is parity with the per-step recompute
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["split", "fused"])
def test_fold_w_r_parity(mode):
    stream = _stream(1, seed=9)
    s, h0 = stream[0]
    params = init_gcn(jax.random.PRNGKey(9), (8, 16, 4))
    cfg = ABFTConfig(mode=mode, threshold=1e-3, relative=True)
    folded = fold_w_r(params, cfg)
    assert all("w_r" in layer for layer in folded["layers"])
    assert folded["layers"][0]["w_r"].shape == (8,)

    g = Graph(s=jnp.asarray(s), h0=jnp.asarray(h0))
    logits_a, rep_a = gcn_apply(params, g, cfg)
    logits_b, rep_b = gcn_apply(folded, g, cfg)
    # identical algebra, identical dtype -> bitwise-equal logits and report
    np.testing.assert_array_equal(np.asarray(logits_a), np.asarray(logits_b))
    assert float(rep_a.max_rel) == float(rep_b.max_rel)
    assert int(rep_a.n_checks) == int(rep_b.n_checks)


def test_fold_w_r_disabled_mode_is_noop():
    params = init_gcn(jax.random.PRNGKey(0), (4, 4, 2))
    assert fold_w_r(params, ABFTConfig(mode="none")) is params


# ---------------------------------------------------------------------------
# (g) packed serving driver: per-graph verdicts match dense graph-for-graph
# ---------------------------------------------------------------------------

def test_serve_block_ell_matches_dense_graph_for_graph():
    from repro.launch.serve_gcn import serve

    stream = _stream(10, seed=4, feat=12, n_lo=16, n_hi=60)
    params = init_gcn(jax.random.PRNGKey(4), (12, 8, 3))
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)

    dense = serve(make_batches(stream, 4, [32, 64]), params, cfg,
                  verbose=False)
    packed = serve(make_packed_batches(stream, 4, block=16,
                                       stripe_multiple=4, width_multiple=2),
                   params, cfg, verbose=False)
    assert dense["graphs"] == packed["graphs"] == 10
    np.testing.assert_array_equal(dense["graph_flags"],
                                  packed["graph_flags"])
    assert not packed["graph_flags"].any()
    assert packed["graphs_per_sec"] > 0


def test_serve_gcn_driver_block_ell_smoke(capsys, monkeypatch):
    from repro.launch.serve_gcn import main
    # keep the test process off the persistent compile cache
    monkeypatch.setattr("repro.launch.serve_gcn.use_compile_cache",
                        lambda: None)

    stats = main(["--graphs", "8", "--batch", "4", "--backend", "block_ell",
                  "--block", "16", "--nodes", "16,56", "--feat", "8",
                  "--hidden", "8", "--classes", "3"])
    assert stats["graphs"] == 8
    assert stats["flags"] == 0 and not stats["graph_flags"].any()
    assert "packed block_ell" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# (h) size-aware pack scheduling (ISSUE 4 satellite): FFD by stripe count
# ---------------------------------------------------------------------------

def test_schedule_packs_equalizes_stripe_loads():
    from repro.engine import schedule_packs

    # adversarial arrival order: big graphs clustered at the front, so
    # arrival chunking makes one huge batch and one tiny one
    stripes = [8, 8, 7, 7, 1, 1, 1, 1]
    groups = schedule_packs(stripes, batch_size=4, stripe_multiple=1)
    assert sorted(gi for g in groups for gi in g) == list(range(8))
    assert all(len(g) <= 4 for g in groups)
    loads = sorted(sum(stripes[i] for i in g) for g in groups)
    arrival_loads = sorted((sum(stripes[:4]), sum(stripes[4:])))
    assert loads == [16, 18]                  # FFD splits 34 near-evenly
    assert arrival_loads == [4, 30]           # arrival order does not
    # determinism
    assert groups == schedule_packs(stripes, 4, 1)


def test_schedule_packs_respects_stripe_multiple_quantum():
    from repro.engine import schedule_packs

    stripes = [5, 4, 3, 3, 2, 1]
    groups = schedule_packs(stripes, batch_size=3, stripe_multiple=4)
    loads = [sum(stripes[i] for i in g) for g in groups]
    # capacity is the mean (9) rounded up to the quantum (12); both bins
    # land within one quantum of each other
    assert max(loads) <= 12
    assert sorted(gi for g in groups for gi in g) == list(range(6))


def test_make_packed_batches_size_schedule_cuts_padding():
    stream = _stream(8, seed=11, n_lo=16, n_hi=120)
    by_size = make_packed_batches(stream, 4, block=16, stripe_multiple=4)
    arrival = make_packed_batches(stream, 4, block=16, stripe_multiple=4,
                                  schedule="arrival")
    with pytest.raises(ValueError):
        make_packed_batches(stream, 4, block=16, schedule="nope")

    # every graph served exactly once, stream positions preserved
    idx = sorted(int(i) for b in by_size for i in b.indices if i >= 0)
    assert idx == list(range(8))
    # FFD never allocates more total padded stripes than arrival chunking
    total = sum(b.bell.n_block_rows for b in by_size)
    assert total <= sum(b.bell.n_block_rows for b in arrival)
    # and the batch stripe counts are more even (max batch no larger)
    assert max(b.bell.n_block_rows for b in by_size) \
        <= max(b.bell.n_block_rows for b in arrival)


def test_serve_size_scheduled_verdicts_stay_stream_ordered():
    """Size-aware reordering must not scramble per-graph verdicts: serving a
    size-scheduled packed stream matches the dense backend graph-for-graph
    in STREAM order, exactly like arrival-order packing."""
    from repro.launch.serve_gcn import serve

    stream = _stream(10, seed=12, feat=12, n_lo=16, n_hi=90)
    params = init_gcn(jax.random.PRNGKey(12), (12, 8, 3))
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)
    dense = serve(make_batches(stream, 4, [32, 64, 128]), params, cfg,
                  verbose=False)
    packed = serve(make_packed_batches(stream, 4, block=16,
                                       stripe_multiple=4, width_multiple=2),
                   params, cfg, verbose=False)
    assert dense["graphs"] == packed["graphs"] == 10
    np.testing.assert_array_equal(dense["graph_flags"],
                                  packed["graph_flags"])
    np.testing.assert_allclose(dense["graph_max_rel"],
                               packed["graph_max_rel"], atol=1e-5)
