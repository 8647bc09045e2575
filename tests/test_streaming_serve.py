"""Streaming serve engine (ISSUE 6 tentpole) + the serve/guard bugs
closed batches were hiding.

Acceptance properties:
  (a) exact-shape packing: ``pack_graphs(stripe_cap=, width_cap=)`` pins
      the jit-visible shape, so different streams padded to the same rung
      share one compile; undersized caps fail fast;
  (b) rung planning: ``plan_rungs`` admits every profiled graph, caps are
      quantized and monotone, ``RungTable.fit`` picks the smallest
      admitting rung;
  (c) the headline contract — a ragged 200-graph stream serves with
      jit-compile count <= rung-table size, per-graph parity with the
      dense single-graph engine, and p50/p99 latency stats;
  (d) backpressure: submits beyond ``queue_capacity`` resolve to explicit
      ``rejected`` verdicts, never silent drops or unbounded buffering;
  (e) oversize degradation (bugfix): a 10x graph mid-stream is served via
      a dedicated singleton shape (or explicitly rejected under
      ``oversize_policy="reject"``) — the stream never crashes;
  (f) flush-on-deadline: a partial bin older than the deadline dispatches
      instead of starving behind a bin that will not fill;
  (g) retry-ladder compile bounds (bugfix): packed and dense per-graph
      retries pad flagged subsets up a power-of-two ladder, so distinct
      flagged counts share O(log) compiles instead of one each;
  (h) activation-retention bugfix: adopted metrics never carry
      ``abft_h_layers`` (the per-layer activation stash the surgical
      closure needs) — the closures still see it;
  (i) repair-accounting bugfix: ``retry_fn`` reports LOGICAL rows
      (sum n_nodes x layers), not the padded sub-pack rows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abft import ABFTConfig
from repro.core.gcn import init_gcn
from repro.engine import (
    Graph,
    StreamingEngine,
    fold_w_r,
    gcn_apply,
    graph_pack_stats,
    make_batches,
    pack_graphs,
    plan_rungs,
    synth_graph_stream,
)
from repro.engine.streaming import (
    PackedRunner,
    RungTable,
    dense_retry_fn,
    make_packed_serve_step,
    next_pow2,
    packed_step_args,
)
from repro.runtime import ABFTGuard, GuardConfig, GuardRefused

FEAT, HIDDEN, CLASSES = 4, 4, 3
BLOCK = 8


def _stream(n, seed=0, n_lo=6, n_hi=28):
    return synth_graph_stream(n, n_lo=n_lo, n_hi=n_hi, feat=FEAT, seed=seed)


def _params(seed=0):
    return init_gcn(jax.random.PRNGKey(seed), (FEAT, HIDDEN, CLASSES))


def _cfg():
    return ABFTConfig(mode="fused", threshold=1e-3, relative=True)


def _engine(stream, *, n_slots=4, profile=None, **kw):
    rungs = plan_rungs(profile if profile is not None else stream,
                       n_slots=n_slots, block=BLOCK, stripe_multiple=4,
                       width_multiple=4)
    return StreamingEngine(_params(), _cfg(), rungs, **kw)


def _dense_ref(s, h0):
    logits, rep = gcn_apply(_params(), Graph(s=jnp.asarray(s),
                                             h0=jnp.asarray(h0)), _cfg())
    assert not bool(rep.flag)
    return np.asarray(logits)


# ---------------------------------------------------------------------------
# (a) exact-shape packing against a rung
# ---------------------------------------------------------------------------

def test_pack_graphs_caps_pin_exact_shape():
    a, b = _stream(3, seed=1), _stream(3, seed=2)
    kw = dict(block=BLOCK, n_slots=4, stripe_multiple=4, width_multiple=4,
              stripe_cap=24, width_cap=4)
    pa = pack_graphs(a, **kw)
    pb = pack_graphs(b, **kw)
    assert pa.bell.values.shape == (24, 4, BLOCK, BLOCK)
    # the bounded-compile contract IS this: same rung -> same jit key
    assert pa.bell.values.shape == pb.bell.values.shape
    assert pa.h0.shape == pb.h0.shape
    assert pa.stripe_graph.shape == pb.stripe_graph.shape
    # cap padding stripes sit in the overflow segment and alias col-block 0
    assert (np.asarray(pa.stripe_graph) == pa.n_slots).sum() > 0
    for g, (s, h0) in enumerate(a):
        o, n = pa.row_offsets[g], pa.n_nodes[g]
        np.testing.assert_allclose(pa.bell.todense()[o:o + n, o:o + n], s,
                                   atol=1e-6)


def test_pack_graphs_caps_too_small_raise():
    stream = _stream(3, seed=1)
    stripes = sum(graph_pack_stats(s, BLOCK)[0] for s, _ in stream)
    with pytest.raises(ValueError):
        pack_graphs(stream, block=BLOCK, stripe_cap=stripes - 1)
    with pytest.raises(ValueError):
        pack_graphs(stream, block=BLOCK, width_cap=0)


# ---------------------------------------------------------------------------
# (b) rung planning
# ---------------------------------------------------------------------------

def test_plan_rungs_admits_every_profiled_graph():
    profile = _stream(24, seed=3, n_lo=6, n_hi=60)
    rungs = plan_rungs(profile, n_slots=4, block=BLOCK, stripe_multiple=4,
                       width_multiple=4, max_rungs=4)
    assert 1 <= len(rungs) <= 4
    caps = [r.stripe_cap for r in rungs.rungs]
    assert caps == sorted(caps)
    assert all(r.stripe_cap % 4 == 0 and r.width_cap % 4 == 0
               for r in rungs.rungs)
    for s, _ in profile:
        st, w = graph_pack_stats(s, BLOCK)
        assert rungs.fit(st, w) is not None, (st, w, rungs.rungs)


def test_rung_table_fit_smallest_and_oversize():
    from repro.engine.streaming import Rung
    t = RungTable(rungs=(Rung(8, 4, 4), Rung(16, 4, 4), Rung(32, 4, 4)),
                  block=BLOCK)
    assert t.fit(5, 2) == t.rungs[0]
    assert t.fit(9, 4) == t.rungs[1]
    assert t.fit(33, 1) is None          # stripe overflow
    assert t.fit(4, 5) is None           # width overflow


# ---------------------------------------------------------------------------
# (c) the headline contract: 200-graph ragged stream, bounded compiles
# ---------------------------------------------------------------------------

def test_stream_200_graphs_bounded_compiles_with_latency_stats():
    stream = _stream(200, seed=4)
    eng = _engine(stream[:32], profile=stream[:32], n_slots=4,
                  queue_capacity=64, flush_deadline=None)
    assert eng.warmup() == len(eng.rungs)
    results = []
    for s, h0 in stream:
        eng.submit(s, h0)
        results.extend(eng.take_results())
    results.extend(eng.drain())

    assert len(results) == 200
    assert [r.rid for r in results] == sorted(r.rid for r in results)
    assert all(r.status == "served" for r in results)
    assert not any(r.flag for r in results)
    # THE contract: compiles bounded by the rung table, not the traffic
    assert eng.compile_count <= len(eng.rungs), \
        (eng.compile_count, len(eng.rungs))
    stats = eng.stats(results)
    assert stats["served"] == 200 and stats["rejected"] == 0
    assert stats["compiles"] <= stats["rung_table_size"]
    assert stats["latency_p50_ms"] is not None
    assert stats["latency_p99_ms"] >= stats["latency_p50_ms"]
    # per-request logits match the single-graph dense engine
    for r in results[::37]:
        s, h0 = stream[r.rid]
        np.testing.assert_allclose(r.logits, _dense_ref(s, h0),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"rid {r.rid}")


# ---------------------------------------------------------------------------
# (d) backpressure: explicit rejection verdicts
# ---------------------------------------------------------------------------

def test_stream_queue_full_rejects_explicitly():
    stream = _stream(10, seed=5)
    # one 8-slot rung + capacity 2: the bin can never fill before the
    # queue bound trips, so submits 3..10 must reject
    eng = _engine(stream, n_slots=8, queue_capacity=2, flush_deadline=None)
    for s, h0 in stream:
        eng.submit(s, h0)
    results = eng.drain()
    by_status = {}
    for r in results:
        by_status.setdefault(r.status, []).append(r)
    assert len(by_status.get("served", [])) == 2
    rejected = by_status["rejected"]
    assert len(rejected) == 8
    assert all("queue full" in r.reason for r in rejected)
    assert all(r.t_verdict is not None for r in rejected)
    assert all(r.logits is None for r in rejected)
    stats = eng.stats(results)
    assert stats["rejected"] == 8 and stats["served"] == 2


# ---------------------------------------------------------------------------
# (e) oversize degradation — the 10x graph that used to kill the stream
# ---------------------------------------------------------------------------

def _with_oversized(seed=6, n=12, at=6, factor=10):
    stream = list(_stream(n, seed=seed, n_lo=6, n_hi=20))
    big = synth_graph_stream(1, n_lo=20 * factor, n_hi=20 * factor,
                             feat=FEAT, seed=seed + 99)[0]
    stream.insert(at, big)
    return stream, at


def test_stream_oversized_graph_served_as_singleton():
    stream, at = _with_oversized()
    eng = _engine([g for i, g in enumerate(stream) if i != at],
                  n_slots=4, oversize_policy="singleton")
    results = []
    for s, h0 in stream:                 # must not raise at the big graph
        eng.submit(s, h0)
        results.extend(eng.take_results())
    results.extend(eng.drain())
    assert len(results) == len(stream)
    assert all(r.status == "served" for r in results)
    assert eng.singleton_dispatches == 1
    # the singleton adds at most one ladder shape beyond the rung table
    assert eng.compile_count <= len(eng.rungs) + 1
    big_s, big_h0 = stream[at]
    big_res = next(r for r in results if r.rid == at)
    np.testing.assert_allclose(big_res.logits, _dense_ref(big_s, big_h0),
                               atol=1e-4, rtol=1e-4)


def test_stream_oversized_graph_reject_policy():
    stream, at = _with_oversized()
    eng = _engine([g for i, g in enumerate(stream) if i != at],
                  n_slots=4, oversize_policy="reject")
    for s, h0 in stream:
        eng.submit(s, h0)
    results = eng.drain()
    big = next(r for r in results if r.rid == at)
    assert big.status == "rejected_oversize"
    assert "stripes" in big.reason and big.logits is None
    others = [r for r in results if r.rid != at]
    assert all(r.status == "served" for r in others)
    assert eng.stats(results)["rejected_oversize"] == 1


def test_oversize_policy_validated():
    with pytest.raises(ValueError, match="oversize_policy"):
        _engine(_stream(2), oversize_policy="explode")


# ---------------------------------------------------------------------------
# (f) flush-on-deadline
# ---------------------------------------------------------------------------

def test_stream_deadline_flushes_partial_bin():
    stream = _stream(2, seed=7)
    eng = _engine(stream, n_slots=4, flush_deadline=1.0)
    eng.submit(*stream[0], now=0.0)
    assert eng.batches_dispatched == 0           # bin open, under deadline
    eng.pump(now=0.5)
    assert eng.batches_dispatched == 0
    eng.pump(now=1.5)                            # oldest waited >= deadline
    assert eng.batches_dispatched == 1
    eng.submit(*stream[1], now=1.6)
    results = eng.drain(now=1.7)
    assert eng.batches_dispatched == 2
    assert [r.status for r in results] == ["served", "served"]
    # partial bins padded to the SAME rung shape: still one compile
    assert eng.compile_count <= len(eng.rungs)


# ---------------------------------------------------------------------------
# (g) bugfix: retry ladders bound recompiles
# ---------------------------------------------------------------------------

def test_packed_retry_ladder_shares_compiles_across_flag_counts():
    # 5 equal one-stripe graphs, quantization 1: flagged subsets of 3 and
    # 4 graphs must pad to the SAME (4-slot) sub-pack shape and share one
    # jitted step — pre-fix each flagged count compiled its own shape
    stream = synth_graph_stream(5, n_lo=8, n_hi=8, feat=FEAT, seed=8)
    pb = pack_graphs(stream, block=BLOCK, stripe_multiple=1,
                     width_multiple=1)
    params = fold_w_r(_params(), _cfg())
    runner = PackedRunner(params, _cfg(), BLOCK)
    out = np.asarray(runner.step_for(pb)(*packed_step_args(pb))[0])
    base = runner.compile_count

    s3 = runner._retry_shape(pb, [pb.items[i] for i in (0, 1, 2)])
    s4 = runner._retry_shape(pb, [pb.items[i] for i in (0, 1, 2, 3)])
    assert s3 == s4 and s3["n_slots"] == 4

    retry = runner.retry_fn(pb)
    out3, m3 = retry(out, np.asarray([0, 1, 2]))
    out4, m4 = retry(out, np.asarray([0, 1, 2, 3]))
    assert runner.compile_count == base + 1, \
        "flagged counts 3 and 4 must share one ladder compile"
    # sliced metrics align to flagged_idx, not the padded sub-pack
    assert m3["abft_graph_flags"].shape == (3,)
    assert m4["abft_graph_flags"].shape == (4,)
    np.testing.assert_allclose(out4, out, atol=1e-5)  # clean re-run patches


def test_dense_retry_pads_up_pow2_ladder():
    stream = _stream(5, seed=9, n_lo=10, n_hi=10)
    b = make_batches(stream, 5, buckets=[16])[0]
    shapes = []

    def recording_step(s, h0):
        shapes.append(tuple(s.shape))
        from repro.engine.streaming import make_serve_step
        return make_serve_step(fold_w_r(_params(), _cfg()), _cfg())(s, h0)

    retry = dense_retry_fn(recording_step, b)
    out = np.zeros((5, 16, CLASSES), np.float32)
    _, m3 = retry(out, np.asarray([0, 1, 2]))
    _, m4 = retry(out, np.asarray([0, 2, 3, 4]))
    # both flagged counts present the SAME padded shape to jit
    assert shapes == [(4, 16, 16), (4, 16, 16)]
    assert m3["abft_graph_flags"].shape == (3,)
    assert m4["abft_graph_flags"].shape == (4,)
    # the all-zero pad slots contribute 0 = 0 checks — never flagged
    assert not m3["abft_graph_flags"].any()


def test_next_pow2():
    assert [next_pow2(n) for n in (1, 2, 3, 4, 5, 7, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 16]


# ---------------------------------------------------------------------------
# (h) bugfix: adopted metrics never retain abft_h_layers
# ---------------------------------------------------------------------------

def test_guard_strips_h_layers_from_adopted_metrics():
    def step():
        return np.zeros(2), {
            "abft_flag": False, "abft_max_rel": 0.0,
            "abft_graph_flags": np.zeros(2, bool),
            "abft_h_layers": [np.ones((64, 4))]}

    g = ABFTGuard()
    _, m = g.run_step_graphs(step, lambda out, idx: (out, {}))
    assert "abft_h_layers" not in m
    assert "abft_graph_flags" in m               # the rest survives


def test_guard_h_layers_visible_to_stripe_closure_stripped_after():
    seen = {}

    def step():
        return np.zeros(2), {
            "abft_flag": True, "abft_max_rel": 1.0,
            "abft_graph_flags": np.asarray([True, False]),
            "abft_stripe_flags": np.asarray([[True, False]]),
            "abft_h_layers": [np.ones((64, 4))]}

    def sretry(out, metrics):
        # the surgical closure is WHY the stash exists — it must see it
        seen["h_layers"] = "abft_h_layers" in metrics
        return out, {"abft_graph_flags": np.zeros(2, bool),
                     "abft_stripes_recomputed": 1,
                     "abft_rows_recomputed": 8}

    g = ABFTGuard(GuardConfig(max_retries=1))
    _, m = g.run_step_graphs(step, lambda out, idx: (out, {}),
                             stripe_retry_fn=sretry)
    assert seen["h_layers"] is True
    assert "abft_h_layers" not in m
    assert not m["abft_graph_flags"].any()


def test_packed_stripe_step_emits_h_layers_engine_result_does_not():
    stream = _stream(3, seed=10)
    pb = pack_graphs(stream, block=BLOCK, stripe_multiple=4)
    params = fold_w_r(_params(), _cfg())
    step = make_packed_serve_step(params, _cfg(), pb.n_slots, block_g=BLOCK,
                                  fused_layer=True, granularity="stripe")
    out, raw = step(*packed_step_args(pb))
    assert "abft_h_layers" in raw                # the closure's operands
    runner = PackedRunner(params, _cfg(), BLOCK, True, "stripe")
    g = ABFTGuard()
    _, adopted = g.adjudicate(out, raw, runner.retry_fn(pb),
                              stripe_retry_fn=runner.stripe_retry_fn(pb))
    assert "abft_h_layers" not in adopted


def test_guard_adjudicate_without_replay_raises_on_escalation():
    def step():
        return np.zeros(1), {"abft_flag": True, "abft_max_rel": 1.0,
                             "abft_graph_flags": np.ones(1, bool)}

    def bad_retry(out, idx):
        return out, {"abft_graph_flags": np.ones(len(idx), bool)}

    g = ABFTGuard(GuardConfig(max_retries=1), restore_fn=lambda: None)
    out, m = step()
    with pytest.raises(GuardRefused, match="no replay"):
        g.adjudicate(out, m, bad_retry)


# ---------------------------------------------------------------------------
# (i) bugfix: retry accounting counts logical rows
# ---------------------------------------------------------------------------

def test_retry_reports_logical_rows_not_padded():
    # 13-node graphs at block 8: 16 padded rows each — the padded basis
    # would report 16 rows/graph/layer, the logical basis 13
    stream = synth_graph_stream(4, n_lo=13, n_hi=13, feat=FEAT, seed=11)
    pb = pack_graphs(stream, block=BLOCK, stripe_multiple=1,
                     width_multiple=1)
    params = fold_w_r(_params(), _cfg())
    runner = PackedRunner(params, _cfg(), BLOCK)
    out = np.asarray(runner.step_for(pb)(*packed_step_args(pb))[0])
    n_layers = len(params["layers"])
    _, m = runner.retry_fn(pb)(out, np.asarray([1]))
    assert int(m["abft_rows_recomputed"]) == 13 * n_layers
    _, m2 = runner.retry_fn(pb)(out, np.asarray([0, 2]))
    assert int(m2["abft_rows_recomputed"]) == 26 * n_layers
