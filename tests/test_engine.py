"""Unified GCN engine tests (ISSUE 2 tentpole).

Acceptance properties:
  (a) all three backends (dense | bcoo | block_ell) produce identical
      logits (atol 1e-4) and identical ABFT flag / max_rel / n_checks
      semantics through the single ``gcn_apply(..., backend=...)`` entry
      point, for every ABFT mode;
  (b) a combination-matmul fault (bit flip in X, eq.-5 column taken from
      the independent H w_r path) is flagged by every backend at the
      paper's 1e-4 absolute threshold;
  (c) bucketed multi-graph batching is exact: the batched dense engine
      step reproduces per-graph logits on the logical rows, and padded
      slots can never flag;
  (d) ABFTGuard: per-instance config (no shared mutable default) and the
      rolling flag-rate window driving should_evict;
  (e) [slow] the Table I smoke campaign through the JAX engine agrees
      with the numpy fault engine on injected bit flips.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abft import ABFTConfig
from repro.core.fault import flip_bit_f32
from repro.core.gcn import (
    init_gcn,
    normalized_adjacency_bcoo,
    normalized_adjacency_dense,
)
from repro.engine import (
    Graph,
    backend_names,
    gcn_apply,
    gcn_layer,
    infer_backend,
    make_backend,
    make_batches,
    pick_bucket,
    synth_graph_stream,
)
from repro.kernels.spmm_abft import dense_to_block_ell
from repro.runtime import ABFTGuard, GuardConfig

BACKENDS = ("dense", "bcoo", "block_ell")


def _graph_triple(seed, n, f, avg_deg=4):
    """(dense S, BCOO S, BlockEll S, H0) of one random undirected graph."""
    rng = np.random.default_rng(seed)
    m = n * avg_deg // 2
    e = rng.integers(0, n, size=(3 * m + 16, 2), dtype=np.int64)
    e = e[e[:, 0] != e[:, 1]]
    e = np.unique(np.sort(e, axis=1), axis=0)[:m]
    s_d = normalized_adjacency_dense(e, n)
    s_b = normalized_adjacency_bcoo(e, n)
    bell = dense_to_block_ell(s_d, block_m=32, block_k=32)
    h0 = jnp.asarray(rng.normal(0, 0.5, size=(n, f)).astype(np.float32))
    return jnp.asarray(s_d), s_b, bell, h0


def _apply(params, s, h0, cfg, backend):
    opts = {"block_g": 32} if backend == "block_ell" else {}
    return gcn_apply(params, Graph(s=s, h0=h0), cfg, backend=backend, **opts)


# ---------------------------------------------------------------------------
# (a) three-backend parity through the one entry point
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["none", "split", "fused"])
@pytest.mark.parametrize("seed,n", [(0, 96), (7, 160)])
def test_backend_parity(seed, n, mode):
    s_d, s_b, bell, h0 = _graph_triple(seed, n, f=24)
    params = init_gcn(jax.random.PRNGKey(seed), (24, 16, 5))
    cfg = ABFTConfig(mode=mode, threshold=1e-3, relative=True)

    results = {b: _apply(params, s, h0, cfg, b)
               for b, s in zip(BACKENDS, (s_d, s_b, bell))}
    ref_logits, ref_rep = results["dense"]
    for b, (logits, rep) in results.items():
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                                   atol=1e-4, rtol=1e-4, err_msg=b)
        assert bool(rep.flag) == bool(ref_rep.flag) is False, b
        assert int(rep.n_checks) == int(ref_rep.n_checks), b
        if cfg.enabled:
            # clean max_rel is each backend's rounding floor — far under tau
            assert float(rep.max_rel) < cfg.threshold / 4, (b, rep)


@pytest.mark.parametrize("backend", ["dense", "bcoo"])
def test_gcn_apply_stashes_s_c_on_graph(backend):
    """Repeated gcn_apply calls on the same staged Graph must not recompute
    the O(nnz) column checksum: the first call stashes the backend's s_c
    back on the Graph, and later calls hand that same array to the backend
    constructor (ISSUE 4 satellite fix)."""
    s_d, s_b, _, h0 = _graph_triple(5, 96, f=12)
    s = {"dense": s_d, "bcoo": s_b}[backend]
    params = init_gcn(jax.random.PRNGKey(5), (12, 8, 3))
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)

    g = Graph(s=s, h0=h0)
    assert g.s_c is None
    logits_1, rep_1 = gcn_apply(params, g, cfg, backend=backend)
    assert g.s_c is not None
    stashed = g.s_c
    logits_2, rep_2 = gcn_apply(params, g, cfg, backend=backend)
    assert g.s_c is stashed                    # reused, not recomputed
    np.testing.assert_array_equal(np.asarray(logits_1),
                                  np.asarray(logits_2))
    assert float(rep_1.max_rel) == float(rep_2.max_rel)

    # a different checksum dtype must NOT reuse the auto-stash (it would
    # silently run the new cfg's checks at the stale precision) — while a
    # user-provided s_c is trusted verbatim across cfgs
    cfg64 = ABFTConfig(mode="fused", threshold=1e-3, relative=True,
                       dtype=jnp.float64)
    gcn_apply(params, g, cfg64, backend=backend)
    assert g.s_c is not stashed
    user = Graph(s=s, h0=h0, s_c=stashed)
    gcn_apply(params, user, cfg64, backend=backend)
    assert user.s_c is stashed


def test_backend_registry_and_inference():
    s_d, s_b, bell, _ = _graph_triple(3, 64, f=8)
    assert set(BACKENDS) <= set(backend_names())
    assert infer_backend(s_d) == "dense"
    assert infer_backend(s_b) == "bcoo"
    assert infer_backend(bell) == "block_ell"
    with pytest.raises(ValueError):
        make_backend(s_d, ABFTConfig(), backend="nope")
    with pytest.raises(ValueError):
        make_backend(s_d, ABFTConfig(), partition=object())
    with pytest.raises(TypeError):
        make_backend(s_d, ABFTConfig(), backend="block_ell")


# ---------------------------------------------------------------------------
# (b) fault in the combination output flags in every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_detects_combination_fault(backend):
    tau = 1e-4
    s_d, s_b, bell, h0 = _graph_triple(11, 128, f=16)
    s = {"dense": s_d, "bcoo": s_b, "block_ell": bell}[backend]
    w = init_gcn(jax.random.PRNGKey(11), (16, 12, 4))["layers"][0]["w"]
    cfg = ABFTConfig(mode="fused", threshold=tau, relative=False)
    opts = {"block_g": 32} if backend == "block_ell" else {}
    bk = make_backend(s, cfg, **opts)

    x = h0 @ w
    x_r = h0 @ w.sum(axis=1)                   # independent eq.-5 path
    _, chk_clean = bk.aggregate(x, x_r)
    assert abs(float(chk_clean.predicted) - float(chk_clean.actual)) < tau / 4

    # bit-flip a combination output element the fault engine's way; pick a
    # site big enough that an exponent flip cannot hide under tau
    x_np = np.asarray(x).copy()
    big = np.argwhere(np.abs(x_np) >= 1e-2)
    i, j = big[7]
    x_np[i, j] = flip_bit_f32(np.float32(x_np[i, j]), 27)
    _, chk_bad = bk.aggregate(jnp.asarray(x_np), x_r)
    div = abs(float(chk_bad.predicted) - float(chk_bad.actual))
    assert div > tau, (backend, div)


# ---------------------------------------------------------------------------
# (c) bucketed multi-graph batching
# ---------------------------------------------------------------------------

def test_pick_bucket():
    assert pick_bucket(17, [32, 64]) == 32
    assert pick_bucket(33, [32, 64]) == 64
    with pytest.raises(ValueError):
        pick_bucket(65, [32, 64])


def test_batched_serving_matches_per_graph():
    stream = synth_graph_stream(10, n_lo=20, n_hi=60, feat=12, seed=4)
    batches = make_batches(stream, batch_size=4, buckets=[32, 64])
    assert sum(b.n_graphs for b in batches) == 10
    assert all(b.s.shape[0] == 4 for b in batches)

    params = init_gcn(jax.random.PRNGKey(4), (12, 8, 3))
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)
    step = jax.jit(lambda s, h: gcn_apply(params, Graph(s=s, h0=h), cfg,
                                          backend="dense"))
    # index the stream by (bucket, order) the same way make_batches does
    per_graph = {id(s): gcn_apply(params, Graph(jnp.asarray(s),
                                                jnp.asarray(h)), cfg)[0]
                 for s, h in stream}
    by_bucket = {}
    for s, h in stream:
        by_bucket.setdefault(pick_bucket(s.shape[0], [32, 64]),
                             []).append((s, h))
    it = {b: iter(v) for b, v in by_bucket.items()}
    for batch in batches:
        logits, rep = step(jnp.asarray(batch.s), jnp.asarray(batch.h0))
        assert not bool(rep.flag)          # padded slots must stay silent
        for bi in range(batch.n_graphs):
            s, h = next(it[batch.bucket])
            n = s.shape[0]
            np.testing.assert_allclose(
                np.asarray(logits[bi, :n]), np.asarray(per_graph[id(s)]),
                atol=1e-5, rtol=1e-5)
            # padded rows are exactly zero (zero-padding is exact)
            assert float(np.abs(np.asarray(logits[bi, n:])).max(initial=0.0)) \
                == 0.0


def test_serve_gcn_driver_smoke(capsys, monkeypatch):
    from repro.launch.serve_gcn import main
    # keep the test process off the persistent compile cache
    monkeypatch.setattr("repro.launch.serve_gcn.use_compile_cache",
                        lambda: None)
    stats = main(["--graphs", "8", "--batch", "4", "--buckets", "32,64",
                  "--nodes", "16,56", "--feat", "8", "--hidden", "8",
                  "--classes", "3"])
    assert stats["graphs"] == 8
    assert stats["graphs_per_sec"] > 0
    assert stats["flags"] == 0
    assert "graphs/sec" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# (d) ABFTGuard config isolation + rolling window
# ---------------------------------------------------------------------------

def test_guard_config_not_shared():
    g1, g2 = ABFTGuard(), ABFTGuard()
    assert g1.cfg is not g2.cfg
    g1.cfg.max_retries = 99
    assert g2.cfg.max_retries == 2


def _flagged_once_step():
    """A step that flags on its first attempt and passes the retry — the
    rolling window records it as a flagged step without entering the
    restore path (whose replay is now re-verified)."""
    calls = {"n": 0}

    def step():
        calls["n"] += 1
        return "ok", {"abft_flag": calls["n"] == 1, "abft_max_rel": 0.0}
    return step


def _clean_step():
    return "ok", {"abft_flag": False, "abft_max_rel": 0.0}


def test_guard_rolling_window_evicts_on_recent_flags():
    cfg = GuardConfig(max_retries=1, evict_rate=0.05, window=20,
                      min_samples=20)
    g = ABFTGuard(cfg)

    for _ in range(200):                       # long clean history
        g.run_step(_clean_step)
    assert not g.should_evict()
    for _ in range(20):                        # chip goes bad NOW
        g.run_step(_flagged_once_step())
    assert g.flag_rate == 1.0                  # window sees only the bad run
    assert g.should_evict()
    assert g.lifetime_flag_rate < 0.1          # lifetime average still tiny
    for _ in range(20):                        # recovers: window drains
        g.run_step(_clean_step)
    assert g.flag_rate == 0.0
    assert not g.should_evict()


def test_guard_window_not_judged_before_min_samples():
    cfg = GuardConfig(max_retries=1, evict_rate=0.0, window=50,
                      min_samples=10)
    g = ABFTGuard(cfg)
    for _ in range(5):
        g.run_step(_flagged_once_step())
    assert not g.should_evict()                # 5 < min_samples
    for _ in range(5):
        g.run_step(_flagged_once_step())
    assert g.should_evict()


# ---------------------------------------------------------------------------
# (e) Table I smoke campaign through the JAX engine (slow-marked: gated out
#     of the default CI matrix, runs in the full job)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_table1_jax_engine_agrees_with_numpy():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.table1_fault_detection import run_jax_engine

    stats = run_jax_engine([], n_campaigns=50)
    assert stats["agree"] + stats["grey"] == stats["n"]
    assert stats["agree"] >= stats["n"] // 2   # grey zone stays a minority
