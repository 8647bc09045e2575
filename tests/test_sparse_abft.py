"""Tentpole tests: sparse-adjacency GCN path with the fused ABFT check.

Three acceptance properties (ISSUE 1):
  (a) fused check_chain prediction == split-check composition on random
      matrix chains, within accumulation tolerance;
  (b) the engine's gcn_apply over a BCOO S (bcoo backend) gives the
      dense backend's logits on random graphs (atol 1e-4), clean runs
      unflagged in both;
  (c) a single injected fault in the SpMM output trips the fused check at
      the paper's Table I absolute thresholds (parity with core/fault.py's
      bit-flip model).

Runs WITHOUT hypothesis (seeded deterministic cases, so the acceptance
criteria hold on minimal installs); with hypothesis installed the same
properties are additionally fuzzed over shapes and seeds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ABFTConfig,
    check_chain,
    check_matmul,
    sparse_col_checksum,
)
from repro.core.datasets import make_reduced
from repro.core.fault import THRESHOLDS, flip_bit_f32
from repro.core.gcn import (
    dataset_to_dense,
    dataset_to_sparse,
    init_gcn,
    normalized_adjacency_bcoo,
    normalized_adjacency_dense,
)
from repro.engine import Graph, gcn_apply, gcn_layer, make_backend
from repro.kernels.spmm_abft import dense_to_block_ell, spmm_abft

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # minimal install: seeded tests below still run
    HAVE_HYPOTHESIS = False

CFG = ABFTConfig(mode="fused", threshold=1e-3, relative=True)


def random_chain(seed, dims, scale=1.0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(0, scale, size=(a, b)).astype(np.float32))
            for a, b in zip(dims[:-1], dims[1:])]


def random_graph(seed, n, avg_deg=4):
    """Distinct undirected ER edges (i<j) as an [m, 2] int array."""
    rng = np.random.default_rng(seed)
    m = n * avg_deg // 2
    e = rng.integers(0, n, size=(3 * m + 16, 2), dtype=np.int64)
    e = e[e[:, 0] != e[:, 1]]
    e = np.unique(np.sort(e, axis=1), axis=0)
    return e[:m]


# ---------------------------------------------------------------------------
# (a) fused chain check == split composition
# ---------------------------------------------------------------------------

def _chain_property(mats):
    out = mats[0]
    for m in mats[1:]:
        out = out @ m
    fused = check_chain(mats, out, CFG)
    # split composition: check the LAST multiply with its true left operand
    left = mats[0]
    for m in mats[1:-1]:
        left = left @ m
    split = check_matmul(left, mats[-1], out, CFG)
    ref = float(np.asarray(out, np.float64).sum())
    scale = max(1.0, abs(ref))
    assert abs(float(fused.predicted) - float(split.predicted)) / scale < 1e-4
    assert abs(float(fused.predicted) - ref) / scale < 1e-4
    assert abs(float(fused.actual) - float(split.actual)) < 1e-6 * scale


@pytest.mark.parametrize("seed,dims", [
    (0, (16, 8, 12)),
    (1, (64, 32, 16)),
    (2, (33, 7, 19, 5)),          # ragged 4-matrix chain
    (3, (128, 64, 64, 32, 8)),    # 5-matrix chain
])
def test_chain_equals_split_composition(seed, dims):
    _chain_property(random_chain(seed, dims, scale=0.3))


# ---------------------------------------------------------------------------
# (b) sparse == dense GCN forward
# ---------------------------------------------------------------------------

def _parity_property(seed, n, f, h, c, mode):
    edges = random_graph(seed, n)
    rng = np.random.default_rng(seed + 1)
    s_dense = jnp.asarray(normalized_adjacency_dense(edges, n))
    s_bcoo = normalized_adjacency_bcoo(edges, n)
    np.testing.assert_allclose(np.asarray(s_bcoo.todense()),
                               np.asarray(s_dense), atol=1e-7)
    h0 = jnp.asarray(rng.normal(0, 0.5, size=(n, f)).astype(np.float32))
    params = init_gcn(jax.random.PRNGKey(seed), (f, h, c))
    cfg = ABFTConfig(mode=mode, threshold=1e-3, relative=True)

    logits_d, rep_d = gcn_apply(params, Graph(s_dense, h0), cfg)
    s_c = make_backend(s_bcoo, cfg).s_c     # None with the check off
    logits_s, rep_s = jax.jit(
        lambda p, s, x, sc: gcn_apply(p, Graph(s, x, sc), cfg)
    )(params, s_bcoo, h0, s_c)

    np.testing.assert_allclose(np.asarray(logits_s), np.asarray(logits_d),
                               atol=1e-4, rtol=1e-4)
    if cfg.enabled:
        assert not bool(rep_d.flag) and not bool(rep_s.flag), \
            (float(rep_d.max_rel), float(rep_s.max_rel))
        assert int(rep_s.n_checks) == int(rep_d.n_checks)


@pytest.mark.parametrize("mode", ["none", "split", "fused"])
@pytest.mark.parametrize("seed,n", [(0, 96), (7, 200), (13, 333)])
def test_sparse_matches_dense_gcn(seed, n, mode):
    _parity_property(seed, n, f=24, h=16, c=5, mode=mode)


def test_dataset_sparse_matches_dense():
    """End-to-end over the synthetic reduced Cora dataset (jit'd)."""
    ds = make_reduced("cora", scale=8, seed=0)
    s_np, h_np, _ = dataset_to_dense(ds)
    s_sp, h_sp, _ = dataset_to_sparse(ds)
    params = init_gcn(jax.random.PRNGKey(0), ds.stats.layer_dims)
    logits_d, _ = gcn_apply(params, Graph(jnp.asarray(s_np),
                                          jnp.asarray(h_np)), CFG)
    s_c = make_backend(s_sp, CFG).s_c
    logits_s, rep = jax.jit(
        lambda p, s, x, sc: gcn_apply(p, Graph(s, x, sc), CFG)
    )(params, s_sp, h_sp, s_c)
    np.testing.assert_allclose(np.asarray(logits_s), np.asarray(logits_d),
                               atol=1e-4, rtol=1e-4)
    assert not bool(rep.flag)


def test_offline_s_c_matches_online():
    ds = make_reduced("citeseer", scale=8, seed=1)
    s_sp, _, _ = dataset_to_sparse(ds)
    offline = make_backend(s_sp, CFG).s_c
    online = sparse_col_checksum(s_sp, CFG.dtype)
    np.testing.assert_allclose(np.asarray(offline), np.asarray(online))
    # and both equal the numpy fault engine's f64 s_c within f32 tolerance
    np.testing.assert_allclose(np.asarray(offline, np.float64),
                               ds.s.col_sums(), atol=1e-5)


# ---------------------------------------------------------------------------
# (c) fault injection trips the fused check at Table I thresholds
# ---------------------------------------------------------------------------

def _spmm_fault_property(seed, threshold):
    """A single bit-flip-style corruption of the SpMM output must move the
    actual checksum away from the kernel's prediction by ≈ the injected
    delta (prefix-delta model, core/fault.py) — detected at |delta| > tau,
    with the clean divergence safely below tau."""
    rng = np.random.default_rng(seed)
    n = 160
    edges = random_graph(seed, n)
    s_dense = normalized_adjacency_dense(edges, n)
    bell = dense_to_block_ell(s_dense, block_m=32, block_k=32)
    x = rng.normal(0, 0.1, size=(n, 16)).astype(np.float32)

    out, chk = spmm_abft(bell, jnp.asarray(x), interpret=True, block_g=32)
    clean_div = abs(float(chk.predicted) - float(chk.actual))
    assert clean_div < threshold / 4, (clean_div, threshold)

    # corrupt one element the way the fault engine does: flip a high
    # exponent bit of an output value.  The element must not be tiny —
    # an exponent flip can SHRINK the value (delta ≈ -old), so |old| must
    # exceed the threshold for the fault to be detectable at all.
    out_np = np.asarray(out).copy()
    big = np.argwhere(np.abs(out_np) >= 1e-3)
    assert big.size, "graph too disconnected for a detectable fault site"
    i, j = big[int(rng.integers(len(big)))]
    old = out_np[i, j]
    new = flip_bit_f32(np.float32(old), 27)
    delta = float(new) - float(old)
    out_np[i, j] = new
    actual_bad = float(out_np.astype(np.float64).sum())
    div = abs(float(chk.predicted) - actual_bad)
    assert div > threshold, (div, delta, threshold)
    # and the divergence is the injected delta, modulo accumulation noise
    assert abs(div - abs(delta)) < max(1e-5 * abs(delta), threshold / 4)


@pytest.mark.parametrize("threshold", list(THRESHOLDS[:2]))   # 1e-4, 1e-5
@pytest.mark.parametrize("seed", [0, 5])
def test_spmm_fault_detected(seed, threshold):
    _spmm_fault_property(seed, threshold)


def test_small_fault_below_threshold_is_silent():
    """Deltas below tau stay silent — threshold semantics, not noise."""
    rng = np.random.default_rng(3)
    n = 128
    s_dense = normalized_adjacency_dense(random_graph(3, n), n)
    bell = dense_to_block_ell(s_dense, block_m=32, block_k=32)
    x = rng.normal(0, 0.1, size=(n, 16)).astype(np.float32)
    out, chk = spmm_abft(bell, jnp.asarray(x), interpret=True, block_g=32)
    out_np = np.asarray(out).astype(np.float64)
    out_np[5, 3] += 2e-5                       # below tau = 1e-4
    div = abs(float(chk.predicted) - float(out_np.sum()))
    assert div < 1e-4


def test_fused_sparse_layer_detects_fault():
    """Core-path (BCOO) fused layer check catches a corrupted H_out."""
    ds = make_reduced("cora", scale=8, seed=2)
    s_sp, h_sp, _ = dataset_to_sparse(ds)
    params = init_gcn(jax.random.PRNGKey(2), ds.stats.layer_dims)
    h_out, (chk,) = gcn_layer(make_backend(s_sp, CFG), h_sp,
                              params["layers"][0]["w"], CFG)
    bad = np.asarray(h_out).astype(np.float64)
    bad[11, 7] += 10.0 * max(float(np.abs(bad).max()), 1.0)
    div = abs(float(chk.predicted) - float(bad.sum()))
    assert div > 1e-4
    clean = abs(float(chk.predicted) - float(chk.actual))
    assert clean < 1e-4


# ---------------------------------------------------------------------------
# hypothesis fuzzing of the same properties (skipped on minimal installs)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1),
           st.lists(st.integers(4, 48), min_size=3, max_size=6))
    def test_chain_property_fuzz(seed, dims):
        _chain_property(random_chain(seed, dims, scale=0.3))

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(48, 160),
           st.sampled_from(["split", "fused"]))
    def test_sparse_dense_parity_fuzz(seed, n, mode):
        _parity_property(seed, n, f=12, h=8, c=4, mode=mode)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_spmm_fault_fuzz(seed):
        _spmm_fault_property(seed, THRESHOLDS[0])
