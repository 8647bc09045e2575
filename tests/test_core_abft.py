"""Unit + property tests for the ABFT core (the paper's contribution)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (
    ABFTConfig,
    Check,
    check_chain,
    check_matmul,
    checked_matmul,
    fused_chain_checksum,
    kahan_total,
    predicted_matmul_checksum,
    summarize,
)
from repro.core.abft import report_traces
from repro.core.checksum import col_checksum, row_checksum, total_checksum
from repro.engine import gcn_layer, make_backend

CFG = ABFTConfig(mode="fused", threshold=1e-3, relative=True)


def rand(shape, seed, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


# ---------------------------------------------------------------------------
# checksum identities
# ---------------------------------------------------------------------------

dims = st.integers(min_value=1, max_value=17)


@settings(max_examples=25, deadline=None)
@given(m=dims, k=dims, n=dims, seed=st.integers(0, 2**20))
def test_matmul_checksum_identity_int(m, k, n, seed):
    """e^T (AB) e == (e^T A)(B e) exactly over integers."""
    rng = np.random.default_rng(seed)
    a = rng.integers(-5, 6, size=(m, k)).astype(np.int64)
    b = rng.integers(-5, 6, size=(k, n)).astype(np.int64)
    lhs = (a @ b).sum()
    rhs = a.sum(0) @ b.sum(1)
    assert lhs == rhs


@settings(max_examples=25, deadline=None)
@given(m=dims, k=dims, j=dims, n=dims, seed=st.integers(0, 2**20))
def test_three_chain_identity_int(m, k, j, n, seed):
    """The paper's eq. (4): e^T (SHW) e == (e^T S) H (W e), exact in ints."""
    rng = np.random.default_rng(seed)
    s = rng.integers(-3, 4, size=(m, k)).astype(np.int64)
    h = rng.integers(-3, 4, size=(k, j)).astype(np.int64)
    w = rng.integers(-3, 4, size=(j, n)).astype(np.int64)
    lhs = (s @ h @ w).sum()
    rhs = (s.sum(0) @ h) @ w.sum(1)
    assert lhs == rhs


def test_fused_chain_checksum_float():
    mats = tuple(rand((d1, d2), i) for i, (d1, d2) in
                 enumerate([(8, 16), (16, 12), (12, 6)]))
    pred = fused_chain_checksum(mats, dtype=jnp.float32)
    out = mats[0] @ mats[1] @ mats[2]
    np.testing.assert_allclose(pred, out.sum(), rtol=2e-4)


def test_predicted_matmul_checksum_batched():
    a = rand((3, 8, 5), 0)
    b = rand((3, 5, 7), 1)
    pred = predicted_matmul_checksum(a, b)
    act = jnp.einsum("bij,bjk->bik", a, b).sum((-2, -1))
    np.testing.assert_allclose(pred, act, rtol=3e-4, atol=1e-4)


def test_kahan_total_precision():
    # f32 naive summation loses ~1e-2 on this adversarial stream; Kahan holds.
    x = jnp.concatenate([jnp.full((1,), 1e8), jnp.full((4096,), 0.1),
                         jnp.full((1,), -1e8)]).reshape(1, -1)
    naive = float(total_checksum(x, jnp.float32))
    kah = float(kahan_total(x))
    exact = 0.1 * 4096
    assert abs(kah - exact) < 0.05          # compensation term still f32
    assert abs(kah - exact) <= abs(naive - exact) * 1e-3


# ---------------------------------------------------------------------------
# checks: clean data passes, corrupted data flags
# ---------------------------------------------------------------------------

def test_checked_matmul_clean():
    a, b = rand((64, 32), 0), rand((32, 48), 1)
    c, chk = checked_matmul(a, b, CFG)
    assert not bool(chk.flag(CFG))
    np.testing.assert_allclose(c, a @ b, rtol=1e-6)


@pytest.mark.parametrize("mode", ["split", "fused"])
def test_gcn_layer_detects_output_corruption(mode):
    s = jnp.abs(rand((32, 32), 0)) / 32
    h = rand((32, 24), 1)
    w = rand((24, 16), 2)
    cfg = ABFTConfig(mode=mode, threshold=1e-3, relative=True)
    if mode == "split":
        h_out, checks = gcn_layer(make_backend(s, cfg), h, w, cfg)
        checks = list(checks)
    else:
        h_out, (chk,) = gcn_layer(make_backend(s, cfg), h, w, cfg)
        checks = [chk]
    assert not bool(summarize(checks, cfg).flag)

    # corrupt one element of the final output -> actual checksum diverges
    bad = h_out.at[3, 5].add(100.0)
    actual_bad = bad.sum()
    chk_bad = dataclasses.replace(checks[-1], actual=actual_bad)
    assert bool(chk_bad.flag(cfg))


def test_split_and_fused_agree_on_final_prediction():
    """The fused prediction equals split's second-check prediction (same
    s_c·x_r contraction) — the savings come from dropping check state, not
    from changing the final comparison."""
    s = jnp.abs(rand((20, 20), 3)) / 20
    h = rand((20, 12), 4)
    w = rand((12, 8), 5)
    split = dataclasses.replace(CFG, mode="split")
    _, (c1, c2) = gcn_layer(make_backend(s, split), h, w, split)
    _, (cf,) = gcn_layer(make_backend(s, CFG), h, w, CFG)
    np.testing.assert_allclose(c2.predicted, cf.predicted, rtol=1e-6)


def test_zero_column_masking_tradeoff():
    """Paper §III: a zero column in S masks first-step faults from GCN-ABFT
    while split ABFT still catches them."""
    s = jnp.abs(rand((16, 16), 6)) / 16
    s = s.at[:, 7].set(0.0)          # kill column 7
    h = rand((16, 8), 7)
    w = rand((8, 4), 8)
    cfg = ABFTConfig(mode="split", threshold=1e-4, relative=True)

    x = h @ w
    x_bad = x.at[7, 2].add(50.0)     # fault lands in row 7 of X
    # split check 1 sees sum(X) diverge
    c1 = check_matmul(h, w, x_bad, cfg)
    assert bool(c1.flag(cfg))
    # fused check: S @ X_bad is identical to S @ X (column 7 of S is zero)
    h_out_bad = s @ x_bad
    from repro.core.checksum import col_checksum as cc, row_checksum as rc
    pred = cc(s, jnp.float32) @ (h.astype(jnp.float32) @ rc(w, jnp.float32))
    diff = jnp.abs(pred - h_out_bad.sum())
    assert float(diff) < 1e-2        # fault invisible to the fused check


def test_chain_check_batched():
    a = jnp.abs(rand((2, 10, 10), 9))
    b = rand((10, 6), 10)
    c = rand((6, 4), 11)
    out = jnp.einsum("bij,jk,kl->bil", a, b, c)
    chk = check_chain([a, b, c], out, CFG)
    assert chk.predicted.shape == (2,)
    assert not bool(chk.flag(CFG))


# ---------------------------------------------------------------------------
# summarize: the compiled report reduction against plain numpy
# ---------------------------------------------------------------------------

# each granularity's corner shape: scalar, [n_graphs], [n_stripes],
# [n_stripes, width]
CHECK_SHAPES = {"layer": (), "graph": (5,), "stripe": (7,), "slot": (7, 3)}


def _np_summary(pairs, cfg):
    """Plain-numpy twin of ``summarize``: (flag, max_rel, n_checks)."""
    flag, rels, n = False, [], 0
    for p, a in pairs:
        d = np.abs(p - a)
        scale = np.maximum(np.float32(1.0), np.abs(a))
        with np.errstate(invalid="ignore"):      # inf / inf is NaN
            rels.append(np.max(d / scale))
        if cfg.relative:
            finite = np.where(np.isfinite(scale), scale, np.float32(1.0))
            ok = d <= np.float32(cfg.threshold) * finite
        else:
            ok = d <= np.float32(cfg.threshold)
        flag |= bool(np.any(~ok))
        n += a.size
    return (np.bool_(flag), np.float32(np.max(rels)), np.float32(n))


def _corner_pair(shape, case, seed):
    rng = np.random.default_rng(seed)
    # |actual| on both sides of 1, so the relative scale is exercised
    a = np.asarray(rng.normal(size=shape)
                   * 10.0 ** rng.integers(-2, 3, size=shape), np.float32)
    p = np.asarray(a * (1 + rng.normal(size=shape) * 1e-6), np.float32)
    flat_p, flat_a = p.reshape(-1), a.reshape(-1)
    last = flat_a.size - 1
    if case == "drift":        # over tau on one element only
        flat_p[last] = flat_a[last] + np.float32(0.5)
    elif case == "nan_predicted":
        flat_p[last] = np.nan
    elif case == "inf_actual":
        flat_a[last] = np.inf
    elif case == "neg_inf_actual":
        flat_a[last] = -np.inf
    return p, a


def _assert_report_equals(report, want):
    flag, max_rel, n = jax.device_get(report)
    assert (flag.dtype, max_rel.dtype, n.dtype) == (
        np.bool_, np.float32, np.float32)
    assert flag == want[0]
    # bit for bit; NaN (an unbounded divergence) only has to be NaN
    np.testing.assert_array_equal(max_rel, want[1])
    if np.isfinite(want[1]):
        assert max_rel.tobytes() == want[1].tobytes()
    assert n.tobytes() == want[2].tobytes()


@pytest.mark.parametrize("relative", [True, False])
@pytest.mark.parametrize("case", ["clean", "drift", "nan_predicted",
                                  "inf_actual", "neg_inf_actual"])
@pytest.mark.parametrize("granularity", ["layer", "graph", "stripe", "slot",
                                         "mixed"])
def test_summarize_matches_numpy(granularity, case, relative):
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=relative)
    grans = list(CHECK_SHAPES) if granularity == "mixed" else [granularity]
    pairs = [_corner_pair(CHECK_SHAPES[g], case if i == len(grans) - 1
                          else "clean", seed=i)
             for i, g in enumerate(grans)]
    checks = [Check(jnp.asarray(p), jnp.asarray(a), g)
              for g, (p, a) in zip(grans, pairs)]
    # a None in the list is dropped, as a mode=none layer's check is
    report = summarize(checks[:1] + [None] + checks[1:], cfg)
    _assert_report_equals(report, _np_summary(pairs, cfg))
    assert bool(report.flag) == (case != "clean")


@pytest.mark.parametrize("cfg, checks", [
    (CFG, []),
    (CFG, [None, None]),
    (ABFTConfig(mode="none"),
     [Check(jnp.float32(1.0), jnp.float32(9.0))]),
], ids=["empty", "all_none", "mode_none"])
def test_summarize_without_checks_reports_zero(cfg, checks):
    before = report_traces()
    report = summarize(checks, cfg)
    _assert_report_equals(report, (np.bool_(False), np.float32(0.0),
                                   np.float32(0.0)))
    assert report_traces() == before       # nothing to compile


def test_summarize_traces_once_per_check_structure():
    cfg = ABFTConfig(mode="fused", threshold=2.5e-3)   # this test's own
    checks = [Check(rand((7,), s), rand((7,), s), "stripe") for s in (1, 2)]
    before = report_traces()
    for _ in range(3):
        summarize(checks, cfg)
    assert report_traces() == before + 1
    summarize(checks[:1], cfg)                 # another structure
    summarize(checks, dataclasses.replace(cfg))   # an equal config
    assert report_traces() == before + 2
    # under an outer jit the body is a nested call of the same cache
    jax.jit(lambda cs: summarize(cs, cfg))(checks)
    assert report_traces() == before + 2


# ---------------------------------------------------------------------------
# GCN model end-to-end
# ---------------------------------------------------------------------------

def test_gcn_apply_and_grad():
    from repro.core.gcn import init_gcn
    from repro.engine import Graph, gcn_apply, gcn_loss
    n, f, h, c = 40, 12, 8, 4
    rng = np.random.default_rng(0)
    s = jnp.asarray(np.abs(rng.normal(size=(n, n))).astype(np.float32) / n)
    x0 = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, c, size=n))
    params = init_gcn(jax.random.PRNGKey(0), (f, h, c))
    logits, report = jax.jit(
        lambda p: gcn_apply(p, Graph(s, x0), CFG))(params)
    assert logits.shape == (n, c)
    assert not bool(report.flag)
    assert np.isfinite(np.asarray(logits)).all()

    (loss, rep), grads = jax.value_and_grad(
        lambda p: gcn_loss(p, Graph(s, x0), labels, None, CFG),
        has_aux=True)(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
