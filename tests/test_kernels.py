"""Pallas kernel validation: interpret=True (CPU) against the pure-jnp
oracles, swept over shapes and dtypes.  TPU is the compile target; interpret
mode executes the same kernel body for correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abft import ABFTConfig
from repro.kernels.matmul_abft.ops import matmul_abft
from repro.kernels.matmul_abft.ref import matmul_abft_ref
from repro.kernels.flash_checksum.ops import flash_attention_checksum
from repro.kernels.flash_checksum.ref import flash_checksum_ref
from repro.kernels.spmm_abft.layout import coo_to_block_ell, dense_to_block_ell
from repro.engine import gcn_layer, make_backend
from repro.kernels.spmm_abft.ops import spmm_abft
from repro.kernels.spmm_abft.ref import spmm_abft_ref

CFG = ABFTConfig(mode="fused", threshold=1e-2, relative=True)


def rnd(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# matmul_abft
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128),
    (256, 384, 128),
    (200, 100, 72),      # padding path
    (128, 512, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_abft_matches_ref(m, k, n, dtype):
    a = rnd(m * 7 + 1, (m, k), dtype)
    b = rnd(n * 13 + 2, (k, n), dtype)
    c, chk = matmul_abft(a, b, block_m=128, block_n=128, block_k=128,
                         interpret=True)
    c_ref, actual_ref, _ = matmul_abft_ref(a, b,
                                           b.astype(jnp.float32).sum(1, keepdims=True))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(c, np.float32),
                               np.asarray(c_ref, np.float32),
                               rtol=tol, atol=tol * 8)
    # checksum consistency: predicted ≈ actual on clean data
    rel = abs(float(chk.predicted) - float(chk.actual)) / \
        max(1.0, abs(float(chk.actual)))
    assert rel < (5e-2 if dtype == jnp.bfloat16 else 1e-4), rel
    assert not bool(chk.flag(ABFTConfig(mode="fused", threshold=0.2,
                                        relative=True)))


def test_matmul_abft_detects_corruption():
    """The kernel check must catch output corruption: emulate by comparing
    a corrupted C's true sum against the kernel's predicted checksum."""
    a = rnd(3, (128, 128), jnp.float32)
    b = rnd(4, (128, 128), jnp.float32)
    c, chk = matmul_abft(a, b, interpret=True)
    c_bad = c.at[7, 9].add(100.0)
    diff = abs(float(chk.predicted) - float(c_bad.sum()))
    assert diff > 50.0


# ---------------------------------------------------------------------------
# flash_checksum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kh,t,s,dh", [
    (1, 4, 4, 128, 128, 64),
    (2, 4, 2, 128, 256, 64),     # GQA
    (1, 4, 1, 256, 256, 128),    # MQA
    (1, 2, 2, 100, 128, 64),     # q padding path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_checksum_matches_ref(b, h, kh, t, s, dh, dtype):
    q = rnd(1, (b, t, h, dh), dtype)
    k = rnd(2, (b, s, kh, dh), dtype)
    v = rnd(3, (b, s, kh, dh), dtype)
    w_or = rnd(4, (h, dh), jnp.float32)

    o, ex = flash_attention_checksum(q, k, v, w_or, causal=True,
                                     block_q=128, block_k=128, interpret=True)
    g = h // kh
    k_e = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    v_e = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, dh)
    vr = jnp.einsum("nsd,nd->ns",
                    v_e.astype(jnp.float32),
                    jnp.tile(w_or, (b, 1)).reshape(b * h, dh))[..., None]
    o_ref, ex_ref = flash_checksum_ref(qf, k_e, v_e, vr.astype(dtype),
                                       causal=True)
    o_ref = o_ref.reshape(b, h, t, dh).transpose(0, 2, 1, 3)
    ex_ref = ex_ref[..., 0].reshape(b, h, t).transpose(0, 2, 1)

    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol * 4)
    np.testing.assert_allclose(np.asarray(ex), np.asarray(ex_ref),
                               rtol=tol * 2, atol=tol * 8)


def test_flash_checksum_equals_chain_identity():
    """Σ o_extra must equal eᵀ(A·V·W_o)e computed the slow way."""
    b, h, t, dh, d = 1, 2, 128, 64, 96
    q = rnd(11, (b, t, h, dh), jnp.float32)
    k = rnd(12, (b, t, h, dh), jnp.float32)
    v = rnd(13, (b, t, h, dh), jnp.float32)
    wo = rnd(14, (h * dh, d), jnp.float32)
    w_or = wo.sum(axis=1).reshape(h, dh)

    o, ex = flash_attention_checksum(q, k, v, w_or, causal=True,
                                     interpret=True)
    out = o.reshape(b, t, h * dh) @ wo
    np.testing.assert_allclose(float(ex.sum()), float(out.sum()),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# spmm_abft (block-ELL sparse aggregation)
# ---------------------------------------------------------------------------

def sparse_rnd(key, m, k, density, scale=0.2):
    rng = np.random.default_rng(key)
    dense = np.where(rng.random((m, k)) < density,
                     rng.normal(0, scale, size=(m, k)), 0.0)
    return dense.astype(np.float32)


@pytest.mark.parametrize("m,k,g,bm,bk,density", [
    (128, 128, 128, 32, 32, 0.10),
    (256, 256, 64, 64, 64, 0.05),
    (100, 100, 20, 32, 32, 0.08),     # ragged rows/cols/features (padding)
    (200, 130, 7, 64, 32, 0.15),      # rectangular + ragged everything
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_abft_matches_ref(m, k, g, bm, bk, density, dtype):
    dense = sparse_rnd(m * 3 + k, m, k, density)
    bell = coo_to_block_ell(*np.nonzero(dense), dense[np.nonzero(dense)],
                            (m, k), block_m=bm, block_k=bk)
    np.testing.assert_allclose(bell.todense(), dense)
    x = rnd(g * 11 + 5, (k, g), dtype)
    xr = x.astype(jnp.float32).sum(axis=1, keepdims=True)

    out, chk = spmm_abft(bell, x, interpret=True, block_g=bm)
    out_ref, actual_ref, extra_ref = spmm_abft_ref(jnp.asarray(dense), x, xr)

    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               rtol=tol, atol=tol * 8)
    scale = max(1.0, abs(float(actual_ref)))
    assert abs(float(chk.actual) - float(actual_ref)) < tol * scale
    assert abs(float(chk.predicted) - float(extra_ref.sum())) < tol * scale
    # checksum consistency on clean data
    rel = abs(float(chk.predicted) - float(chk.actual)) / scale
    assert rel < (5e-2 if dtype == jnp.bfloat16 else 1e-4), rel
    assert not bool(chk.flag(ABFTConfig(mode="fused", threshold=0.2,
                                        relative=True)))


def test_spmm_abft_detects_corruption():
    dense = sparse_rnd(42, 128, 128, 0.1)
    bell = dense_to_block_ell(dense, block_m=32, block_k=32)
    x = rnd(6, (128, 16), jnp.float32)
    out, chk = spmm_abft(bell, x, interpret=True, block_g=32)
    bad = out.at[17, 3].add(100.0)
    diff = abs(float(chk.predicted) - float(bad.sum()))
    assert diff > 50.0


def test_spmm_abft_carried_column_chain():
    """Threading x_r = H w_r through the kernel yields the eq.-4 chain
    prediction s_c H w_r — the full fused GCN-ABFT layer check."""
    n, f, g = 160, 24, 16
    dense = sparse_rnd(7, n, n, 0.07)
    bell = dense_to_block_ell(dense, block_m=32, block_k=32)
    h = rnd(8, (n, f), jnp.float32) * 0.3
    w = rnd(9, (f, g), jnp.float32)

    cfg = ABFTConfig(mode="fused", dtype=jnp.float32)
    bk = make_backend(bell, cfg, backend="block_ell", block_g=32,
                      interpret=True)
    h_out, (chk,) = gcn_layer(bk, h, w, cfg)
    ref = dense @ np.asarray(h @ w)
    np.testing.assert_allclose(np.asarray(h_out), ref, rtol=1e-5, atol=1e-5)
    s_c = dense.astype(np.float64).sum(axis=0)
    w_r = np.asarray(w, np.float64).sum(axis=1)
    pred_ref = float(s_c @ (np.asarray(h, np.float64) @ w_r))
    scale = max(1.0, abs(pred_ref))
    assert abs(float(chk.predicted) - pred_ref) / scale < 1e-5
    assert abs(float(chk.actual) - ref.sum()) / scale < 1e-4


def test_spmm_abft_empty_trailing_column_block():
    """All nonzeros in the leading columns: padded_cols < K, so ops must
    TRIM x instead of padding it (regression: negative jnp.pad widths)."""
    dense = np.zeros((64, 64), np.float32)
    dense[:, :30] = sparse_rnd(11, 64, 30, 0.3)
    bell = dense_to_block_ell(dense, block_m=32, block_k=32)
    assert bell.padded_cols < 64
    x = rnd(12, (64, 8), jnp.float32)
    out, chk = spmm_abft(bell, x, interpret=True, block_g=32)
    np.testing.assert_allclose(np.asarray(out), dense @ np.asarray(x),
                               rtol=1e-5, atol=1e-5)
    rel = abs(float(chk.predicted) - float(chk.actual)) / \
        max(1.0, abs(float(chk.actual)))
    assert rel < 1e-4
