"""The plain reference and its control."""
import numpy as np

from bench import graphs
from bench.references import gcn as ref


def test_bf16_rounding_matches_known_values():
    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 3 * 2**-9, 3.14159265], np.float32)
    got = ref.to_bf16(x)
    # 1 + 2^-8 is a tie between 1 and 1 + 2^-7: to even, 1
    assert got[0] == 1.0 and got[1] == 1.0
    assert got[2] == np.float32(1.0 + 2**-7)
    assert abs(got[3] - 3.140625) == 0.0


def test_high_is_coarser_than_highest_but_close():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 96)).astype(np.float32)
    b = rng.standard_normal((96, 16)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    e_hi = np.abs(ref.matmul(a, b, "highest") - exact).max()
    e_lo = np.abs(ref.matmul(a, b, "high") - exact).max()
    assert e_lo > 3 * e_hi
    assert e_lo < 1e-3 * np.abs(exact).max()


def test_coo_and_dense_aggregation_agree():
    g = {"nodes": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
         "mean_degree": 4.5, "feature_nnz": 6}
    s, h0 = graphs.request_pool(g, 32, 1, seed=3)[0]
    r, c = np.nonzero(s)
    w = [np.full((32, 4), 0.1, np.float32), np.full((4, 2), 0.2, np.float32)]
    dense = ref.forward(ref.dense_aggregate(s, "highest"), h0, w, "highest")
    coo = ref.forward(ref.coo_aggregate(r, c, s[r, c], s.shape[0], "highest"),
                      h0, w, "highest")
    np.testing.assert_allclose(dense, coo, rtol=1e-5, atol=1e-7)
