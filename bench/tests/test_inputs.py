"""Inputs come from the seed: same seed, same inputs; sizes are a fixed set."""
import numpy as np

from bench import graphs

G = {"nodes": {"median": 32, "sigma": 0.8, "min": 8, "max": 276},
     "mean_degree": 4.5, "feature_nnz": 50}


def test_request_pool_is_seeded():
    a = graphs.request_pool(G, 500, 8, seed=2**31 + 5)
    b = graphs.request_pool(G, 500, 8, seed=2**31 + 5)
    for (sa, ha), (sb, hb) in zip(a, b):
        np.testing.assert_array_equal(sa, sb)
        np.testing.assert_array_equal(ha, hb)


def test_sizes_are_one_multiset_for_every_seed():
    def sizes(seed):
        return sorted(s.shape[0] for s, _ in
                      graphs.request_pool(G, 500, 64, seed))
    assert sizes(1) == sizes(2**31 + 99)
    sz = graphs.lognormal_sizes(512, 32, 0.8, 8, 276)
    assert sz.min() >= 8 and sz.max() <= 276
    assert abs(np.median(sz) - 32) <= 1


def test_request_features_match_the_mix():
    s, h0 = graphs.request_pool(G, 500, 1, seed=7)[0]
    assert ((h0 > 0).sum(axis=1) == 50).all()
    np.testing.assert_allclose(h0.sum(axis=1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(s, s.T)


def test_exponential_gaps_have_the_rate():
    gaps = graphs.exponential_gaps(4000, 250.0)
    assert abs(gaps.mean() - 1 / 250.0) < 0.02 / 250.0


def test_whole_graph_matches_the_published_counts():
    cfg = {"name": "t", "layer_dims": [40, 16, 3],
           "graph": {"nodes": 500, "undirected_edges": 1100,
                     "feature_nnz": 4000}}
    g = graphs.make_graph(cfg, 9, 2)
    assert g.s.data.size == 2 * 1100 + 500
    assert len(g.features) == 2
    assert g.features[0].data.size == 4000
    assert not np.array_equal(g.features[0].todense(),
                              g.features[1].todense())
