"""Inputs come from the seed: same seed, same inputs; sizes are a fixed set."""
import hashlib

import numpy as np
import pytest

from bench import graphs, harness

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


def _digest(g):
    h = hashlib.sha256()
    f = g.features[0]
    for a in (g.s.row, g.s.col, g.s.data, f.row, f.col, f.data):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("graph, digest", [
    ({"nodes": 300, "undirected_edges": 700, "feature_nnz": 3000},
     "f1cb631f066223ef871b0be5eb038f5d763585e366156c8b2d412ed2b9b199ae"),
    (None,
     "104c33057fff69ed0304e14d62613842a768c86daa1576dda393b306b8f2dd4f")])
def test_erdos_renyi_inputs_are_pinned(graph, digest):
    """The Erdos-Renyi cells read the inputs they read before graphs got a
    second topology (digests taken before it), at the tiny cut and at
    Cora's full counts."""
    config = harness.load_json(harness.BENCH / "configs" / "gcn-cora.json")
    if graph is not None:
        config = {**config, "layer_dims": [64, 16, 7], "graph": graph}
    assert _digest(graphs.make_graph(config, 2**31 + 11, 1)) == digest


@pytest.fixture(scope="module")
def clustered():
    return harness.load_json(harness.BENCH / "configs" /
                             "gcn-pubmed-clustered.json")


def test_lfr_graph_matches_the_published_counts(clustered):
    g = clustered["graph"]
    n, m = g["nodes"], g["undirected_edges"]
    seed = 2**31 + 21
    edges, comm = graphs.lfr_edges(g, graphs._stream(clustered["name"], seed))
    assert edges.shape == (m, 2)
    assert (edges[:, 0] < edges[:, 1]).all()            # no self loops
    assert np.unique(edges[:, 0] * n + edges[:, 1]).size == m
    assert np.bincount(edges.ravel(), minlength=n).max() <= g["max_degree"]
    intra = (comm[edges[:, 0]] == comm[edges[:, 1]]).mean()
    assert abs(intra - (1 - g["mixing"])) <= 0.02
    # node ids contiguous by community, communities in the order drawn
    assert comm.size == n and (np.diff(comm) >= 0).all()
    sizes = np.bincount(comm)
    assert sizes.min() >= g["community_min"]
    assert sizes.max() <= g["community_max"]
    whole = graphs.make_graph(clustered, seed, 1)
    assert whole.s.data.size == 2 * m + n
    assert whole.features[0].data.size == g["feature_nnz"]
    assert whole.features[0].shape == (n, clustered["layer_dims"][0])


def _tiles(s, block):
    return np.unique((s.row // block) * s.shape[0] + s.col // block)


def test_lfr_graph_is_seeded(clustered):
    """Same seed, same graph; another seed, another labelling of the one
    wiring, which stores the same block-ELL tiles (the same work)."""
    from conftest import cut_graph
    config = {**clustered,
              "graph": cut_graph(clustered["graph"], 2000, 5000, 30000)}
    a = graphs.make_graph(config, 2**31 + 7, 1)
    b = graphs.make_graph(config, 2**31 + 7, 1)
    c = graphs.make_graph(config, 2**31 + 8, 1)
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(c)
    assert not np.array_equal(a.s.todense(), c.s.todense())
    np.testing.assert_array_equal(_tiles(a.s, config["block"]),
                                  _tiles(c.s, config["block"]))
    assert np.unique(a.s.row * 2000 + a.s.col).size == 2 * 5000 + 2000
