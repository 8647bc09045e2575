"""The control (the reference one precision step down, float32 at
``Precision.HIGH``: three bfloat16 passes) must fail the ``logit_gap``
limit that the program passes.  Here the scheme is emulated on the host at
a tiny size; ``bench/control.py`` reads it on the chip at full size."""
import numpy as np
import pytest

from bench import drivers, graphs, harness
from bench.references import gcn as ref


@pytest.mark.parametrize("workload", ["pubmed.full", "cora.full"])
def test_control_fails_whole_graph_limit(bench, workload):
    from conftest import tiny
    config, traffic = tiny(bench, workload)
    limit = harness.load_json(
        harness.ROOT / harness.find(bench["configs"], config["name"],
                                    "configuration")["file"]
    )["check"]["logit_gap"]
    config = dict(config)
    config["graph"] = {"nodes": 2000, "undirected_edges": 5000,
                       "feature_nnz": 30000}
    config["layer_dims"] = [256, 16, config["layer_dims"][-1]]
    seed = 5
    w = [np.asarray(x) for x in drivers.make_weights(seed, config["layer_dims"])]
    g = graphs.make_graph(config, seed, 1)
    s, h0 = g.s, g.features[0].todense()
    exact = ref.forward(ref.coo_aggregate(s.row, s.col, s.data, s.shape[0],
                                          "highest"), h0, w, "highest")
    control = ref.forward(ref.coo_aggregate(s.row, s.col, s.data, s.shape[0],
                                            "high"), h0, w, "high")
    assert ref.gap(control, exact) > limit


def test_control_fails_stream_limit(bench):
    from conftest import tiny
    config, traffic = tiny(bench, "pubmed.stream")
    limit = config["check"]["logit_gap"]
    dims = [500, 16, 3]
    w = [np.asarray(x) for x in drivers.make_weights(3, dims)]
    pool = graphs.request_pool(
        {"nodes": {"median": 32, "sigma": 0.8, "min": 8, "max": 276},
         "mean_degree": 4.5, "feature_nnz": 50}, 500, 64, seed=3)
    worst, scale = 0.0, 0.0
    for s, h0 in pool:
        exact = ref.forward(ref.dense_aggregate(s, "highest"), h0, w,
                            "highest")
        control = ref.forward(ref.dense_aggregate(s, "high"), h0, w, "high")
        worst = max(worst, float(np.abs(control - exact).max()))
        scale = max(scale, float(np.abs(exact).max()))
    assert worst / scale > limit
