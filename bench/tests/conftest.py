"""Shared fixtures of the benchmark's own tests (run with
``JAX_PLATFORMS=cpu python -m pytest bench/tests``; outside the repo's
``testpaths``).  Runs here use tiny shapes and Pallas interpret mode."""
import copy
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


@pytest.fixture(scope="session")
def bench():
    return harness.load_benchmark()


@pytest.fixture(scope="session")
def peak():
    return harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"]


def cut_graph(graph, nodes, edges, feature_nnz):
    """A configuration's ``graph`` section at other counts, with the same
    topology; an LFR graph's largest degree and community sizes scaled
    down to fit."""
    out = {**graph, "nodes": nodes, "undirected_edges": edges,
           "feature_nnz": feature_nnz}
    if graph.get("topology") == "lfr":
        out["max_degree"] = min(graph["max_degree"], nodes // 10)
        out["community_max"] = min(graph["community_max"], nodes // 5)
        out["community_min"] = min(graph["community_min"],
                                   out["community_max"] // 6)
    return out


def tiny(bench, workload, traffic_name=None):
    """The cell's own configuration and traffic (or the named traffic
    file), cut to a CPU-sized graph (same layer structure, widths cut to
    keep interpret mode quick)."""
    cell = harness.find(bench["workloads"], workload, "workload")
    conf = harness.find(bench["configs"], cell["config"], "configuration")
    config = copy.deepcopy(harness.load_json(harness.ROOT / conf["file"]))
    traffic = copy.deepcopy(harness.load_json(
        harness.BENCH / "traffic" / f"{traffic_name or cell['traffic']}.json"))
    config["layer_dims"] = [64, 16, config["layer_dims"][-1]]
    config["graph"] = cut_graph(config["graph"], 300, 700, 3000)
    if "pool" in traffic:
        traffic.update(pool=24, profile_graphs=32, warm_requests=16)
        traffic["graphs"]["nodes"]["max"] = 140
        traffic["graphs"]["feature_nnz"] = 8
        if "rate_per_s" in traffic:
            traffic["rate_per_s"] = 8
    return config, traffic


@pytest.fixture
def run_tiny(bench, peak):
    """Drive one tiny run of a cell through the harness, as a function."""
    def go(workload, seconds=1.0, seed=2**31 + 11, traffic=None,
           metrics=None):
        config, traffic = tiny(bench, workload, traffic)
        if metrics is None:
            metrics = harness.metrics_for(bench, workload, False)
        cell = harness.find(bench["workloads"], workload, "workload")
        return harness.measure(
            config, traffic, metrics, seed, seconds,
            False, chips=cell["chips"], t_start=0.0, peak=peak,
            require_compiled=lambda interpret: None)
    return go
