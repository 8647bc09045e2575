"""Each traffic mix driven end to end at a tiny size on the CPU (Pallas in
interpret mode), through the harness called as a function; and the
command's refusal to run without a TPU."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
KEYS = ("correct", "attempted", "failed", "metrics", "checks")


@pytest.mark.parametrize("workload", ["pubmed.full", "cora.full",
                                      "pubmed.stream",
                                      "pubmed.full_clustered"])
def test_cell_runs_and_is_correct(bench, run_tiny, workload):
    out = run_tiny(workload)
    for key in KEYS:
        assert key in out
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in harness.metrics_for(bench, workload, False)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_backlog_traffic_runs_and_is_correct(run_tiny):
    """The closed-loop backlog mix, kept as data for a later cell (it left
    BENCHMARK.json for want of steadiness), still drives end to end."""
    specs = [{"name": "graphs_per_s", "unit": "graphs/s"},
             {"name": "setup_s", "unit": "s"}]
    out = run_tiny("pubmed.stream", traffic="backlog", metrics=specs)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"graphs_per_s", "setup_s"}
    assert out["counters"]["window_served"] > 0


def test_every_cell_has_its_metrics(bench):
    for cell in bench["workloads"]:
        e2e = harness.metrics_for(bench, cell["name"], False)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert harness.metrics_for(bench, cell["name"], True)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
        for cell in m.get("workloads", []):
            harness.find(bench["workloads"], cell, "workload")


def test_command_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, "bench/run.py", "--workload", "cora.full",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr


def test_harness_names_no_cell_config_or_metric(bench):
    text = "".join((harness.BENCH / f).read_text()
                   for f in ("run.py", "harness.py"))
    drivers = sorted((harness.BENCH / "drivers").glob("*.py"))
    names = ([c["name"] for c in bench["workloads"]]
             + [c["name"] for c in bench["configs"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
             + [f.stem for f in drivers if not f.stem.startswith("_")])
    for name in names:
        assert name not in text, name
    for flag in ("fused_layer", "fused_network", "vmem_budget", "interpret="):
        for f in drivers:
            assert flag not in f.read_text(), (flag, f.name)


ECHO = """
def run(ctx):
    ctx.counters["chips"] = ctx.chips
    ctx.setup_end = ctx.clock()
    ctx.window_s = ctx.seconds
    ctx.attempted = 1
    ctx.check("echo_gap", 0.0, 0.0)
"""


def test_a_driver_added_as_a_file_runs(monkeypatch, tmp_path, peak):
    """A new cell brings its driver, traffic, configuration and metric as
    new files; the harness finds them by name and hands the driver the
    cell's chips."""
    for d in ("drivers", "traffic", "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "drivers" / "echo.py").write_text(ECHO)
    (tmp_path / "traffic" / "echo_mix.json").write_text('{"driver": "echo"}')
    (tmp_path / "metrics" / "echo_chips.py").write_text(
        "def read(run):\n    return run.ctx.counters['chips']\n")
    (tmp_path / "echo.json").write_text('{"name": "echo-config"}')
    monkeypatch.setattr(harness, "BENCH", tmp_path)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    bench = {"configs": [{"name": "echo-config", "file": "echo.json"}],
             "workloads": [{"name": "echo.cell", "config": "echo-config",
                            "traffic": "echo_mix", "chips": 4}],
             "end_to_end": [{"name": "echo_chips", "unit": "chips"}],
             "per_layer": []}
    out = harness.run_cell("echo.cell", 2**31 + 3, 0.1, False, bench=bench,
                           t_start=0.0, peak=peak,
                           require_compiled=lambda interpret: None)
    assert out["correct"] is True
    assert out["counters"]["chips"] == 4
    assert out["metrics"]["echo_chips"] == {"value": 4.0, "unit": "chips"}


@pytest.mark.parametrize("name", ["no_such_driver", "_stream",
                                  "../harness", "sub/full_graph"])
def test_unknown_or_private_driver_is_refused(name):
    with pytest.raises((FileNotFoundError, ValueError)) as err:
        harness.load_driver(name)
    assert str(harness.BENCH / "drivers" / f"{name}.py") in str(err.value)


def test_result_line_is_json(run_tiny):
    out = run_tiny("cora.full", seconds=0.2)
    json.loads(json.dumps(out))
