"""One run of one benchmark cell, driven by ``BENCHMARK.json``.

Nothing here names a cell, a configuration, a traffic mix, a driver or a
metric.  The cell's entry names its configuration (a file of sizes under
``bench/configs``) and its traffic mix (a data file under
``bench/traffic`` whose ``driver`` names a load generator,
``bench/drivers/<driver>.py``, whose ``run(ctx)`` is loaded by path); each
metric is read by ``bench/metrics/<name>.py``, whose ``read(run)`` returns a
number or ``None`` when there is nothing to read.  The driver gets the
cell's ``chips`` in ``ctx.chips``.

So a new configuration, even of another model on a mesh of chips, comes as
new files and new entries only: its sizes under ``bench/configs``; a
traffic file that names its driver; the driver ``bench/drivers/<driver>.py``
(shared pieces in ``bench/drivers/__init__.py``); its plain reference under
``bench/references``; its metric readers under ``bench/metrics``; and its
kernel's ``(ops, bytes)`` in a new module beside ``bench/cost.py``, which
stays the yardstick of the GCN aggregation kernel.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    config: dict
    peak: dict
    ctx: Any                              # drivers.Ctx after the run
    setup_time: float
    trace: Any = None                     # trace_reduce.Trace (traced runs)


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The cell's end-to-end metrics (untraced) or per-layer metrics
    (traced): those that list the cell, or list no cells at all."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def _load(path: pathlib.Path, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str) -> Callable[[Run], Optional[float]]:
    return _load(BENCH / "metrics" / f"{name}.py", "bench_metric_", name).read


def load_driver(name: str) -> Callable[[Any], None]:
    """The ``run(ctx)`` of ``bench/drivers/<name>.py``.  A traffic file
    names it, so the name is checked: no private helper (``_...``), no path
    outside that directory, and a file that exists."""
    path = BENCH / "drivers" / f"{name}.py"
    if name.startswith("_") or "/" in name or "\\" in name:
        raise ValueError(f"{name!r} is not a driver's name: {path} is a "
                         "private helper or lies outside the drivers")
    if not path.is_file():
        raise FileNotFoundError(f"no driver {name!r}: {path} does not exist")
    return _load(path, "bench_driver_", name).run


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: dict, **kw) -> Dict[str, Any]:
    """Run one cell of ``BENCHMARK.json`` once: load its configuration and
    traffic files and :func:`measure`."""
    cell = find(bench["workloads"], workload, "workload")
    conf_entry = find(bench["configs"], cell["config"], "configuration")
    config = load_json(ROOT / conf_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return measure(config, traffic, metrics_for(bench, workload, trace), seed,
                   seconds, trace, chips=cell["chips"], **kw)


def measure(config: dict, traffic: dict, metric_specs: List[dict],
            seed: int, seconds: float, trace: bool, *, chips: int,
            t_start: float, peak: dict,
            require_compiled: Callable[[bool], None],
            trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """Drive one run and return its result line (``device`` apart from
    what the run itself measured)."""
    from bench import drivers

    ctx = drivers.Ctx(config=config, traffic=traffic, seed=seed,
                      seconds=seconds,
                      trace_dir=trace_dir if trace else None,
                      require_compiled=require_compiled, chips=chips)
    load_driver(traffic["driver"])(ctx)
    run = Run(config=config, peak=peak, ctx=ctx,
              setup_time=ctx.setup_end - t_start)
    device: Dict[str, Any] = {"memory_peak_bytes": ctx.memory_peak_bytes}
    breakdown = None
    if trace:
        from bench import trace_reduce
        run.trace = trace_reduce.load(trace_reduce.find_trace_file(trace_dir))
        window = run.trace.window("bench.window")
        device["busy_s"] = trace_reduce.busy_ns(run.trace, window) * 1e-9
        device["window_s"] = (window[1] - window[0]) * 1e-9
        breakdown = {
            "device_ops": [list(x) for x in
                           trace_reduce.top_ops(run.trace, window)],
            "idle_gaps": [list(x) for x in
                          trace_reduce.idle_by_span(run.trace, window)]}
    metrics = {}
    for spec in metric_specs:
        value = load_reader(spec["name"])(run)
        if value is None:
            print(f"bench: metric {spec['name']} found nothing to read",
                  file=sys.stderr)
            continue
        metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    out = {"correct": all(v <= limit for _, v, limit in ctx.checks),
           "attempted": ctx.attempted, "failed": ctx.failed,
           "metrics": metrics, "device_extra": device,
           "counters": ctx.counters}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": v, "limit": limit}
                     for name, v, limit in ctx.checks}
    return out
