#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this machine holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` runs the window under the JAX profiler and reports its
per-layer metrics, with the device's busy time and a breakdown.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and last ``checks``: each number compared beside its
limit); the same comparisons are the last lines of standard error.  With no
TPU, fewer chips than the cell asks for, or a device kind missing from
``bench/peaks.json``, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class NoChip(RuntimeError):
    pass


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """Persistent compilation cache at the checkout's fixed path, every
    program cached, so only a cell's first run in a checkout compiles."""
    import jax

    from repro.kernels.runtime import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def chip_or_fail(jax, chips: int, peaks: dict):
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"default backend is {backend!r}, not 'tpu'")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devices


def require_compiled(interpret: bool) -> None:
    if interpret:
        raise NoChip("the program resolved its kernels to interpret mode")


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness

    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload, "workload")
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    jax = setup_jax()
    try:
        devices = chip_or_fail(jax, cell["chips"], peaks)
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
            out = harness.run_cell(
                args.workload, args.seed, args.seconds, bool(args.trace),
                bench=bench, t_start=T_START,
                peak=peaks[devices[0].device_kind],
                require_compiled=require_compiled, trace_dir=tdir)
    except NoChip as err:
        print(f"bench: {err}; nothing was run", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              **out.pop("device_extra")}
    checks = out.pop("checks")
    line = {**out, "device": device, "checks": checks}
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
