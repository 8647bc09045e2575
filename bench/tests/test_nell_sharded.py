"""The four-chip cell ``nell.full_sharded`` at a tiny size on four virtual
CPU devices (Pallas in interpret mode), and the faults that exist only
across chips: each must make a run incorrect.

The device count of a process is fixed when JAX starts, so every run here
is a child process (``python bench/tests/test_nell_sharded.py <case>``)
with ``--xla_force_host_platform_device_count=4``; it prints one JSON line.
The readers of the device trace are checked on a hand-made trace of four
device planes: a CPU trace has none."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = pathlib.Path(__file__).resolve().parent
CELL = "nell.full_sharded"
SEED = 2**31 + 17
# the cell's per-layer readers that a CPU trace can feed (no device plane)
HOST_READERS = ("engine.host_ms_per_forward", "check.host_ms_per_forward",
                "shard.exchange_mb_per_forward", "forward.mfu_chips")


# -- the child process --------------------------------------------------------

def _plant(case):
    """Break the program under test in one way that exists only across
    chips."""
    import jax
    import jax.numpy as jnp

    from repro.engine import sharded
    from repro.kernels.spmm_abft import kernel

    if case == "shard_output_altered":
        # shard 1's output rows changed after its kernel wrote them
        real = kernel.spmm_abft_kernel

        def broken(*args, **kw):
            out, sums, extra = real(*args, **kw)
            hit = jax.lax.axis_index("graph") == 1
            return out.at[0, 0].add(jnp.where(hit, 0.5, 0.0)), sums, extra
        kernel.spmm_abft_kernel = broken
    elif case == "corner_psum_dropped":
        # each shard's partial corner taken for the global one
        def local(granularity, axis, out_l, sums_l, extra_l):
            return out_l, extra_l.sum(), sums_l.sum()
        sharded._partials = local
    elif case == "fault_not_on_its_shard":
        # the hook's global stripe given to every shard unchanged: no
        # shard but the first can ever reach it
        def global_index(run, inject, n_local, axis):
            return run(inject)
        sharded._owned = global_index
    elif case != "clean":
        raise ValueError(case)


def _child(case):
    sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]
    from bench import drivers, harness, trace_reduce
    from conftest import tiny

    _plant(case)
    bench = harness.load_benchmark()
    peak = harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"]
    config, traffic = tiny(bench, CELL)
    out = harness.measure(config, traffic,
                          harness.metrics_for(bench, CELL, False), SEED, 1.0,
                          False, chips=4, t_start=0.0, peak=peak,
                          require_compiled=lambda interpret: None)
    if case == "clean":
        # a traced run: the readers of the program's spans
        import tempfile
        with tempfile.TemporaryDirectory() as tdir:
            ctx = drivers.Ctx(config=config, traffic=traffic, seed=SEED + 1,
                              seconds=0.5, trace_dir=tdir, chips=4,
                              require_compiled=lambda interpret: None)
            harness.load_driver(traffic["driver"])(ctx)
            run = harness.Run(config=config, peak=peak, ctx=ctx,
                              setup_time=0.0, trace=trace_reduce.load(
                                  trace_reduce.find_trace_file(tdir)))
            out["traced"] = {n: harness.load_reader(n)(run)
                             for n in HOST_READERS}
            out["traced_counters"] = ctx.counters
            out["kernel_calls"] = ctx.kernel_calls
    out["config"] = config
    print(json.dumps(out, default=float))


def run_case(case):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, str(pathlib.Path(__file__)), case],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# -- the tests -----------------------------------------------------------------

@pytest.fixture(scope="module")
def clean():
    return run_case("clean")


def test_tiny_cell_is_correct_on_four_devices(bench, clean):
    from bench import harness
    assert clean["correct"] is True, clean["checks"]
    assert clean["attempted"] > 0 and clean["failed"] == 0
    want = {m["name"] for m in harness.metrics_for(bench, CELL, False)}
    assert set(clean["metrics"]) == want


def test_traced_run_reads_the_program_spans(clean):
    """The readers that a CPU trace can feed read a value; the exchange
    moves each layer's X and x_r at the stripes' padded rows, no more."""
    got = clean["traced"]
    for name in HOST_READERS:
        assert got[name] is not None and got[name] > 0, name
    dims = clean["config"]["layer_dims"]
    call = clean["kernel_calls"][0]
    rows = 4 * call["vals_shape"][0] * call["vals_shape"][2]
    want = sum(rows * (g + 1) * 4 for g in dims[1:]) / 1e6
    assert got["shard.exchange_mb_per_forward"] == pytest.approx(want)
    # one kernel call a shard a layer, each over a quarter of the stripes
    assert len(clean["kernel_calls"]) == 4 * (len(dims) - 1)


@pytest.mark.parametrize("case", ["shard_output_altered",
                                  "corner_psum_dropped",
                                  "fault_not_on_its_shard"])
def test_fault_across_chips_is_incorrect(case):
    out = run_case(case)
    assert out["correct"] is False, case
    failed = {n for n, c in out["checks"].items() if c["value"] > c["limit"]}
    assert failed == ({"logit_gap"} if case == "shard_output_altered"
                      else {"fault_missed"}), out["checks"]


def _trace(planes):
    from bench import trace_reduce
    ev = trace_reduce.Event
    return trace_reduce.Trace(
        devices={f"/device:TPU:{i}": [ev(name, a, b) for name, a, b in evs]
                 for i, evs in enumerate(planes)},
        spans=[ev("bench.window", 0, 100)])


def _run(trace, forwards=1):
    from bench import drivers, harness
    ctx = drivers.Ctx(config={}, traffic={}, seed=0, seconds=0.0,
                      trace_dir=None, chips=len(trace.devices),
                      require_compiled=lambda interpret: None)
    ctx.counters["forwards"] = forwards
    return harness.Run(config={}, peak={}, ctx=ctx, setup_time=0.0,
                       trace=trace)


def test_device_readers_average_over_the_planes():
    from bench import harness
    # plane 0: busy 50, kernel 40, all-gather 5, all-reduce 5
    # plane 1: busy 80, kernel 40, all-gather 20 (async pair), idle rest
    trace = _trace([
        [("spmm_abft_kernel", 0, 40), ("all-gather", 40, 45),
         ("all-reduce", 45, 50)],
        [("spmm_abft_kernel", 10, 50), ("all-gather-start", 50, 60),
         ("all-gather-done", 60, 70), ("fusion", 70, 90)],
    ])
    run = _run(trace)
    share = harness.load_reader("shard.exchange_share")(run)
    assert share == pytest.approx(100 * (10 / 50 + 20 / 80) / 2)
    busy = harness.load_reader("spmm_abft.chip_busy_share")(run)
    assert busy == pytest.approx(100 * (40 / 50 + 40 / 80) / 2)


def test_roofline_counts_one_call_a_shard_a_layer(peak):
    """``spmm_abft_roofline`` matches its kernel events, summed over the
    four planes, to one ``kernel_calls`` entry a shard a layer."""
    from bench import cost, harness
    call = {"kernel": "spmm_abft", "vals_shape": (129, 400, 128, 128),
            "vals_itemsize": 4, "cols_shape": (129, 400), "cols_itemsize": 4}
    calls = [dict(call, out_width=g) for g in (64, 186) for _ in range(4)]
    trace = _trace([[("spmm_abft_kernel", 0, 40), ("spmm_abft_kernel", 50,
                                                   90)]] * 4)
    run = _run(trace)
    run.peak = peak
    run.ctx.kernel_calls = calls
    least = sum(cost.least_seconds(*cost.spmm_abft_cost(
        c["vals_shape"], 4, c["cols_shape"], 4, c["out_width"]), peak)[0]
        for c in calls)
    got = harness.load_reader("spmm_abft_roofline")(run)
    assert got == pytest.approx(100 * least / (4 * 80e-9))
    run.ctx.kernel_calls = calls[:2]        # one call a layer: no match
    assert harness.load_reader("spmm_abft_roofline")(run) is None


def test_device_readers_find_nothing_without_collectives():
    from bench import harness
    run = _run(_trace([[("spmm_abft_kernel", 0, 40)],
                       [("spmm_abft_kernel", 0, 30)]]))
    assert harness.load_reader("shard.exchange_share")(run) is None
    run = _run(_trace([[("spmm_abft_kernel", 0, 40), ("all-gather", 40, 50)]]))
    assert harness.load_reader("shard.exchange_share")(run) is None
    run.trace = None
    assert harness.load_reader("spmm_abft.chip_busy_share")(run) is None


def test_control_fails_the_limit(bench):
    """The reference one precision step down (Precision.HIGH, emulated on
    the host) fails the configuration's ``logit_gap`` limit at NELL's
    widths on a small graph."""
    from bench import drivers, graphs, harness
    from bench.references import gcn as ref
    from conftest import cut_graph, tiny
    config, _ = tiny(bench, CELL)
    config["graph"] = cut_graph(config["graph"], 3000, 6000, 4200)
    config["layer_dims"] = [5414, 64, 186]
    limit = config["check"]["logit_gap"]
    w = [np.asarray(x) for x in drivers.make_weights(5, config["layer_dims"])]
    g = graphs.make_graph(config, 5, 1)
    s, h0 = g.s, g.features[0].todense()
    exact = ref.forward(ref.coo_aggregate(s.row, s.col, s.data, s.shape[0],
                                          "highest"), h0, w, "highest")
    control = ref.forward(ref.coo_aggregate(s.row, s.col, s.data, s.shape[0],
                                            "high"), h0, w, "high")
    assert ref.gap(control, exact) > limit
    assert harness.find(bench["workloads"], CELL, "workload")["chips"] == 4


if __name__ == "__main__":
    _child(sys.argv[1])
