"""Program spans (``repro.runtime.spans``) under the JAX profiler.

One checked forward and a short stream run under ``jax.profiler.trace``;
the recorded ``.xplane.pb`` is read back with ``ProfileData``:

  (a) the forward's span tree: one ``gcn.forward``, one ``gcn.layer`` per
      layer, the check's spans only when the check is on;
  (b) the stream's spans sum to the engine's counters: seals by cause,
      staged bytes by purpose, dispatches, adjudications;
  (c) an injected fault records the guard's repair tiers;
  (d) logits and verdicts are bitwise the same with and without a trace;
  (e) the vocabulary is exactly the names the program's call sites use.
"""
import ast
import glob
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abft import ABFTConfig, report_traces
from repro.core.gcn import init_gcn
from repro.engine import (Graph, StreamingEngine, fold_w_r, gcn_apply,
                          make_backend, plan_rungs, synth_graph_stream)
from repro.kernels.spmm_abft import dense_to_block_ell
from repro.runtime import ABFTGuard, GuardConfig
from repro.runtime.spans import SPANS, span

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
PREFIXES = ("gcn.", "stream.", "guard.")
DIMS = (24, 16, 5)
FEAT, BLOCK = 4, 8


def _recorded(tmp_path, fn):
    """Run ``fn`` under the profiler; (its result, the program spans as
    (name, start_ns, end_ns, ids) in start order)."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in data.planes if not plane.name.startswith("/device")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIXES)]
    return out, sorted(spans, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


# ---------------------------------------------------------------------------
# (a) one forward
# ---------------------------------------------------------------------------

def _forward_case(mode, threshold=1e-3):
    """One staged graph and its checked forward (not yet called)."""
    rng = np.random.default_rng(3)
    n = 48
    s = (rng.random((n, n)) < 0.08).astype(np.float32)
    s = np.maximum(s, s.T) + np.eye(n, dtype=np.float32)
    s /= s.sum(axis=1, keepdims=True)
    bell = dense_to_block_ell(s, block_m=16, block_k=16)
    h0 = jnp.asarray(rng.normal(0, 0.5, (n, DIMS[0])).astype(np.float32))
    cfg = ABFTConfig(mode=mode, threshold=threshold, relative=True)
    params = fold_w_r(init_gcn(jax.random.PRNGKey(0), DIMS), cfg)
    bk = make_backend(bell, cfg, backend="block_ell", block_g=16,
                      interpret=True)

    def forward():
        logits, report = gcn_apply(params, Graph(s=bell, h0=h0), cfg,
                                   backend=bk)
        return jax.device_get((logits, report.flag, report.max_rel))
    return forward


@pytest.mark.parametrize("mode", ["fused", "none"])
def test_forward_span_tree(mode, tmp_path):
    forward = _forward_case(mode)
    forward()                                    # compile outside the trace
    _, spans = _recorded(tmp_path, forward)
    names = [s[0] for s in spans]
    assert set(names) <= set(SPANS)
    fwd, = _named(spans, "gcn.forward")
    assert fwd[3] == {"mode": mode}
    layers = _named(spans, "gcn.layer")
    assert [s[3]["layer"] for s in layers] == list(range(len(DIMS) - 1))
    assert all(_inside(s, fwd) for s in layers)
    for name in ("gcn.combine", "gcn.aggregate"):
        found = _named(spans, name)
        assert len(found) == len(layers)
        assert all(_inside(s, layer) for s, layer in zip(found, layers))
    checks = {"gcn.check_column": len(layers), "gcn.corners": len(layers),
              "gcn.summarize": 1}
    for name, count in checks.items():
        assert names.count(name) == (count if mode == "fused" else 0), name
    if mode == "fused":
        summary, = _named(spans, "gcn.summarize")
        assert _inside(summary, fwd) and summary[1] >= layers[-1][2]
        # the corner reduction follows the aggregation's kernel, inside it
        assert all(_inside(c, a) for c, a in zip(
            _named(spans, "gcn.corners"), _named(spans, "gcn.aggregate")))


def test_report_compiles_once_for_repeated_forwards(tmp_path):
    # the report reduction is one compiled program per check structure:
    # traced on the first forward, reused by every later one (a threshold
    # of this test's own, so the first forward is the structure's first)
    forward = _forward_case("fused", threshold=1.5e-3)
    before = report_traces()
    forward()
    assert report_traces() == before + 1

    def four_more():
        return [forward() for _ in range(4)]

    outs, spans = _recorded(tmp_path, four_more)
    assert report_traces() == before + 1
    assert not any(bool(flag) for _, flag, _ in outs)
    forwards = _named(spans, "gcn.forward")
    summaries = _named(spans, "gcn.summarize")
    assert len(forwards) == len(summaries) == 4
    assert all(_inside(s, f) for s, f in zip(summaries, forwards))


# ---------------------------------------------------------------------------
# (b) + (c) a short stream
# ---------------------------------------------------------------------------

def _stream_engine(**kw):
    stream = synth_graph_stream(10, n_lo=6, n_hi=28, feat=FEAT, seed=5)
    rungs = plan_rungs(stream, n_slots=4, block=BLOCK, stripe_multiple=4,
                       width_multiple=4)
    params = init_gcn(jax.random.PRNGKey(0), (FEAT, 4, 3))
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)
    engine = StreamingEngine(params, cfg, rungs, flush_deadline=1.0,
                             interpret=True, **kw)
    engine.warmup()
    return engine, stream


def _drive(engine, stream):
    """Every seal cause once or more: four graphs fill a bin, one waits
    past the deadline, the rest are drained."""
    out = []
    for g in stream[:4]:
        engine.submit(*g, now=0.0)
    engine.submit(*stream[4], now=0.1)
    engine.pump(now=2.0)
    out += engine.take_results()
    for g in stream[5:]:
        engine.submit(*g, now=2.1)
    out += engine.drain(now=2.2)
    return sorted(out, key=lambda r: r.rid)


def _reconcile(engine, spans):
    stats = engine.stats()
    seals = _named(spans, "stream.seal")
    for cause, count in stats["seals"].items():
        assert sum(s[3]["cause"] == cause for s in seals) == count, cause
    for purpose, total in stats["staged_bytes"].items():
        assert sum(s[3]["bytes"] for s in _named(spans, "stream.stage")
                   if s[3]["purpose"] == purpose) == total, purpose
    # a failover re-dispatches a batch the guard refused, with no seal
    batches = list(range(stats["batches"]))
    dispatches = _named(spans, "stream.dispatch")
    assert len(dispatches) == len(seals) + stats["failovers"]
    assert [s[3]["batch"] for s in dispatches] == batches
    if not stats["failovers"]:
        assert [s[3]["batch"] for s in seals] == batches
    resolves = _named(spans, "stream.resolve")
    assert sorted(s[3]["batch"] for s in resolves) == batches
    assert len(_named(spans, "guard.adjudicate")) == engine.guard.steps
    assert len(_named(spans, "guard.sync")) == engine.guard.steps
    assert sum(s[3]["batches"] for s in _named(spans, "stream.materialize")
               ) == len(batches) - stats["failovers"]
    # a packed seal packs once; a seal on the dense fallback does not pack
    packs = _named(spans, "stream.pack")
    assert all(sum(_inside(p, seal) for p in packs) <= 1 for seal in seals)
    assert all(any(_inside(p, seal) for seal in seals) for p in packs)


def test_stream_spans_sum_to_counters(tmp_path):
    engine, stream = _stream_engine(selfcheck_interval=2)
    results, spans = _recorded(tmp_path, lambda: _drive(engine, stream))
    assert [r.status for r in results] == ["served"] * len(stream)
    assert set(s[0] for s in spans) <= set(SPANS)
    stats = engine.stats()
    assert stats["seals"]["full"] >= 1
    assert stats["seals"]["deadline"] >= 1
    assert stats["seals"]["drain"] >= 1
    assert stats["staged_bytes"]["step"] == stats["staged_bytes"]["replay"]
    _reconcile(engine, spans)
    assert len(_named(spans, "stream.selfcheck")) == \
        engine._selfcheck.checks_run
    # the packed step is jitted and warm: no engine span runs per batch (a
    # span in traced code records the trace, once per compile)
    assert not [s for s in spans if s[0].startswith("gcn.")]
    assert not _named(spans, "guard.retry")


@pytest.mark.parametrize("granularity,tiers", [
    ("graph", {"graph", "restore"}),
    ("stripe", {"stripe"}),
])
def test_injected_fault_records_guard_retry(granularity, tiers, tmp_path):
    # no site turns persistent here, so each flagged batch climbs the tiers
    guard = ABFTGuard(GuardConfig(max_retries=1, max_restores=1,
                                  persistent_threshold=64),
                      restore_fn=lambda: None)
    engine, stream = _stream_engine(guard=guard, granularity=granularity,
                                    inject=(0, 0, 0, 100.0))
    results, spans = _recorded(tmp_path, lambda: _drive(engine, stream))
    assert [r.status for r in results] == ["served"] * len(stream)
    assert not any(r.flag for r in results)
    retries = _named(spans, "guard.retry")
    assert {s[3]["tier"] for s in retries} == tiers
    performed = [s for s in retries if s[3]["tier"] != "restore"]
    assert len(performed) == guard.retries
    assert sum(s[3]["tier"] == "restore" for s in retries) == guard.restores
    for r in retries:
        assert any(_inside(r, a) for a in _named(spans, "guard.adjudicate"))
    _reconcile(engine, spans)


# ---------------------------------------------------------------------------
# (d) a trace changes no output
# ---------------------------------------------------------------------------

def test_outputs_bitwise_equal_with_and_without_trace(tmp_path):
    forward = _forward_case("fused")
    plain = forward()
    traced, _ = _recorded(tmp_path / "forward", forward)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    engine, stream = _stream_engine()
    plain = _drive(engine, stream)
    engine, stream = _stream_engine()
    traced, _ = _recorded(tmp_path / "stream", lambda: _drive(engine,
                                                              stream))
    assert [(r.rid, r.flag, r.max_rel) for r in plain] == \
        [(r.rid, r.flag, r.max_rel) for r in traced]
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.logits, b.logits)


# ---------------------------------------------------------------------------
# (e) the vocabulary
# ---------------------------------------------------------------------------

def _call_site_names():
    found = set()
    for path in SRC.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "span" and node.args
                    and isinstance(node.args[0], ast.Constant)):
                found.add(node.args[0].value)
    return found


def test_vocabulary_is_the_call_sites():
    assert len(set(SPANS)) == len(SPANS)
    assert _call_site_names() == set(SPANS)


def test_unknown_span_name_raises():
    with pytest.raises(ValueError):
        span("gcn.nothing")
