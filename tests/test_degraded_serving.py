"""Degraded-backend serving (ISSUE 9): sticky-fault discrimination in
the guard, the streaming engine's backend ladder, the hung-dispatch
watchdog wiring, and the periodic check-path self-check.

Acceptance properties:
  (a) watchdog satellites: ``stop()`` without a prior ``start()`` is a
      no-op (no TypeError, no phantom sample) and warmup uses a TRUE
      running mean, not a pairwise EWMA blend;
  (b) the headline e2e contract — with a sticky accumulator fault baked
      into the level-0 backend, the guard classifies the site persistent
      within the configured window, the engine checkpoints, degrades
      down its ladder, and KEEPS SERVING: every submitted request gets a
      verdict, none dropped, none hung;
  (c) the degraded dense fallback is numerically clean (no flags on
      clean traffic) and its logits match the packed backend's;
  (d) ``hang_timeout`` forces adjudication of a stuck in-flight batch
      through ``pump`` (fake clock);
  (e) the engine's periodic self-check catches a corrupted eq.-5 fold
      mid-stream, refolds, rebuilds its steps, and the stream continues.
"""
import jax
import numpy as np
import pytest

from repro.core.abft import ABFTConfig
from repro.engine import StreamingEngine, plan_rungs, synth_graph_stream
from repro.runtime import ABFTGuard, GuardConfig, GuardRefused
from repro.runtime.watchdog import StragglerWatchdog

FEAT, HIDDEN, CLASSES = 8, 16, 4


def _stream(n=12, seed=0):
    return synth_graph_stream(n, n_lo=16, n_hi=40, feat=FEAT, seed=seed)


def _params(seed=0):
    rng = np.random.default_rng(seed)
    return {"layers": [
        {"w": (rng.normal(size=(FEAT, HIDDEN)) * 0.3).astype(np.float32),
         "b": np.zeros(HIDDEN, np.float32)},
        {"w": (rng.normal(size=(HIDDEN, CLASSES)) * 0.3).astype(
            np.float32),
         "b": np.zeros(CLASSES, np.float32)}]}


def _engine(stream, *, guard=None, **kw):
    rungs = plan_rungs(stream[:4], n_slots=4, block=8)
    return StreamingEngine(_params(), ABFTConfig(threshold=1e-3), rungs,
                           guard=guard, keep_logits=True, **kw)


def _serve_all(engine, stream):
    results = []
    for s, h0 in stream:
        engine.submit(s, h0)
        results.extend(engine.take_results())
    results.extend(engine.drain())
    return results


# ---------------------------------------------------------------------------
# (a) watchdog satellites
# ---------------------------------------------------------------------------

def test_watchdog_stop_without_start_is_noop():
    wd = StragglerWatchdog()
    assert wd.stop() is False           # regression: raised TypeError
    assert wd.n == 0 and wd.ewma == 0.0  # no phantom sample recorded


def test_watchdog_warmup_is_true_running_mean():
    times = iter([0.0, 1.0, 1.0, 5.0, 5.0, 6.0])
    wd = StragglerWatchdog(warmup=3, clock=lambda: next(times))
    for _ in range(3):
        wd.start()
        wd.stop()
    # samples 1.0, 4.0, 1.0 -> mean 2.0 (the pairwise EWMA blend gave
    # 0.5*(0.5*(1+4)+1) = 1.75)
    assert wd.ewma == pytest.approx(2.0)


def test_watchdog_slow_steps_tracked_without_polluting_ewma():
    t = {"now": 0.0}
    wd = StragglerWatchdog(threshold=2.0, warmup=2,
                           clock=lambda: t["now"])
    for dt in (1.0, 1.0):
        wd.start()
        t["now"] += dt
        wd.stop()
    base = wd.ewma
    wd.start()
    t["now"] += 50.0                    # a straggler
    assert wd.stop() is True
    assert wd.events == 1 and wd.slow_streak == 1
    assert wd.ewma == base              # outlier kept out of the estimate


# ---------------------------------------------------------------------------
# (b)+(c) the e2e degrade contract
# ---------------------------------------------------------------------------

def _sticky_guard():
    return ABFTGuard(GuardConfig(max_retries=1, max_restores=1,
                                 persistent_window=4,
                                 persistent_threshold=2))


@pytest.mark.parametrize("fusion", [{}, {"fused_network": True}],
                         ids=["two-pass", "fused-network"])
def test_sticky_fault_degrades_backend_and_keeps_serving(fusion, tmp_path):
    """A stuck accumulator in the level-0 backend: retries re-execute
    through the same poisoned backend (doomed), the guard classifies the
    site persistent, and the engine checkpoints + walks its ladder while
    every request still gets served."""
    stream = _stream(12)
    engine = _engine(stream, guard=_sticky_guard(),
                     inject=(0, 0, 0, 100.0),
                     watchdog=StragglerWatchdog(warmup=2),
                     hang_timeout=30.0,
                     checkpoint_dir=str(tmp_path / "ckpt"),
                     selfcheck_interval=4, **fusion)
    assert engine.stats()["backend_ladder"][-1] == "dense"
    results = _serve_all(engine, stream)

    stats = engine.stats(results)
    assert stats["served"] == stats["submitted"] == len(stream)
    assert sorted(r.rid for r in results) == list(range(len(stream)))
    assert all(r.status == "served" for r in results)
    assert stats["degrades"] >= 1 and stats["failovers"] >= 1
    assert stats["degrade_level"] >= 1          # left the poisoned level
    assert stats["active_backend"] != stats["backend_ladder"][0] or \
        stats["degrade_level"] >= 1
    # the sticky site was discriminated, not retried forever
    tiers = stats["repair_tiers"]
    assert tiers["persistent_sites"] or tiers["persistent_escalations"] \
        or stats["failovers"] >= 1
    # checkpoint written at the failover boundary
    ckpts = list((tmp_path / "ckpt").iterdir())
    assert ckpts, "no checkpoint written on degrade"
    # post-degrade traffic is clean: later results carry no flags
    tail = [r for r in results if r.rid >= 8]
    assert tail and not any(r.flag for r in tail)


class _DeviceFaultGuard(ABFTGuard):
    """A device error surfacing at adjudication, the first host sync."""

    def adjudicate(self, *args, **kwargs):
        raise jax.errors.JaxRuntimeError("INTERNAL: simulated device fault")


def test_device_error_at_adjudication_is_not_swallowed():
    """Only the guard's own refusal degrades the ladder: a device error is
    a RuntimeError too, but it must propagate instead of quietly moving
    the stream onto the dense backend."""
    assert issubclass(GuardRefused, RuntimeError)
    stream = _stream(4)
    engine = _engine(stream, guard=_DeviceFaultGuard())
    with pytest.raises(jax.errors.JaxRuntimeError, match="simulated"):
        _serve_all(engine, stream)
    assert engine.degrades == 0 and engine.failovers == 0
    assert engine.stats()["active_backend"] == "two-pass"


def test_dense_fallback_matches_packed_logits():
    """The terminal dense backend must agree with the packed backend on
    clean traffic — degraded service returns the same answers."""
    stream = _stream(6)
    packed = _engine(stream)
    dense = _engine(stream)
    dense._degrade("test: force dense")
    while not dense._active_dense():
        dense._degrade("test: force dense")
    rp = {r.rid: r for r in _serve_all(packed, stream)}
    rd = {r.rid: r for r in _serve_all(dense, stream)}
    assert sorted(rp) == sorted(rd)
    assert dense.stats()["active_backend"] == "dense"
    assert dense.dense_dispatches >= 1
    for rid in rp:
        assert rp[rid].status == rd[rid].status == "served"
        assert not rd[rid].flag
        np.testing.assert_allclose(rp[rid].logits, rd[rid].logits,
                                   rtol=2e-4, atol=2e-5)


def test_degrade_reroutes_oversize_singletons():
    stream = _stream(6)
    big = synth_graph_stream(1, n_lo=220, n_hi=240, feat=FEAT, seed=9)[0]
    engine = _engine(stream)
    engine._degrade("test: force dense")
    while not engine._active_dense():
        engine._degrade("test: force dense")
    results = _serve_all(engine, stream + [big])
    assert len(results) == 7 and all(r.status == "served" for r in results)
    assert engine.singleton_dispatches == 1


# ---------------------------------------------------------------------------
# (d) hung-dispatch timeout through pump
# ---------------------------------------------------------------------------

def test_hang_timeout_flushes_inflight_batch():
    t = {"now": 0.0}
    stream = _stream(8)
    engine = _engine(stream, hang_timeout=5.0, flush_deadline=0.001,
                     clock=lambda: t["now"])
    for s, h0 in stream[:4]:
        engine.submit(s, h0)
    t["now"] += 0.01
    engine.pump()                       # deadline flush -> dispatch
    assert engine._inflight is not None
    t["now"] += 10.0                    # the dispatch "hangs"
    engine.pump()
    assert engine.hang_flushes == 1
    assert engine._inflight is None     # forced adjudication resolved it
    results = engine.take_results()
    assert len(results) == 4 and all(r.status == "served" for r in results)
    results.extend(engine.drain())


# ---------------------------------------------------------------------------
# (e) periodic self-check wiring in the engine
# ---------------------------------------------------------------------------

def test_engine_selfcheck_repairs_corrupted_fold_midstream():
    from repro.engine import verify_w_r
    from repro.faults import FaultInjector, FaultModel
    stream = _stream(12)
    engine = _engine(stream, selfcheck_interval=1)
    # corrupt the carried eq.-5 fold in place mid-stream (a NaN stuck-at:
    # the nastiest case — a naive comparison would never flag again)
    inj = FaultInjector(FaultModel(site="w_r", kind="stuck",
                                   stuck_value=float("nan")))
    assert inj.fires(0)
    engine.params = inj.apply_params(engine.params)
    assert verify_w_r(engine.params, engine.cfg) == [0]
    results = _serve_all(engine, stream)
    stats = engine.stats(results)
    assert stats["selfcheck_trips"] >= 1
    assert stats["selfcheck_repairs"] >= 1
    assert verify_w_r(engine.params, engine.cfg) == []   # refolded
    assert stats["served"] == len(stream)
    assert all(r.status == "served" for r in results)


def test_selfcheck_interval_validation():
    stream = _stream(4)
    with pytest.raises(ValueError):
        _engine(stream, selfcheck_interval=0)
    with pytest.raises(ValueError):
        _engine(stream, hang_timeout=0.0)


def test_stats_surface_robustness_counters():
    stream = _stream(4)
    engine = _engine(stream)
    stats = engine.stats(_serve_all(engine, stream))
    for key in ("repair_tiers", "backend_ladder", "active_backend",
                "degrade_level", "degrades", "failovers",
                "dense_dispatches", "hang_flushes", "watchdog_events",
                "selfcheck_runs", "selfcheck_trips", "selfcheck_repairs"):
        assert key in stats, key
    assert stats["degrades"] == 0 and stats["failovers"] == 0
    assert stats["repair_tiers"]["slot"] == 0
