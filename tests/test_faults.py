"""Fault-injection subsystem (ISSUE 9 tentpole): declarative fault
models, bitcast/sticky injectors, the check-path self-check, and the
campaign driver.

Acceptance properties:
  (a) ``flip_bits`` is an involution (re-flip restores bitwise) and the
      injector's sticky kinds re-apply the SAME corruption each step —
      a clean rewrite between steps is undone, which is what makes a
      retry on re-read operands doomed;
  (b) check-path corruption coverage: a bit-flip in the folded ``w_r``
      or the staged ``s_c`` is caught by the periodic self-check
      (bitwise re-derivation), a NaN stuck-at is flagged by the shipped
      NaN-safe comparison while the naive ``d > tau`` verdict stays
      silent — the campaign reports it as a would-be false negative;
  (c) the campaign detects every above-threshold accumulator upset,
      records zero flags on the clean control, measures (not asserts)
      SDC for the architecturally-silent consistent-corruption sites,
      and surfaces the guard's repair-tier distribution including
      persistent-site classification for sticky kinds.
"""
import math

import numpy as np
import pytest

from repro.core.abft import ABFTConfig, Check
from repro.engine import CheckPathSelfCheck, verify_s_c, verify_w_r
from repro.faults import (
    CHECK_PATH_SITES,
    FaultInjector,
    FaultModel,
    flip_bits,
    run_fault_campaign,
    sweep_models,
)


# ---------------------------------------------------------------------------
# model + injector mechanics
# ---------------------------------------------------------------------------

def test_fault_model_validates():
    with pytest.raises(ValueError):
        FaultModel(site="nonsense")
    with pytest.raises(ValueError):
        FaultModel(site="weights", kind="nonsense")
    with pytest.raises(ValueError):
        FaultModel(site="weights", timing="nonsense")
    m = FaultModel(site="w_r", kind="stuck", stuck_value=float("nan"))
    assert m.sticky and m.check_path
    assert m.to_dict()["stuck_value"] == "nan"   # JSON round-trippable


def test_flip_bits_is_involution():
    rng = np.random.default_rng(0)
    for dtype in (np.float32, np.float64, np.int32):
        a = (rng.normal(size=8) * 10).astype(dtype)
        b = flip_bits(a, 3, 30)
        assert not np.array_equal(a, b)
        assert np.array_equal(flip_bits(b, 3, 30), a)
        assert b.dtype == a.dtype


def test_transient_fires_once_sticky_latches():
    t = FaultInjector(FaultModel(site="weights", kind="bitflip", step=2))
    assert [t.fires(i) for i in range(5)] == [False, False, True, False,
                                             False]
    s = FaultInjector(FaultModel(site="weights", kind="stuck", step=2,
                                 stuck_value=9.0))
    assert [s.fires(i) for i in range(5)] == [False, False, True, True,
                                             True]


def test_sticky_reapplies_same_corruption():
    inj = FaultInjector(FaultModel(site="weights", kind="stuck",
                                   stuck_value=7.0, seed=3))
    params = {"layers": [{"w": np.zeros((4, 4), np.float32)}]}
    a = inj.apply_params(params)["layers"][0]["w"]
    # the operand was rewritten clean between steps; the stuck cell
    # comes back at the same coordinate with the same value
    b = inj.apply_params(params)["layers"][0]["w"]
    assert np.array_equal(a, b)
    assert (a == 7.0).sum() == 1
    assert not np.shares_memory(a, params["layers"][0]["w"])


def test_bernoulli_timing_is_memoized():
    inj = FaultInjector(FaultModel(site="weights", timing="bernoulli",
                                   p=0.5, seed=1))
    draws = [inj.fires(i) for i in range(16)]
    assert draws == [inj.fires(i) for i in range(16)]  # replay-stable
    assert any(draws)


def test_cols_table_corruption_stays_in_range():
    inj = FaultInjector(FaultModel(site="cols_table", kind="bitflip",
                                   seed=0))
    cols = np.arange(12, dtype=np.int32).reshape(3, 4) % 5
    c2, _, _ = inj.apply_batch(cols, None, None)
    assert c2.max() < 5 and c2.min() >= 0   # valid index, silent corruption
    assert not np.array_equal(c2, cols)


# ---------------------------------------------------------------------------
# NaN-safe comparison + check-path self-check  (satellite: check-path
# corruption coverage)
# ---------------------------------------------------------------------------

def test_check_flag_is_nan_safe():
    import jax.numpy as jnp
    cfg = ABFTConfig(threshold=1e-3)
    chk = Check(predicted=jnp.float32(float("nan")),
                actual=jnp.float32(1.0))
    assert bool(chk.flag(cfg))          # NaN divergence must flag...
    d = abs(float("nan") - 1.0)
    assert not d > cfg.threshold        # ...though the naive verdict is
    #                                     silent: the would-be FN


def _folded_params(seed=0):
    from repro.engine.api import fold_w_r
    rng = np.random.default_rng(seed)
    params = {"layers": [
        {"w": (rng.normal(size=(4, 6)) * 0.3).astype(np.float32),
         "b": np.zeros(6, np.float32)},
        {"w": (rng.normal(size=(6, 3)) * 0.3).astype(np.float32),
         "b": np.zeros(3, np.float32)}]}
    return fold_w_r(params, ABFTConfig())


@pytest.mark.parametrize("corrupt", ["bitflip", "nan"])
def test_selfcheck_catches_w_r_corruption(corrupt):
    cfg = ABFTConfig(threshold=1e-3)
    params = _folded_params()
    assert verify_w_r(params, cfg) == []
    inj = FaultInjector(FaultModel(
        site="w_r", kind="stuck" if corrupt == "nan" else "bitflip",
        stuck_value=float("nan") if corrupt == "nan" else None, layer=1))
    assert inj.fires(0)
    bad = inj.apply_params(params)
    assert verify_w_r(bad, cfg) == [1]
    # repair: refold from source weights -> clean again
    sc = CheckPathSelfCheck(cfg, interval=1)
    assert sc.maybe_check(bad, 0) == [1] and sc.trips == 1
    assert verify_w_r(sc.repair(bad), cfg) == []


def test_selfcheck_catches_s_c_corruption():
    import jax.numpy as jnp
    from repro.core.abft import sparse_col_checksum
    from repro.engine.api import Graph
    cfg = ABFTConfig(threshold=1e-3)
    s = jnp.asarray(np.eye(6, dtype=np.float32))
    g = Graph(s=s, h0=jnp.ones((6, 4), jnp.float32),
              s_c=sparse_col_checksum(s, cfg.dtype))
    assert not verify_s_c(g, cfg)
    inj = FaultInjector(FaultModel(site="s_c", kind="stuck",
                                   stuck_value=float("nan")))
    assert inj.fires(0)
    inj.apply_graph(g)
    assert verify_s_c(g, cfg)


def test_selfcheck_cadence():
    cfg = ABFTConfig(threshold=1e-3)
    params = _folded_params()
    sc = CheckPathSelfCheck(cfg, interval=4)
    ran = [sc.maybe_check(params, t) is not None for t in range(8)]
    assert ran == [True, False, False, False, True, False, False, False]
    assert sc.checks_run == 2 and sc.trips == 0
    with pytest.raises(ValueError):
        CheckPathSelfCheck(cfg, interval=0)


# ---------------------------------------------------------------------------
# campaign driver
# ---------------------------------------------------------------------------

def test_sweep_models_grid():
    models = sweep_models(reps=1)
    labels = {m.label() for m in models}
    assert "accumulator/bitflip/targeted" in labels
    # check-path sites gain the NaN stuck-at extras
    nan_models = [m for m in models if m.stuck_value is not None
                  and math.isnan(m.stuck_value)]
    assert {m.site for m in nan_models} == set(CHECK_PATH_SITES)


@pytest.fixture(scope="module")
def campaign_payload():
    models = [
        FaultModel(site="accumulator", kind="bitflip", step=1,
                   delta=100.0),
        FaultModel(site="accumulator", kind="stuck", step=1, delta=100.0),
        FaultModel(site="weights", kind="stuck", step=1, stuck_value=7.0,
                   seed=2),
        FaultModel(site="features", kind="bitflip", step=1, bit=30,
                   seed=3),
        FaultModel(site="w_r", kind="stuck", step=1,
                   stuck_value=float("nan"), seed=6),
        FaultModel(site="s_c", kind="stuck", step=1,
                   stuck_value=float("nan"), seed=7),
    ]
    return run_fault_campaign(models, n_steps=4)


def test_campaign_detects_accumulator_upsets(campaign_payload):
    for kind in ("bitflip", "stuck"):
        agg = campaign_payload["by_site_kind"][f"accumulator/{kind}"]
        assert agg["detection_rate"] == 1.0
        assert agg["mean_detection_latency"] == 0.0
    # sticky accumulator: retries are doomed -> the guard escalates
    assert campaign_payload["by_site_kind"]["accumulator/stuck"][
        "escalations"] == 1


def test_campaign_clean_control_has_no_false_positives(campaign_payload):
    assert campaign_payload["clean_control"]["flagged"] == 0
    assert campaign_payload["clean_control"]["false_positive_rate"] == 0.0


def test_campaign_reports_would_be_false_negatives(campaign_payload):
    """A NaN in the check path silences the naive ``d > tau`` comparison;
    the NaN-safe check + self-check still catch it, and the campaign
    reports the discrepancy as a would-be false negative."""
    for site in ("w_r", "s_c"):
        [e] = [e for e in campaign_payload["experiments"]
               if e["model"]["site"] == site]
        assert e["would_be_false_negative"]
        assert e["naive_flagged_steps"] == []     # naive verdict: silent
        assert e["flagged_steps"]                 # NaN-safe verdict: loud
        assert e["selfcheck_detected"]            # root cause pinpointed
        assert e["false_positive_steps"]          # and data was CLEAN


def test_campaign_classifies_sticky_sites_persistent(campaign_payload):
    [e] = [e for e in campaign_payload["experiments"]
           if e["model"]["site"] == "weights"]
    assert e["escalated"]
    tiers = e["repair_tiers"]
    assert tiers["suspect"] and tiers["persistent_sites"]
    total = campaign_payload["repair_tiers_total"]
    assert total["graph"] > 0 and total["persistent_escalations"] > 0


def test_campaign_measures_consistent_corruption(campaign_payload):
    """features/cols_table corruption feeds both sides of eq. 4-6, so
    ABFT may be silent there — the campaign measures the outcome rather
    than asserting detection, and any divergence it finds without a flag
    is recorded as SDC."""
    [e] = [e for e in campaign_payload["experiments"]
           if e["model"]["site"] == "features"]
    assert e["fired_steps"] == [1]
    # every fired step is accounted: detected, SDC, or masked
    accounted = set(e["sdc_steps"]) | set(e["masked_steps"]) | \
        set(e["flagged_steps"])
    assert set(e["fired_steps"]) <= accounted


def test_campaign_payload_is_json_ready(campaign_payload):
    import json
    text = json.dumps(campaign_payload)
    assert '"interpret"' in text and '"authoritative"' in text
    assert campaign_payload["authoritative"] == \
        (not campaign_payload["interpret"])
