"""Checkpoint / optimizer / data / runtime substrate tests."""
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.data.synthetic import SyntheticLM
from repro.optim import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    compress_int8,
    cosine_warmup,
    decompress_int8,
    ef_compress_grads,
    global_norm,
)
from repro.runtime import ABFTGuard, StragglerWatchdog


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
            "b": [jnp.asarray(rng.normal(size=(3,)), jnp.float32),
                  {"c": jnp.asarray(7, jnp.int32)}]}


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 5, tree)
    restored, step = load_checkpoint(str(tmp_path), tree)
    assert step == 5
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_manager_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    tree = _tree()
    for s in (1, 2, 3):
        mgr.save(s, tree)
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_00000002", "step_00000003"]
    restored, step = mgr.restore(tree)
    assert step == 3


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=True)
    mgr.save(1, _tree())
    mgr.wait()
    _, step = mgr.restore(_tree())
    assert step == 1


def test_elastic_reshard_restore(tmp_path):
    from repro.checkpoint import reshard_restore
    tree = _tree()
    save_checkpoint(str(tmp_path), 9, tree)
    shardings = jax.tree.map(lambda _: None, tree)
    restored, step = reshard_restore(str(tmp_path), tree, shardings)
    assert step == 9


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_reduces_quadratic():
    w = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(w)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    for _ in range(150):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(w)
        w, state = adamw_update(w, g, state, cfg, 1.0)
    assert float(jnp.abs(w["w"]).max()) < 0.05


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(global_norm(clipped)) <= 1.0 + 1e-5
    assert float(gn) > 30


def test_cosine_warmup_monotone_then_decay():
    import numpy as np
    xs = [float(cosine_warmup(jnp.asarray(s), 10, 100)) for s in range(0, 100, 5)]
    assert xs[0] < xs[1] <= 1.0
    assert xs[-1] < xs[3]


@settings(max_examples=20, deadline=None)
@given(scale=st.floats(1e-3, 1e3), seed=st.integers(0, 100))
def test_int8_compression_bounded_error(scale, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)) * scale, jnp.float32)
    q, s = compress_int8(x)
    err = jnp.abs(decompress_int8(q, s) - x).max()
    assert float(err) <= float(s) * 0.5 + 1e-6


def test_error_feedback_preserves_mass():
    """Error feedback: compressed + residual == original (exactly)."""
    g = {"w": jnp.asarray([0.1, -0.25, 3.0], jnp.float32)}
    ef = {"w": jnp.zeros(3, jnp.float32)}
    deq, ef2 = ef_compress_grads(g, ef)
    np.testing.assert_allclose(np.asarray(deq["w"]) + np.asarray(ef2["w"]),
                               np.asarray(g["w"]), rtol=1e-6)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_synthetic_lm_deterministic_and_learnable():
    d1 = SyntheticLM(vocab_size=64, seq_len=32, batch_size=4, seed=1)
    d2 = SyntheticLM(vocab_size=64, seq_len=32, batch_size=4, seed=1)
    b1, b2 = next(d1.batches()), next(d2.batches())
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # structure: successor function fires often
    succ = d1._succ
    hits = (succ[b1["tokens"][:, :-1]] == b1["tokens"][:, 1:]).mean()
    assert hits > 0.5


def test_synthetic_lm_host_sharding_differs():
    d = SyntheticLM(vocab_size=64, seq_len=16, batch_size=2, seed=1)
    b0 = next(d.batches(host_id=0))
    b1 = next(d.batches(host_id=1))
    assert not np.array_equal(b0["tokens"], b1["tokens"])


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------

def test_abft_guard_retry_then_restore():
    calls = {"n": 0}

    def flaky_step(state):
        calls["n"] += 1
        flagged = calls["n"] <= 2
        return state + 1, {"abft_flag": flagged, "abft_max_rel": 0.5}

    g = ABFTGuard()
    out, m = g.run_step(flaky_step, 0)
    assert out == 1 and calls["n"] == 3      # two retries then success

    # persistent flag: restore must be followed by a verified replay —
    # the guard adopts the replayed step's output, not the failed attempt's
    fault = {"on": True}

    def bad_until_restore(state):
        return state + 1, {"abft_flag": fault["on"], "abft_max_rel": 1.0}

    def restore():
        fault["on"] = False

    g2 = ABFTGuard(restore_fn=restore)
    out, m = g2.run_step(bad_until_restore, 0)
    assert out == 1 and bool(m["abft_flag"]) is False
    assert g2.restores == 1


def test_straggler_watchdog():
    import time
    wd = StragglerWatchdog(threshold=5.0, warmup=3)
    for _ in range(6):
        wd.start(); time.sleep(0.001); wd.stop()
    wd.start(); time.sleep(0.05)
    assert wd.stop() is True
    assert wd.events == 1


# ---------------------------------------------------------------------------
# persistent compile cache placement
# ---------------------------------------------------------------------------

def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch,
                                                         tmp_path):
    from repro.kernels.runtime import use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev   # nothing set


def test_compile_cache_defaults_to_checkout(monkeypatch):
    from repro.kernels.runtime import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        checkout = pathlib.Path(__file__).resolve().parents[1]
        assert pathlib.Path(path) == checkout / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
