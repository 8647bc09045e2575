"""Edge cases of the kernel operand contract (ISSUE 4 satellite).

``prepare_operands`` / ``trim_output`` (spmm_abft) and
``prepare_fused_operands`` (gcn_fused) were only exercised implicitly
through full layer runs.  These pin the tricky paths down directly:

  * the row-TRIM path: when trailing column stripes of S hold no nonzero
    tiles, X/H rows beyond the last referenced stripe are dropped (sound:
    no stored tile can read them) — and the kernel result still matches
    the dense product;
  * non-lane-multiple feature dims passed at their logical width and
    trimmed back from the kernel's lanes;
  * trim_output round-trips through stripe and lane padding.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abft import ABFTConfig
from repro.kernels.gcn_fused import gcn_fused_layer, prepare_fused_operands
from repro.kernels.spmm_abft import dense_to_block_ell, spmm_abft
from repro.kernels.spmm_abft.ops import (
    fit_rows,
    prepare_operands,
    trim_output,
)


def _bell_with_empty_tail_cols(n=96, block=32, seed=0):
    """S [n, n] whose nonzeros all sit in column block 0 — the trailing
    column stripes are empty, so padded_cols < n and the x operand TRIMS."""
    rng = np.random.default_rng(seed)
    s = np.zeros((n, n), np.float32)
    s[:, :block] = rng.random((n, block)).astype(np.float32) \
        * (rng.random((n, block)) < 0.3)
    bell = dense_to_block_ell(s, block_m=block, block_k=block)
    assert bell.padded_cols == block < n
    return s, bell


def test_fit_rows_pads_and_trims():
    x = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    up = fit_rows(x, 9)
    assert up.shape == (9, 2)
    assert float(jnp.abs(up[6:]).max()) == 0.0
    down = fit_rows(x, 4)
    np.testing.assert_array_equal(np.asarray(down), np.asarray(x[:4]))
    same = fit_rows(x, 6)
    assert same.shape == (6, 2)


def test_prepare_operands_row_trim_path():
    s, bell = _bell_with_empty_tail_cols()
    n = s.shape[0]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 0.5, size=(n, 8)).astype(np.float32))
    xp, xrp = prepare_operands(bell, x, None)
    # trimmed to exactly the referenced stripes, features at their logical
    # width (the kernel lays x_r into the lane padding itself)
    assert xp.shape == (32, 8)
    assert xrp.shape == (32, 1)
    np.testing.assert_allclose(np.asarray(xp), np.asarray(x[:32]))
    np.testing.assert_allclose(np.asarray(xrp[:, 0]),
                               np.asarray(x[:32]).sum(axis=1), rtol=1e-6)
    # and the kernel math over the trimmed operand equals the dense product
    out, chk = spmm_abft(bell, x, block_g=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), s @ np.asarray(x),
                               atol=1e-5)
    assert abs(float(chk.predicted) - float(chk.actual)) < 1e-4


@pytest.mark.parametrize("g", [1, 7, 31, 33])
def test_non_lane_multiple_feature_dims(g):
    rng = np.random.default_rng(g)
    n = 64
    s = (rng.random((n, n)) < 0.1).astype(np.float32) * 0.5
    bell = dense_to_block_ell(s, block_m=32, block_k=32)
    x = jnp.asarray(rng.normal(0, 0.5, size=(n, g)).astype(np.float32))
    xp, _ = prepare_operands(bell, x, None)
    assert xp.shape == (n, g)          # no lane pad: the kernel owns it
    out, _ = spmm_abft(bell, x, block_g=32, interpret=True)
    assert out.shape == (n, g)
    np.testing.assert_allclose(np.asarray(out), s @ np.asarray(x), atol=1e-5)


def test_trim_output_round_trip():
    rng = np.random.default_rng(2)
    n, g = 90, 5                      # n not a block multiple, g not lanes
    s = (rng.random((n, n)) < 0.15).astype(np.float32) * 0.3
    bell = dense_to_block_ell(s, block_m=32, block_k=32)
    padded = jnp.asarray(rng.normal(size=(bell.padded_rows, 32))
                         .astype(np.float32))
    trimmed = trim_output(bell, padded, g)
    assert trimmed.shape == (n, g)
    np.testing.assert_array_equal(np.asarray(trimmed),
                                  np.asarray(padded[:n, :g]))
    # full round-trip through the kernel: padded shapes in, logical out
    x = jnp.asarray(rng.normal(0, 0.5, size=(n, g)).astype(np.float32))
    out, _ = spmm_abft(bell, x, block_g=32, interpret=True)
    assert out.shape == (n, g)
    np.testing.assert_allclose(np.asarray(out), s @ np.asarray(x), atol=1e-5)


def test_prepare_fused_operands_contract():
    s, bell = _bell_with_empty_tail_cols()
    rng = np.random.default_rng(3)
    f, g = 10, 6
    h = jnp.asarray(rng.normal(size=(s.shape[0], f)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(f, g)).astype(np.float32))
    hp, wp, wrp = prepare_fused_operands(bell, h, w, None, block_g=32)
    assert hp.shape == (32, 32)        # rows trimmed, features padded
    assert wp.shape == (32, 32) and wrp.shape == (32, 1)
    assert float(jnp.abs(wrp).max(initial=0.0)) == 0.0   # check disabled
    assert float(jnp.abs(wp[f:]).max(initial=0.0)) == 0.0
    assert float(jnp.abs(wp[:, g:]).max(initial=0.0)) == 0.0
    # and the fused layer over the trimmed H equals the dense chain
    out, chk = gcn_fused_layer(bell, h, w, jnp.asarray(np.asarray(w)
                                                       .sum(axis=1)),
                               block_g=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               s @ (np.asarray(h) @ np.asarray(w)),
                               atol=1e-5)
    assert abs(float(chk.predicted) - float(chk.actual)) < 1e-4


def _packed_batch(g, seed):
    """Three small graphs packed block-diagonally (square 32-blocks), with
    features of width g and a carried column, covering every padded row."""
    from repro.engine.batching import pack_graphs
    rng = np.random.default_rng(seed)
    graphs = []
    for n in (40, 56, 24):
        s = (rng.random((n, n)) < 0.15).astype(np.float32) * 0.4
        graphs.append((s, np.zeros((n, 1), np.float32)))
    pb = pack_graphs(graphs, block=32)
    rows = pb.bell.padded_rows
    x = rng.normal(0, 0.5, size=(rows, g)).astype(np.float32)
    xr = (50.0 + rng.normal(size=(rows, 1))).astype(np.float32)
    return pb, x, xr


@pytest.mark.parametrize("g", [1, 3, 16, 64, 127, 128, 186])
def test_check_lane_parity(g):
    """The eq.-5 column rides in lane g of X's padding: at every width
    (127 puts it last in its lane block, 128 needs a second block) the
    one-dot kernel gives S @ X, S @ x_r, per-stripe sums over the output
    lanes alone, a flagged upset, and the packed path's per-graph
    corners."""
    from repro.kernels.spmm_abft import spmm_abft_packed
    from repro.kernels.spmm_abft.kernel import spmm_abft_kernel
    from repro.kernels.spmm_abft.ops import device_block_ell

    rng = np.random.default_rng(100 + g)
    n, k, bm = 80, 72, 32
    s = ((rng.random((n, k)) < 0.2) * rng.normal(0, 0.5, (n, k))
         ).astype(np.float32)
    bell = dense_to_block_ell(s, block_m=bm, block_k=bm)
    x = rng.normal(0, 0.5, size=(k, g)).astype(np.float32)
    # a check column far from X·e and large: a sum that took in lane g
    # could not pass for one over lanes < g
    xr = (50.0 + rng.normal(size=(k, 1))).astype(np.float32)
    cols, vals = device_block_ell(bell)
    xp, xrp = prepare_operands(bell, jnp.asarray(x), jnp.asarray(xr))
    out, sums, extra = spmm_abft_kernel(cols, vals, xp, xrp, interpret=True)
    lanes = -(-(g + 1) // 128) * 128
    assert out.shape == (bell.padded_rows, lanes)
    s64 = s.astype(np.float64)
    np.testing.assert_allclose(np.asarray(out)[:n, :g], s64 @ x,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(extra)[:n], s64 @ xr,
                               rtol=1e-5, atol=1e-4)
    per_stripe = np.asarray(out, np.float64)[:, :g].reshape(
        bell.n_block_rows, bm, g).sum(axis=(1, 2))
    np.testing.assert_allclose(np.asarray(sums)[:, 0], per_stripe,
                               rtol=1e-5, atol=1e-4)

    # an accumulator upset in stripe 1 flags that stripe's corner alone
    cfg = ABFTConfig(mode="fused", threshold=1e-2, relative=False)
    _, clean = spmm_abft(bell, jnp.asarray(x), granularity="stripe",
                         interpret=True)
    _, bad = spmm_abft(bell, jnp.asarray(x), granularity="stripe",
                       interpret=True, inject=(1, 0, 4.0))
    assert not bool(clean.flag(cfg))
    div = np.abs(np.asarray(bad.predicted) - np.asarray(bad.actual))
    assert div[1] == pytest.approx(4.0, rel=1e-3)
    assert float(np.delete(div, 1).max()) < 1e-2

    # the packed path: one corner per graph, each its own rows' sums
    pb, xb, xrb = _packed_batch(g, seed=g)
    pcols, pvals = device_block_ell(pb.bell)
    segments = jnp.asarray(pb.stripe_graph)
    outb, chk = spmm_abft_packed(pcols, pvals, jnp.asarray(xb),
                                 jnp.asarray(xrb), segments,
                                 num_segments=pb.n_slots, interpret=True)
    dense = pb.bell.todense().astype(np.float64)
    ref = dense @ xb
    np.testing.assert_allclose(np.asarray(outb), ref, rtol=1e-5, atol=1e-5)
    pred, actual = np.zeros(pb.n_slots), np.zeros(pb.n_slots)
    for slot in range(pb.n_slots):
        lo = pb.row_offsets[slot]
        hi = lo + -(-pb.n_nodes[slot] // pb.block) * pb.block
        pred[slot] = (dense[lo:hi] @ xrb).sum()
        actual[slot] = ref[lo:hi].sum()
    np.testing.assert_allclose(np.asarray(chk.predicted), pred, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(chk.actual), actual, rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("g", [16, 128, 186])
@pytest.mark.parametrize("inject", [None, (1, 0, 4.0)],
                         ids=["clean", "inject"])
def test_kernel_body_is_one_highest_dot(g, inject):
    """Each grid step runs one MXU dot at HIGHEST: the check column is not
    a second dot, and the precision does not slip."""
    import jax

    from repro.analysis.coverage import iter_eqns
    from repro.kernels.spmm_abft.kernel import spmm_abft_kernel

    nbm, width, b, k = 3, 2, 32, 64
    closed = jax.make_jaxpr(functools.partial(
        spmm_abft_kernel, interpret=True, inject=inject))(
        jnp.zeros((nbm, width), jnp.int32),
        jnp.zeros((nbm, width, b, b), jnp.float32),
        jnp.zeros((k, g), jnp.float32), jnp.zeros((k, 1), jnp.float32))
    calls = [e for e, _ in iter_eqns(closed)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    dots = [e for e, path in iter_eqns(closed, into_pallas=True)
            if "pallas_call" in path and e.primitive.name == "dot_general"]
    assert len(dots) == 1
    precision = dots[0].params["precision"]
    assert precision is not None
    assert all(p == jax.lax.Precision.HIGHEST for p in precision)
