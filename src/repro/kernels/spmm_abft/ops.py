"""Public wrappers for the spmm_abft Pallas kernel: host layout → device
arrays, padding to block multiples, final stripe-sum reduction and Check
construction.  A checked GCN layer over this kernel is the engine's
``block_ell`` backend (``repro.engine``).

CPU has no Pallas TPU backend: pass ``interpret=True`` (tests do; the
engine resolves it through ``kernels/runtime.resolve_interpret``).
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.abft import Check
from repro.runtime.spans import span

from .kernel import spmm_abft_kernel
from .layout import BlockEll


def device_block_ell(bell: BlockEll) -> Tuple[jax.Array, jax.Array]:
    """(block_cols, values) as device arrays — stage once per static graph."""
    return jnp.asarray(bell.block_cols), jnp.asarray(bell.values)


def fit_rows(x: jax.Array, rows: int) -> jax.Array:
    """Pad or trim x's leading axis to ``rows``.  Trimming is sound: it
    only happens when trailing column-blocks of S hold no nonzero tiles,
    so those x rows are never referenced by any stored tile.  Shared with
    the fused-layer kernel's operand prep (``kernels/gcn_fused/ops.py``)."""
    if x.shape[0] > rows:
        return x[:rows]
    if x.shape[0] < rows:
        return jnp.pad(x, [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1))
    return x



def prepare_operands(bell: BlockEll, x: jax.Array, xr: Optional[jax.Array]
                     ) -> Tuple[jax.Array, jax.Array]:
    """The kernel's operand contract, shared by the single-device and the
    shard_map'd caller: rows padded to cover every referenced column
    stripe (>= one block_k), the feature axis left at its logical width
    (the kernel lays x_r into X's lane padding itself), and ``xr``
    defaulting to the standalone column X·e in f32."""
    if xr is None:
        xr = x.astype(jnp.float32).sum(axis=1, keepdims=True)
    k_pad = max(bell.padded_cols, bell.block_k)
    return fit_rows(x, k_pad), fit_rows(xr.astype(jnp.float32), k_pad)


def trim_output(bell: BlockEll, out: jax.Array, g: int) -> jax.Array:
    """Drop stripe/lane padding back to the logical [n, g] output."""
    return out[:bell.shape[0], :g]


def stripe_check_corners(stripe_sums: jax.Array, extra: jax.Array) -> Check:
    """Per-stripe kernel partials -> one eq.-6 corner PER ROW-STRIPE.

    The finest check granularity the kernels support: the grid already
    accumulates (actual, predicted) per row-stripe — this just declines to
    collapse them, so a flipped bit names the stripe it landed in and
    recovery can re-execute exactly those rows.  Exact by linearity, same
    argument as the per-graph segmentation; padding stripes (all-zero
    tiles) compare 0 = 0 and can never flag.  Shared by the two-pass
    (``spmm_abft*``) and single-pass (``gcn_fused*``) wrappers."""
    nbm = stripe_sums.shape[0]
    pred = extra[:, 0].reshape(nbm, -1).sum(axis=1)
    return Check(predicted=pred, actual=stripe_sums[:, 0],
                 granularity="stripe")


def spmm_abft(bell: BlockEll, x: jax.Array, xr: Optional[jax.Array] = None,
              *, block_g: int = 128, interpret: bool = False,
              granularity: str = "layer",
              inject: Optional[Tuple[int, int, float]] = None,
              _staged: Optional[Tuple[jax.Array, jax.Array]] = None
              ) -> Tuple[jax.Array, Check]:
    """out = S @ X with the fused ABFT check computed in the same pass.

    ``xr`` is the carried right-checksum column: X·e by default (standalone
    check of this multiply), or H·w_r threaded from the combination matmul
    for the full GCN-ABFT chain (eq. 4) — then Check.predicted equals
    s_c H w_r without s_c ever being applied online.
    ``granularity="stripe"`` keeps the kernel's per-row-stripe partials as
    individual corners ([n_block_rows] fields) instead of collapsing to one
    scalar; ``"layer"`` (default) is the paper's single corner.
    ``_staged`` lets a long-lived caller (the engine's block_ell backend)
    reuse already-staged (block_cols, values) device arrays.
    Returns (out [n, g], Check(predicted=Σ S·xr, actual=Σ out)).
    """
    n, _k_logical = bell.shape
    g = x.shape[1]
    cols, vals = _staged if _staged is not None else device_block_ell(bell)
    xp, xrp = prepare_operands(bell, x, xr)
    out, stripe_sums, extra = spmm_abft_kernel(cols, vals, xp, xrp,
                                               interpret=interpret,
                                               inject=inject, block_g=block_g)
    out = trim_output(bell, out, g)
    # the corners are the GCN check only where the caller carried the
    # eq.-5 column; an unchecked layer passes none and drops them
    with (span("gcn.corners") if xr is not None
          else contextlib.nullcontext()):
        if granularity == "stripe":
            return out, stripe_check_corners(stripe_sums, extra)
        return out, Check(predicted=extra[:n, 0].sum(),
                          actual=stripe_sums.sum())


def validate_packed_operands(vals: jax.Array, rows: int, name: str) -> None:
    """Shared contract of the block-diagonal packed kernels: square blocks
    (stripe offset == column-block offset) and a row operand covering every
    padded stripe."""
    nbm, _width, bm, bk = vals.shape
    if bm != bk:
        raise ValueError("block-diagonal packing needs square blocks; "
                         f"got block_m={bm}, block_k={bk}")
    if rows != nbm * bm:
        raise ValueError(f"{name} covers {rows} rows; packed system has "
                         f"{nbm * bm} (= {nbm} stripes x {bm})")


def packed_check_corners(stripe_sums: jax.Array, extra: jax.Array,
                         segments: jax.Array, num_segments: int) -> Check:
    """Per-stripe kernel partials -> one eq.-6 check corner per packed
    graph.  Exact by linearity: each graph owns whole contiguous stripes,
    so segment-summing decomposes the batch checksum with no cross-talk;
    padding stripes fall in the explicit overflow segment (id ==
    num_segments) and are sliced away.  Shared by the two-pass
    (``spmm_abft_packed``) and single-pass (``gcn_fused_packed``) paths —
    the overflow-segment convention lives exactly once."""
    nbm = stripe_sums.shape[0]
    pred_stripe = extra[:, 0].reshape(nbm, -1).sum(axis=1)
    pred = jax.ops.segment_sum(pred_stripe, segments,
                               num_segments=num_segments + 1,
                               indices_are_sorted=True)[:num_segments]
    actual = jax.ops.segment_sum(stripe_sums[:, 0], segments,
                                 num_segments=num_segments + 1,
                                 indices_are_sorted=True)[:num_segments]
    return Check(predicted=pred, actual=actual, granularity="graph")


def spmm_abft_packed(cols: jax.Array, vals: jax.Array, x: jax.Array,
                     xr: Optional[jax.Array], segments: jax.Array,
                     *, num_segments: int, block_g: int = 128,
                     interpret: bool = False, granularity: str = "graph",
                     inject: Optional[Tuple[int, int, float]] = None
                     ) -> Tuple[jax.Array, Optional[Check]]:
    """Block-diagonal packed SpMM with *per-graph* fused check corners.

    ``cols``/``vals`` are the staged (possibly traced) block-ELL arrays of a
    block-diagonal packed system (``engine.batching.pack_graphs``) with
    square blocks, ``x`` the stacked [rows, g] combination output covering
    every padded row, ``xr`` the stacked carried eq.-5 column (or ``None``
    to disable checking), and ``segments`` the [n_block_rows] stripe → graph
    id map (padding stripes carry id ``num_segments`` and are dropped).

    Because the checksum is linear and each graph owns whole contiguous
    stripes, segment-summing the kernel's per-stripe partials decomposes the
    batch check *exactly* into one eq.-6 corner per graph:

        actual[g] = Σ_{stripes of g} Σ out_stripe
        pred[g]   = Σ_{rows of g} (S x_r)_row

    so a flipped bit in one packed graph perturbs only that graph's corner.
    ``granularity="stripe"`` refines further: the per-stripe partials stay
    un-segmented ([n_block_rows] corners), so the fault names the exact
    stripe and a surgical retry can re-execute only those rows.
    Everything here is shape-static, so the whole call jits with
    ``cols``/``vals``/``segments`` as traced per-batch arguments — no
    recompile across batches of the same packed shape.
    Returns (out [rows, g], Check(predicted [G], actual [G]) | None).
    """
    validate_packed_operands(vals, x.shape[0], "x")
    rows, g = x.shape
    want_check = xr is not None
    xrp = (jnp.zeros((rows, 1), jnp.float32) if xr is None
           else xr.astype(jnp.float32))
    out, stripe_sums, extra = spmm_abft_kernel(cols, vals, x, xrp,
                                               interpret=interpret,
                                               inject=inject, block_g=block_g)
    out = out[:, :g]
    if not want_check:
        return out, None
    if granularity == "stripe":
        return out, stripe_check_corners(stripe_sums, extra)
    return out, packed_check_corners(stripe_sums, extra, segments,
                                     num_segments)

