"""Public wrappers for the fused GCN-layer kernel: operand padding, the
final checksum reduction, Check construction, the packed (block-diagonal)
per-graph variant, and the VMEM / HBM cost models that decide when fusion
is worthwhile.

CPU has no Pallas TPU backend: pass ``interpret=True`` (tests and the CPU
engine default do).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.abft import Check
from repro.kernels.spmm_abft.layout import BlockEll
from repro.kernels.spmm_abft.ops import (
    device_block_ell,
    fit_rows,
    packed_check_corners,
    stripe_check_corners,
    validate_packed_operands,
)

from .kernel import gcn_fused_kernel, gcn_network_kernel
from .vmem import (  # noqa: F401  (re-exported beside the HBM models)
    FUSED_VMEM_BUDGET,
    _lanes,
    fused_layer_fits,
    fused_network_fits,
    fused_vmem_bytes,
    network_vmem_bytes,
)

Array = jax.Array


def _pad_axis(a: Array, axis: int, multiple: int) -> Array:
    size = a.shape[axis]
    pad = -size % multiple
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _pad_weights(w: Array, wr: Optional[Array], block_g: int
                 ) -> Tuple[Array, Array]:
    """W [f, g] -> f32 [fp, gp] and wr (vector/column/None) -> f32 [fp, 1];
    ``wr=None`` (check disabled) becomes a zero column the specialized
    kernel never reads.  The ONE place the weight-operand contract lives —
    the single-graph and packed entry points both pad through here."""
    f = w.shape[0]
    wr = (jnp.zeros((f, 1), jnp.float32) if wr is None
          else wr.astype(jnp.float32).reshape(f, 1))
    wp = _pad_axis(_pad_axis(w.astype(jnp.float32), 0, block_g), 1, block_g)
    return wp, _pad_axis(wr, 0, block_g)


def prepare_fused_operands(bell: BlockEll, h: Array, w: Array,
                           wr: Optional[Array], block_g: int
                           ) -> Tuple[Array, Array, Array]:
    """The fused kernel's operand contract: H rows padded (or trimmed — see
    :func:`~repro.kernels.spmm_abft.ops.fit_rows`) to cover every referenced
    column stripe, both feature axes padded to ``block_g`` lane multiples,
    and ``wr`` defaulting to zeros (check disabled) in f32.

    Zero padding is exact end to end: padded H columns meet padded W rows
    (both zero), padded W/wr columns add zero output lanes that the caller
    trims, and padded H rows are never referenced by any stored tile.
    """
    k_pad = max(bell.padded_cols, bell.block_k)
    hp = _pad_axis(fit_rows(h, k_pad), 1, block_g)
    wp, wrp = _pad_weights(w, wr, block_g)
    return hp, wp, wrp


def slot_check_corners(slot_acts: Array, slot_preds: Array) -> Check:
    """Telescoped per-slot running sums -> one eq.-6 corner PER (stripe,
    ell-slot) grid step — the finest granularity the sweep itself has.

    The kernel records Σ acc and Σ ex after every slot; the slot corner is
    the adjacent difference along the slot axis.  Telescoping is what makes
    detection exact: an accumulator upset between two recordings shifts
    every later running sum by the same delta, so exactly one difference
    diverges — per-slot sums rebuilt from tile products would miss faults
    that corrupt the accumulator itself.  On a clean run each difference is
    bounded by twice the stripe-level f32 noise (both running sums are
    valid partial-sweep eq.-6 comparisons by linearity)."""
    zeros = jnp.zeros((slot_acts.shape[0], 1), slot_acts.dtype)
    return Check(predicted=jnp.diff(slot_preds, axis=1, prepend=zeros),
                 actual=jnp.diff(slot_acts, axis=1, prepend=zeros),
                 granularity="slot")


def gcn_fused_layer(bell: BlockEll, h: Array, w: Array,
                    w_r: Optional[Array] = None, *, block_g: int = 128,
                    interpret: bool = False,
                    granularity: str = "layer",
                    inject: Optional[Tuple[int, int, float]] = None,
                    _staged: Optional[Tuple[Array, Array]] = None
                    ) -> Tuple[Array, Optional[Check]]:
    """out = S (H W) with the single eq. 4–6 check, in ONE kernel sweep.

    ``w_r`` is the folded right checksum W·e ([g_in] vector or [g_in, 1]
    column; offline at weight-load time — ``engine.fold_w_r``).  ``None``
    disables checking (mode="none"): the kernel still runs single-pass and
    statically elides the eq.-5 dots.  Like the two-pass spmm_abft kernel
    path, checks accumulate in f32 regardless of ``ABFTConfig.dtype``
    (the TPU-production convention; pair with ``kahan`` off-kernel if f32
    noise floors matter).
    ``granularity="stripe"`` keeps the sweep's per-row-stripe partials as
    individual corners instead of one scalar (fault localization).
    ``_staged`` lets a long-lived caller reuse already-staged
    (block_cols, values) device arrays.
    Returns (out [n, g], Check(predicted=Σ S H w_r, actual=Σ out) | None).
    """
    n, _ = bell.shape
    g = w.shape[1]
    cols, vals = _staged if _staged is not None else device_block_ell(bell)
    want_check = w_r is not None
    with_slots = want_check and granularity == "slot"
    hp, wp, wrp = prepare_fused_operands(bell, h, w, w_r, block_g)
    res = gcn_fused_kernel(cols, vals, hp, wp, wrp, interpret=interpret,
                           inject=inject, with_check=want_check,
                           with_slots=with_slots)
    out, stripe_sums, extra = res[:3]
    out = out[:n, :g]
    if not want_check:
        return out, None
    if with_slots:
        return out, slot_check_corners(res[3], res[4])
    if granularity == "stripe":
        return out, stripe_check_corners(stripe_sums, extra)
    return out, Check(predicted=extra[:n, 0].sum(),
                      actual=stripe_sums.sum())


def gcn_fused_packed(cols: Array, vals: Array, h: Array, w: Array,
                     w_r: Optional[Array], segments: Array, *,
                     num_segments: int, block_g: int = 128,
                     interpret: bool = False, granularity: str = "graph",
                     inject: Optional[Tuple[int, int, float]] = None
                     ) -> Tuple[Array, Optional[Check]]:
    """Fused layer over a block-diagonal packed batch with *per-graph*
    eq.-6 corners — the single-pass analogue of ``spmm_abft_packed``.

    The kernel's per-stripe checksum partials segment-sum into one corner
    per packed graph exactly as in the two-pass path (the checksum is
    linear and each graph owns whole contiguous stripes), so a fault inside
    the fused sweep flags only the graph whose stripes it landed in.
    ``granularity="stripe"`` keeps the partials un-segmented (one corner
    per row-stripe) so the fault names the exact stripe.
    Everything is shape-static: jits with cols/vals/segments traced.
    """
    validate_packed_operands(vals, h.shape[0], "h")
    g = w.shape[1]
    want_check = w_r is not None
    with_slots = want_check and granularity == "slot"
    hp = _pad_axis(h, 1, block_g)
    wp, wrp = _pad_weights(w, w_r, block_g)
    res = gcn_fused_kernel(cols, vals, hp, wp, wrp, interpret=interpret,
                           inject=inject, with_check=want_check,
                           with_slots=with_slots)
    out, stripe_sums, extra = res[:3]
    out = out[:, :g]
    if not want_check:
        return out, None
    if with_slots:
        return out, slot_check_corners(res[3], res[4])
    if granularity == "stripe":
        return out, stripe_check_corners(stripe_sums, extra)
    return out, packed_check_corners(stripe_sums, extra, segments,
                                     num_segments)


# ---------------------------------------------------------------------------
# Whole-network fusion: L layers in ONE HBM traversal.
# ---------------------------------------------------------------------------

def _network_weight_stacks(ws: Sequence[Array],
                           wrs: Sequence[Optional[Array]], block_g: int
                           ) -> Tuple[Array, Array, int, List[int]]:
    """Pad every layer's W / w_r to ONE shared lane-rounded width P (the max
    over all layer widths) and stack to [L, P, P] / [L, P, 1].  One shared P
    is what lets the activation matrix live in two fixed VMEM buffers
    across the whole depth; the zero padding is exact at every layer
    (zero activation columns meet zero weight rows, and relu(0) = 0 keeps
    the invariant inductive)."""
    dims = [int(ws[0].shape[0])] + [int(w.shape[1]) for w in ws]
    p = _lanes(max(dims), block_g)
    wstack, wrstack = [], []
    for w, wr in zip(ws, wrs):
        f, g = w.shape
        wr_col = (jnp.zeros((f, 1), jnp.float32) if wr is None
                  else wr.astype(jnp.float32).reshape(f, 1))
        wstack.append(jnp.pad(w.astype(jnp.float32),
                              [(0, p - f), (0, p - g)]))
        wrstack.append(jnp.pad(wr_col, [(0, p - f), (0, 0)]))
    return jnp.stack(wstack), jnp.stack(wrstack), p, dims


def _network_checks(tele_acts: Array, tele_preds: Array, granularity: str,
                    segments: Optional[Array], num_segments: Optional[int]
                    ) -> List[Check]:
    """Per-layer Checks from the network kernel's telescoped running sums
    [L, nbm, width].  The final telescope value of a stripe IS its stripe
    corner (the same Σ acc / Σ ex the single-layer sweep emits), so every
    granularity reduces from the telescopes exactly as it would from a
    sequential per-layer run."""
    checks: List[Check] = []
    for ell in range(tele_acts.shape[0]):
        ta, tp = tele_acts[ell], tele_preds[ell]
        if granularity == "slot":
            checks.append(slot_check_corners(ta, tp))
        elif granularity == "stripe":
            checks.append(Check(predicted=tp[:, -1], actual=ta[:, -1],
                                granularity="stripe"))
        elif granularity == "graph":
            pred = jax.ops.segment_sum(tp[:, -1], segments,
                                       num_segments=num_segments + 1,
                                       indices_are_sorted=True
                                       )[:num_segments]
            actual = jax.ops.segment_sum(ta[:, -1], segments,
                                         num_segments=num_segments + 1,
                                         indices_are_sorted=True
                                         )[:num_segments]
            checks.append(Check(predicted=pred, actual=actual,
                                granularity="graph"))
        else:
            checks.append(Check(predicted=tp[:, -1].sum(),
                                actual=ta[:, -1].sum()))
    return checks


def gcn_network_packed(cols: Array, vals: Array, h0: Array,
                       ws: Sequence[Array], wrs: Sequence[Optional[Array]],
                       segments: Optional[Array], *,
                       num_segments: Optional[int] = None,
                       block_g: int = 128, interpret: bool = False,
                       granularity: str = "graph",
                       inject: Optional[Tuple[int, int, int, float]] = None,
                       stash_acts: bool = False
                       ) -> Tuple[Array, List[Optional[Check]],
                                  Optional[Tuple[Array, ...]]]:
    """An L-layer GCN over a block-diagonal packed batch in ONE kernel
    sweep: relu + the next layer's combination fold into the aggregation
    epilogue, the activation matrix ping-pongs between two VMEM buffers,
    and the eq.-5 column is carried across every layer boundary — one check
    per layer, taken pre-activation, exactly as the sequential path.

    ``wrs`` entries are the folded per-layer W·e (all present, or all
    ``None`` to disable checking).  ``inject=(layer, stripe, slot, delta)``
    is the accumulator fault hook.  ``stash_acts=True`` additionally writes
    each layer's post-ReLU slab to HBM and returns the per-layer inputs
    ``h_layers`` (h0, relu(out_0), …) for the surgical-repair tiers.
    Returns (out [rows, g_last], [Check | None] per layer,
    h_layers | None).
    """
    validate_packed_operands(vals, h0.shape[0], "h0")
    n_layers = len(ws)
    want_check = wrs[0] is not None
    wstack, wrstack, p, dims = _network_weight_stacks(ws, wrs, block_g)
    hp = _pad_axis(h0.astype(jnp.float32), 1, p)
    res = gcn_network_kernel(cols, vals, hp, wstack, wrstack,
                             interpret=interpret, inject=inject,
                             with_check=want_check, stash_acts=stash_acts)
    out, tele_acts, tele_preds, acts = res
    out = out[:, :dims[-1]]
    if want_check:
        checks = _network_checks(tele_acts, tele_preds, granularity,
                                 segments, num_segments)
    else:
        checks = [None] * n_layers
    h_layers = None
    if stash_acts:
        h_layers = (h0,) + tuple(acts[ell][:, :dims[ell + 1]]
                                 for ell in range(n_layers - 1))
    return out, checks, h_layers


def gcn_network_layer(bell: BlockEll, h: Array, ws: Sequence[Array],
                      wrs: Sequence[Optional[Array]], *, block_g: int = 128,
                      interpret: bool = False, granularity: str = "layer",
                      inject: Optional[Tuple[int, int, int, float]] = None,
                      stash_acts: bool = False
                      ) -> Tuple[Array, List[Optional[Check]],
                                 Optional[Tuple[Array, ...]]]:
    """Single-graph whole-network fusion (see :func:`gcn_network_packed`).

    Requires square blocks; H is padded to the full nbm*block_m stripe rows
    (the activation buffer must cover every output stripe AND every
    referenced column block — a square adjacency always satisfies this).
    Returns (out [n, g_last], [Check | None] per layer, h_layers | None);
    stashed h_layers keep the padded stripe rows (the repair path indexes
    them by stripe)."""
    if bell.block_m != bell.block_k:
        raise ValueError("whole-network fusion needs square blocks; got "
                         f"block_m={bell.block_m}, block_k={bell.block_k}")
    if granularity == "graph":
        raise ValueError("granularity='graph' needs a packed batch "
                         "(gcn_network_packed with segments)")
    n, _ = bell.shape
    rows = bell.n_block_rows * bell.block_m
    assert bell.padded_cols <= rows
    cols, vals = device_block_ell(bell)
    n_layers = len(ws)
    want_check = wrs[0] is not None
    wstack, wrstack, p, dims = _network_weight_stacks(ws, wrs, block_g)
    hp = _pad_axis(fit_rows(h.astype(jnp.float32), rows), 1, p)
    res = gcn_network_kernel(cols, vals, hp, wstack, wrstack,
                             interpret=interpret, inject=inject,
                             with_check=want_check, stash_acts=stash_acts)
    out, tele_acts, tele_preds, acts = res
    out = out[:n, :dims[-1]]
    if want_check:
        checks = _network_checks(tele_acts, tele_preds, granularity,
                                 None, None)
    else:
        checks = [None] * n_layers
    h_layers = None
    if stash_acts:
        h_layers = (fit_rows(h, rows),) + \
            tuple(acts[ell][:, :dims[ell + 1]] for ell in range(n_layers - 1))
    return out, checks, h_layers


# ---------------------------------------------------------------------------
# Cost models: when is fusing the right call?  The VMEM working-set models
# (fused_vmem_bytes / network_vmem_bytes and their *_fits predicates) live
# in vmem.py beside this module, where the engine's path decision and the
# static lint both read them.  The HBM traffic models below price a
# BlockEll layout.
# ---------------------------------------------------------------------------

def hbm_bytes_twopass(bell: BlockEll, f: int, g: int, *,
                      block_g: int = 128, itemsize: int = 4) -> int:
    """Modeled HBM bytes of one two-pass layer: the XLA combination pass
    (read H and W, write X, plus the independent eq.-5 column H·w_r), the
    fold of x_r into X's lane padding (read both, write the [K, G]
    operand, G = g + 1 rounded up to lanes), then the spmm_abft kernel
    pass (read S tiles + index table, read one operand tile per stored
    slot, write out — check lane included — and sums).

    The tile count is the padded nbm × width table — ELL padding slots are
    scheduled like real tiles in both paths, so the comparison is fair.
    """
    gp = _lanes(g + 1, block_g)
    nbm, width = bell.n_block_rows, bell.width
    bm, bk = bell.block_m, bell.block_k
    tiles = nbm * width
    k_pad = max(bell.padded_cols, bell.block_k)
    n = bell.shape[0]
    combine = n * f + f * g + k_pad * g             # read H, W; write X
    eq5 = n * f + f + k_pad                         # read H, w_r; write x_r
    fold = k_pad * (g + 1) + k_pad * gp             # read X, x_r; write both
    aggregate = (tiles * (bm * bk + bk * gp)        # S, X|x_r tiles
                 + nbm * width                      # i32 index table ~ 1 word
                 + nbm * bm * gp + nbm)             # out (+ check lane), sums
    return itemsize * (combine + eq5 + fold + aggregate)


def hbm_bytes_fused(bell: BlockEll, f: int, g: int, *,
                    block_g: int = 128, itemsize: int = 4) -> int:
    """Modeled HBM bytes of one fused layer: a single kernel pass — read S
    tiles + index table, read one H tile per stored slot, read W and w_r
    once (resident thereafter), write out / sums / extra.  X never exists
    in HBM; H is read through the same tile schedule X was before."""
    fp, gp = _lanes(f, block_g), _lanes(g, block_g)
    nbm, width = bell.n_block_rows, bell.width
    bm, bk = bell.block_m, bell.block_k
    tiles = nbm * width
    return itemsize * (tiles * (bm * bk + bk * fp)  # S, H tiles
                       + nbm * width                # index table
                       + fp * gp + fp               # W, w_r (once)
                       + nbm * bm * gp + nbm + nbm * bm)


def hbm_bytes_network(bell: BlockEll, dims: Sequence[int], *,
                      block_g: int = 128, stash_acts: bool = False,
                      itemsize: int = 4) -> int:
    """Modeled HBM bytes of the whole-network kernel: S tiles + the index
    table are re-read once per layer (same as running the per-layer fused
    kernel L times), but the H tiles stream from HBM only at layer 0, each
    W/w_r slab is read once, and only the final logits are written —
    every intermediate activation stays in VMEM.  ``stash_acts`` adds one
    [rows, P] slab write per layer (repairability export, never re-read),
    which still strictly undercuts per-layer fusion's write-then-re-read
    of the same activations through the tile schedule.

    All widths pay the shared lane-padded P = max over layer dims — the
    price of fixed activation buffers; compare against
    ``sum(hbm_bytes_fused(bell, f_l, g_l))`` which pads per layer.
    """
    p = _lanes(max(dims), block_g)
    nbm, width = bell.n_block_rows, bell.width
    bm = bell.block_m
    n_layers = len(dims) - 1
    tiles = nbm * width
    rows = nbm * bm
    traffic = (n_layers * tiles * bm * bell.block_k  # S tiles, per layer
               + nbm * width                         # index table (once)
               + tiles * bell.block_k * p            # H0 tiles (layer 0)
               + n_layers * (p * p + p)              # W / w_r stack
               + rows * p                            # final logits, once
               + 2 * n_layers * nbm * width)         # slot telescopes
    if stash_acts:
        traffic += n_layers * rows * p
    return itemsize * traffic

