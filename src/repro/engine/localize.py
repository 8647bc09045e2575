"""Stripe-surgical fault recovery: re-execute ONLY the rows a fault hit.

The eq. 4–6 corner is linear, so the packed kernels can keep their
per-row-stripe checksum partials as individual corners
(``granularity="stripe"``) — a detected fault then *names the stripe* it
corrupted instead of condemning a whole graph.  This module turns that
name into the cheapest exact repair the layout admits:

  1. **gather** the flagged stripes' tile rows + column-index table into a
     sub-system (:func:`gather_stripe_system`) — the cols table keeps its
     original column-block indices, so the FULL packed H stays the operand
     and no re-packing happens;
  2. **recompute** those stripes through the kernel THAT RAN THEM.  A
     fused-pass layer replays through the single-pass fused kernel
     (``kernels/gcn_fused``); a two-pass layer whose combination output X
     was stashed (``abft_x_layers``, ``gcn_forward(..., return_x=True)``)
     replays its aggregation through the two-pass spmm kernel against
     that exact X.  Each grid stripe accumulates independently in the
     same slot order over the same tiles, so either way the recomputed
     rows are *bit-for-bit* the values a clean full sweep would have
     produced.  (A two-pass original with no stashed X falls back to the
     fused recompute — exact up to f32 reassociation, re-verified by its
     own corners, just not bitwise — and a layer whose [f, g] working set
     exceeds the fused VMEM budget escalates instead of running a kernel
     the engine rejected.);
  3. **splice** the rows back (through ReLU for non-final layers) and
     propagate: a repaired stripe's rows are column blocks of the next
     layer, so only the stripes whose cols table references them (nonzero
     tiles — block-diagonal keeps this inside the owning graph) need
     re-execution downstream, not the whole graph;
  4. **re-verify**: the sub-sweep carries its own per-stripe corners; any
     corner still flagged aborts the repair and the guard escalates to the
     per-graph retry tier.

Recovery cost is counted in re-executed rows (``abft_rows_recomputed``):
a last-layer fault costs one stripe; an early-layer fault costs one stripe
plus the reachable downstream stripes — strictly less than the per-graph
retry's rows(graph) x layers whenever a graph spans more than one stripe.

:func:`surgical_slot_retry` is the tier below: at ``granularity="slot"``
the fused kernels' telescoped corners name the exact (stripe, ell-slot)
the fault landed in, and the repair refines downstream propagation to the
*rows that actually changed*.  After recomputing a flagged stripe it diffs
the new post-ReLU rows against the stashed activations; a downstream
stripe re-executes only if one of its stored tiles has a nonzero column
AT a changed row (0·x = 0 exactly, so skipping a zero column is sound —
and a fault ReLU already masked to zero propagates nowhere).  That is
strictly fewer rows than the stripe tier's any-nonzero-tile reach
whenever the changed-row footprint is narrower than the whole column
block.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.abft import ABFTConfig
from repro.core.checksum import row_checksum
from repro.engine.backends import kernel_paths
from repro.kernels.runtime import resolve_interpret
from repro.kernels.spmm_abft.layout import BlockEll

log = logging.getLogger(__name__)


def gather_stripe_system(bell: BlockEll, stripe_idx) -> BlockEll:
    """Sub-system holding only ``stripe_idx``'s tile rows.

    The column-block indices are NOT remapped: the sub-system's stripes
    still gather from the full packed H/X rows, which is what makes the
    recompute a pure row-subset of the original sweep (same tiles, same
    slot order, same operand values — bitwise-identical stripe outputs).
    """
    idx = np.asarray(stripe_idx, np.int64)
    return BlockEll(values=bell.values[idx],
                    block_cols=bell.block_cols[idx],
                    shape=(int(idx.size) * bell.block_m, bell.shape[1]))


def _layer_stripe_flags(sflags: np.ndarray, n_layers: int) -> np.ndarray:
    """[n_checks, nbm] per-check stripe flags -> [n_layers, nbm].

    Fused mode emits one check per layer; split mode two (combination +
    corner).  Rows group contiguously per layer, so OR-reducing each
    layer's group attributes every flag to the layer that must re-execute.
    """
    if sflags.ndim != 2 or sflags.shape[0] % n_layers or not sflags.shape[0]:
        raise ValueError(
            f"abft_stripe_flags has shape {sflags.shape}; expected "
            f"[k*{n_layers} checks, n_stripes] (k checks per layer)")
    per = sflags.shape[0] // n_layers
    return sflags.reshape(n_layers, per, sflags.shape[1]).any(axis=1)


def _layer_slot_flags(slflags: np.ndarray, n_layers: int) -> np.ndarray:
    """[n_checks, nbm, width] per-check slot flags -> [n_layers, nbm,
    width], same contiguous-per-layer grouping as the stripe reduction."""
    if slflags.ndim != 3 or slflags.shape[0] % n_layers \
            or not slflags.shape[0]:
        raise ValueError(
            f"abft_slot_flags has shape {slflags.shape}; expected "
            f"[k*{n_layers} checks, n_stripes, width]")
    per = slflags.shape[0] // n_layers
    return slflags.reshape((n_layers, per) + slflags.shape[1:]).any(axis=1)


def _stashed_x_layers(metrics, n_layers: int):
    """Writable copies of the step's per-layer combination outputs
    (``abft_x_layers``), or None when the step didn't stash them.  Entries
    are None for layers a fused hook ran (no X ever existed)."""
    xs = metrics.get("abft_x_layers")
    if xs is None:
        return None
    xs = [None if x is None else np.array(x) for x in xs]
    if len(xs) != n_layers:
        raise ValueError(f"abft_x_layers carries {len(xs)} arrays; "
                         f"the model has {n_layers} layers")
    return xs


def _recompute_stripes(bell: BlockEll, todo, w, w_r, h_ell, x_ell,
                       cfg: ABFTConfig, *, block_g: int, interpret: bool):
    """Re-execute ``todo``'s stripes of one layer through the kernel that
    ran them originally: the two-pass spmm against the stashed X when
    ``x_ell`` is given (bit-for-bit replay of a two-pass layer), else the
    single-pass fused kernel (bit-for-bit for a fused original).  Returns
    (sub_out, per-stripe Check), or None when the layer exceeds the fused
    VMEM budget and no X is stashed — the caller escalates rather than
    forcing a kernel the engine itself refused to run."""
    sub = gather_stripe_system(bell, todo)
    if x_ell is not None:
        from repro.kernels.spmm_abft.ops import spmm_abft
        xr = (jnp.asarray(h_ell).astype(cfg.dtype)
              @ jnp.asarray(w_r))[:, None]
        return spmm_abft(sub, jnp.asarray(x_ell), xr, block_g=block_g,
                         granularity="stripe", interpret=interpret)
    from repro.kernels.gcn_fused.ops import gcn_fused_layer
    if kernel_paths(w.shape, bell.block_m, bell.block_k,
                    bell.n_block_rows * bell.block_m, fused_layer=True,
                    fused_network=False, block_g=block_g) != ("fused",):
        return None
    return gcn_fused_layer(sub, jnp.asarray(h_ell), w, w_r, block_g=block_g,
                           granularity="stripe", interpret=interpret)


def surgical_stripe_retry(pb, params, cfg: ABFTConfig, out, metrics,
                          *, block_g: int = 128,
                          interpret: Optional[bool] = None
                          ) -> Tuple[np.ndarray, Dict[str, Any]]:
    """Repair a flagged packed step by re-executing only the hit stripes.

    ``pb`` is the :class:`~repro.engine.batching.PackedGraphs` batch the
    step ran; ``metrics`` must carry ``abft_stripe_flags`` (the
    per-(check, stripe) verdicts) and ``abft_h_layers`` (every layer's
    input activations, ``gcn_forward(..., return_intermediates=True)``);
    ``abft_x_layers`` (the stashed two-pass combination outputs,
    ``return_x=True``), when present, lets two-pass layers replay through
    the spmm kernel bit-for-bit instead of escalating on VMEM-fallback
    layers.  Returns ``(repaired_out, sub_metrics)`` in the guard's
    stripe-tier contract: ``sub_metrics['abft_graph_flags']`` is the FULL
    [n_slots] vector (all-False on verified success; the original flags
    when the repair could not be verified, so the guard escalates), plus
    the ``abft_rows_recomputed`` / ``abft_stripes_recomputed`` accounting.
    """
    interpret = resolve_interpret(interpret)
    layers = params["layers"]
    n_layers = len(layers)
    sflags = _layer_stripe_flags(
        np.asarray(metrics["abft_stripe_flags"], bool), n_layers)
    h_layers = [np.array(h) for h in metrics["abft_h_layers"]]  # writable
    if len(h_layers) != n_layers:
        raise ValueError(f"abft_h_layers carries {len(h_layers)} arrays; "
                         f"the model has {n_layers} layers")
    x_layers = _stashed_x_layers(metrics, n_layers)
    bell = pb.bell
    bm = bell.block_m
    stripe_graph = np.asarray(pb.stripe_graph)
    n_slots = pb.n_slots
    orig_flags = np.asarray(metrics["abft_graph_flags"], bool).copy()

    def escalate(reason: str):
        log.error("ABFT stripe repair escalating: %s", reason)
        return np.asarray(out), {
            "abft_graph_flags": orig_flags,
            "abft_rows_recomputed": rows_recomputed,
            "abft_stripes_recomputed": stripes_recomputed,
        }

    rows_recomputed = 0
    stripes_recomputed = 0
    repaired = np.array(out)                                    # writable
    graph_rel = np.zeros(n_slots, np.float32)
    dirty_cols: set = set()          # column blocks whose H rows changed
    for ell in range(n_layers):
        flagged = set(np.nonzero(sflags[ell])[0].tolist())  # abftlint: sync-ok (post-flag repair path)
        if any(stripe_graph[s] >= n_slots for s in flagged):
            # a padding stripe's corner is 0 = 0 by construction; it
            # flagging means the batch invariants are broken — do not
            # guess, hand the step to the coarser tiers
            return escalate("padding stripe flagged")
        reach = _reachable_stripes(bell, dirty_cols)
        reached = {s for s in np.nonzero(reach)[0].tolist()  # abftlint: sync-ok
                   if stripe_graph[s] < n_slots}
        todo = sorted(flagged | reached)
        if not todo:
            continue
        w = layers[ell]["w"]
        w_r = layers[ell].get("w_r")
        if w_r is None:
            w_r = row_checksum(w, cfg.dtype)
        x_ell = x_layers[ell] if x_layers is not None else None
        res = _recompute_stripes(bell, todo, w, w_r, h_layers[ell], x_ell,
                                 cfg, block_g=block_g, interpret=interpret)
        if res is None:
            # the engine itself would refuse to run this layer fused
            # (resident W exceeds the VMEM budget) and no X was stashed —
            # recovery must not be the one place that kernel is forced to
            # run
            return escalate(f"layer {ell} [f, g]={tuple(w.shape)} exceeds "
                            f"the fused VMEM budget and no X is stashed")
        sub_out, chk = res
        rows_recomputed += len(todo) * bm
        stripes_recomputed += len(todo)
        if bool(chk.flag(cfg)):  # abftlint: sync-ok
            return escalate(f"recomputed stripes still flagged at layer "
                            f"{ell}")
        _, rel = chk.elementwise(cfg)
        rel = np.asarray(rel)  # abftlint: sync-ok
        sub_out = np.asarray(sub_out)  # abftlint: sync-ok
        for k, s in enumerate(todo):
            r0 = s * bm
            rows = sub_out[k * bm:(k + 1) * bm]
            if ell < n_layers - 1:
                h_layers[ell + 1][r0:r0 + bm] = np.maximum(rows, 0.0)
                if x_layers is not None and x_layers[ell + 1] is not None:
                    # the spliced activations invalidate the NEXT layer's
                    # stashed combination rows — refresh them so its
                    # replay consumes the repaired operands
                    x_layers[ell + 1][r0:r0 + bm] = np.asarray(  # abftlint: sync-ok
                        jnp.asarray(h_layers[ell + 1][r0:r0 + bm])
                        @ jnp.asarray(layers[ell + 1]["w"]))
            else:
                repaired[r0:r0 + bm] = rows
            graph_rel[stripe_graph[s]] = max(graph_rel[stripe_graph[s]],
                                             float(rel[k]))  # abftlint: sync-ok
        dirty_cols = set(todo)       # square blocks: stripe s == col block s
    log.warning("ABFT: stripe-surgical repair verified clean "
                "(%d stripes / %d rows re-executed)",
                stripes_recomputed, rows_recomputed)
    return repaired, {
        "abft_graph_flags": np.zeros(n_slots, bool),
        "abft_graph_max_rel": graph_rel,
        "abft_rows_recomputed": rows_recomputed,
        "abft_stripes_recomputed": stripes_recomputed,
    }


def _reachable_stripes(bell: BlockEll, col_blocks: set) -> np.ndarray:
    """[n_block_rows] mask of stripes that read any of ``col_blocks``' rows
    through a stored (nonzero) tile.  ELL padding tiles alias column-block
    0 with all-zero values — they must not mark graph 0's stripes dirty."""
    if not col_blocks:
        return np.zeros(bell.n_block_rows, bool)
    hit = np.isin(bell.block_cols,
                  np.fromiter(col_blocks, np.int64, len(col_blocks)))
    stored = np.abs(bell.values).max(axis=(2, 3)) > 0
    return (hit & stored).any(axis=1)


def _rows_reachable_stripes(bell: BlockEll,
                            dirty: Dict[int, np.ndarray]) -> np.ndarray:
    """[n_block_rows] mask of stripes that read a CHANGED row of a dirty
    column block through a nonzero tile column — the slot tier's row-level
    refinement of :func:`_reachable_stripes`.  A tile column that is all
    zero contributes exactly 0 regardless of the operand row (0·x = 0 in
    f32), so skipping it cannot change the recomputed output bitwise."""
    mask = np.zeros(bell.n_block_rows, bool)
    if not dirty:
        return mask
    # nonzero per tile COLUMN: tile columns index the operand's local rows
    colnz = np.abs(bell.values).max(axis=2) > 0      # [nbm, width, bk]
    for cb, rowmask in dirty.items():
        if not rowmask.any():
            continue
        hit = bell.block_cols == cb                  # [nbm, width]
        mask |= (hit[:, :, None] & colnz
                 & rowmask[None, None, :]).any(axis=(1, 2))
    return mask


def surgical_slot_retry(pb, params, cfg: ABFTConfig, out, metrics,
                        *, block_g: int = 128,
                        interpret: Optional[bool] = None
                        ) -> Tuple[np.ndarray, Dict[str, Any]]:
    """The ladder's finest tier: repair from per-(stripe, slot) verdicts
    with row-level downstream propagation.

    Same contract as :func:`surgical_stripe_retry` (FULL-batch
    ``abft_graph_flags``, rows/stripes accounting; the guard escalates to
    the stripe tier when the repair cannot be verified), but consumes
    ``metrics['abft_slot_flags']`` ([n_checks, n_stripes, width] telescope
    corners) and refines propagation: after recomputing a flagged stripe
    it diffs the new post-ReLU rows against the stashed activations and
    marks ONLY the changed rows dirty — a downstream stripe re-executes
    only if a stored tile reads a changed row through a nonzero column.
    A fault whose corruption ReLU masks to zero (or that never alters the
    post-activation rows) therefore propagates to nothing, and the tier
    re-executes strictly fewer rows than the stripe tier whenever the
    changed-row footprint is narrower than the whole column block.
    """
    interpret = resolve_interpret(interpret)
    layers = params["layers"]
    n_layers = len(layers)
    slflags = _layer_slot_flags(
        np.asarray(metrics["abft_slot_flags"], bool), n_layers)
    h_layers = [np.array(h) for h in metrics["abft_h_layers"]]  # writable
    if len(h_layers) != n_layers:
        raise ValueError(f"abft_h_layers carries {len(h_layers)} arrays; "
                         f"the model has {n_layers} layers")
    x_layers = _stashed_x_layers(metrics, n_layers)
    bell = pb.bell
    bm = bell.block_m
    stripe_graph = np.asarray(pb.stripe_graph)
    n_slots = pb.n_slots
    orig_flags = np.asarray(metrics["abft_graph_flags"], bool).copy()

    def escalate(reason: str):
        log.error("ABFT slot repair escalating: %s", reason)
        return np.asarray(out), {
            "abft_graph_flags": orig_flags,
            "abft_rows_recomputed": rows_recomputed,
            "abft_stripes_recomputed": stripes_recomputed,
        }

    rows_recomputed = 0
    stripes_recomputed = 0
    repaired = np.array(out)                                    # writable
    graph_rel = np.zeros(n_slots, np.float32)
    dirty: Dict[int, np.ndarray] = {}    # col block -> [bm] changed rows
    for ell in range(n_layers):
        flagged = set(np.nonzero(slflags[ell].any(axis=1))[0].tolist())  # abftlint: sync-ok (post-flag repair path)
        if any(stripe_graph[s] >= n_slots for s in flagged):
            return escalate("padding stripe flagged")
        reach = _rows_reachable_stripes(bell, dirty)
        reached = {s for s in np.nonzero(reach)[0].tolist()  # abftlint: sync-ok
                   if stripe_graph[s] < n_slots}
        todo = sorted(flagged | reached)
        dirty = {}
        if not todo:
            continue
        w = layers[ell]["w"]
        w_r = layers[ell].get("w_r")
        if w_r is None:
            w_r = row_checksum(w, cfg.dtype)
        x_ell = x_layers[ell] if x_layers is not None else None
        res = _recompute_stripes(bell, todo, w, w_r, h_layers[ell], x_ell,
                                 cfg, block_g=block_g, interpret=interpret)
        if res is None:
            return escalate(f"layer {ell} [f, g]={tuple(w.shape)} exceeds "
                            f"the fused VMEM budget and no X is stashed")
        sub_out, chk = res
        rows_recomputed += len(todo) * bm
        stripes_recomputed += len(todo)
        if bool(chk.flag(cfg)):  # abftlint: sync-ok
            return escalate(f"recomputed stripes still flagged at layer "
                            f"{ell}")
        _, rel = chk.elementwise(cfg)
        rel = np.asarray(rel)  # abftlint: sync-ok
        sub_out = np.asarray(sub_out)  # abftlint: sync-ok
        for k, s in enumerate(todo):
            r0 = s * bm
            rows = sub_out[k * bm:(k + 1) * bm]
            if ell < n_layers - 1:
                act = np.maximum(rows, 0.0)
                changed = (act != h_layers[ell + 1][r0:r0 + bm]).any(axis=1)
                h_layers[ell + 1][r0:r0 + bm] = act
                if changed.any():
                    # square blocks: stripe s == column block s; only the
                    # rows that actually changed can perturb downstream
                    dirty[s] = changed
                    if x_layers is not None and x_layers[ell + 1] is not None:
                        x_layers[ell + 1][r0:r0 + bm] = np.asarray(  # abftlint: sync-ok
                            jnp.asarray(act)
                            @ jnp.asarray(layers[ell + 1]["w"]))
            else:
                repaired[r0:r0 + bm] = rows
            graph_rel[stripe_graph[s]] = max(graph_rel[stripe_graph[s]],
                                             float(rel[k]))  # abftlint: sync-ok
    log.warning("ABFT: slot-surgical repair verified clean "
                "(%d stripes / %d rows re-executed)",
                stripes_recomputed, rows_recomputed)
    return repaired, {
        "abft_graph_flags": np.zeros(n_slots, bool),
        "abft_graph_max_rel": graph_rel,
        "abft_rows_recomputed": rows_recomputed,
        "abft_stripes_recomputed": stripes_recomputed,
    }
