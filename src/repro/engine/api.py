"""Unified GCN engine: one entry point, three aggregation backends.

This module is the ONE place where the paper's check algebra lives:

  * eq. (5): the extra column x_r = H w_r formed during the combination;
  * eq. (4)/(6): the fused corner comparison s_c H w_r vs e^T H_out e,
    produced by the backend's ``aggregate(x, x_r)``;
  * split baseline (eqs. 2–3): the per-matmul check of X = H W plus the
    same aggregation corner;
  * ReLU chain-breaking: checks are taken pre-activation; every layer is
    one linear chain, activations end it (paper §III);
  * report reduction: ``summarize`` / ``merge_reports`` from core.abft.

It is the only way in: every caller that runs a checked GCN builds a
backend here (``make_backend``) and calls ``gcn_layer`` / ``gcn_forward``
/ ``gcn_apply`` / ``gcn_loss``.

    logits, report = gcn_apply(params, Graph(s, h0), cfg,
                               backend="block_ell",
                               partition=Partition(mesh, "graph"))
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.abft import (
    ABFTConfig,
    ABFTReport,
    Check,
    fold_w_r_tree,
    resolve_w_r,
    summarize,
)

from repro.runtime.spans import span

from .backends import AggregationBackend, make_backend

Array = jax.Array
Params = Any

_EXACT = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class Graph:
    """One graph as the engine consumes it.

    ``s`` is the normalized adjacency in any backend format (dense array,
    BCOO, or host-side BlockEll); ``h0`` the dense node features; ``s_c``
    the optional offline column checksum e^T S (precompute once per static
    graph — computed once and auto-stashed back here on the first
    ``gcn_forward`` call when absent).  Dense ``s``/``h0`` may carry
    leading batch axes (batched multi-graph serving).  On a partition,
    ``h0`` may be held with its rows: padded to the stripes' rows and
    sharded by rows (``engine.sharded.stage_rows``); every layer's output,
    the logits too, then keeps those rows, the padding ones zero.

    The auto-stash assumes a *static* graph: it is invalidated when ``s``
    is rebound to a new object or the checksum dtype changes, but cannot
    see in-place mutation of a numpy ``s`` — mutate-in-place callers must
    reset ``s_c = None`` (or build a fresh Graph) themselves.
    """

    s: Any
    h0: Array
    s_c: Optional[Array] = None

    @property
    def n(self) -> int:
        return int(self.h0.shape[-2])



def gcn_layer(bk: AggregationBackend, h: Array, w: Array, cfg: ABFTConfig,
              *, w_r: Optional[Array] = None, return_x: bool = False
              ) -> Tuple[Array, List[Check]]:
    """One pre-activation GCN layer H_out = S (H W) under ABFT policy.

    The canonical eq. 4–6 algebra: ``w_r = W e`` (offline in deployment —
    pass it in to fold at weight-load time), the eq.-5 column
    ``x_r = H w_r`` taken from the *independent* path (never from row-sums
    of the computed X: a fault in X would cancel), and the backend's fused
    corner check.  ``fused`` emits that single check; ``split`` adds the
    combination-matmul check (eq. 2–3 baseline); ``none`` emits nothing.

    Backends with a whole-layer hook (:meth:`AggregationBackend.layer` —
    the block-ELL backend's single-pass fused kernel) take the fused/none
    modes without ever materializing X; the split baseline needs X for its
    combination check, so it always runs the generic two-pass path below.

    A passed-in ``w_r`` must have been folded at this config's checksum
    dtype: consuming a stale fold verbatim would silently run every check
    at the old precision, so a mismatch raises instead.

    ``return_x=True`` appends the materialized combination output X to the
    result — ``None`` when the backend's fused layer hook ran (X never
    existed).  The stripe-surgical repair uses the stashed X to replay a
    two-pass layer's aggregation bit-for-bit.
    """
    w_r = resolve_w_r(w, w_r, cfg)
    if cfg.mode != "split":
        fused = bk.layer(h, w, cfg, w_r=w_r)
        if fused is not NotImplemented:
            h_out, chk = fused
            checks = [] if chk is None else [chk]
            return (h_out, checks, None) if return_x else (h_out, checks)
    # full-precision f32 dots: on TPU, XLA's default runs an f32 dot as one
    # bf16 pass, and X and the eq.-5 column would then round apart far
    # enough to flag clean layers
    with span("gcn.combine"):
        x = jnp.matmul(h, w, precision=_EXACT)
    if not cfg.enabled:
        with span("gcn.aggregate"):
            h_out, _ = bk.aggregate(x, None)
        return (h_out, [], x) if return_x else (h_out, [])
    with span("gcn.check_column"):
        x_r = jnp.matmul(h.astype(cfg.dtype), w_r, precision=_EXACT)
    with span("gcn.aggregate"):
        h_out, chk = bk.aggregate(x, x_r)
    if cfg.mode == "split":
        # the backend owns the split check's granularity: generic
        # check_matmul scalars, or per-graph corners on the packed path
        checks = [bk.combination_check(h, w, x, cfg, w_r=w_r), chk]
    else:
        checks = [chk]
    return (h_out, checks, x) if return_x else (h_out, checks)


def fold_w_r(params: Params, cfg: ABFTConfig) -> Params:
    """Fold the per-layer right checksum w_r = W·e into the params, once,
    at weight-load time (the paper's "offline" eq.-5 convention).

    Without the fold :func:`gcn_forward` recomputes ``row_checksum(w)``
    every layer every step; with it, each layer carries a ``w_r`` entry in
    ``cfg.dtype`` that the layer math consumes verbatim — bitwise-identical
    checks, zero per-step recompute.  Re-fold after any weight update (or
    if ``cfg.dtype`` changes).

    Delegates to the tree-generic :func:`repro.core.abft.fold_w_r_tree`:
    any params pytree folds (GCN ``{"layers": [...]}``, transformer trees,
    GAT layers) — every dict with a ``"w"`` weight gains its ``"w_r"``.
    """
    return fold_w_r_tree(params, cfg)


def gcn_forward(params: Params, graph: Graph, cfg: ABFTConfig, *,
                backend=None, partition=None, return_intermediates=False,
                return_x=False, **backend_opts) -> Tuple[Array, List[Check]]:
    """Forward pass through all layers; returns (logits, per-layer checks).

    The backend is constructed once per call (s_c staged/computed once,
    shared by every layer) — or passed in as an already-built
    :class:`AggregationBackend` instance (the jitted packed serving step
    builds one from traced arrays).  For the fused/none check modes the
    backend's whole-network hook (:meth:`AggregationBackend.network`) is
    consulted first — the block-ELL backend's ``fused_network`` option
    runs every layer in one kernel sweep with the activations resident in
    VMEM; on ``NotImplemented`` the per-layer loop below runs (which in
    turn consults the per-layer hook).  ReLU between layers breaks the
    checksum chain, so each layer carries its own check — the paper's
    per-layer fused granularity — on both paths.  Layers carrying a
    folded ``w_r`` (:func:`fold_w_r`) skip the per-step row_checksum
    recompute.

    ``return_intermediates=True`` appends a result: the tuple of every
    layer's *input* activations (h_layers[0] is h0, h_layers[l] the
    post-ReLU input to layer l) — from the loop for free, or stashed by
    the whole-network kernel (one extra write per layer, never re-read).
    The stripe-surgical retry consumes these to re-execute a flagged
    layer's stripes from the exact operands the faulted pass read.
    ``return_x=True`` appends one more: the tuple of per-layer
    combination outputs X (``None`` for layers a fused hook ran), letting
    the repair replay a two-pass layer's aggregation bit-for-bit.
    """
    if isinstance(backend, AggregationBackend):
        bk = backend
    else:
        s_c = graph.s_c
        if s_c is not None and getattr(graph, "_s_c_auto", False) and (
                getattr(graph, "_s_c_dtype", None) != cfg.dtype
                or getattr(graph, "_s_c_src", None) is not graph.s):
            # an auto-stash from an earlier call under a different checksum
            # dtype, or for a since-replaced adjacency operand: reusing it
            # would run this call's checks at a stale precision / against a
            # stale e^T S.  User-provided s_c is trusted verbatim.  (The
            # dtype key is the REQUESTED cfg.dtype, not the realized array
            # dtype, so x64-disabled f64 requests still cache.)
            s_c = None
        bk = make_backend(graph.s, cfg, backend=backend, s_c=s_c,
                          partition=partition, **backend_opts)
        if s_c is None:
            # stash the backend's (possibly O(nnz)-computed) column checksum
            # back on the graph: repeated gcn_apply/gcn_forward calls on the
            # same staged Graph reuse it instead of recomputing every call
            stashed = getattr(bk, "s_c", None)
            graph.s_c = stashed
            graph._s_c_auto = stashed is not None
            graph._s_c_dtype = cfg.dtype
            graph._s_c_src = graph.s
    h = graph.h0
    layers = params["layers"]
    wrs: Optional[List[Optional[Array]]] = None
    if cfg.mode != "split":
        wrs = [resolve_w_r(layer["w"], layer.get("w_r"), cfg)
               for layer in layers]
        net = bk.network(h, [layer["w"] for layer in layers], wrs, cfg,
                         stash=return_intermediates)
        if net is not NotImplemented:
            logits, layer_checks, net_h_layers = net
            checks = [c for c in layer_checks if c is not None]
            xs = (None,) * len(layers)
            if return_intermediates:
                return ((logits, checks, net_h_layers, xs) if return_x
                        else (logits, checks, net_h_layers))
            return (logits, checks, xs) if return_x else (logits, checks)
    checks = []
    h_layers: List[Array] = []
    x_layers: List[Optional[Array]] = []
    for i, layer in enumerate(layers):
        with span("gcn.layer", layer=i):
            h_layers.append(h)
            w_r = wrs[i] if wrs is not None else layer.get("w_r")
            h_out, cs, x = gcn_layer(bk, h, layer["w"], cfg, w_r=w_r,
                                     return_x=True)
            checks.extend(cs)
            x_layers.append(x)
            h = jax.nn.relu(h_out) if i < len(layers) - 1 else h_out
    if return_intermediates:
        return ((h, checks, tuple(h_layers), tuple(x_layers)) if return_x
                else (h, checks, tuple(h_layers)))
    return (h, checks, tuple(x_layers)) if return_x else (h, checks)


def gcn_apply(params: Params, graph: Graph, cfg: ABFTConfig, *,
              backend=None, partition=None,
              **backend_opts) -> Tuple[Array, ABFTReport]:
    """The engine entry point: logits + one replicated ABFTReport.

    ``backend`` is ``"dense" | "bcoo" | "block_ell"`` (inferred from the
    adjacency operand when omitted) or an already-built
    :class:`AggregationBackend` (``make_backend``), whose counters then
    outlive the call; ``partition`` a
    :class:`~repro.engine.sharded.Partition` for stripe-sharded block-ELL
    aggregation (per-shard partial checks psum into this same report).
    """
    # the span holds the report too: a forward's verdict is part of it
    with span("gcn.forward", mode=cfg.mode):
        logits, checks = gcn_forward(params, graph, cfg, backend=backend,
                                     partition=partition, **backend_opts)
        # with the check off there is nothing to reduce: no span
        with (span("gcn.summarize") if cfg.enabled
              else contextlib.nullcontext()):
            report = summarize(checks, cfg)
    return logits, report


def gcn_loss(params: Params, graph: Graph, labels: Array,
             mask: Optional[Array], cfg: ABFTConfig
             ) -> Tuple[Array, ABFTReport]:
    """Mean node-classification NLL over ``mask`` (all nodes when
    ``None``), with the forward's report as the auxiliary output — for
    ``jax.value_and_grad(..., has_aux=True)`` in a checked train step."""
    logits, report = gcn_apply(params, graph, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if mask is not None:
        nll = jnp.where(mask, nll, 0.0)
        loss = nll.sum() / jnp.maximum(mask.sum(), 1)
    else:
        loss = nll.mean()
    return loss, report
