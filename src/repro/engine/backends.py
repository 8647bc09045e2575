"""Aggregation backends for the unified GCN engine.

A backend owns exactly one thing: the aggregation matmul H_out = S @ X and
the eq.-6 corner of the fused check for that multiply.  Everything else —
the eq.-5 extra column x_r = H w_r, split-vs-fused policy, ReLU
chain-breaking, report reduction — lives once in ``engine/api.py``.

The protocol is deliberately narrow::

    aggregate(x, x_r) -> (h_out, Check | None)

``x`` is the combination output X = H W; ``x_r`` is the carried checksum
column H w_r (a [..., n]-vector, or ``None`` when checking is disabled).
When ``x_r`` is given, the returned :class:`~repro.core.abft.Check` holds
``predicted = s_c @ x_r`` (equivalently ``Σ S x_r`` — the kernel backend
never materializes s_c online) and ``actual = Σ H_out``.

Three built-in backends, selected by name or inferred from the operand:

  * ``dense``     — jnp matmul over a dense S; batched leading axes ok.
  * ``bcoo``      — ``jax.experimental.sparse`` BCOO aggregation with the
                    O(nnz) offline s_c (``sparse_col_checksum``).
  * ``block_ell`` — the Pallas spmm_abft kernel over a padded block-ELL
                    layout; the check rides the kernel's fused epilogue,
                    and a :class:`~repro.engine.sharded.Partition` shards
                    row-stripes across a mesh axis with psum'd partials.

New backends register with :func:`register_backend`; the registry is the
single dispatch point for ``gcn_apply(..., backend=...)``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.abft import (GRANULARITIES, ABFTConfig, Check, CheckedOp,
                             _total)
from repro.core.checksum import col_checksum
from repro.kernels.runtime import resolve_interpret
from repro.runtime.spans import span

Array = jax.Array

_REGISTRY: Dict[str, Callable[..., "AggregationBackend"]] = {}


def _validate_granularity(name: str, granularity: str,
                          supported: Tuple[str, ...]) -> str:
    if granularity not in GRANULARITIES:
        raise ValueError(f"granularity {granularity!r} not in "
                         f"{GRANULARITIES}")
    if granularity not in supported:
        raise ValueError(
            f"{name} backend supports granularity in {supported}, not "
            f"{granularity!r}; stripe-granular corners need the block_ell "
            f"kernel path (per-row-stripe checksum partials)")
    return granularity


def register_backend(name: str):
    """Class decorator: make ``name`` resolvable by :func:`get_backend`."""
    def deco(cls):
        _REGISTRY[name] = cls
        cls.name = name
        return cls
    return deco


def get_backend(name: str) -> Callable[..., "AggregationBackend"]:
    if name not in _REGISTRY:
        raise ValueError(f"unknown engine backend {name!r}; "
                         f"registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def backend_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def infer_backend(s: Any) -> str:
    """Map an adjacency operand to its natural backend name."""
    from repro.kernels.spmm_abft.layout import BlockEll, BlockEllPlan
    from repro.engine.batching import PackedGraphs
    from jax.experimental import sparse as jsparse
    if isinstance(s, (BlockEll, BlockEllPlan, PackedGraphs)):
        return "block_ell"
    if isinstance(s, jsparse.BCOO):
        return "bcoo"
    return "dense"


class AggregationBackend(CheckedOp):
    """Protocol base; subclasses implement :meth:`aggregate`.

    An aggregation backend is a :class:`~repro.core.abft.CheckedOp`
    implementation: calling it runs one whole GCN layer under the engine's
    eq. 4–6 algebra —

        h_out, checks = bk(cfg, h, w, w_r=folded_w_r)

    — delegating to ``engine.gcn_layer`` (which in turn consults the
    backend's :meth:`layer`/:meth:`network` fusion hooks and
    :meth:`aggregate`).  Subclassers that only ever implemented
    ``aggregate`` keep working unchanged; the CheckedOp surface is additive.

    Constructors take only the options they honour — an unknown or
    inapplicable keyword (``block_g`` on dense, a typo'd ``interpet``)
    raises TypeError instead of being silently dropped.

    ``granularity`` declares what one element of the emitted Check
    attributes a fault to: ``"layer"`` (one scalar corner per linear
    chain — the paper's check), ``"graph"`` (one corner per packed /
    batched graph), or ``"stripe"`` (one corner per block-ELL row-stripe —
    fault localization; block_ell only).
    """

    name = "abstract"
    op_id = "gcn_layer"
    granularity = "layer"

    def __init__(self, s: Any, cfg: ABFTConfig, *, s_c: Optional[Array] = None,
                 partition=None):
        raise NotImplementedError

    def __call__(self, cfg: ABFTConfig, h: Array, w: Array, *,
                 w_r: Optional[Array] = None):
        """CheckedOp entry point: one pre-activation GCN layer
        ``H_out = S (H W)`` with its declared-granularity check(s)."""
        from .api import gcn_layer
        h_out, checks = gcn_layer(self, h, w, cfg, w_r=w_r)
        if not checks:
            return h_out, None
        return h_out, (checks[0] if len(checks) == 1 else checks)

    def aggregate(self, x: Array, x_r: Optional[Array]
                  ) -> Tuple[Array, Optional[Check]]:
        raise NotImplementedError

    def layer(self, h: Array, w: Array, cfg: ABFTConfig, *,
              w_r: Optional[Array] = None):
        """Whole-layer hook: execute H_out = S (H W) plus the eq. 4–6 check
        in one backend-fused step, returning (h_out, Check | None) — or
        ``NotImplemented`` to make the engine run the generic two-pass path
        (combination via XLA, then :meth:`aggregate`).

        Only consulted for the fused/none check modes: the split baseline
        (eqs. 2–3) checks the combination product X itself, and a layer
        that never materializes X has nothing for that check to read.

        The ``fused_hits``/``fused_fallbacks`` counters on implementing
        backends count *decisions*, taken eagerly or at trace time — a
        jitted step counts once per compile, not once per batch (the
        serving driver surfaces trace-time fallbacks eagerly instead).
        """
        return NotImplemented

    def network(self, h0: Array, ws, wrs, cfg: ABFTConfig, *,
                stash: bool = False):
        """Whole-network hook: execute EVERY layer — combination,
        aggregation, ReLU, and the next layer's combination — in one
        backend-fused sweep, returning ``(logits, [Check | None] per
        layer, h_layers | None)``, or ``NotImplemented`` to make the
        engine run its per-layer loop (which still consults
        :meth:`layer` for each).

        ``ws``/``wrs`` are the per-layer weights and folded eq.-5
        columns (``wrs`` all ``None`` when checking is off — the checks
        stay per-layer and pre-activation either way).  ``stash=True``
        asks for the per-layer input activations ``h_layers`` (the
        surgical-repair tiers replay from them); a backend that cannot
        export them must return ``NotImplemented`` rather than a
        ``None`` third element when stash is requested.

        Like :meth:`layer`, only consulted for the fused/none modes:
        the split baseline checks the combination product X itself,
        which whole-network fusion never materializes.
        """
        return NotImplemented

    def combination_check(self, h: Array, w: Array, x: Array,
                          cfg: ABFTConfig, *, w_r: Optional[Array] = None
                          ) -> Check:
        """Split-mode (eq. 2–3) check of the combination matmul x = h w.

        The default is the generic :func:`~repro.core.abft.check_matmul`;
        backends whose check granularity is finer than "one scalar per
        operand" (the packed block-diagonal batch) override it so the split
        check matches their aggregate corner's per-graph shape.
        """
        from repro.core.abft import check_matmul
        return check_matmul(h, w, x, cfg)


@register_backend("dense")
class DenseBackend(AggregationBackend):
    """S as a dense jnp array.  Leading batch axes broadcast: S [..., n, n]
    with X [..., n, g] yields batched scalar checks, which ``summarize``
    reduces — this is what batched multi-graph serving runs on."""

    def __init__(self, s: Array, cfg: ABFTConfig, *,
                 s_c: Optional[Array] = None, partition=None,
                 granularity: str = "layer"):
        if partition is not None:
            raise ValueError("dense backend does not support partition=; "
                             "use backend='block_ell'")
        # "graph" is what the batched leading axes already deliver (one
        # scalar corner per batch element); "stripe" has no meaning without
        # the block-ELL row-stripe partials.
        self.granularity = _validate_granularity("dense", granularity,
                                                 ("layer", "graph"))
        self.s = jnp.asarray(s)
        self.cfg = cfg
        self.s_c = s_c if s_c is not None else (
            col_checksum(self.s, cfg.dtype) if cfg.enabled else None)

    def aggregate(self, x, x_r):
        # full precision: TPU's default f32 dot is one bf16 pass, which
        # rounds the two sides of the check apart
        exact = jax.lax.Precision.HIGHEST
        h_out = jnp.matmul(self.s, x, precision=exact)
        if x_r is None:
            return h_out, None
        pred = jnp.einsum("...k,...k->...", self.s_c, x_r, precision=exact)
        return h_out, Check(predicted=pred, actual=_total(h_out, self.cfg),
                            granularity=self.granularity)


@register_backend("bcoo")
class BcooBackend(AggregationBackend):
    """S as a jax.experimental.sparse BCOO; s_c is the O(nnz) offline
    segment-sum (``sparse_col_checksum``) shared across layers/steps."""

    def __init__(self, s: Any, cfg: ABFTConfig, *,
                 s_c: Optional[Array] = None, partition=None,
                 granularity: str = "layer"):
        if partition is not None:
            raise ValueError("bcoo backend does not support partition=; "
                             "use backend='block_ell'")
        self.granularity = _validate_granularity("bcoo", granularity,
                                                 ("layer",))
        from repro.core.abft import sparse_col_checksum
        self.s = s
        self.cfg = cfg
        self.s_c = s_c if s_c is not None else (
            sparse_col_checksum(s, cfg.dtype) if cfg.enabled else None)

    def aggregate(self, x, x_r):
        h_out = self.s @ x
        if x_r is None:
            return h_out, None
        pred = jnp.einsum("...k,...k->...", self.s_c, x_r)
        return h_out, Check(predicted=pred, actual=_total(h_out, self.cfg))


def kernel_paths(dims: Sequence[int], block_m: int, block_k: int, rows: int,
                 *, fused_layer: bool, fused_network: bool,
                 block_g: int = 128, budget: Optional[int] = None
                 ) -> Tuple[str, ...]:
    """The kernel each layer of a ``dims = [f0, f1, ..., fL]`` stack takes
    on the block-ELL path: ``"network"``, ``"fused"`` or ``"two_pass"``.

    ``"network"`` for every layer when ``fused_network`` is on, the blocks
    are square and the whole-network working set (two ping-pong buffers of
    ``rows`` activation rows at the widest layer) fits ``budget``;
    otherwise, layer by layer, ``"fused"`` when ``fused_layer`` is on and
    that layer's [f, g] working set fits, else ``"two_pass"``.  ``budget``
    defaults to ``FUSED_VMEM_BUDGET``.

    The one home of the decision: the backend's trace-time hooks
    (:meth:`BlockEllBackend.layer` / :meth:`~BlockEllBackend.network`),
    the serving runner's eager counts and warnings
    (``PackedRunner.fusion_counts``) and the stripe repair's replay
    (``engine/localize.py``) all read it.  Only the fused/none check
    modes consult it; split always runs two-pass.
    """
    from repro.kernels.gcn_fused.vmem import (FUSED_VMEM_BUDGET,
                                              fused_layer_fits,
                                              fused_network_fits)
    if budget is None:
        budget = FUSED_VMEM_BUDGET
    dims = [int(d) for d in dims]
    if fused_network and block_m == block_k and fused_network_fits(
            dims, block_m, rows, block_g=block_g, budget=budget):
        return ("network",) * (len(dims) - 1)
    return tuple(
        "fused" if fused_layer and fused_layer_fits(
            f, g, block_m, block_k, block_g=block_g, budget=budget)
        else "two_pass"
        for f, g in zip(dims[:-1], dims[1:]))


@register_backend("block_ell")
class BlockEllBackend(AggregationBackend):
    """S as a host-side padded block-ELL (``kernels/spmm_abft/layout.py``);
    aggregation runs through the Pallas spmm_abft kernel, whose fused
    epilogue carries the eq.-5 column so predicted = Σ S x_r = s_c H w_r
    without an online s_c pass.

    With ``partition=Partition(mesh, axis)`` the row-stripes shard across
    the mesh axis via shard_map; each shard contributes a partial
    (predicted, actual) pair that psums into the replicated global check —
    exactly the single-device eq.-6 scalar, because the checksum is linear.

    A :class:`~repro.engine.batching.PackedGraphs` operand (block-diagonal
    packed batch) routes through the segmented epilogue instead: the
    kernel's per-stripe checksum partials segment-sum into one eq.-6 corner
    *per packed graph*, so the Check fields are [n_slots] batched scalars
    and a fault in one graph flags only that graph's corner.

    ``fused_layer=True`` additionally activates the whole-layer hook
    (:meth:`layer`): fused/none-mode layers run through the single-pass
    ``kernels/gcn_fused`` kernel — combination, aggregation, and checksum
    in one HBM traversal — falling back to the two-pass path above when
    the layer's [f, g] working set exceeds ``vmem_budget``.

    ``fused_network=True`` activates the whole-network hook
    (:meth:`network`): an entire fused/none-mode forward runs through the
    ``gcn_network_kernel`` sweep — the activation matrix ping-pongs
    between two VMEM buffers and never touches HBM — falling back to the
    per-layer ladder (fused layer, then two-pass) when the depth-wide
    working set exceeds ``vmem_budget`` or the blocks are not square.
    ``network_hits``/``network_fallbacks`` count those decisions.

    ``granularity="stripe"`` declines every collapse: the kernels' per-
    row-stripe checksum partials stay individual corners ([n_block_rows]
    Check fields), so a detected fault names the stripe it corrupted and
    the guard's surgical retry re-executes only those rows.
    ``granularity="slot"`` refines below stripes on the fused kernel
    paths ([n_block_rows, width] telescope-difference corners naming the
    exact ell-slot); the two-pass fallback cannot split a stripe's sweep,
    so it degrades slot corners to stripe corners for that layer.
    Defaults to ``"graph"`` for packed batches and ``"layer"`` otherwise.

    ``inject=(layer, stripe, slot, delta)`` is the CI fault-injection
    hook: the given layer's aggregation sweep perturbs one accumulator
    element mid-flight, in whichever kernel runs that layer (whole-
    network, fused single-layer, or the two-pass spmm — all three carry
    the hook, so fallback paths are injectable too).  On a partition the
    stripe is global: only the shard that owns it perturbs it.

    ``s`` may also be a :class:`~repro.kernels.spmm_abft.layout.
    BlockEllPlan` (COO planned, tiles not built).  On a partition each
    shard's stripes are then built and staged on their own
    (:func:`~repro.engine.sharded.stage_rows`), so the host holds one
    shard's tiles at a time; ``staged_bytes`` gives the bytes of tiles
    and column indices on each device.  Features held with their rows
    (the padded stripes' rows, sharded by rows over the partition) stay
    so: only X and x_r cross devices, once a layer.
    """

    def __init__(self, s: Any, cfg: ABFTConfig, *,
                 s_c: Optional[Array] = None, partition=None,
                 block_g: int = 128, interpret: Optional[bool] = None,
                 fused_layer: bool = False,
                 fused_network: bool = False,
                 vmem_budget: Optional[int] = None,
                 granularity: Optional[str] = None,
                 inject: Optional[Tuple[int, int, int, float]] = None):
        from repro.kernels.spmm_abft.layout import BlockEll, BlockEllPlan
        from repro.engine.batching import PackedGraphs
        self.cfg = cfg
        self.block_g = block_g
        self.partition = partition
        self.interpret = resolve_interpret(interpret)
        self.fused_layer = fused_layer
        self.fused_network = fused_network
        self.vmem_budget = vmem_budget
        self.fused_hits = 0
        self.fused_fallbacks = 0
        self.network_hits = 0
        self.network_fallbacks = 0
        self.segments = None
        self.n_slots = None
        packed = isinstance(s, PackedGraphs)
        self._set_granularity(granularity, packed=packed)
        self._set_inject(inject)
        if packed:
            if partition is not None:
                raise ValueError("packed block-diagonal batches do not "
                                 "support partition= (stripes already "
                                 "interleave graphs)")
            self.segments = jnp.asarray(s.stripe_graph)
            self.n_slots = s.n_slots
            s = s.bell
        elif not isinstance(s, (BlockEll, BlockEllPlan)):
            raise TypeError("block_ell backend needs a BlockEll, "
                            "BlockEllPlan or PackedGraphs operand; convert "
                            "with dense_to_block_ell/coo_to_block_ell/"
                            "plan_block_ell or engine.batching.pack_graphs")
        elif isinstance(s, BlockEllPlan) and partition is None:
            s = s.build()
        self.bell = s
        if partition is None:
            from repro.kernels.spmm_abft.ops import device_block_ell
            self.cols, self.vals = device_block_ell(s)
        else:
            # each shard's stripes are built and go straight to their own
            # device, never all through the host or the default device
            from .sharded import stage_rows
            n = partition.n_shards
            self.cols, self.vals = stage_rows(
                partition, -(-s.n_block_rows // n) * n, s.tiles)

    @property
    def staged_bytes(self) -> Dict[int, int]:
        """Bytes of the staged tile table (tiles and column indices) on
        each device, by device id."""
        out: Dict[int, int] = {}
        for a in (self.cols, self.vals):
            for shard in a.addressable_shards:
                out[shard.device.id] = (out.get(shard.device.id, 0)
                                        + shard.data.nbytes)
        return out

    def _set_granularity(self, granularity: Optional[str], *, packed: bool):
        if granularity is None:
            granularity = "graph" if packed else "layer"
        # packed batches must stay at least graph-attributable (the guard's
        # per-graph retry reads per-graph corners); single systems have no
        # graph segmentation to offer
        supported = (("graph", "stripe", "slot") if packed
                     else ("layer", "stripe", "slot"))
        if granularity == "slot" and self.partition is not None:
            raise ValueError(
                "granularity='slot' is not plumbed through the sharded "
                "paths (sharded_spmm_abft and sharded_gcn_fused keep one "
                "partial per stripe, none per ell-slot) — use "
                "granularity='stripe' there")
        self.granularity = _validate_granularity("block_ell", granularity,
                                                 supported)

    def _set_inject(self, inject):
        if inject is not None:
            if len(inject) != 4:
                raise ValueError("inject is (layer, stripe, slot, delta); "
                                 f"got {inject!r}")
        self.inject = inject
        # which whole-layer call the injection lands in — advanced at trace
        # time, so a jitted step injects into the same layer every batch
        self._layer_calls = 0

    @classmethod
    def from_staged(cls, cols: Array, vals: Array, segments: Array,
                    n_slots: int, cfg: ABFTConfig, *, block_g: int = 128,
                    interpret: bool = False, fused_layer: bool = False,
                    fused_network: bool = False,
                    vmem_budget: Optional[int] = None,
                    granularity: Optional[str] = None,
                    inject: Optional[Tuple[int, int, int, float]] = None
                    ) -> "BlockEllBackend":
        """Packed backend over already-staged (possibly traced) arrays.

        This is the jit-friendly constructor for batched serving: a jitted
        step takes (cols, vals, segments, h0) as *arguments*, so batches of
        the same packed shape share one compile instead of baking each
        batch's tile table in as constants.
        """
        bk = cls.__new__(cls)
        bk.cfg = cfg
        bk.block_g = block_g
        bk.partition = None
        bk.interpret = interpret
        bk.fused_layer = fused_layer
        bk.fused_network = fused_network
        bk.vmem_budget = vmem_budget
        bk.fused_hits = 0
        bk.fused_fallbacks = 0
        bk.network_hits = 0
        bk.network_fallbacks = 0
        bk.bell = None
        bk.cols, bk.vals = cols, vals
        bk.segments = segments
        bk.n_slots = n_slots
        bk._set_granularity(granularity, packed=True)
        bk._set_inject(inject)
        return bk

    def _paths(self, dims, *, network: bool) -> Tuple[str, ...]:
        """:func:`kernel_paths` at this backend's staged block shape."""
        nbm, _width, bm, bk = self.vals.shape
        return kernel_paths(dims, bm, bk, nbm * bm,
                            fused_layer=self.fused_layer,
                            fused_network=network, block_g=self.block_g,
                            budget=self.vmem_budget)

    def layer(self, h, w, cfg, *, w_r=None):
        """Single-pass fused layer (``kernels/gcn_fused``): the combination
        H W is recomputed tile-by-tile inside the aggregation sweep with W
        and w_r VMEM-resident, so X never touches HBM.  Falls back to the
        engine's two-pass path (returns ``NotImplemented``) when the option
        is off or the layer's [f, g] working set exceeds the VMEM budget.
        """
        if not self.fused_layer:
            return NotImplemented
        from .sharded import rows_held
        if self.partition is not None and rows_held(
                h, self.partition, self.vals.shape[0] * self.vals.shape[2]):
            # the fused sweep reads every H row on every shard: features
            # held with their rows take the two-pass path, which moves
            # only X across devices
            self.fused_fallbacks += 1
            return NotImplemented
        from repro.kernels.gcn_fused.ops import (gcn_fused_layer,
                                                 gcn_fused_packed)
        if self._paths(w.shape, network=False) != ("fused",):
            self.fused_fallbacks += 1
            return NotImplemented
        self.fused_hits += 1
        inject = None
        if self.inject is not None and self._layer_calls == self.inject[0]:
            inject = tuple(self.inject[1:])
        self._layer_calls += 1
        with span("gcn.aggregate"):
            if self.segments is not None:
                return gcn_fused_packed(self.cols, self.vals, h, w, w_r,
                                        self.segments,
                                        num_segments=self.n_slots,
                                        block_g=self.block_g,
                                        granularity=self.granularity,
                                        interpret=self.interpret,
                                        inject=inject)
            if self.partition is None:
                return gcn_fused_layer(self.bell, h, w, w_r,
                                       block_g=self.block_g,
                                       granularity=self.granularity,
                                       interpret=self.interpret,
                                       inject=inject,
                                       _staged=(self.cols, self.vals))
            from .sharded import sharded_gcn_fused
            return sharded_gcn_fused(self.bell, self.cols, self.vals, h, w,
                                     w_r, self.partition,
                                     block_g=self.block_g,
                                     granularity=self.granularity,
                                     interpret=self.interpret, inject=inject)

    def network(self, h0, ws, wrs, cfg, *, stash=False):
        """Whole-network fusion (``kernels/gcn_fused``'s network kernel):
        every layer's combination + aggregation + ReLU runs in one sweep
        with the activation matrix ping-ponging between two VMEM buffers —
        it never touches HBM — and the eq.-5 column carried across each
        layer boundary, so the checks stay per-layer and pre-activation.

        Falls back to the per-layer ladder (returns ``NotImplemented``)
        when the option is off, the operand is sharded or non-square, or
        the depth-wide working set (ping-pong buffers at the shared
        lane-rounded max width) exceeds the VMEM budget.
        """
        if not self.fused_network or self.partition is not None:
            return NotImplemented
        from repro.kernels.gcn_fused.ops import (gcn_network_layer,
                                                 gcn_network_packed)
        dims = [ws[0].shape[0]] + [w.shape[1] for w in ws]
        if self._paths(dims, network=True)[0] != "network":
            self.network_fallbacks += 1
            return NotImplemented
        self.network_hits += 1
        self._layer_calls += len(ws)     # the sweep consumed every layer
        with span("gcn.layer", layer="network"):
            if self.segments is not None:
                return gcn_network_packed(self.cols, self.vals, h0, ws, wrs,
                                          self.segments,
                                          num_segments=self.n_slots,
                                          block_g=self.block_g,
                                          granularity=self.granularity,
                                          interpret=self.interpret,
                                          inject=self.inject,
                                          stash_acts=stash)
            return gcn_network_layer(self.bell, h0, ws, wrs,
                                     block_g=self.block_g,
                                     granularity=self.granularity,
                                     interpret=self.interpret,
                                     inject=self.inject, stash_acts=stash)

    def combination_check(self, h, w, x, cfg, *, w_r=None):
        if self.granularity in ("stripe", "slot"):
            # slot corners need the fused kernels' telescopes; split mode's
            # two-pass combination check localizes at stripe granularity
            # per-stripe eq. 2–3 corners: rows group by stripe (row ->
            # stripe is just a reshape), matching the aggregate corner's
            # [n_block_rows] shape so split mode localizes too
            from repro.core.checksum import row_checksum
            nbm, bm = self.vals.shape[0], self.vals.shape[2]
            if w_r is None:
                w_r = row_checksum(w, cfg.dtype)
            rows = nbm * bm
            if h.shape[0] != rows:    # single-graph: pad the stripe residue
                h = jnp.pad(h, ((0, rows - h.shape[0]), (0, 0)))
                x = jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)))
            hsum = h.astype(cfg.dtype).reshape(nbm, bm, -1).sum(axis=1)
            actual = x.astype(cfg.dtype).reshape(nbm, bm, -1).sum(axis=(1, 2))
            return Check(predicted=hsum @ w_r, actual=actual,
                         granularity="stripe")
        if self.segments is None:
            return super().combination_check(h, w, x, cfg, w_r=w_r)
        # per-graph eq. 2–3 corners: rows of h/x are contiguous per graph
        # (row -> stripe -> graph), so both checksum sides segment exactly —
        #   predicted[g] = (Σ_{rows∈g} h) · w_r,  actual[g] = Σ_{rows∈g} x
        from repro.core.checksum import row_checksum
        bm = self.vals.shape[2]
        row_graph = jnp.repeat(self.segments, bm)
        nseg = self.n_slots + 1                    # + overflow (pad stripes)
        hsum = jax.ops.segment_sum(h.astype(cfg.dtype), row_graph,
                                   num_segments=nseg,
                                   indices_are_sorted=True)[:self.n_slots]
        if w_r is None:
            w_r = row_checksum(w, cfg.dtype)
        pred = hsum @ w_r
        actual = jax.ops.segment_sum(x.astype(cfg.dtype).sum(axis=1),
                                     row_graph, num_segments=nseg,
                                     indices_are_sorted=True)[:self.n_slots]
        return Check(predicted=pred, actual=actual, granularity="graph")

    def aggregate(self, x, x_r):
        if x.ndim != 2:
            raise ValueError("block_ell backend is single-graph ([n, g]); "
                             "batch via engine.batching or the dense backend")
        xr_col = None if x_r is None else x_r.astype(jnp.float32)[:, None]
        # the two-pass kernel cannot split a stripe's ell-sweep into slot
        # corners; slot-granularity layers that fall through to this path
        # degrade to stripe corners (still surgical, one rung coarser)
        gran = "stripe" if self.granularity == "slot" else self.granularity
        inject = None
        if self.inject is not None and self._layer_calls == self.inject[0]:
            inject = tuple(self.inject[1:])
        layer = self._layer_calls
        self._layer_calls += 1
        if self.segments is not None:
            from repro.kernels.spmm_abft.ops import spmm_abft_packed
            return spmm_abft_packed(self.cols, self.vals, x, xr_col,
                                    self.segments, num_segments=self.n_slots,
                                    block_g=self.block_g,
                                    granularity=gran,
                                    interpret=self.interpret, inject=inject)
        from repro.kernels.spmm_abft.ops import spmm_abft
        if self.partition is None:
            out, chk = spmm_abft(self.bell, x, xr_col, block_g=self.block_g,
                                 granularity=gran,
                                 interpret=self.interpret, inject=inject,
                                 _staged=(self.cols, self.vals))
            return out, (chk if x_r is not None else None)
        from .sharded import sharded_spmm_abft
        return sharded_spmm_abft(
            self.bell, self.cols, self.vals, x, xr_col, self.partition,
            block_g=self.block_g, granularity=gran,
            interpret=self.interpret, inject=inject, layer=layer)


def make_backend(s: Any, cfg: ABFTConfig, *, backend: Optional[str] = None,
                 s_c: Optional[Array] = None, partition=None,
                 **opts) -> AggregationBackend:
    """Resolve + construct the aggregation backend for operand ``s``."""
    name = backend or infer_backend(s)
    return get_backend(name)(s, cfg, s_c=s_c, partition=partition, **opts)
