"""ABFT checking layer: split (baseline) and fused (GCN-ABFT) checks.

Every check produces a :class:`Check` — a (predicted, actual) pair of scalars
(or batched scalars).  Checks are pytrees, so they flow through jit/pjit/scan
unchanged; a training step collects all layer checks and reduces them with
:func:`summarize` into a single replicated flag + max divergence that the
runtime layer (``runtime/abft_guard.py``) acts on.

Three policies (``ABFTConfig.mode``):
  * ``none``  — no checks (perf baseline).
  * ``split`` — the paper's baseline: one check per matmul (eqs. 2–3).
  * ``fused`` — GCN-ABFT: one check per *linear chain* (eq. 4).  Chains are
    broken by nonlinearities; isolated matmuls degrade to split checks.

The engine-facing contract is the :class:`CheckedOp` protocol: a checked op
takes its operands plus folded check vectors and returns ``(out, Check)`` at
a declared granularity.  The eq. 4–6 chaining/fold/report algebra that
backs every implementation — :func:`resolve_w_r`, :func:`fold_w_r_tree`,
:func:`check_chain`, :func:`per_op_report` — lives here, op-generically:
none of it mentions GCNs.  ``engine/api.py`` (GCN layers), ``engine/lm.py``
(transformer prefill/decode), ``engine/gat.py`` (GAT aggregation) and the
``kernels/matmul_abft`` / ``kernels/flash_checksum`` Pallas ops are all
implementations of this one protocol.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from .checksum import (
    col_checksum,
    kahan_total,
    predicted_matmul_checksum,
    row_checksum,
    total_checksum,
)
from .marker import tag_check, tagging_enabled

Array = jax.Array

MODES = ("none", "split", "fused")

# Check granularities, coarsest to finest.  "layer" is one scalar corner per
# linear chain (the paper's granularity); "graph" segments the corner per
# packed graph (exact by linearity — PR 3); "stripe" keeps the kernel's
# per-row-stripe partials as individual corners, so a detected fault names
# the stripe it corrupted and recovery can re-execute just those rows;
# "slot" differences the kernel's telescoped per-ell-slot running sums into
# one corner per (stripe, slot) grid step — a fault names the exact tile
# product (or accumulator step) that produced it.
GRANULARITIES = ("layer", "graph", "stripe", "slot")


@dataclasses.dataclass(frozen=True)
class ABFTConfig:
    """Static configuration for ABFT checking (hashable; safe as jit static)."""

    mode: str = "fused"
    # Accumulation dtype for checksums.  Paper: float64 (CPU repro benches);
    # TPU production: float32 (+ kahan=True to compensate).
    dtype: Any = jnp.float32
    kahan: bool = False
    # Detection threshold tau.  relative=True flags when
    #   |pred - actual| > threshold * max(1, |actual|)
    # which is what a deployment wants; the paper's Table I uses absolute
    # thresholds (relative=False) in 1e-4..1e-7.
    threshold: float = 1e-3
    relative: bool = True

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"abft mode {self.mode!r} not in {MODES}")

    @property
    def enabled(self) -> bool:
        return self.mode != "none"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Check:
    """One checksum comparison.  Fields may be scalars or batched scalars.

    ``granularity`` records what one element of the comparison attributes a
    fault to — ``"layer"`` (scalar corner per chain), ``"graph"`` (one
    corner per packed graph), or ``"stripe"`` (one corner per block-ELL
    row-stripe).  It is static pytree metadata, not a traced value, so
    checks flow through jit/shard_map unchanged and report reducers can
    dispatch on it without a device read.
    """

    predicted: Array
    actual: Array
    granularity: str = "layer"

    def __post_init__(self):
        if self.granularity not in GRANULARITIES:
            raise ValueError(f"check granularity {self.granularity!r} not "
                             f"in {GRANULARITIES}")

    def diff(self) -> Array:
        # every report path (flag/elementwise/summarize/per_*_report)
        # funnels through this subtraction, so routing the pair through
        # the check-sink marker here is what lets `abftlint`'s coverage
        # pass see "this value reached an eq. 4-6 comparison" in the
        # jaxpr.  tag_check is identity (and a no-op outside lint traces).
        p, a = tag_check(self.predicted, self.actual, self.granularity)
        return jnp.abs(p - a)

    def _scale(self) -> Array:
        return _finite(jnp.maximum(1.0, jnp.abs(self.actual)))

    def flag(self, cfg: ABFTConfig) -> Array:
        return jnp.any(_tripped(self.diff(), self._scale(), cfg))

    def elementwise(self, cfg: ABFTConfig) -> tuple[Array, Array]:
        """Per-element (flags, rel divergence) — the shared reduction core
        of :func:`per_graph_report` / :func:`per_stripe_report`.  NaN-safe
        like :meth:`flag`: a NaN comparison flags its element."""
        d = self.diff()
        scale = self._scale()
        return _tripped(d, scale, cfg), (d / scale).astype(jnp.float32)

    def tree_flatten(self):
        return (self.predicted, self.actual), self.granularity

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)


def _finite(scale: Array) -> Array:
    # the relative scale must stay FINITE: an overflowed output
    # (actual = ±inf, e.g. a high exponent bit flip in a weight)
    # would make tau*scale infinite and the comparison pass silently
    # (inf <= inf).  Clamped to 1.0, the infinite divergence flags.
    return jnp.where(jnp.isfinite(scale), scale, 1.0)


def _tripped(d: Array, scale: Array, cfg: ABFTConfig) -> Array:
    # NaN-safe: a NaN divergence (corrupted checksum path — a bit
    # flip in w_r/s_c/the carried eq.-5 column propagating to pred)
    # must FLAG.  ``d > tau`` is False for NaN, which would silently
    # disable ABFT, so the comparison is negated: not (d <= tau).
    # ``scale`` is the finite-clamped relative scale (see ``_finite``).
    if cfg.relative:
        return ~(d <= cfg.threshold * scale)
    return ~(d <= cfg.threshold)


class ABFTReport(NamedTuple):
    """Aggregated result of all checks in one step (pytree of scalars)."""

    flag: Array       # bool — any check tripped
    max_rel: Array    # worst relative divergence seen
    n_checks: Array   # number of scalar comparisons performed


def _total(a: Array, cfg: ABFTConfig) -> Array:
    if cfg.kahan:
        return kahan_total(a.astype(cfg.dtype))
    return total_checksum(a, cfg.dtype)


def check_matmul(a: Array, b: Array, c: Array, cfg: ABFTConfig,
                 *, b_r: Optional[Array] = None) -> Check:
    """Split-ABFT check of an already-computed product c = a @ b.

    Batched operands are fine (leading axes broadcast): one scalar check per
    batch element, reduced later by :func:`summarize`.  A folded right
    checksum ``b_r = B·e`` (from :func:`fold_w_r_tree` at weight-load time)
    skips the per-step row-sum of B; it must have been folded at this
    config's checksum dtype (validated — a stale fold raises).
    """
    if b_r is None:
        pred = predicted_matmul_checksum(a, b, cfg.dtype)
    else:
        b_r = resolve_w_r(b, b_r, cfg)
        pred = jnp.einsum("...k,...k->...", col_checksum(a, cfg.dtype), b_r)
    return Check(predicted=pred, actual=_total(c, cfg))


def checked_matmul(a: Array, b: Array, cfg: ABFTConfig,
                   precision=None) -> tuple[Array, Optional[Check]]:
    """Compute a @ b and (mode-dependent) its ABFT check."""
    c = jnp.matmul(a, b, precision=precision)
    if not cfg.enabled:
        return c, None
    return c, check_matmul(a, b, c, cfg)


def check_chain(mats: Sequence[Array], out: Array, cfg: ABFTConfig) -> Check:
    """Fused (GCN-ABFT) check of out = mats[0] @ ... @ mats[-1].

    Supports batched leading axes on any operand: the left checksum vector is
    pushed through the chain with einsum-free matmuls (broadcasting applies).
    """
    v = col_checksum(mats[0], cfg.dtype)                    # [..., k0]
    for m in mats[1:-1]:
        v = jnp.einsum("...k,...kj->...j", v, m.astype(cfg.dtype))
    pred = jnp.einsum("...k,...k->...", v, row_checksum(mats[-1], cfg.dtype))
    return Check(predicted=pred, actual=_total(out, cfg))


# ---------------------------------------------------------------------------
# The CheckedOp protocol and its op-generic fold/report algebra.
#
# Hoisted out of engine/api.py::gcn_layer/gcn_forward: nothing below is
# GCN-specific.  An op's check vectors fold once at weight-load time
# (resolve_w_r / fold_w_r_tree — the paper's "offline" eq.-5 convention),
# the op returns (out, Check) at its declared granularity, and the report
# algebra (summarize / per_op_report / per_graph_report / ...) reduces the
# checks into verdicts the runtime guard acts on.
# ---------------------------------------------------------------------------

def resolve_w_r(w: Array, w_r: Optional[Array],
                cfg: ABFTConfig) -> Optional[Array]:
    """Resolve one op's right checksum w_r = W·e: computed at ``cfg.dtype``
    when absent, validated against the REALIZED checksum dtype when folded
    (x64-disabled f64 requests realize as f32), ``None`` when checking is
    off.  Every CheckedOp implementation shares this so a stale fold raises
    identically everywhere."""
    if not cfg.enabled:
        return None
    if w_r is None:
        return row_checksum(w, cfg.dtype)
    want = jax.dtypes.canonicalize_dtype(jnp.dtype(cfg.dtype))
    if jnp.asarray(w_r).dtype != want:
        raise ValueError(
            f"folded w_r has dtype {jnp.asarray(w_r).dtype} but "
            f"cfg.dtype realizes as {want}: the checks would run at a "
            f"stale precision.  Re-fold the params (fold_w_r_tree / "
            f"engine.fold_w_r) after changing ABFTConfig.dtype (or drop "
            f"the fold to recompute w_r per step)")
    return w_r


def fold_w_r_tree(params: Any, cfg: ABFTConfig, *, lead_axes: int = 0,
                  compute_dtype: Any = None) -> Any:
    """Tree-generic offline fold: walk any params pytree and add a folded
    right checksum ``"w_r"`` next to every ``"w"`` weight leaf.

    The convention is ``init_dense``'s: ``w`` is ``[d_in, *d_out]`` and the
    fold sums over every output axis — ``w_r = W·e`` of the 2-D flattened
    weight, one value per input feature.  ``lead_axes`` names leading
    batch/stack axes to preserve (1 for scan-stacked transformer segment
    params: each unit keeps its own fold).  Existing ``"w_r"`` entries are
    overwritten — re-fold after any weight update or ``cfg.dtype`` change.
    Non-dict leaves and dicts without a ``"w"`` array pass through
    untouched, so one call folds a whole model: GCN ``params["layers"]``,
    transformer QKV/MLP/head denses, GAT layers.

    ``compute_dtype`` quantizes the weights to the model's compute dtype
    *before* the checksum accumulation — pass the model's activation dtype
    (e.g. bfloat16) so the folded prediction matches the weights the
    product actually consumed; leaving it off on a low-precision model
    injects the master-vs-compute quantization gap into every comparison.
    """
    if not cfg.enabled:
        return params

    def _fold(node):
        if isinstance(node, dict):
            out = {k: _fold(v) for k, v in node.items()}
            w = node.get("w")
            if w is not None and hasattr(w, "ndim") and \
                    w.ndim >= 2 + lead_axes:
                # fold on the array as-is (numpy stays numpy): the
                # self-check re-derives with the SAME summation so the
                # comparison is bitwise, and converting would change the
                # reduction order
                if compute_dtype is not None:
                    w = w.astype(compute_dtype)
                w = w.astype(cfg.dtype)
                out["w_r"] = w.reshape(*w.shape[:1 + lead_axes], -1).sum(-1)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(_fold(v) for v in node)
        return node

    return _fold(params)


class CheckedOp:
    """Protocol for one checked op — the engine's unit of ABFT coverage.

    A checked op takes its operands plus folded check vectors and returns
    ``(out, Check)`` at a declared granularity::

        op = SomeOp(...)
        params = op.fold(params, cfg)          # offline, at weight load
        out, check = op(cfg, *operands, **folded_check_vectors)

    ``check`` is the registered-pytree :class:`Check` (or ``None`` when
    ``cfg.mode == "none"``; ops whose policy emits several comparisons —
    e.g. the split eq. 2–3 baseline — may return a list of Checks).  The
    contract implementations must honour:

      * the *predicted* side is computed only from the op's inputs and
        folded vectors — never from the output (a fault would cancel);
      * ``granularity`` declares what one comparison element attributes a
        fault to (see :data:`GRANULARITIES`);
      * ``op_id`` keys the op's verdicts in per-op reports and guard
        repair sites (``"op:<id>"``) — stable across steps of one serving
        trace.

    Implementations: the GCN ``AggregationBackend``s (``engine/backends``),
    the transformer LM ops (``engine/lm``), GAT layers (``engine/gat``),
    and the Pallas kernels ``kernels/matmul_abft`` / ``flash_checksum``.
    """

    op_id: str = "op"
    granularity: str = "layer"

    def fold(self, params: Any, cfg: ABFTConfig) -> Any:
        """Fold this op's check vectors into ``params`` at load time."""
        return fold_w_r_tree(params, cfg)

    def __call__(self, cfg: ABFTConfig, *operands, **folded):
        raise NotImplementedError


class MatmulOp(CheckedOp):
    """Reference split-ABFT op (eqs. 2–3): ``out = A @ B``, one scalar
    comparison, optional folded ``b_r``."""

    op_id = "matmul"

    def __call__(self, cfg: ABFTConfig, a: Array, b: Array, *,
                 b_r: Optional[Array] = None):
        c = jnp.matmul(a, b)
        if not cfg.enabled:
            return c, None
        return c, check_matmul(a, b, c, cfg, b_r=b_r)


class ChainOp(CheckedOp):
    """Reference fused op (eqs. 4–6): ``out = M0 @ ... @ Mk`` with ONE
    comparison for the whole linear chain, optional folded right checksum
    of the last matrix."""

    op_id = "chain"

    def __call__(self, cfg: ABFTConfig, *mats: Array,
                 w_r: Optional[Array] = None):
        out = mats[0]
        for m in mats[1:]:
            out = jnp.matmul(out, m)
        if not cfg.enabled:
            return out, None
        if w_r is None:
            return out, check_chain(mats, out, cfg)
        w_r = resolve_w_r(mats[-1], w_r, cfg)
        v = col_checksum(mats[0], cfg.dtype)
        for m in mats[1:-1]:
            v = jnp.einsum("...k,...kj->...j", v, m.astype(cfg.dtype))
        pred = jnp.einsum("...k,...k->...", v, w_r)
        return out, Check(predicted=pred, actual=_total(out, cfg))


def per_op_report(checks: Sequence[Optional[Check]], cfg: ABFTConfig, *,
                  prefix: str = "op") -> tuple[tuple, Array, Array]:
    """Per-op twin of :func:`summarize`: one verdict per check element,
    keyed by a static op id.

    Returns ``(op_ids, flags, max_rel)`` where ``op_ids`` is a tuple of
    static strings and ``flags``/``max_rel`` are aligned ``[n_ops]``
    vectors.  A check whose fields are batched — e.g. a scanned transformer
    segment stacks one comparison per layer into ``[count]`` leaves —
    contributes one verdict per element with a ``:L{j}`` suffix, so a
    flagged op names the layer it fired in.  The ids are positional within
    one step's static check structure: stable across steps of a compiled
    serving trace, which is all the guard's persistent-site discrimination
    needs.
    """
    checks = [c for c in checks if c is not None]
    if not checks or not cfg.enabled:
        return (), jnp.zeros((0,), bool), jnp.zeros((0,), jnp.float32)
    ids: list = []
    flags, rels = [], []
    for i, c in enumerate(checks):
        f, r = c.elementwise(cfg)
        f, r = jnp.ravel(f), jnp.ravel(r)
        n = int(f.shape[0])
        if n == 1:
            ids.append(f"{prefix}{i}")
        else:
            ids.extend(f"{prefix}{i}:L{j}" for j in range(n))
        flags.append(f)
        rels.append(r.astype(jnp.float32))
    return tuple(ids), jnp.concatenate(flags), jnp.concatenate(rels)


# ---------------------------------------------------------------------------
# Adjacency helpers, generic over BCOO or dense S.  For a static graph
# s_c = e^T S never changes — compute it once offline
# (:func:`sparse_col_checksum`) and pass it to every layer/step.
# ---------------------------------------------------------------------------

def _is_bcoo(s: Any) -> bool:
    from jax.experimental import sparse as jsparse
    return isinstance(s, jsparse.BCOO)


def sparse_matmul(s: Any, x: Array) -> Array:
    """S @ X for BCOO or dense S (BCOO lowers to scatter-add dot_general)."""
    return (s @ x) if _is_bcoo(s) else jnp.matmul(s, x)


def sparse_col_checksum(s: Any, dtype: Any = jnp.float32) -> Array:
    """e^T S without densifying: O(nnz) segment-sum over column indices.

    This is the offline s_c precompute for static graphs — call it once per
    graph and pass it to every layer (``engine.Graph(s_c=...)``).
    """
    if not _is_bcoo(s):
        return col_checksum(s, dtype)
    data = s.data.astype(dtype)
    cols = s.indices[..., 1]
    return jax.ops.segment_sum(data, cols, num_segments=s.shape[1])


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------

# Traces of the compiled report body; read through report_traces().  Two
# threads may trace at once, hence the lock.
_report_traces = 0
_report_traces_lock = threading.Lock()


def report_traces() -> int:
    """How many times :func:`summarize`'s compiled body has been traced.

    One per distinct check structure (the checks' shapes, dtypes and
    granularities, ``cfg``, the tagging state).  A count that rises with
    every call means the jit cache misses and each report recompiles.
    """
    return _report_traces


def summarize(checks: Sequence[Optional[Check]], cfg: ABFTConfig) -> ABFTReport:
    """Reduce an arbitrary collection of checks to one replicated report.

    Runs as one compiled program per check structure (under an outer
    trace it is a nested call, inlined into the caller's program)."""
    checks = [c for c in checks if c is not None]
    if not checks or not cfg.enabled:
        z = jnp.zeros((), jnp.float32)
        return ABFTReport(flag=jnp.zeros((), bool), max_rel=z, n_checks=z)
    return _summarize_compiled(checks, cfg, tagging_enabled())


@functools.partial(jax.jit, static_argnames=("cfg", "tagging"))
def _summarize_compiled(checks: list[Check], cfg: ABFTConfig,
                        tagging: bool) -> ABFTReport:
    # ``tagging`` only keys the trace cache: ``Check.diff`` reads the same
    # state while this traces.  Without it a lint trace would reuse an
    # untagged trace and lose its check-sink equations.
    del tagging
    global _report_traces
    with _report_traces_lock:
        _report_traces += 1
    flags, rels, n = [], [], 0
    for c in checks:
        d = c.diff()
        scale = jnp.maximum(1.0, jnp.abs(c.actual))
        # max_rel divides by the unclamped scale; only the flag clamps it
        rels.append(jnp.max(d / scale))
        flags.append(jnp.any(_tripped(d, _finite(scale), cfg)))
        n += int(np_size(c.actual))
    return ABFTReport(
        flag=jnp.stack(flags).any(),
        max_rel=jnp.stack(rels).max().astype(jnp.float32),
        n_checks=jnp.asarray(float(n), jnp.float32),
    )


def per_graph_report(checks: Sequence[Optional[Check]], cfg: ABFTConfig,
                     n: int, *, segments: Optional[Array] = None
                     ) -> tuple[Array, Array]:
    """Elementwise twin of :func:`summarize` for batched checks: one verdict
    per graph instead of one reduced step flag.

    Every check's fields must be [n] batched scalars (the dense batched
    backend and the packed block-ELL segmented epilogue both emit these) —
    OR, when ``segments`` (the [n_stripes] stripe → graph map) is given,
    stripe-granular checks whose fields match the segments shape: their
    per-stripe verdicts reduce onto the owning graphs (OR of flags, max of
    divergences; padding stripes carry id ``n`` — the overflow segment —
    and are dropped).  Returns (flags [n] bool, max_rel [n] f32) — OR / max
    across checks (i.e. across layers), *not* across graphs, so the serving
    layer can retry only the flagged graphs.
    """
    checks = [c for c in checks if c is not None]
    if not checks or not cfg.enabled:
        return jnp.zeros((n,), bool), jnp.zeros((n,), jnp.float32)
    seg_shape = None if segments is None else tuple(jnp.shape(segments))
    flags, rels = None, None
    for c in checks:
        # dispatch on the check's DECLARED granularity, not on shape alone:
        # a packed batch whose stripe count happens to equal its slot count
        # would otherwise read stripe corners as per-graph verdicts and
        # retry the wrong graphs (adopting the corrupted one)
        if c.granularity not in ("stripe", "slot") and c.actual.shape == (n,):
            f, r = c.elementwise(cfg)
        elif c.granularity == "slot" and seg_shape is not None \
                and c.actual.shape[:1] == seg_shape:
            # slot-granular corners [n_stripes, width]: reduce the slot axis
            # (OR / max) to per-stripe verdicts, then segment-reduce onto
            # the owning graphs exactly like stripe corners below
            fs, rs = c.elementwise(cfg)
            fs, rs = fs.any(axis=1), rs.max(axis=1)
            seg = jnp.asarray(segments)
            f = jax.ops.segment_sum(fs.astype(jnp.int32), seg,
                                    num_segments=n + 1,
                                    indices_are_sorted=True)[:n] > 0
            r = jnp.maximum(jax.ops.segment_max(rs, seg,
                                                num_segments=n + 1,
                                                indices_are_sorted=True)[:n],
                            0.0)
        elif c.granularity == "stripe" and seg_shape is not None \
                and c.actual.shape == seg_shape:
            # stripe-granular corners: segment-reduce onto the graphs.
            # segment_sum-of-bools ORs (empty slots own no stripes -> 0 ->
            # False); max of rels floors at 0 so the -inf identity of empty
            # segments never leaks into reporting.
            fs, rs = c.elementwise(cfg)
            seg = jnp.asarray(segments)
            f = jax.ops.segment_sum(fs.astype(jnp.int32), seg,
                                    num_segments=n + 1,
                                    indices_are_sorted=True)[:n] > 0
            r = jnp.maximum(jax.ops.segment_max(rs, seg,
                                                num_segments=n + 1,
                                                indices_are_sorted=True)[:n],
                            0.0)
        else:
            # a scalar (or otherwise-shaped) check cannot be attributed to
            # one graph; silently broadcasting it would mark every graph
            # flagged and defeat the per-graph retry
            raise ValueError(
                f"per_graph_report needs [n={n}]-batched checks, got "
                f"shape {c.actual.shape}; use a backend that emits "
                f"per-graph corners (dense batched / packed block_ell)")
        flags = f if flags is None else flags | f
        rels = r if rels is None else jnp.maximum(rels, r)
    return flags, rels


def per_stripe_report(checks: Sequence[Optional[Check]], cfg: ABFTConfig,
                      n_stripes: int) -> tuple[Array, Array]:
    """Finest-granularity report: one verdict per (check, row-stripe).

    Every check's fields must be [n_stripes] per-stripe corners (the
    block-ELL backends at ``granularity="stripe"``) or [n_stripes, width]
    slot corners (``granularity="slot"``; the slot axis reduces by OR/max —
    a stripe is flagged when any of its slots is).  Returns
    (flags [L, n_stripes] bool, max_rel [L, n_stripes] f32) with one row per
    check — the layer axis is preserved, NOT reduced, because the surgical
    retry must know *which layer's* stripe to re-execute (a fault at layer
    L only dirties downstream values computed from it).
    """
    checks = [c for c in checks if c is not None]
    if not checks or not cfg.enabled:
        return (jnp.zeros((0, n_stripes), bool),
                jnp.zeros((0, n_stripes), jnp.float32))
    flags, rels = [], []
    for c in checks:
        if c.granularity == "slot" and c.actual.ndim == 2 \
                and c.actual.shape[0] == n_stripes:
            f, r = c.elementwise(cfg)
            f, r = f.any(axis=1), r.max(axis=1)
        elif c.actual.shape == (n_stripes,) and c.granularity == "stripe":
            f, r = c.elementwise(cfg)
        else:
            raise ValueError(
                f"per_stripe_report needs [n_stripes={n_stripes}] "
                f"stripe-granular checks, got shape {c.actual.shape} "
                f"(granularity={c.granularity!r}); build the backend with "
                f"granularity='stripe'")
        flags.append(f)
        rels.append(r)
    return jnp.stack(flags), jnp.stack(rels)


def per_slot_report(checks: Sequence[Optional[Check]], cfg: ABFTConfig,
                    n_stripes: int, width: int) -> tuple[Array, Array]:
    """Finest-granularity report: one verdict per (check, stripe, ell-slot).

    Slot-granular checks carry [n_stripes, width] corners (adjacent
    differences of the kernel's telescoped running sums — see
    ``slot_check_corners``); stripe-granular checks in the same forward
    (e.g. a layer that fell back to the two-pass kernel mid-network)
    contribute an all-False slab — they still flag at stripe granularity
    via :func:`per_stripe_report`, they just cannot attribute a slot.
    Returns (flags [L, n_stripes, width] bool, max_rel [...] f32).
    """
    checks = [c for c in checks if c is not None]
    if not checks or not cfg.enabled:
        return (jnp.zeros((0, n_stripes, width), bool),
                jnp.zeros((0, n_stripes, width), jnp.float32))
    flags, rels = [], []
    for c in checks:
        if c.granularity == "slot" and \
                c.actual.shape == (n_stripes, width):
            f, r = c.elementwise(cfg)
        elif c.granularity == "stripe" and c.actual.shape == (n_stripes,):
            f = jnp.zeros((n_stripes, width), bool)
            r = jnp.zeros((n_stripes, width), jnp.float32)
        else:
            raise ValueError(
                f"per_slot_report needs [n_stripes={n_stripes}, "
                f"width={width}] slot-granular checks, got shape "
                f"{c.actual.shape} (granularity={c.granularity!r}); build "
                f"the backend with granularity='slot'")
        flags.append(f)
        rels.append(r)
    return jnp.stack(flags), jnp.stack(rels)


def np_size(x: Array) -> int:
    try:
        return int(x.size)
    except Exception:  # traced value — shape is static anyway
        import numpy as _np
        return int(_np.prod(x.shape)) if x.shape else 1


def merge_reports(reports: Sequence[ABFTReport]) -> ABFTReport:
    """Combine reports from scanned layers / multiple blocks."""
    reports = list(reports)
    if not reports:
        z = jnp.zeros((), jnp.float32)
        return ABFTReport(jnp.zeros((), bool), z, z)
    return ABFTReport(
        flag=jnp.stack([r.flag for r in reports]).any(),
        max_rel=jnp.stack([r.max_rel for r in reports]).max(),
        n_checks=jnp.stack([r.n_checks for r in reports]).sum(),
    )
