"""Sharded block-ELL aggregation: row-stripes over a mesh axis via shard_map.

The checksum is linear, so sharding the aggregation shards the check: each
device owns a contiguous slab of block-ELL row-stripes and computes

    out_local   = S_local @ X          (X replicated: column blocks of any
                                        stripe may reference any X row)
    pred_local  = Σ S_local x_r        (the carried eq.-5 column)
    actual_local= Σ out_local

and a single ``lax.psum`` over the graph axis turns the per-shard partials
into exactly the global eq.-6 comparison — the same scalar the single-device
kernel produces, because Σ over shards commutes with Σ over rows.  The
report stays replicated; the output rows stay sharded (P(axis) on stripes).

Requires ``n_block_rows % n_shards == 0``: the staged table ends in
all-zero padding stripes, which contribute nothing to either side of the
check.  :func:`stage_rows` builds and stages it one shard at a time.

Where the features are held with their rows — ``rows`` = the padded
stripes' rows, sharded by rows over the axis — the combination
``X = H W`` and the eq.-5 column ``x_r = H w_r`` run on each device's own
rows, and only X and x_r cross devices: one all-gather a layer
(:func:`gather_rows`, span ``gcn.exchange``).  The output keeps those rows,
sharded, into the next layer.  Any other operand is replicated and the
output trimmed to the graph's rows.
"""
from __future__ import annotations

import dataclasses
import functools
import types
from typing import Callable, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.abft import Check
from repro.runtime.spans import span

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Partition:
    """Where the graph's row-stripes live: one mesh axis."""

    mesh: Mesh
    axis: str = "graph"

    def __post_init__(self):
        if self.axis not in self.mesh.axis_names:
            raise ValueError(f"axis {self.axis!r} not in mesh axes "
                             f"{self.mesh.axis_names}")

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis]


class GraphShardingRules:
    """PartitionSpecs for the stripe-sharded block-ELL engine backend."""

    def __init__(self, mesh: Mesh, axis: str = "graph"):
        assert axis in mesh.axis_names, (axis, mesh.axis_names)
        self.mesh = mesh
        self.axis = axis

    def stripe_spec(self) -> P:
        """block_cols [nbm, width] — stripes over the graph axis."""
        return P(self.axis)

    def tile_spec(self) -> P:
        """values [nbm, width, bm, bk] — stripes over the graph axis."""
        return P(self.axis)

    def activation_spec(self) -> P:
        """X / x_r stay replicated: column blocks gather arbitrary rows."""
        return P()

    def out_spec(self) -> P:
        """H_out rows live where their stripes live."""
        return P(self.axis)

    def report_spec(self) -> P:
        """Checks psum to replicated scalars."""
        return P()

    def stripe_report_spec(self) -> P:
        """Per-stripe check corners (granularity='stripe'): each shard's
        [nbm_local] partials stay on the stripe axis and concatenate into
        the global per-stripe vector instead of psum-collapsing."""
        return P(self.axis)


def stage_rows(partition: Partition, rows: int,
               build: Callable[[int, int], object]):
    """Arrays of ``rows`` leading rows, sharded by rows over the
    partition, built and staged one shard at a time.

    ``build(lo, hi)`` returns the host rows ``[lo, hi)``: one array, or a
    tuple of arrays that share the leading axis.  Each shard's rows go to
    its own devices (span ``gcn.stage``, ids ``shard`` and ``bytes``) and
    are dropped before the next shard is built, so the host never holds
    more than one shard.  Returns what ``build`` returns, as global
    device arrays."""
    if rows % partition.n_shards:
        raise ValueError(f"{rows} rows do not split over "
                         f"{partition.n_shards} shards")
    sharding = NamedSharding(partition.mesh, P(partition.axis))
    starts = {}                         # first row -> its devices
    for dev, index in sharding.devices_indices_map((rows,)).items():
        starts.setdefault(index[0].start or 0, []).append(dev)
    per = rows // partition.n_shards
    placed = {}
    single = False
    for shard, lo in enumerate(sorted(starts)):
        host = build(lo, lo + per)
        single = not isinstance(host, tuple)
        host = (host,) if single else host
        nbytes = sum(a.nbytes for a in host)
        with span("gcn.stage", shard=shard, bytes=nbytes):
            for dev in starts[lo]:
                placed[dev] = [jax.device_put(a, dev) for a in host]
            # on the device before the next shard is built: the host
            # holds one shard at a time
            jax.block_until_ready(  # abftlint: sync-ok
                [placed[dev] for dev in starts[lo]])
        del host
    parts = list(placed.values())
    out = tuple(jax.make_array_from_single_device_arrays(
        (rows,) + parts[0][i].shape[1:], sharding,
        [p[i] for p in parts]) for i in range(len(parts[0])))
    return out[0] if single else out


def rows_held(a, partition: Partition, rows: int) -> bool:
    """Whether ``a`` holds ``rows`` rows sharded by rows over the
    partition's axis (features kept with their stripes)."""
    sharding = getattr(a, "sharding", None)
    if not (a.shape[0] == rows and isinstance(sharding, NamedSharding)
            and sharding.mesh == partition.mesh and len(sharding.spec)):
        return False
    return sharding.spec[0] in (partition.axis, (partition.axis,))


@functools.lru_cache(maxsize=16)
def _gather_program(mesh: Mesh, axis: str, count: int):
    def body(*parts):
        return tuple(jax.lax.all_gather(p, axis, tiled=True) for p in parts)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(axis),) * count,
                                 out_specs=(P(),) * count, check_vma=False))


def gather_rows(partition: Partition, *arrays: Array) -> Tuple[Array, ...]:
    """Every device's copy of row-sharded arrays: one all-gather each, in
    one program."""
    return _gather_program(partition.mesh, partition.axis, len(arrays))(
        *arrays)


def _check_specs(rules, granularity: str):
    """Check-partial out_specs: psum'd scalars stay replicated; stripe
    corners stay sharded on the stripe axis and concatenate globally."""
    spec = rules.stripe_report_spec() if granularity == "stripe" \
        else rules.report_spec()
    return (rules.out_spec(), spec, spec)


def _owned(run, inject, n_local: int, axis: str):
    """``run(hook)`` on this shard: with a fault hook
    ``(stripe, slot, delta)`` at a global stripe, only the shard that owns
    that stripe perturbs it, at its local index; every other shard runs
    clean."""
    if inject is None:
        return run(None)
    stripe, slot, delta = inject
    owner, local = divmod(stripe, n_local)
    return jax.lax.cond(jax.lax.axis_index(axis) == owner,
                        lambda: run((local, slot, delta)),
                        lambda: run(None))


def _partials(granularity: str, axis: str, out_l, sums_l, extra_l):
    if granularity == "stripe":
        nbm_l = sums_l.shape[0]
        return (out_l, extra_l[:, 0].reshape(nbm_l, -1).sum(axis=1),
                sums_l[:, 0])
    return (out_l, jax.lax.psum(extra_l.sum(), axis),
            jax.lax.psum(sums_l.sum(), axis))


@functools.lru_cache(maxsize=64)
def _spmm_program(mesh: Mesh, axis: str, padded_cols: int, block_k: int,
                  block_g: int, interpret: bool, granularity: str,
                  inject: Optional[Tuple[int, int, float]]):
    """One jitted program: operand padding, the stripe-sharded kernel and
    the check partials."""
    from repro.kernels.spmm_abft import kernel
    from repro.kernels.spmm_abft.ops import prepare_operands

    rules = GraphShardingRules(mesh, axis)
    layout = types.SimpleNamespace(padded_cols=padded_cols, block_k=block_k)

    def body(cols_l, vals_l, x_rep, xr_rep):
        def run(hook):
            return kernel.spmm_abft_kernel(cols_l, vals_l, x_rep, xr_rep,
                                           interpret=interpret, inject=hook,
                                           block_g=block_g)
        return _partials(granularity, axis,
                         *_owned(run, inject, cols_l.shape[0], axis))

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rules.stripe_spec(), rules.tile_spec(),
                  rules.activation_spec(), rules.activation_spec()),
        out_specs=_check_specs(rules, granularity),
        check_vma=False)  # pallas_call has no replication rule

    @jax.jit
    def program(cols, vals, x, xr):
        xp, xrp = prepare_operands(layout, x, xr)
        out, pred, actual = shard(cols, vals, xp, xrp)
        return out[:, :x.shape[1]], pred, actual
    return program


@functools.lru_cache(maxsize=64)
def _fused_program(mesh: Mesh, axis: str, padded_cols: int, block_k: int,
                   block_g: int, interpret: bool, granularity: str,
                   with_check: bool,
                   inject: Optional[Tuple[int, int, float]]):
    from repro.kernels.gcn_fused import kernel
    from repro.kernels.gcn_fused.ops import prepare_fused_operands

    rules = GraphShardingRules(mesh, axis)
    layout = types.SimpleNamespace(padded_cols=padded_cols, block_k=block_k)

    def body(cols_l, vals_l, h_rep, w_rep, wr_rep):
        def run(hook):
            return kernel.gcn_fused_kernel(cols_l, vals_l, h_rep, w_rep,
                                           wr_rep, interpret=interpret,
                                           inject=hook,
                                           with_check=with_check)
        return _partials(granularity, axis,
                         *_owned(run, inject, cols_l.shape[0], axis))

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rules.stripe_spec(), rules.tile_spec(),
                  rules.activation_spec(), rules.activation_spec(),
                  rules.activation_spec()),
        out_specs=_check_specs(rules, granularity),
        check_vma=False)  # pallas_call has no replication rule

    @jax.jit
    def program(cols, vals, h, w, wr):
        hp, wp, wrp = prepare_fused_operands(layout, h, w, wr, block_g)
        out, pred, actual = shard(cols, vals, hp, wp, wrp)
        return out[:, :w.shape[1]], pred, actual
    return program


def _result(bell, vals: Array, rows: int, out, pred, actual,
            want_check: bool, granularity: str):
    """Trim the output to the graph's rows unless its operand held the
    padded stripes' rows, and build the Check."""
    if rows != vals.shape[0] * vals.shape[2]:
        out = out[:bell.shape[0]]
    if not want_check:
        return out, None
    return out, Check(predicted=pred, actual=actual, granularity=granularity)


def sharded_spmm_abft(bell, cols: Array, vals: Array, x: Array,
                      xr: Optional[Array], partition: Partition, *,
                      block_g: int = 128, interpret: bool = False,
                      granularity: str = "layer",
                      inject: Optional[Tuple[int, int, float]] = None,
                      layer: int = 0) -> Tuple[Array, Optional[Check]]:
    """out = S @ X over stripe-sharded (cols, vals) with the psum'd check.

    ``cols``/``vals`` are the staged device arrays of ``bell`` (a BlockEll
    or BlockEllPlan; its stripes padded so they divide the axis); ``x`` is
    [rows, g]; ``xr`` the carried [rows, 1] checksum column or None (check
    disabled).  Held rows (:func:`rows_held`) are gathered first, the
    exchange of layer ``layer``; the output then keeps them, row-sharded.
    Any other ``x`` is replicated and the output trimmed to ``bell``'s rows.
    ``granularity="stripe"`` keeps each shard's per-stripe partials as
    corners: instead of psum-collapsing, the [nbm_local] vectors stay
    sharded on the stripe axis and *concatenate* into the global
    [n_block_rows] per-stripe check — exactly the single-device stripe
    corners, because each stripe lives on exactly one shard.
    ``inject=(stripe, slot, delta)`` perturbs the accumulator of that
    global stripe on the shard that owns it (the CI fault hook).
    Returns (out [rows, g], Check | None).
    """
    rows = vals.shape[0] * vals.shape[2]
    if rows_held(x, partition, rows):
        parts = (x,) if xr is None else (x, xr)
        with span("gcn.exchange", layer=layer,
                  bytes=sum(int(p.size) * p.dtype.itemsize for p in parts)):
            parts = gather_rows(partition, *parts)
        x, xr = parts[0], (None if xr is None else parts[1])
    else:
        rows = x.shape[0]
    program = _spmm_program(partition.mesh, partition.axis, bell.padded_cols,
                            bell.block_k, block_g, interpret, granularity,
                            inject)
    return _result(bell, vals, rows, *program(cols, vals, x, xr),
                   xr is not None, granularity)


def sharded_gcn_fused(bell, cols: Array, vals: Array, h: Array, w: Array,
                      wr: Optional[Array], partition: Partition, *,
                      block_g: int = 128, interpret: bool = False,
                      granularity: str = "layer",
                      inject: Optional[Tuple[int, int, float]] = None
                      ) -> Tuple[Array, Optional[Check]]:
    """One whole GCN layer out = S (H W) over stripe-sharded (cols, vals)
    through the single-pass fused kernel, with the psum'd check.

    The fusion composes with the sharding unchanged: H, W, and w_r are
    replicated (any stripe's column blocks may reference any H row, and W
    is tiny), each shard sweeps its own stripes recomputing X tiles in
    VMEM, and the per-shard (predicted, actual) partials psum into the
    same global eq.-6 corner as the two-pass path — Σ over shards commutes
    with Σ over rows.  So H crosses devices whole: features held with
    their rows take the two-pass path instead, which moves only X.
    ``wr`` is the folded right checksum W·e (vector or column) or None
    (check disabled — the kernel statically elides the eq.-5 dots).
    ``inject`` as in :func:`sharded_spmm_abft`.
    Returns (out [n, g] trimmed, Check | None).
    """
    program = _fused_program(partition.mesh, partition.axis,
                             bell.padded_cols, bell.block_k, block_g,
                             interpret, granularity, wr is not None, inject)
    return _result(bell, vals, h.shape[0],
                   *program(cols, vals, h, w, wr), wr is not None,
                   granularity)
