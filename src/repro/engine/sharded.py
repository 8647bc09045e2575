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

Requires ``n_block_rows % n_shards == 0``; the block-ELL backend pads with
all-zero stripes (``pad_block_rows``) before staging, which contribute
nothing to either side of the check.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

from repro.core.abft import Check

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


def _check_specs(rules, granularity: str):
    """Check-partial out_specs: psum'd scalars stay replicated; stripe
    corners stay sharded on the stripe axis and concatenate globally."""
    spec = rules.stripe_report_spec() if granularity == "stripe" \
        else rules.report_spec()
    return (rules.out_spec(), spec, spec)


def sharded_spmm_abft(bell, cols: Array, vals: Array, x: Array,
                      xr: Optional[Array], partition: Partition, *,
                      block_g: int = 128, interpret: bool = False,
                      granularity: str = "layer"
                      ) -> Tuple[Array, Optional[Check]]:
    """out = S @ X over stripe-sharded (cols, vals) with the psum'd check.

    ``cols``/``vals`` are the staged device arrays of ``bell`` (already
    padded so stripes divide the axis); ``x`` is [n, g] replicated; ``xr``
    the carried [n, 1] checksum column or None (check disabled).
    ``granularity="stripe"`` keeps each shard's per-stripe partials as
    corners: instead of psum-collapsing, the [nbm_local] vectors stay
    sharded on the stripe axis and *concatenate* into the global
    [n_block_rows] per-stripe check — exactly the single-device stripe
    corners, because each stripe lives on exactly one shard.
    Returns (out [n, g] row-sharded then trimmed, Check | None).
    """
    from repro.kernels.spmm_abft.kernel import spmm_abft_kernel
    from repro.kernels.spmm_abft.ops import prepare_operands, trim_output
    from repro.launch.mesh import GraphShardingRules

    g = x.shape[1]
    want_check = xr is not None
    xp, xrp = prepare_operands(bell, x, xr, block_g)

    axis = partition.axis
    rules = GraphShardingRules(partition.mesh, axis)

    def body(cols_l, vals_l, x_rep, xr_rep):
        out_l, sums_l, extra_l = spmm_abft_kernel(
            cols_l, vals_l, x_rep, xr_rep, interpret=interpret)
        if granularity == "stripe":
            nbm_l = sums_l.shape[0]
            return (out_l, extra_l[:, 0].reshape(nbm_l, -1).sum(axis=1),
                    sums_l[:, 0])
        pred = jax.lax.psum(extra_l.sum(), axis)
        actual = jax.lax.psum(sums_l.sum(), axis)
        return out_l, pred, actual

    shard = jax.shard_map(
        body, mesh=partition.mesh,
        in_specs=(rules.stripe_spec(), rules.tile_spec(),
                  rules.activation_spec(), rules.activation_spec()),
        out_specs=_check_specs(rules, granularity),
        check_vma=False)  # pallas_call has no replication rule
    out, pred, actual = shard(cols, vals, xp, xrp)
    out = trim_output(bell, out, g)
    if not want_check:
        return out, None
    return out, Check(predicted=pred, actual=actual, granularity=granularity)


def sharded_gcn_fused(bell, cols: Array, vals: Array, h: Array, w: Array,
                      wr: Optional[Array], partition: Partition, *,
                      block_g: int = 128, interpret: bool = False,
                      granularity: str = "layer"
                      ) -> Tuple[Array, Optional[Check]]:
    """One whole GCN layer out = S (H W) over stripe-sharded (cols, vals)
    through the single-pass fused kernel, with the psum'd check.

    The fusion composes with the sharding unchanged: H, W, and w_r are
    replicated (any stripe's column blocks may reference any H row, and W
    is tiny), each shard sweeps its own stripes recomputing X tiles in
    VMEM, and the per-shard (predicted, actual) partials psum into the
    same global eq.-6 corner as the two-pass path — Σ over shards commutes
    with Σ over rows.  ``wr`` is the folded right checksum W·e (vector or
    column) or None (check disabled — the kernel statically elides the
    eq.-5 dots).  Returns (out [n, g] trimmed, Check | None).
    """
    from repro.kernels.gcn_fused.kernel import gcn_fused_kernel
    from repro.kernels.gcn_fused.ops import prepare_fused_operands
    from repro.kernels.spmm_abft.ops import trim_output
    from repro.launch.mesh import GraphShardingRules

    g = w.shape[1]
    want_check = wr is not None
    hp, wp, wrp = prepare_fused_operands(bell, h, w, wr, block_g)

    axis = partition.axis
    rules = GraphShardingRules(partition.mesh, axis)

    def body(cols_l, vals_l, h_rep, w_rep, wr_rep):
        out_l, sums_l, extra_l = gcn_fused_kernel(
            cols_l, vals_l, h_rep, w_rep, wr_rep, interpret=interpret,
            with_check=want_check)
        if granularity == "stripe":
            nbm_l = sums_l.shape[0]
            return (out_l, extra_l[:, 0].reshape(nbm_l, -1).sum(axis=1),
                    sums_l[:, 0])
        pred = jax.lax.psum(extra_l.sum(), axis)
        actual = jax.lax.psum(sums_l.sum(), axis)
        return out_l, pred, actual

    shard = jax.shard_map(
        body, mesh=partition.mesh,
        in_specs=(rules.stripe_spec(), rules.tile_spec(),
                  rules.activation_spec(), rules.activation_spec(),
                  rules.activation_spec()),
        out_specs=_check_specs(rules, granularity),
        check_vma=False)  # pallas_call has no replication rule
    out, pred, actual = shard(cols, vals, hp, wp, wrp)
    out = trim_output(bell, out, g)
    if not want_check:
        return out, None
    return out, Check(predicted=pred, actual=actual, granularity=granularity)
