"""Static VMEM checks: the fused kernels' working-set model applied
before anything compiles.

The model itself (``fused_vmem_bytes`` / ``network_vmem_bytes`` and their
``*_fits`` budget predicates) describes the fused kernels and lives with
them in ``repro.kernels.gcn_fused.vmem``; this module imports those very
objects (``tests/test_abftlint.py`` asserts the identity), so a lint
verdict of "fits" is a guarantee about what the engine will decide, not a
parallel model that can drift.

Two passes, coarse to fine:

* :func:`lint_rung_table` — evaluate every rung of a streaming
  ``RungTable`` against the budget for a given layer stack, *before*
  ``warmup()`` compiles anything;
* :func:`pallas_call_vmem_bytes` / :func:`jaxpr_vmem_report` — estimate
  any traced ``pallas_call``'s footprint directly from its BlockSpecs /
  grid, without executing, for kernels the analytic models don't know.

Jaxpr introspection imports are deferred into the functions that need
them.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Sequence

from repro.kernels.gcn_fused.vmem import (
    FUSED_VMEM_BUDGET,
    fused_layer_fits,
    fused_network_fits,
    fused_vmem_bytes,
    network_vmem_bytes,
)


# ---------------------------------------------------------------------------
# RungTable lint: evaluate the streaming server's whole shape menu against
# the budget before warmup() compiles a single rung.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RungVerdict:
    """Static VMEM verdict for one rung of a streaming shape menu."""

    stripe_cap: int
    width_cap: int
    n_slots: int
    rows: int                 # stripe_cap * block — padded row count
    network_bytes: Optional[int]   # whole-network working set (if requested)
    layer_bytes: int          # widest per-layer fused working set
    budget: int
    network_fits: Optional[bool]
    layer_fits: bool

    @property
    def fits(self) -> bool:
        """The rung is lint-clean when its *requested* fusion tier fits:
        the whole-network tier when enabled, else the per-layer tier."""
        if self.network_fits is not None:
            return self.network_fits
        return self.layer_fits

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def lint_rung_table(table: Any, dims: Sequence[int], *, block: int,
                    block_g: int = 128,
                    budget: int = FUSED_VMEM_BUDGET,
                    fused_network: bool = False) -> List[RungVerdict]:
    """Evaluate every rung in a ``RungTable`` against the VMEM budget.

    ``table`` is duck-typed (anything with ``.rungs`` whose entries carry
    ``stripe_cap``/``width_cap``/``n_slots``) so this module never imports
    the engine.  ``dims`` is the layer-width stack ``[f0, f1, ..., fL]``
    of the model the server will run; ``block`` is the packed block size
    (bm == bk for the packed kernels).  Uses the exact predicates the
    runtime consults, so a "fits" here is the compile-time decision.
    """
    dims = [int(d) for d in dims]
    out: List[RungVerdict] = []
    for r in table.rungs:
        rows = int(r.stripe_cap) * int(block)
        layer_bytes = max(
            fused_vmem_bytes(dims[ell], dims[ell + 1], block, block,
                             block_g=block_g)
            for ell in range(len(dims) - 1))
        net_bytes = net_fits = None
        if fused_network:
            net_bytes = network_vmem_bytes(dims, block, rows,
                                           block_g=block_g)
            net_fits = fused_network_fits(dims, block, rows,
                                          block_g=block_g, budget=budget)
        out.append(RungVerdict(
            stripe_cap=int(r.stripe_cap), width_cap=int(r.width_cap),
            n_slots=int(r.n_slots), rows=rows,
            network_bytes=net_bytes, layer_bytes=layer_bytes,
            budget=int(budget), network_fits=net_fits,
            layer_fits=all(
                fused_layer_fits(dims[ell], dims[ell + 1], block, block,
                                 block_g=block_g, budget=budget)
                for ell in range(len(dims) - 1))))
    return out


def assert_rung_table_fits(table: Any, dims: Sequence[int], *, block: int,
                           block_g: int = 128,
                           budget: int = FUSED_VMEM_BUDGET,
                           fused_network: bool = False) -> List[RungVerdict]:
    """:func:`lint_rung_table`, raising ``ValueError`` naming each
    over-budget rung — the lint-time rejection the streaming server wants
    *before* ``warmup()`` compiles anything."""
    verdicts = lint_rung_table(table, dims, block=block, block_g=block_g,
                               budget=budget, fused_network=fused_network)
    bad = [v for v in verdicts if not v.fits]
    if bad:
        tiers = [(f"rung(stripes={v.stripe_cap}, width={v.width_cap}, "
                  f"slots={v.n_slots}): "
                  f"{(v.network_bytes if v.network_fits is not None else v.layer_bytes)} "
                  f"bytes > budget {v.budget}") for v in bad]
        raise ValueError(
            "RungTable exceeds the VMEM budget at its requested fusion "
            "tier; these rungs would silently fall back at every step:\n  "
            + "\n  ".join(tiers))
    return verdicts


# ---------------------------------------------------------------------------
# Generic static estimator: any traced pallas_call, from its BlockSpecs.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PallasVmemEstimate:
    """Static footprint of one traced ``pallas_call`` equation."""

    name: str
    provenance: str
    grid: tuple
    block_bytes: int      # in/out blocks, double-buffered
    scratch_bytes: int
    total_bytes: int
    budget: int

    @property
    def fits(self) -> bool:
        return self.total_bytes <= self.budget

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _block_nbytes(block_shape, aval) -> int:
    """Bytes of one pipeline block: the BlockSpec's block shape at the
    operand dtype; a None mapping means the whole operand is resident.
    Traced block dims are ``Blocked(block_size=...)`` (and kin); squeezed
    or ``None`` dims contribute 1."""
    itemsize = getattr(getattr(aval, "dtype", None), "itemsize", 4)
    if block_shape is None:
        shape = tuple(getattr(aval, "shape", ()) or ())
    else:
        sizes = (getattr(d, "block_size", d) for d in block_shape)
        shape = tuple(d if isinstance(d, int) else 1 for d in sizes)
    n = 1
    for d in shape:
        n *= int(d)
    return n * itemsize


def pallas_call_vmem_bytes(eqn: Any, *,
                           budget: int = FUSED_VMEM_BUDGET
                           ) -> PallasVmemEstimate:
    """Estimate a ``pallas_call`` equation's VMEM footprint WITHOUT
    executing it: every in/out BlockSpec block is double-buffered by the
    pipeline, scratch avals are resident once.

    This is deliberately a lower bound — it models buffers, not register
    pressure or compiler-inserted spills — but it is computed from the
    same BlockSpecs the compiler will honor, so an over-budget verdict
    here is already fatal.
    """
    from jax._src import source_info_util

    params = eqn.params
    gm = params["grid_mapping"]
    grid = tuple(int(g) for g in getattr(gm, "grid", ()) or ())
    jaxpr = params["jaxpr"]

    mappings = list(getattr(gm, "block_mappings", ()) or ())
    # operand avals, positionally aligned with block_mappings: index/scalar
    # prefetch operands precede them, scratch avals live only on the inner
    # jaxpr's tail invars
    n_scratch = int(getattr(gm, "num_scratch_operands", 0) or 0)
    op_avals = [v.aval for v in eqn.invars] + [v.aval for v in eqn.outvars]
    block_bytes = 0
    for i, bm in enumerate(mappings):
        aval = op_avals[i] if i < len(op_avals) else None
        bshape = getattr(bm, "block_shape", None)
        block_bytes += 2 * _block_nbytes(bshape, aval)   # double-buffered

    scratch_bytes = 0
    if n_scratch:
        for v in jaxpr.invars[len(jaxpr.invars) - n_scratch:]:
            aval = getattr(v, "aval", None)
            shape = tuple(getattr(aval, "shape", ()) or ())
            itemsize = getattr(getattr(aval, "dtype", None), "itemsize", 4)
            scratch_bytes += int(math.prod(shape)) * itemsize if shape \
                else itemsize

    name = getattr(params.get("name_and_src_info"), "name", None) \
        or params.get("name", "pallas_call")
    prov = source_info_util.summarize(eqn.source_info)
    total = block_bytes + scratch_bytes
    return PallasVmemEstimate(name=str(name), provenance=prov, grid=grid,
                              block_bytes=block_bytes,
                              scratch_bytes=scratch_bytes,
                              total_bytes=total, budget=int(budget))


def jaxpr_vmem_report(closed_jaxpr: Any, *,
                      budget: int = FUSED_VMEM_BUDGET
                      ) -> List[PallasVmemEstimate]:
    """Walk a ClosedJaxpr (recursing through jit/scan/etc. sub-jaxprs)
    and statically estimate every ``pallas_call`` found."""
    from repro.analysis.coverage import iter_eqns

    out = []
    for eqn, _path in iter_eqns(closed_jaxpr):
        if eqn.primitive.name == "pallas_call":
            out.append(pallas_call_vmem_bytes(eqn, budget=budget))
    return out
