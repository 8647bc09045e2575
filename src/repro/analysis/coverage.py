"""ABFT coverage verifier: prove every matmul in a traced step flows into
an eq. 4-6 checksum comparison.

The paper's value proposition is *total* coverage — every three-matrix
GCN product guarded by one fused checksum — but until this pass existed
nothing could verify that property; it was asserted by hand-written
parity tests per kernel.  This module makes it a theorem about the
jaxpr:

1. Trace the step under :func:`repro.core.marker.check_tagging`, so
   every ``Check.diff()`` comparison leaves an ``abft_check_sink``
   equation in the trace (see ``core/marker.py``).
2. Flatten the ClosedJaxpr recursively — jit, custom_jvp/vjp, scan,
   while, cond sub-jaxprs are walked with *alias* edges tying inner
   binders to outer operands (scan carries additionally loop back), so
   dataflow is tracked precisely across call boundaries instead of
   smearing "output depends on every input" over them.
3. Collect **op sites**: every ``dot_general`` equation, and every
   ``pallas_call`` whose kernel jaxpr contains a ``dot_general``
   (matmul-shaped — the spmm/fused/network kernels all are).  A
   pallas_call is one site, not many: its internal matmuls are covered
   by the checksum its own epilogue emits, so the site is checked iff
   any of its outputs (the actual-checksum corners included) reaches a
   sink.
4. Run backward reachability from every sink's inputs over the def-use
   graph.  A site is **checked** iff one of its outputs is an ancestor
   of a sink input; the granularities of the sinks it reaches are
   recorded per site.

Anything that fails step 4 is reported with its jaxpr provenance
(``file:line (fn)`` via ``source_info_util``) and serialized into a
machine-readable :class:`CoverageManifest` that tests and CI diff
against golden values — the LM example's manifest doubles as ROADMAP
item 2's TODO list.

Sub-jaxprs of primitives this walker does not understand are traversed
conservatively (no alias edges, coarse in->out dependence): matmuls
inside them still become sites, and they stay *unchecked* unless a sink
reaches them through the coarse edges — the lint fails loud rather than
silently trusting unknown control flow.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple

from repro.core.marker import CHECK_SINK

# primitives that never carry payload dataflow we care about tracing
# through sub-jaxprs specially; everything else with a jaxpr param gets
# the conservative fallback
_CALL_PRIMS = ("jit", "closed_call", "core_call", "remat", "remat2",
               "checkpoint", "custom_jvp_call", "custom_vjp_call",
               "custom_vjp_call_jaxpr")


def _closed(j: Any) -> Any:
    """Normalize Jaxpr vs ClosedJaxpr param values to (jaxpr, ok)."""
    inner = getattr(j, "jaxpr", None)
    return j.jaxpr if inner is not None and hasattr(j, "consts") else j


def _is_var(v: Any) -> bool:
    # Literals carry .val; Vars don't.  DropVars are Vars (never read, so
    # keeping them is harmless).
    return not hasattr(v, "val")


@dataclasses.dataclass
class OpSite:
    """One matmul-shaped operation occurrence in the traced step."""

    kind: str                 # "dot_general" | "pallas_call"
    name: str                 # primitive or kernel name
    out_shape: Tuple[int, ...]
    provenance: str           # "file:line (fn)"
    path: str                 # jaxpr nesting path, e.g. "jit/jit"
    checked: bool = False
    granularities: Tuple[str, ...] = ()

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["out_shape"] = list(self.out_shape)
        d["granularities"] = list(self.granularities)
        return d


@dataclasses.dataclass
class CoverageManifest:
    """Machine-readable result of one coverage run — the golden artifact
    tests and CI assert against."""

    step: str
    n_sinks: int
    sink_granularities: Tuple[str, ...]
    checked_ops: List[OpSite]
    unchecked_ops: List[OpSite]

    @property
    def n_checked(self) -> int:
        return len(self.checked_ops)

    @property
    def n_unchecked(self) -> int:
        return len(self.unchecked_ops)

    @property
    def coverage(self) -> float:
        total = self.n_checked + self.n_unchecked
        return 1.0 if total == 0 else self.n_checked / total

    def to_dict(self) -> dict:
        return {
            "step": self.step,
            "n_sinks": self.n_sinks,
            "sink_granularities": list(self.sink_granularities),
            "n_checked": self.n_checked,
            "n_unchecked": self.n_unchecked,
            "coverage": round(self.coverage, 6),
            "checked_ops": [s.to_dict() for s in self.checked_ops],
            "unchecked_ops": [s.to_dict() for s in self.unchecked_ops],
        }

    def to_json(self, **kw) -> str:
        kw.setdefault("indent", 2)
        return json.dumps(self.to_dict(), **kw)


def _provenance(eqn: Any) -> str:
    from jax._src import source_info_util
    try:
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"


def _pallas_name(eqn: Any) -> str:
    nsi = eqn.params.get("name_and_src_info")
    name = getattr(nsi, "name", None) or eqn.params.get("name")
    return str(name) if name else "pallas_call"


def _kernel_has_dot(jaxpr: Any) -> bool:
    """Matmul-shaped test: the pallas kernel's jaxpr (recursively)
    contains a dot_general."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            return True
        for v in eqn.params.values():
            inner = _maybe_jaxpr(v)
            if inner is not None and _kernel_has_dot(inner):
                return True
    return False


def _maybe_jaxpr(v: Any) -> Optional[Any]:
    if hasattr(v, "eqns") and hasattr(v, "invars"):
        return v
    inner = getattr(v, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return None


def iter_eqns(closed_jaxpr: Any, *, into_pallas: bool = False
              ) -> Iterator[Tuple[Any, str]]:
    """Yield (eqn, nesting_path) over a ClosedJaxpr and its sub-jaxprs.

    ``pallas_call`` kernel bodies are skipped unless ``into_pallas`` —
    coverage treats a kernel as one opaque checked unit, and the VMEM
    pass only needs the call equation itself.
    """
    def walk(jaxpr, path):
        for eqn in jaxpr.eqns:
            yield eqn, path
            if eqn.primitive.name == "pallas_call" and not into_pallas:
                continue
            for v in eqn.params.values():
                inner = _maybe_jaxpr(v)
                if inner is not None:
                    yield from walk(inner, f"{path}/{eqn.primitive.name}")
                elif isinstance(v, (tuple, list)):
                    for item in v:
                        inner = _maybe_jaxpr(item)
                        if inner is not None:
                            yield from walk(
                                inner, f"{path}/{eqn.primitive.name}")

    yield from walk(closed_jaxpr.jaxpr, "")


@dataclasses.dataclass
class _Graph:
    """Reverse def-use graph over Var object ids.

    Def-use: each outvar points back at its equation's invars.  Alias
    (an inner jaxpr binder standing for an outer operand, or a scan
    carry looping back) is *equality*, so it contributes edges in BOTH
    directions — backward reachability may cross it either way.  Keying
    by raw ``id(var)`` (SSA: one defining equation per Var) avoids any
    stale-representative hazards a union-find over a growing edge map
    would have.
    """

    rev: Dict[int, Set[int]]
    sites: List[Tuple[OpSite, List[Any]]]   # site, its outvars
    sinks: List[Tuple[str, List[Any]]]      # granularity, sink invars


def _add_edges(g: _Graph, invars: Sequence[Any], outvars: Sequence[Any]):
    ins = {id(v) for v in invars if _is_var(v)}
    for o in outvars:
        if _is_var(o):
            g.rev.setdefault(id(o), set()).update(ins)


def _alias_all(g: _Graph, outer: Sequence[Any], inner: Sequence[Any]):
    for a, b in zip(outer, inner):
        if _is_var(a) and _is_var(b):
            g.rev.setdefault(id(a), set()).add(id(b))
            g.rev.setdefault(id(b), set()).add(id(a))


def _walk(g: _Graph, jaxpr: Any, path: str) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        params = eqn.params

        if prim == CHECK_SINK:
            g.sinks.append((str(params.get("granularity", "?")),
                            [v for v in eqn.invars if _is_var(v)]))
            _add_edges(g, eqn.invars, eqn.outvars)
            continue

        if prim == "dot_general":
            site = OpSite(kind="dot_general", name="dot_general",
                          out_shape=tuple(eqn.outvars[0].aval.shape),
                          provenance=_provenance(eqn),
                          path=path or "/")
            g.sites.append((site, list(eqn.outvars)))
            _add_edges(g, eqn.invars, eqn.outvars)
            continue

        if prim == "pallas_call":
            if _kernel_has_dot(params["jaxpr"]):
                site = OpSite(kind="pallas_call", name=_pallas_name(eqn),
                              out_shape=tuple(eqn.outvars[0].aval.shape),
                              provenance=_provenance(eqn),
                              path=path or "/")
                g.sites.append((site, list(eqn.outvars)))
            # opaque unit: every output depends on every input; the
            # kernel's internal dot_generals are the site itself
            _add_edges(g, eqn.invars, eqn.outvars)
            continue

        if prim in _CALL_PRIMS:
            inner = params.get("jaxpr") or params.get("call_jaxpr") \
                or params.get("fun_jaxpr")
            inner = _closed(inner) if inner is not None else None
            if inner is not None:
                n_consts = int(params.get("num_consts", 0) or 0)
                outer_in = list(eqn.invars)[n_consts:]
                # align from the tail when lengths disagree (some custom
                # calls prepend residuals/consts we didn't account for)
                k = min(len(outer_in), len(inner.invars))
                _alias_all(g, outer_in[-k:], list(inner.invars)[-k:])
                _alias_all(g, eqn.outvars, inner.outvars)
                _walk(g, inner, f"{path}/{prim}")
                continue

        elif prim == "scan":
            inner = _closed(params["jaxpr"])
            nc, ncar = int(params["num_consts"]), int(params["num_carry"])
            _alias_all(g, eqn.invars, inner.invars)
            _alias_all(g, eqn.outvars, inner.outvars)
            # carry loop-back: iteration i+1's carry binder is iteration
            # i's carry output
            _alias_all(g, list(inner.outvars)[:ncar],
                       list(inner.invars)[nc:nc + ncar])
            _walk(g, inner, f"{path}/scan")
            continue

        elif prim == "while":
            body = _closed(params["body_jaxpr"])
            cond = _closed(params["cond_jaxpr"])
            cn, bn = int(params["cond_nconsts"]), int(params["body_nconsts"])
            carry = list(eqn.invars)[cn + bn:]
            _alias_all(g, list(eqn.invars)[cn:cn + bn],
                       list(body.invars)[:bn])
            _alias_all(g, carry, list(body.invars)[bn:])
            _alias_all(g, list(eqn.invars)[:cn], list(cond.invars)[:cn])
            _alias_all(g, carry, list(cond.invars)[cn:])
            _alias_all(g, eqn.outvars, body.outvars)
            _alias_all(g, list(body.outvars), list(body.invars)[bn:])
            _walk(g, body, f"{path}/while")
            _walk(g, cond, f"{path}/while")
            continue

        elif prim == "cond":
            ops = list(eqn.invars)[1:]
            for br in params["branches"]:
                inner = _closed(br)
                _alias_all(g, ops, inner.invars)
                _alias_all(g, eqn.outvars, inner.outvars)
                _walk(g, inner, f"{path}/cond")
            continue

        # conservative fallback for any other primitive carrying
        # sub-jaxprs: traverse (sites inside still get reported) but
        # don't pretend we know the dataflow — coarse in->out edges only
        for v in params.values():
            for item in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = _maybe_jaxpr(item)
                if inner is not None:
                    _walk(g, inner, f"{path}/{prim}")
        _add_edges(g, eqn.invars, eqn.outvars)


def analyze_jaxpr(closed_jaxpr: Any, *, step: str = "") -> CoverageManifest:
    """Run the coverage analysis on an already-traced ClosedJaxpr.

    The trace must have been taken under
    :func:`repro.core.marker.check_tagging` for sinks to exist; a trace
    with zero sinks reports every matmul unchecked (which is exactly
    what an unguarded model should look like).
    """
    g = _Graph(rev={}, sites=[], sinks=[])
    _walk(g, closed_jaxpr.jaxpr, "")

    # backward reachability, one sweep per granularity so each checked
    # site can name the granularities of the comparisons it feeds
    ancestors_by_gran: Dict[str, Set[int]] = {}
    for gran, invars in g.sinks:
        seen = ancestors_by_gran.setdefault(gran, set())
        frontier = [id(v) for v in invars]
        while frontier:
            node = frontier.pop()
            if node in seen:
                continue
            seen.add(node)
            frontier.extend(g.rev.get(node, ()))

    checked, unchecked = [], []
    for site, outvars in g.sites:
        classes = {id(v) for v in outvars if _is_var(v)}
        grans = sorted(gran for gran, anc in ancestors_by_gran.items()
                       if classes & anc)
        if grans:
            site.checked = True
            site.granularities = tuple(grans)
            checked.append(site)
        else:
            unchecked.append(site)

    return CoverageManifest(
        step=step, n_sinks=len(g.sinks),
        sink_granularities=tuple(sorted({gr for gr, _ in g.sinks})),
        checked_ops=checked, unchecked_ops=unchecked)


def analyze_step(fn: Any, *args: Any, step: str = "",
                 **make_jaxpr_kwargs: Any) -> CoverageManifest:
    """Trace ``fn(*args)`` under check tagging and analyze coverage.

    ``fn`` must close over everything static; ``args`` are example
    operands (shapes matter, values don't — nothing executes).
    """
    import jax

    from repro.core.marker import check_tagging

    with check_tagging():
        closed = jax.make_jaxpr(fn, **make_jaxpr_kwargs)(*args)
    return analyze_jaxpr(closed, step=step)


def format_report(m: CoverageManifest, *, verbose: bool = False) -> str:
    """Human-readable lint report for one manifest."""
    lines = [f"[coverage] step={m.step or '<unnamed>'}: "
             f"{m.n_checked} checked, {m.n_unchecked} unchecked matmul "
             f"site(s); {m.n_sinks} check sink(s) "
             f"({', '.join(m.sink_granularities) or 'none'})"]
    for s in m.unchecked_ops:
        lines.append(f"  UNCHECKED {s.kind} {s.name} out={list(s.out_shape)}"
                     f" at {s.provenance}  [{s.path}]")
    if verbose:
        for s in m.checked_ops:
            lines.append(f"  checked   {s.kind} {s.name} "
                         f"out={list(s.out_shape)} at {s.provenance} "
                         f"-> {','.join(s.granularities)}")
    return "\n".join(lines)
