"""Sparse vs dense aggregation: check-op savings across sparsity + runtime.

Two views, per the arithmetic-intensity framing (Kosaian & Rashmi, 2021 —
ABFT overhead hurts most in memory-bound sparse kernels):

  1. analytic check-op savings of fused vs split at realistic sparsities
     (the paper's graphs span 1e-4 .. 1e-2 adjacency density; we sweep a
     synthetic density axis at fixed paper-like shapes, plus the four real
     dataset stats) — savings grow as the graph gets sparser because the
     split baseline's per-multiply overhead stops amortizing;
  2. measured wall-clock of the dense JAX path vs the BCOO sparse path
     (reduced datasets, whatever backend is available) with ABFT mode swept
     none/split/fused, demonstrating the sparse path is what makes
     larger-than-toy graphs feasible at all.

    PYTHONPATH=src python -m benchmarks.sparse_vs_dense
"""
from __future__ import annotations

import time
from typing import List


def _time(fn, *args, reps: int = 5) -> float:
    """Median wall-clock microseconds of jit'd fn(*args) after warmup."""
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return sorted(ts)[len(ts) // 2]


def sparsity_sweep() -> List[tuple]:
    """(density, split_Mops, fused_Mops, savings%) at a PubMed-like shape."""
    from repro.core.datasets import GraphStats
    from repro.core.opcount import gcn_op_counts

    rows = []
    n, f, h, c = 20000, 500, 16, 3
    for density in (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1):
        nnz = int(density * n * n)
        und = max((nnz - n) // 2, 0)
        st = GraphStats(f"d{density:g}", n, und, f, n * f // 20, h, c)
        oc = gcn_op_counts(st.name, stats=st)
        rows.append((density, oc.split_check / 1e6, oc.fused_check / 1e6,
                     oc.check_savings * 100))
    return rows


def run(csv: List[str]) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import ABFTConfig
    from repro.core.datasets import make_reduced
    from repro.core.gcn import dataset_to_dense, dataset_to_sparse, init_gcn
    from repro.core.opcount import gcn_op_counts
    from repro.engine import Graph, gcn_apply, make_backend

    print("\n=== sparse vs dense: fused-check savings across sparsity ===")
    print(f"{'density':>9s} {'split M':>9s} {'fused M':>9s} {'savings%':>9s}")
    for density, sp, fu, sav in sparsity_sweep():
        print(f"{density:9.0e} {sp:9.3f} {fu:9.3f} {sav:9.1f}")
        csv.append(f"sparse_savings_d{density:g},0,{sav:.2f}")

    print("\n--- paper graphs (full size, analytic) ---")
    for name in ("cora", "citeseer", "pubmed", "nell"):
        oc = gcn_op_counts(name)
        print(f"{name:9s} split {oc.split_check/1e6:8.2f}M "
              f"fused {oc.fused_check/1e6:8.2f}M "
              f"savings {oc.check_savings*100:5.1f}%")
        csv.append(f"sparse_savings_{name},0,{oc.check_savings*100:.2f}")

    print(f"\n=== measured forward wall-clock ({jax.default_backend()}) ===")
    print(f"{'graph':14s} {'mode':6s} {'dense us':>10s} {'bcoo us':>10s} "
          f"{'ratio':>6s}")
    for name, scale in (("cora", 4), ("citeseer", 4), ("pubmed", 8)):
        ds = make_reduced(name, scale=scale, seed=0)
        s_np, h_np, _ = dataset_to_dense(ds)
        s_d, h_d = jnp.asarray(s_np), jnp.asarray(h_np)
        s_sp, h_sp, _ = dataset_to_sparse(ds)
        params = init_gcn(jax.random.PRNGKey(0), ds.stats.layer_dims)
        for mode in ("none", "split", "fused"):
            cfg = ABFTConfig(mode=mode)
            s_c = make_backend(s_sp, cfg).s_c     # None with the check off
            f_dense = jax.jit(lambda p, s, x: gcn_apply(p, Graph(s, x), cfg))
            f_sparse = jax.jit(
                lambda p, s, x, sc: gcn_apply(p, Graph(s, x, sc), cfg))
            t_d = _time(f_dense, params, s_d, h_d)
            t_s = _time(f_sparse, params, s_sp, h_sp, s_c)
            print(f"{ds.name:14s} {mode:6s} {t_d:10.1f} {t_s:10.1f} "
                  f"{t_d / max(t_s, 1e-9):6.2f}")
            csv.append(f"sparse_fwd_{ds.name}_{mode},{t_s:.1f},"
                       f"{t_d / max(t_s, 1e-9):.2f}")


if __name__ == "__main__":
    out: List[str] = []
    run(out)
    print("\ncsv:")
    print("\n".join(out))
