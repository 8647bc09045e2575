"""Quickstart: the paper in 60 seconds.

Builds a synthetic Cora-sized GCN, runs inference with both ABFT variants,
injects a fault, and shows (a) identical clean behaviour, (b) detection by
both, (c) the op-count savings of the fused checksum.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ABFTConfig
from repro.core.datasets import make_reduced
from repro.core.gcn import dataset_to_dense, dataset_to_sparse, init_gcn
from repro.core.opcount import gcn_op_counts
from repro.engine import Graph, gcn_apply, gcn_layer, make_backend
from repro.kernels.spmm_abft import dense_to_block_ell


def main():
    print("=== GCN-ABFT quickstart ===\n")
    ds = make_reduced("cora", scale=4, seed=0)
    s_np, h_np, _ = dataset_to_dense(ds)
    s, h = jnp.asarray(s_np), jnp.asarray(h_np)
    dims = ds.stats.layer_dims
    params = init_gcn(jax.random.PRNGKey(0), dims)

    for mode in ("split", "fused"):
        cfg = ABFTConfig(mode=mode, threshold=1e-3, relative=True)
        logits, report = jax.jit(
            lambda p, s, h: gcn_apply(p, Graph(s, h), cfg))(params, s, h)
        print(f"{mode:6s}: clean forward  flag={bool(report.flag)} "
              f"max_rel={float(report.max_rel):.2e} "
              f"checks={int(report.n_checks)}")

    # inject a fault into the first layer's combination output
    w = params["layers"][0]["w"]
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)
    h_out, (chk,) = gcn_layer(make_backend(s, cfg), h, w, cfg)
    bad = h_out.at[5, 3].add(h_out.std() * 1e3)
    diff = abs(float(chk.predicted) - float(bad.sum()))
    print(f"\ninjected fault: |predicted - actual| = {diff:.3e} "
          f"-> detected: {diff > 1e-3 * abs(float(bad.sum()))}")

    # sparse aggregation path: same logits, same checks, scales past toy
    # graphs — S stays a BCOO and s_c = e^T S is precomputed once offline
    cfg = ABFTConfig(mode="fused", threshold=1e-3, relative=True)
    s_sp, h_sp, _ = dataset_to_sparse(ds)
    s_c = make_backend(s_sp, cfg).s_c
    logits_sp, rep_sp = jax.jit(
        lambda p, s, x, sc: gcn_apply(p, Graph(s, x, sc), cfg)
    )(params, s_sp, h_sp, s_c)
    logits_d, _ = gcn_apply(params, Graph(s, h), cfg)
    err = float(jnp.abs(logits_sp - logits_d).max())
    print(f"\nsparse (BCOO) path: max |logit diff| vs dense = {err:.2e} "
          f"flag={bool(rep_sp.flag)}")

    # every backend behind the one entry point — identical logits and
    # report semantics from dense, BCOO, and the block-ELL Pallas kernel
    bell = dense_to_block_ell(s_np, block_m=32, block_k=32)
    print("\nunified engine, one entry point per backend:")
    for backend, graph in (("dense", Graph(s, h)),
                           ("bcoo", Graph(s_sp, h_sp, s_c=s_c)),
                           ("block_ell", Graph(bell, h))):
        lg, rep = gcn_apply(params, graph, cfg, backend=backend,
                            **({"block_g": 32}
                               if backend == "block_ell" else {}))
        err = float(jnp.abs(lg - logits_d).max())
        print(f"  {backend:9s} |logit diff|={err:.2e} "
              f"flag={bool(rep.flag)} checks={int(rep.n_checks)}")
    print("  (batched multi-graph serving: python -m repro.launch.serve_gcn)")

    print("\nop-count savings (full-size graphs, paper Table II):")
    for name in ("cora", "citeseer", "pubmed", "nell"):
        oc = gcn_op_counts(name)
        print(f"  {name:9s} check ops: split {oc.split_check/1e6:7.3f}M "
              f"fused {oc.fused_check/1e6:7.3f}M  "
              f"(saves {oc.check_savings*100:.1f}%)")


if __name__ == "__main__":
    main()
