"""Paper Table I: fault-detection accuracy under single bit flips.

Campaigns per (dataset × ABFT mode): site chosen ∝ op counts (mm_bias
configurable — the paper's wide-MAC-array accelerator implies a larger
matmul share; we report mm_bias=5 as primary and mm_bias=1 in the CSV),
uniform bit, thresholds 1e-4..1e-7.  Trained weights (teacher-labelled
synthetic graphs, cached) set realistic activation magnitudes.

CPU budget knobs (documented deviations): campaign counts default to
1000/dataset·mode (paper: 5000 — the paper notes more campaigns do not
change behaviour; our ±1σ ≈ 0.7 % at n=1000); Nell uses 400.
"""
from __future__ import annotations

import os
import pickle
import time
from typing import List

import numpy as np

CACHE = "results/cache"
N_CAMPAIGNS = {"cora": 1000, "citeseer": 1000, "pubmed": 800, "nell": 400}
EPOCHS = {"cora": 150, "citeseer": 150, "pubmed": 80, "nell": 40}
LR = {"cora": 0.5, "citeseer": 0.5, "pubmed": 0.3, "nell": 0.1}
THRESH = (1e-4, 1e-5, 1e-6, 1e-7)
MM_BIAS = 5.0

PAPER_1E7 = {  # (split det, split fp, fused det, fused fp) at tau=1e-7
    "cora": (95.80, 4.20, 96.66, 3.34),
    "citeseer": (95.44, 4.56, 97.06, 2.94),
    "pubmed": (96.38, 3.62, 97.42, 2.58),
    "nell": (96.90, 3.10, 97.82, 2.18),
}


def _trained_model(name: str):
    from repro.core.datasets import make_dataset
    from repro.core.fault import NumpyGCN, train_weights_numpy

    os.makedirs(CACHE, exist_ok=True)
    path = os.path.join(CACHE, f"{name}_weights.pkl")
    ds = make_dataset(name, seed=0, normalize=False)
    if os.path.exists(path):
        with open(path, "rb") as f:
            ws = pickle.load(f)
    else:
        ws = train_weights_numpy(ds, epochs=EPOCHS[name], lr=LR[name], seed=0)
        with open(path, "wb") as f:
            pickle.dump(ws, f)
    return ds, NumpyGCN(ds, weights=ws)


def run(csv: List[str]) -> None:
    from repro.core.fault import run_campaigns

    print("\n=== Table I: fault-detection accuracy (single bit flip) ===")
    print(f"(synthetic stand-in graphs; n per cell as configured; "
          f"mm_bias={MM_BIAS} primary)")
    for name in ("cora", "citeseer", "pubmed", "nell"):
        t0 = time.perf_counter()
        ds, model = _trained_model(name)
        acc = float((model.pred_cls == ds.labels).mean())
        n = N_CAMPAIGNS[name]
        line = {"split": None, "fused": None}
        for mode in ("split", "fused"):
            s = run_campaigns(model, mode, n=n, seed=7, thresholds=THRESH,
                              mm_bias=MM_BIAS)
            line[mode] = s
            # secondary: op-proportional site weighting
            s1 = run_campaigns(model, mode, n=n // 2, seed=8,
                               thresholds=THRESH, mm_bias=1.0)
            dt = (time.perf_counter() - t0) * 1e6 / n
            for tau in THRESH:
                csv.append(
                    f"table1_{name}_{mode}_tau{tau:.0e}_detected,{dt:.1f},"
                    f"{s.detected[tau]:.2f}")
            csv.append(f"table1_{name}_{mode}_bias1_det_1e-7,{dt:.1f},"
                       f"{s1.detected[1e-7]:.2f}")
        p = PAPER_1E7[name]
        sp, fu = line["split"], line["fused"]
        print(f"\n{name} (train acc {acc:.2f}, n={n}, campaigns "
              f"{(time.perf_counter()-t0):.1f}s)")
        print(f"  {'tau':>6s} | split: det    fp  silent | "
              f"fused: det    fp  silent")
        for tau in THRESH:
            print(f"  {tau:6.0e} | {sp.detected[tau]:6.2f} "
                  f"{sp.false_pos[tau]:5.2f} {sp.silent[tau]:6.2f} | "
                  f"     {fu.detected[tau]:6.2f} {fu.false_pos[tau]:5.2f} "
                  f"{fu.silent[tau]:6.2f}")
        print(f"  paper @1e-7: split {p[0]:.2f}/{p[1]:.2f}, "
              f"fused {p[2]:.2f}/{p[3]:.2f} (det/fp)")
        print(f"  criticality: {sp.critical_rate:.1f}% of output-corrupting "
              f"faults flip ≥1 node; avg {sp.avg_nodes_affected:.2f}% nodes")
        # the paper's key orderings:
        ok1 = fu.false_pos[1e-7] <= sp.false_pos[1e-7] + 0.5
        ok2 = fu.silent[1e-7] < 0.5 and sp.silent[1e-7] < 0.5
        print(f"  [claims] fused FP <= split FP: {ok1}; "
              f"zero-silent @1e-7: {ok2}")


def run_jax_engine(csv: List[str], n_campaigns: int = 50,
                   dataset: str = "cora", scale: int = 8, seed: int = 0,
                   tau: float = 1e-4) -> dict:
    """Smoke-scale Table I campaign routed through the JAX sparse engine.

    Per campaign, a bit flip is injected into a combination output element
    X_k[i, j] and the corrupted X runs through the engine's BCOO
    aggregation (``aggregate(x_bad, x_r)`` with the eq.-5 column from the
    independent clean path) — so the JAX fused check itself produces the
    verdict on faulted data.  The numpy engine's f64 prefix-delta model
    predicts the same fault's checksum effect (delta · s_c[i], the
    aggregation gain of column i), and the two verdicts must agree at the
    paper's absolute threshold.  Effective deltas within a small grey zone
    of tau are tallied but not asserted — there the engines' differing
    accumulation floors (f64 vs compensated f32) legitimately dominate.
    """
    import jax
    import jax.numpy as jnp
    import numpy.testing as npt

    from repro.core.abft import ABFTConfig, sparse_col_checksum
    from repro.core.datasets import make_reduced
    from repro.core.fault import NumpyGCN, flip_bit_f32, train_weights_numpy
    from repro.core.gcn import dataset_to_sparse
    from repro.engine import Graph, gcn_forward, make_backend

    print(f"\n=== Table I smoke via JAX engine: {dataset} x{scale} "
          f"n={n_campaigns} tau={tau:.0e} ===")
    ds = make_reduced(dataset, scale=scale, seed=seed)
    ws = train_weights_numpy(ds, epochs=40, lr=0.5, seed=seed)
    model = NumpyGCN(ds, weights=ws)
    s_sp, h_sp, _ = dataset_to_sparse(ds)
    params = {"layers": [{"w": jnp.asarray(w)} for w in ws]}
    cfg = ABFTConfig(mode="fused", threshold=tau, relative=False, kahan=True)
    s_c = sparse_col_checksum(s_sp, cfg.dtype)
    logits, _ = gcn_forward(params, Graph(s=s_sp, h0=h_sp, s_c=s_c), cfg,
                            backend="bcoo")
    scale_l = max(1.0, float(np.abs(model.logits).max()))
    npt.assert_allclose(np.asarray(logits), model.logits,
                        atol=1e-3 * scale_l, rtol=1e-3)

    # one backend, reused by every campaign; clean per-layer residuals from
    # the same (x, x_r) operands the corrupted runs will use
    bk = make_backend(s_sp, cfg, backend="bcoo", s_c=s_c)
    agg = jax.jit(lambda x, xr: bk.aggregate(x, xr)[1])
    xs = [st.x for st in model.layers]
    xrs = [jnp.asarray(st.x_r.astype(np.float32)) for st in model.layers]
    resid_np = [st.sum_hout - st.pred2 for st in model.layers]
    resid_jax = []
    for k in range(len(ws)):
        c = agg(jnp.asarray(xs[k]), xrs[k])
        r = float(c.actual) - float(c.predicted)
        assert abs(r) < tau / 4, (k, r, "clean JAX residual above tau/4")
        resid_jax.append(r)

    rng = np.random.default_rng(seed + 7)
    det_np = det_jx = agree = grey = 0
    for _ in range(n_campaigns):
        k = int(rng.integers(len(ws)))
        x = xs[k]
        i, j = int(rng.integers(x.shape[0])), int(rng.integers(x.shape[1]))
        old = np.float32(x[i, j])
        new = flip_bit_f32(old, int(rng.integers(32)))
        # numpy verdict: X_k[i,j] += delta lands in Σ H_out with the
        # aggregation gain Σ S[:, i] = s_c[i] (f64 prefix-delta model)
        eff = (float(new) - float(old)) * float(model.s_c[i])
        np_flag = not (abs(resid_np[k] + eff) <= tau)
        # JAX verdict: the engine's fused check on the corrupted operand
        x_bad = x.copy()
        x_bad[i, j] = new
        chk = agg(jnp.asarray(x_bad), xrs[k])
        jx_flag = not (abs(float(chk.actual) - float(chk.predicted)) <= tau)
        det_np += int(np_flag)
        det_jx += int(jx_flag)
        if tau / 5 <= abs(eff) <= 5 * tau:
            grey += 1
        else:
            assert np_flag == jx_flag, (k, eff, resid_np[k], resid_jax[k])
            agree += 1
    print(f"  detected: numpy {100.0*det_np/n_campaigns:.1f}%  "
          f"jax {100.0*det_jx/n_campaigns:.1f}%  "
          f"(agree {agree}/{n_campaigns}, grey-zone {grey})")
    csv.append(f"table1_jax_{dataset}_det_tau{tau:.0e},0,"
               f"{100.0*det_jx/n_campaigns:.2f}")
    csv.append(f"table1_jax_{dataset}_agree,0,{agree}")
    return {"det_np": det_np, "det_jax": det_jx, "agree": agree,
            "grey": grey, "n": n_campaigns}


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="numpy", choices=["numpy", "jax"])
    ap.add_argument("--campaigns", type=int, default=50,
                    help="jax-engine campaign count (numpy engine uses the "
                         "per-dataset N_CAMPAIGNS table)")
    args = ap.parse_args()
    out: List[str] = []
    if args.engine == "jax":
        run_jax_engine(out, n_campaigns=args.campaigns)
    else:
        run(out)
