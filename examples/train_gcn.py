"""End-to-end driver 1: train a GCN on a synthetic Cora-sized graph with
ABFT-checked steps (a few hundred steps on CPU).

    PYTHONPATH=src python examples/train_gcn.py --steps 300 --mode fused
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ABFTConfig
from repro.core.datasets import make_reduced
from repro.core.gcn import dataset_to_dense, init_gcn
from repro.engine import Graph, gcn_loss
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_warmup
from repro.runtime import ABFTGuard


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--mode", default="fused",
                    choices=["none", "split", "fused"])
    ap.add_argument("--scale", type=int, default=4)
    args = ap.parse_args()

    ds = make_reduced("cora", scale=args.scale, seed=0)
    s_np, h_np, y_np = dataset_to_dense(ds)
    s, h, y = jnp.asarray(s_np), jnp.asarray(h_np), jnp.asarray(y_np)
    dims = ds.stats.layer_dims
    abft = ABFTConfig(mode=args.mode, threshold=1e-2, relative=True)
    opt_cfg = AdamWConfig(lr=1e-2, weight_decay=1e-4)

    params = init_gcn(jax.random.PRNGKey(0), dims)
    state = {"params": params, "opt": adamw_init(params)}

    @jax.jit
    def step(state):
        (loss, report), grads = jax.value_and_grad(
            lambda p: gcn_loss(p, Graph(s, h), y, None, abft), has_aux=True
        )(state["params"])
        lr = cosine_warmup(state["opt"]["step"], 20, args.steps)
        p2, o2 = adamw_update(state["params"], grads, state["opt"],
                              opt_cfg, lr)
        return {"params": p2, "opt": o2}, {
            "loss": loss, "abft_flag": report.flag,
            "abft_max_rel": report.max_rel}

    guard = ABFTGuard()
    t0 = time.time()
    for i in range(args.steps):
        state, m = guard.run_step(step, state)
        if i % 50 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(m['loss']):.4f} "
                  f"abft_max_rel={float(m['abft_max_rel']):.2e} "
                  f"flags={guard.flags}")
    dt = time.time() - t0
    print(f"\n{args.steps} steps in {dt:.1f}s "
          f"({dt/args.steps*1e3:.1f} ms/step); ABFT mode={args.mode}; "
          f"flagged steps: {guard.flags}")


if __name__ == "__main__":
    main()
