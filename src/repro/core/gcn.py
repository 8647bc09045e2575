"""The paper's target model: a Kipf & Welling GCN's parameters and its
normalized adjacency D^-1/2 (A + I) D^-1/2, dense or BCOO.

The checked forward pass (eqs. 4–6 per layer, ReLU chain-breaking, the
report) is ``repro.engine``'s: ``gcn_apply(params, Graph(s, h0), cfg)``.
The per-MAC fault-injection model lives in the numpy engine
(``core/fault.py``), matching the paper's accelerator-level evaluation.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
Params = Dict[str, Any]


def init_gcn(rng: jax.Array, dims: Sequence[int]) -> Params:
    """Glorot-initialized weights for a len(dims)-1 layer GCN."""
    keys = jax.random.split(rng, len(dims) - 1)
    layers = []
    for k, (fin, fout) in zip(keys, zip(dims[:-1], dims[1:])):
        scale = jnp.sqrt(6.0 / (fin + fout))
        layers.append({"w": jax.random.uniform(k, (fin, fout), jnp.float32,
                                               -scale, scale)})
    return {"layers": layers}


def normalized_adjacency_bcoo(edges: np.ndarray, n: int):
    """D^-1/2 (A + I) D^-1/2 as a BCOO sparse matrix (any graph size)."""
    from jax.experimental import sparse as jsparse
    src = np.concatenate([edges[:, 0], edges[:, 1], np.arange(n)])
    dst = np.concatenate([edges[:, 1], edges[:, 0], np.arange(n)])
    # dedupe (symmetrization may duplicate bidirectional input edges)
    key = src * n + dst
    _, keep = np.unique(key, return_index=True)
    src, dst = src[keep], dst[keep]
    deg = np.bincount(src, minlength=n).astype(np.float64)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    vals = (dinv[src] * dinv[dst]).astype(np.float32)
    idx = np.stack([src, dst], axis=1).astype(np.int32)
    return jsparse.BCOO((jnp.asarray(vals), jnp.asarray(idx)),
                        shape=(n, n))


def dataset_to_sparse(ds) -> Tuple[Any, Array, np.ndarray]:
    """(S as BCOO, dense H0, labels) views of a core.datasets.GraphDataset.

    H0 stays dense on device: after the first combination every activation
    is dense anyway, and the paper's sparse-H0 op accounting lives in the
    analytic model (core/opcount.py), not the JAX path.
    """
    return ds.s.to_bcoo(), jnp.asarray(ds.features.todense()), ds.labels


def normalized_adjacency_dense(edges: np.ndarray, n: int) -> np.ndarray:
    """D^-1/2 (A + I) D^-1/2 as a dense float32 matrix (small graphs)."""
    a = np.zeros((n, n), np.float32)
    a[edges[:, 0], edges[:, 1]] = 1.0
    a[edges[:, 1], edges[:, 0]] = 1.0
    np.fill_diagonal(a, 1.0)
    d = a.sum(1)
    dinv = 1.0 / np.sqrt(np.maximum(d, 1.0))
    return (a * dinv[None, :]) * dinv[:, None]


def dataset_to_dense(ds) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(S, H0, labels) dense views of a core.datasets.GraphDataset."""
    return ds.s.todense(), ds.features.todense(), ds.labels
