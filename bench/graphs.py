"""Input generation for the benchmark, kept apart from the program so that a
change to the program cannot move the inputs it is measured on.

Two generators, both driven by a configuration or traffic file and a seed:

* :func:`make_graph` builds one whole graph at a configuration's published
  statistics (nodes, undirected edges, feature nonzeros, feature width): an
  Erdos-Renyi stand-in, symmetrized, self loops added, sym-normalized, with
  sparse nonnegative row-normalized "bag-of-words" features.  This is a copy
  of the program's own dataset generator (``repro.core.datasets``), which
  reproduces the paper's Table II operation counts.
* :func:`request_pool` builds the small per-request graphs of a streamed
  mix: node counts at fixed log-normal quantiles (the same multiset for every
  seed, in a seeded order), a stated mean degree, and feature rows with a
  stated number of nonzeros, row-normalized.  Adapted from the program's
  ``synth_graph_stream``, whose uniform sizes and Gaussian features do not
  look like neighbour-sampled citation graphs.
"""
from __future__ import annotations

import dataclasses
import statistics
import zlib
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass
class Coo:
    """Sparse matrix as COO triplets (duplicates are summed by consumers)."""

    row: np.ndarray
    col: np.ndarray
    data: np.ndarray
    shape: Tuple[int, int]

    def todense(self) -> np.ndarray:
        out = np.zeros(self.shape, np.float32)
        np.add.at(out, (self.row, self.col), self.data)
        return out


@dataclasses.dataclass
class WholeGraph:
    s: Coo                   # D^-1/2 (A + I) D^-1/2
    features: List[Coo]      # the feature matrices a run rotates through


def _stream(tag: str, seed: int) -> np.random.Generator:
    salt = zlib.crc32(tag.encode()) & 0xFFFF
    return np.random.default_rng(np.random.SeedSequence([salt, int(seed)]))


def _sample_edges(n: int, m: int, rng: np.random.Generator) -> np.ndarray:
    """m distinct undirected edges (i < j), uniform."""
    got = np.empty((0, 2), np.int64)
    while got.shape[0] < m:
        k = int((m - got.shape[0]) * 1.3) + 16
        e = rng.integers(0, n, size=(k, 2), dtype=np.int64)
        e = e[e[:, 0] != e[:, 1]]
        e = np.sort(e, axis=1)
        got = np.unique(np.concatenate([got, e], axis=0), axis=0)
    return got[:m]


def _normalized_adjacency(edges: np.ndarray, n: int) -> Coo:
    src = np.concatenate([edges[:, 0], edges[:, 1], np.arange(n)])
    dst = np.concatenate([edges[:, 1], edges[:, 0], np.arange(n)])
    deg = np.bincount(src, minlength=n).astype(np.float64)
    dinv = 1.0 / np.sqrt(deg)
    vals = (dinv[src] * dinv[dst]).astype(np.float32)
    return Coo(src, dst, vals, (n, n))


def _features(n: int, feat_dim: int, nnz: int,
              rng: np.random.Generator) -> Coo:
    """Sparse nonnegative rows, at least one nonzero each, row-normalized."""
    per_row = np.full(n, nnz // n, np.int64)
    extra = nnz - per_row.sum()
    if extra > 0:
        per_row[rng.choice(n, size=extra, replace=False)] += 1
    per_row = np.maximum(per_row, 1)
    rows = np.repeat(np.arange(n), per_row)
    cols = rng.integers(0, feat_dim, size=rows.size, dtype=np.int64)
    vals = rng.uniform(0.5, 1.5, size=rows.size).astype(np.float32)
    rsum = np.zeros(n, np.float64)
    np.add.at(rsum, rows, vals.astype(np.float64))
    vals = (vals / rsum[rows]).astype(np.float32)
    return Coo(rows, cols, vals, (n, feat_dim))


def make_graph(config: dict, seed: int, n_features: int) -> WholeGraph:
    """A whole graph at the configuration's published statistics, with
    ``n_features`` independent feature matrices of the same sparsity."""
    g = config["graph"]
    rng = _stream(config["name"], seed)
    edges = _sample_edges(g["nodes"], g["undirected_edges"], rng)
    s = _normalized_adjacency(edges, g["nodes"])
    feats = [_features(g["nodes"], config["layer_dims"][0],
                       g["feature_nnz"], rng) for _ in range(n_features)]
    return WholeGraph(s=s, features=feats)


def lognormal_sizes(count: int, median: float, sigma: float, lo: int,
                    hi: int) -> np.ndarray:
    """``count`` node counts at the log-normal's evenly spaced quantiles,
    clipped: the same multiset whatever the seed."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / count)
                  for i in range(count)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def exponential_gaps(count: int, rate: float) -> np.ndarray:
    """``count`` inter-arrival gaps at the exponential's evenly spaced
    quantiles (mean ``1/rate``): a Poisson process's gaps, fixed as a set."""
    q = (np.arange(count) + 0.5) / count
    return -np.log1p(-q) / rate


def request_graph(n: int, mean_degree: float, feat_dim: int, feat_nnz: int,
                  rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """One request: dense normalized adjacency [n, n] and features [n, f]."""
    m = min(max(int(round(n * mean_degree / 2)), 1), n * (n - 1) // 2)
    s = _normalized_adjacency(_sample_edges(n, m, rng), n).todense()
    rows = np.repeat(np.arange(n), feat_nnz)
    cols = np.concatenate([rng.choice(feat_dim, size=feat_nnz, replace=False)
                           for _ in range(n)])
    vals = rng.uniform(0.5, 1.5, size=rows.size).astype(np.float32)
    h0 = np.zeros((n, feat_dim), np.float32)
    h0[rows, cols] = vals
    h0 /= h0.sum(axis=1, keepdims=True)
    return s, h0


def request_pool(graphs: dict, feat_dim: int, count: int, seed: int
                 ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``count`` request graphs of a traffic file's ``graphs`` section:
    sizes from :func:`lognormal_sizes`, shuffled and filled from ``seed``."""
    nodes = graphs["nodes"]
    sizes = lognormal_sizes(count, nodes["median"], nodes["sigma"],
                            nodes["min"], nodes["max"])
    rng = _stream("request_pool", seed)
    sizes = rng.permutation(sizes)
    return [request_graph(int(n), graphs["mean_degree"], feat_dim,
                          graphs["feature_nnz"], rng) for n in sizes]
