"""Input generation for the benchmark, kept apart from the program so that a
change to the program cannot move the inputs it is measured on.

Two generators, both driven by a configuration or traffic file and a seed:

* :func:`make_graph` builds one whole graph at a configuration's published
  statistics (nodes, undirected edges, feature nonzeros, feature width),
  symmetrized, self loops added, sym-normalized, with sparse nonnegative
  row-normalized "bag-of-words" features.  The configuration's
  ``graph.topology`` picks the edges: ``erdos_renyi`` (the default), a
  copy of the program's own dataset generator (``repro.core.datasets``),
  which reproduces the paper's Table II operation counts; or ``lfr``
  (:func:`lfr_edges`), heavy-tailed degrees and communities after
  Lancichinetti, Fortunato and Radicchi (arXiv:0805.4770), node ids
  contiguous by community.  An ``lfr`` graph is wired once for the
  configuration and relabelled by the seed inside each block-row stripe
  and community, so that every seed stores the same block-ELL tiles: the
  widest stripe sets the work, and with a wiring per seed it swung by
  about a tenth from seed to seed.
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


def _power_quantiles(count: int, exponent: float, lo: float,
                     hi: float) -> np.ndarray:
    """``count`` values at the evenly spaced quantiles of the power law
    ``p(x) ~ x**-exponent`` on ``[lo, hi]``, ascending: the same multiset
    whatever the seed."""
    q = (np.arange(count) + 0.5) / count
    a = 1.0 - exponent
    return (lo ** a + q * (hi ** a - lo ** a)) ** (1.0 / a)


def _degree_weights(n: int, mean: float, exponent: float,
                    hi: float) -> np.ndarray:
    """Expected degrees: power-law quantiles up to ``hi`` whose mean is
    ``mean`` (the lower end found by bisection)."""
    lo_a, lo_b = 1e-3, mean
    for _ in range(100):
        mid = 0.5 * (lo_a + lo_b)
        if _power_quantiles(n, exponent, mid, hi).mean() < mean:
            lo_a = mid
        else:
            lo_b = mid
    return _power_quantiles(n, exponent, 0.5 * (lo_a + lo_b), hi)


def _community_sizes(n: int, exponent: float, lo: int, hi: int,
                     rng: np.random.Generator) -> np.ndarray:
    """Community sizes at the power law's quantiles on ``[lo, hi]``, as many
    communities as make ``n`` nodes, the difference spread one node at a
    time over the largest with room; in an order drawn from ``rng``."""
    count = max(int(round(n / _power_quantiles(1000, exponent, lo, hi)
                          .mean())), 1)
    sizes = np.rint(_power_quantiles(count, exponent, lo, hi)).astype(np.int64)
    while sizes.sum() != n:
        diff = n - int(sizes.sum())
        room = np.flatnonzero(sizes < hi if diff > 0 else sizes > lo)[::-1]
        if room.size == 0:
            raise ValueError(f"{n} nodes do not fit communities of {lo}-{hi}")
        sizes[room[:abs(diff)]] += 1 if diff > 0 else -1
    return rng.permutation(sizes)


def _distinct_capped(edges: np.ndarray, n: int, cap: int) -> np.ndarray:
    """The first occurrence of each edge, in order, less every edge that
    comes after its endpoint already has ``cap`` earlier edges: a node's
    degree stays at most ``cap``."""
    _, first = np.unique(edges[:, 0] * n + edges[:, 1], return_index=True)
    edges = edges[np.sort(first)]
    ends = edges.reshape(-1)
    order = np.argsort(ends, kind="stable")
    sorted_ends = ends[order]
    rank = np.empty_like(order)
    rank[order] = (np.arange(order.size)
                   - np.searchsorted(sorted_ends, sorted_ends, side="left"))
    return edges[(rank.reshape(-1, 2) < cap).all(axis=1)]


def _fill(edges: np.ndarray, draw, count: int, n: int, cap: int
          ) -> np.ndarray:
    """``edges`` and then ``count`` new distinct ones from ``draw(k)`` (k
    candidate pairs ``i < j``, in draw order), every degree at most
    ``cap``."""
    target = edges.shape[0] + count
    for _ in range(1000):
        if edges.shape[0] >= target:
            return edges[:target]
        k = int((target - edges.shape[0]) * 1.3) + 16
        edges = _distinct_capped(np.concatenate([edges, draw(k)]), n, cap)
    raise RuntimeError(f"could not draw {count} distinct edges")


def lfr_edges(g: dict, rng: np.random.Generator
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Distinct undirected edges ``i < j`` and each node's community, from
    a configuration's ``graph`` section with ``topology: lfr``.

    Expected degrees are power-law quantiles (``degree_exponent``, at most
    ``max_degree``, mean ``2 * undirected_edges / nodes``) and community
    sizes likewise (``community_exponent``, ``community_min`` to
    ``community_max``).  Communities take contiguous node ids in the order
    they were drawn.  Nodes are placed largest expected degree first, each
    in a random free place of a community with room for its share inside
    (LFR's rule; where none has, the largest with a free place), so the
    many that fit anywhere land at random.  Edges are degree-corrected: an
    endpoint is drawn in proportion to its expected degree.  A share
    ``1 - mixing`` of the edges joins two nodes of one community (the
    first endpoint drawn from the whole graph, the second from its
    community), the rest join two communities; each share is drawn to its
    exact count, with no self loop, no duplicate, and no node over
    ``max_degree``."""
    n, m = g["nodes"], g["undirected_edges"]
    mu, cap = g["mixing"], g["max_degree"]
    sizes = _community_sizes(n, g["community_exponent"], g["community_min"],
                             g["community_max"], rng)
    comm = np.repeat(np.arange(sizes.size), sizes)
    start = np.concatenate([[0], np.cumsum(sizes)])
    w = _degree_weights(n, 2.0 * m / n, g["degree_exponent"], cap)[::-1]

    # places (node ids) by community size, largest first, ties at random
    place = np.lexsort((rng.random(n), -sizes[comm]))
    room = sizes[comm][place] - 1                 # non-increasing
    fits = np.searchsorted(-room, -(1.0 - mu) * w, side="right")
    u = rng.random(n)
    head = int(np.searchsorted(fits, n, side="left"))   # the ones that do
    for j in range(head):                                # not fit anywhere
        i = j + int(u[j] * (fits[j] - j)) if fits[j] > j else j
        place[[j, i]] = place[[i, j]]
    place[head:] = rng.permutation(place[head:])
    weight = np.empty(n)
    weight[place] = w
    cum = np.cumsum(weight)

    def ends(lo, hi):
        return np.minimum(np.searchsorted(cum, lo + rng.random(lo.size)
                                          * (hi - lo), side="right"), n - 1)

    def pairs(a, b):
        keep = a != b
        return np.sort(np.stack([a[keep], b[keep]], axis=1), axis=1)

    def intra(k):
        a = ends(np.zeros(k), np.full(k, cum[-1]))
        c = comm[a]
        lo = np.where(start[c] > 0, cum[start[c] - 1], 0.0)
        return pairs(a, ends(lo, cum[start[c + 1] - 1]))

    def inter(k):
        a = ends(np.zeros(k), np.full(k, cum[-1]))
        b = ends(np.zeros(k), np.full(k, cum[-1]))
        keep = comm[a] != comm[b]
        return pairs(a[keep], b[keep])

    m_intra = int(round((1.0 - mu) * m))
    edges = _fill(np.empty((0, 2), np.int64), intra, m_intra, n, cap)
    return _fill(edges, inter, m - m_intra, n, cap), comm


def _relabel_in_stripes(edges: np.ndarray, comm: np.ndarray, block: int,
                        rng: np.random.Generator) -> np.ndarray:
    """Node ids shuffled inside each run of ids that shares a ``block``-row
    stripe and a community: another labelling with the same stored
    block-ELL tiles and the same contiguous communities."""
    n = comm.size
    segment = np.arange(n) // block * (int(comm[-1]) + 1) + comm
    new = np.empty(n, np.int64)
    new[np.lexsort((rng.random(n), segment))] = np.arange(n)
    return np.sort(new[edges], axis=1)


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
    topology = g.get("topology", "erdos_renyi")
    if topology == "erdos_renyi":
        edges = _sample_edges(g["nodes"], g["undirected_edges"], rng)
    elif topology == "lfr":
        # one wiring for the configuration, so every seed stores the same
        # tiles and does the same work; the seed relabels it
        edges, comm = lfr_edges(g, _stream("lfr wiring " + config["name"], 0))
        edges = _relabel_in_stripes(edges, comm, config["block"], rng)
    else:
        raise ValueError(f"unknown graph topology {topology!r}")
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
