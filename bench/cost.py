"""Operations and bytes, computed from shapes: the yardstick for roofline and
utilization metrics.

* :func:`true_ops` is the paper's Table II "True Out" count of one GCN
  forward (copied from the program's ``repro.core.opcount``): a MAC counts
  two operations, the first combination uses the sparse feature matrix, and
  check work is not counted.
* :func:`spmm_abft_cost` is what one call of the block-ELL aggregation
  kernel must move and compute, from the shapes and dtypes of the operands
  the backend staged: every stored tile is read once and multiplies a
  ``block_k``-row slice of X plus the one carried check column.  Only the
  logical output width counts, not its lane padding, so the least time it
  gives is a lower bound.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def true_ops(nodes: int, undirected_edges: int, feature_nnz: int,
             layer_dims: Sequence[int]) -> int:
    """Table II "True Out": combination plus aggregation over all layers."""
    adj_nnz = 2 * undirected_edges + nodes           # A + I, both directions
    ops = 0
    for i, (f, g) in enumerate(zip(layer_dims[:-1], layer_dims[1:])):
        h_nnz = feature_nnz if i == 0 else nodes * f
        ops += 2 * h_nnz * g + 2 * adj_nnz * g
    return ops


def spmm_abft_cost(vals_shape: Tuple[int, int, int, int], vals_itemsize: int,
                   cols_shape: Tuple[int, int], cols_itemsize: int,
                   out_width: int, x_itemsize: int = 4) -> Tuple[int, int]:
    """(operations, bytes) of one ``spmm_abft`` call.

    ``vals_shape`` is the staged tile table ``[stripes, ell_width, bm, bk]``
    and ``cols_shape`` its column-block index table; ``out_width`` the
    logical output width g.  Each stored tile does a ``[bm, bk] @ [bk, g+1]``
    product (the output plus the eq.-5 check column) and reads its tile and
    a ``[bk, g+1]`` slice of X; each stripe writes ``[bm, g+1]`` outputs and
    one checksum partial."""
    nbm, width, bm, bk = vals_shape
    tiles = nbm * width
    cols = out_width + 1
    ops = 2 * tiles * bm * bk * cols
    nbytes = (tiles * bm * bk * vals_itemsize
              + cols_shape[0] * cols_shape[1] * cols_itemsize
              + tiles * bk * cols * x_itemsize
              + nbm * bm * cols * x_itemsize
              + nbm * 4)
    return ops, nbytes


def least_seconds(ops: int, nbytes: int, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
