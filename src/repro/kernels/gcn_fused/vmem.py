"""VMEM working-set model of the fused kernels — the single source of truth.

Pure integer arithmetic on layer widths and block shapes: what the
single-pass layer kernel and the whole-network kernel keep resident in
VMEM, and the budget predicates built on it.  The engine's kernel-path
decision (``engine/backends.py::kernel_paths``), the whole-network
kernel's scoped-VMEM request (``kernel.py``) and the static checker
(``abftlint --passes vmem``, ``analysis/vmem.py``) all read these same
objects, so a lint verdict of "fits" is the decision the engine takes.
"""
from __future__ import annotations

from typing import Sequence

# Conservative per-core VMEM budget for the fused layer's resident + working
# set.  Real TPU cores have ~16 MB; half of it leaves the scheduler slack
# for double-buffered DMA and keeps the fallback decision robust across
# generations.
FUSED_VMEM_BUDGET = 8 * 1024 * 1024


def _lanes(n: int, block_g: int) -> int:
    return -(-n // block_g) * block_g


def fused_vmem_bytes(f: int, g: int, bm: int, bk: int, *,
                     block_g: int = 128, itemsize: int = 4) -> int:
    """Model of the fused kernel's peak VMEM working set in bytes.

    Resident across the grid: W [fp, gp] and w_r [fp, 1].  Per step,
    double-buffered by the pipeline: the S tile [bm, bk] and the H tile
    [bk, fp].  Plus the output block [bm, gp], the f32 accumulator scratch
    [bm, gp], the extra-column scratch, and the recomputed x tile [bk, gp].
    """
    fp, gp = _lanes(f, block_g), _lanes(g, block_g)
    resident = fp * gp + fp
    streamed = 2 * (bm * bk + bk * fp)
    working = 2 * bm * gp + bk * gp + bm * gp + 2 * bm
    return itemsize * (resident + streamed + working)


def fused_layer_fits(f: int, g: int, bm: int, bk: int, *,
                     block_g: int = 128,
                     budget: int = FUSED_VMEM_BUDGET) -> bool:
    """True when the fused layer's working set fits the VMEM budget — the
    engine falls back to the two-pass kernel otherwise (W too wide to stay
    resident)."""
    return fused_vmem_bytes(f, g, bm, bk, block_g=block_g) <= budget


def network_vmem_bytes(dims: Sequence[int], bm: int, rows: int, *,
                       block_g: int = 128, itemsize: int = 4) -> int:
    """Model of the whole-network kernel's peak VMEM working set.

    Dominant term: the two ping-pong activation buffers [rows, P] that keep
    the whole activation matrix resident across layer boundaries (absent
    for a single layer).  Resident per layer: one W slab [P, P] + w_r [P].
    Per step, double-buffered: the S tile and (layer 0 only, but the
    pipeline allocates it throughout) the H0 tile.  Plus the output block,
    the f32 accumulator, the recomputed x tile, and the extra column.
    """
    p = _lanes(max(dims), block_g)
    n_layers = len(dims) - 1
    act = 2 * rows * p if n_layers > 1 else 0
    resident = p * p + p
    streamed = 2 * (bm * bm + bm * p)
    working = 2 * bm * p + bm * p + bm * p + 2 * bm
    return itemsize * (act + resident + streamed + working)


def fused_network_fits(dims: Sequence[int], bm: int, rows: int, *,
                       block_g: int = 128,
                       budget: int = FUSED_VMEM_BUDGET) -> bool:
    """True when the whole-network working set — activation ping-pong
    buffers included — fits the VMEM budget; the engine falls back to
    per-layer fused (then two-pass) otherwise."""
    return network_vmem_bytes(dims, bm, rows, block_g=block_g) <= budget
