"""Plain reference of a Kipf & Welling GCN forward, in numpy.

    logits = S relu(S (H W1)) W2          (any depth: ReLU between layers)

It imports nothing of the program.  ``precision`` names how every product
is rounded:

* ``"highest"``: float32 products, float32 sums (the reference);
* ``"high"``: the three-pass bfloat16 scheme a TPU runs for
  ``Precision.HIGH`` (each float32 operand split into a bfloat16 high and
  low part; hi*hi + hi*lo + lo*hi, each product exact in float32).  This is
  the control: the step below the float32 ``HIGHEST`` the configuration
  states.
"""
from __future__ import annotations

from typing import Callable, Sequence

import numpy as np


def to_bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    bias = ((u >> 16) & 1) + np.uint32(0x7FFF)
    return ((u + bias) & np.uint32(0xFFFF0000)).view(np.float32)


def _split(a: np.ndarray):
    a = np.asarray(a, np.float32)
    hi = to_bf16(a)
    return hi, to_bf16(a - hi)


def matmul(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    if precision == "highest":
        return a @ b
    if precision != "high":
        raise ValueError(f"precision {precision!r} not in ('highest', 'high')")
    ah, al = _split(a)
    bh, bl = _split(b)
    return (ah @ bl + al @ bh) + ah @ bh


def coo_aggregate(row: np.ndarray, col: np.ndarray, data: np.ndarray,
                  n: int, precision: str) -> Callable[[np.ndarray], np.ndarray]:
    """S @ X for S given as COO triplets (scatter-add of row products)."""
    def agg(x: np.ndarray) -> np.ndarray:
        x = np.asarray(x, np.float32)
        if precision == "highest":
            prod = data[:, None] * x[col]
        else:
            dh, dl = _split(data)
            xh, xl = _split(x[col])
            prod = (dh[:, None] * xl + dl[:, None] * xh) + dh[:, None] * xh
        out = np.zeros((n, x.shape[1]), np.float32)
        np.add.at(out, row, prod)
        return out
    return agg


def dense_aggregate(s: np.ndarray, precision: str
                    ) -> Callable[[np.ndarray], np.ndarray]:
    return lambda x: matmul(s, x, precision)


def forward(aggregate: Callable[[np.ndarray], np.ndarray], h0: np.ndarray,
            weights: Sequence[np.ndarray], precision: str) -> np.ndarray:
    h = np.asarray(h0, np.float32)
    for i, w in enumerate(weights):
        h = aggregate(matmul(h, w, precision))
        if i < len(weights) - 1:
            h = np.maximum(h, 0.0)
    return h


def gap(out: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap to the reference, as a share of its largest magnitude."""
    scale = float(np.abs(ref).max())
    return float(np.abs(np.asarray(out, np.float32) - ref).max()) / scale
