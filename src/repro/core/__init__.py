"""The paper's contribution: GCN-ABFT fused checksums + the ABFT substrate.

Public surface:
  checksum  — checksum primitives (col/row/total, Kahan, fused-chain)
  abft      — ABFTConfig + split/fused checks, CheckedOp, reports
  gcn       — GCN parameters (Kipf & Welling) and normalized adjacency;
              the checked forward is repro.engine's
  datasets  — synthetic stand-ins for Cora/Citeseer/PubMed/Nell
  opcount   — analytic op-count model (paper Table II)
  fault     — bit-flip fault-injection engine (paper Table I)
"""
from .abft import (  # noqa: F401
    ABFTConfig,
    ABFTReport,
    ChainOp,
    Check,
    CheckedOp,
    MatmulOp,
    check_chain,
    check_matmul,
    checked_matmul,
    fold_w_r_tree,
    per_op_report,
    resolve_w_r,
    merge_reports,
    sparse_col_checksum,
    sparse_matmul,
    summarize,
)
from .checksum import (  # noqa: F401
    col_checksum,
    fused_chain_checksum,
    kahan_total,
    predicted_matmul_checksum,
    row_checksum,
    total_checksum,
)
