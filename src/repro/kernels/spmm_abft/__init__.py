from .layout import (  # noqa: F401
    BlockEll,
    BlockEllPlan,
    coo_to_block_ell,
    dense_to_block_ell,
    pad_block_rows,
    plan_block_ell,
    stack_block_ell,
)
from .ops import (  # noqa: F401
    spmm_abft,
    spmm_abft_packed,
    stripe_check_corners,
)
