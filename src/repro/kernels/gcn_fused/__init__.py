"""Single-pass fused GCN-ABFT layer kernel (combination + aggregation +
checksum in one HBM traversal) and the whole-network variant that carries
relu + the next layer's combination across layer boundaries in VMEM (see
kernel.py for the dataflow)."""
from .kernel import gcn_fused_kernel, gcn_network_kernel  # noqa: F401
from .ops import (  # noqa: F401
    FUSED_VMEM_BUDGET,
    fused_layer_fits,
    fused_network_fits,
    fused_vmem_bytes,
    gcn_fused_layer,
    gcn_fused_packed,
    gcn_network_layer,
    gcn_network_packed,
    hbm_bytes_fused,
    hbm_bytes_network,
    hbm_bytes_twopass,
    network_vmem_bytes,
    prepare_fused_operands,
    slot_check_corners,
)
from .ref import gcn_fused_ref  # noqa: F401
