# Kernel layer: compute hot-spots identified by the roofline analysis.
# flash_attention — removes the S x S score HBM traffic (memory-bound
#   attention baseline); ssd_scan — chunked Mamba2/mLSTM state passing in
#   VMEM; fedavg — the MMFL server's weighted multi-client aggregation;
#   routed_experts — drop-free MoE dispatch over a grouped matmul.
from repro.kernels.gmm import routed_experts  # noqa: F401
from repro.kernels.ops import (  # noqa: F401
    fedavg_aggregate,
    flash_attention,
    fused_aggregate,
    gated_rmsnorm,
    rmsnorm,
    ssd_scan,
)
