from repro.kernels.fused.fused import (  # noqa: F401
    fused_sweep_pallas,
    fused_vmem_bytes_estimate,
)
from repro.kernels.fused.ops import (  # noqa: F401
    fused_sweep_op,
    pick_fused_tile_n,
    require_fused_platform,
)
from repro.kernels.fused.ref import fused_sweep_ref  # noqa: F401
