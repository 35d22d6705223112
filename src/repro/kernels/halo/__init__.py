# Fused halo move-application kernel (DESIGN.md §5).
from repro.kernels.halo.ops import (  # noqa: F401
    HALO_MAX_CAND,
    apply_moves,
    resolve_halo,
)
from repro.kernels.halo.ref import (  # noqa: F401
    halo_apply_range_ref,
    halo_apply_ref,
)
