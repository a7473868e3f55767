"""Core paper contribution: communication-efficient distributed eigenspace
estimation via Procrustes fixing (Charisopoulos, Benson & Damle)."""

from repro.core.procrustes import (  # noqa: F401
    align,
    align_batch,
    newton_schulz_polar,
    polar_factor,
    procrustes_distance,
    procrustes_rotation,
    sign_fix,
)
from repro.core.orthonorm import (  # noqa: F401
    cholesky_qr2,
    orthonormalize,
    resolve_orth,
)
from repro.core.metrics import dist_2, dist_f, eigengap, intdim  # noqa: F401
from repro.core.subspace import (  # noqa: F401
    local_eigenbasis,
    subspace_iteration,
    top_r_eigh,
)
from repro.core.eigenspace import (  # noqa: F401
    central_estimate,
    iterative_refinement,
    local_bases,
    naive_average,
    procrustes_fix_average,
    projector_average,
    qr_orthonormalize,
    refinement_rounds,
)
from repro.core.covariance import empirical_covariance  # noqa: F401
from repro.core.distributed import (  # noqa: F401
    broadcast_from,
    distributed_pca,
    distributed_pca_from_covs,
    procrustes_average_collective,
    sign_average_collective,
)
