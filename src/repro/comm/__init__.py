"""Communication-topology subsystem: how refinement rounds talk.

The paper's one-shot claim is a statement about communication schedules,
so the schedule is a first-class, *independently selectable* axis here —
``topology=`` ("psum" | "gather" | "ring" | "auto") is orthogonal to
``backend=`` (which only selects the compute path), and ``comm_bits=``
(32 | 16 | 8 | "auto") sets the wire precision those schedules move their
payloads at.  The registry, the analytic bits-per-round cost model, and
the mesh primitives live in ``repro.comm.topology``; the wire-precision
codecs (identity / bf16 / stochastic int8 with error feedback) in
``repro.comm.quantize``; the overlapped ring schedule in
``repro.comm.ring``.  ``repro.core.distributed`` dispatches on the
resolved topology; ``benchmarks/bench_comm.py`` and
``repro.launch.dryrun`` consume the cost model instead of hand-writing
the formulas.

This package deliberately depends only on ``jax`` at
import time (core/kernels imports are function-level), so it sits below
``repro.core`` in the layering.
"""

from repro.comm.membership import (  # noqa: F401
    Membership,
    pod_membership,
    resolve_membership,
)
from repro.comm.quantize import (  # noqa: F401
    COMM_BITS,
    COMM_BITS_CHOICES,
    PARITY_TOL,
    Codec,
    get_codec,
    message_bits,
    resolve_comm_bits,
    wire_broadcast,
    wire_psum_mean,
)
from repro.comm.topology import (  # noqa: F401
    DATA_AXIS,
    MODEL_AXIS,
    POD_AXIS,
    TOPOLOGIES,
    TOPOLOGY_CHOICES,
    CommCost,
    broadcast_from,
    comm_cost,
    fan_projector_words,
    paper_coordinator_words,
    resolve_topology,
)
from repro.comm.ring import (  # noqa: F401
    DEFAULT_RING_CHUNK,
    chunk_spans,
    fused_ring_rounds,
    ring_rounds,
)
from repro.comm.hier import hier_rounds  # noqa: F401
