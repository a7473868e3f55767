"""Topology registry, mesh primitives, and the words-per-round cost model.

A *topology* is the communication schedule a refinement round runs over a
mesh axis; it is independent of ``backend=`` (which picks the compute
path).  Four are registered:

  * ``"psum"``   — broadcast shard 0's basis as the reference (one d·r
                   all-reduce), solve the r x r Procrustes problem locally
                   on every shard, then one psum of the aligned bases plus
                   a replicated orthonormalization.  One d·r all-reduce per
                   round after the broadcast.
  * ``"gather"`` — the paper's coordinator form, replicated: one all-gather
                   of the m local bases per shard, then the stacked
                   Algorithm 1/2 rounds run communication-free on the
                   (m, d, r) stack (``repro.core.eigenspace``, any
                   backend).  Pays m·d·r once; materializes the stack.
  * ``"ring"``   — the overlapped schedule (``repro.comm.ring``): the
                   bases circulate a ppermute ring in d-chunks and each
                   shard consumes its neighbor's basis the hop it arrives
                   (Gram against the reference, align, accumulate into the
                   running V̄).  Communication overlaps the Gram phase and
                   the (m, d, r) stack is never materialized — O(d·r)
                   working set instead of the gather's O(m·d·r).
  * ``"hier"``   — the two-level schedule (``repro.comm.hier``) over a
                   2-D (pod, local) mesh: each round aligns locally, runs
                   one masked f32 psum over the ``local`` axis (the fast
                   intra-pod link) to form a pod-representative sum, then
                   circulates only the p pod sums around a chunked
                   ppermute ring over the ``pod`` axis (the slow
                   inter-pod link, quantized at ``comm_bits``).  Per
                   device the slow link carries O(p·d·r) ring-hop bits
                   instead of the flat ring's O(m·d·r).

``"auto"`` resolves against the *resolved* backend to the pre-topology-
subsystem pairing (gather under the pallas kernels, psum under XLA), so
callers that never pass ``topology=`` keep their exact old schedule.

Cost-model conventions (shared by ``benchmarks/bench_comm.py``, the
bench-smoke CI check, and ``repro.launch.dryrun`` — do not re-derive these
inline):

  * ``CommCost.bits`` is the primary quantity: *wire bits per estimation*
    at the requested ``comm_bits=`` tier.  One (d, r) basis message costs
    ``quantize.message_bits(d, r, comm_bits)`` — ``d·r·comm_bits`` payload
    plus the f32[r] per-column scale (32·r bits) that rides with every
    int8 message.
  * ``CommCost.words`` keeps the *logical collective payload words per
    estimation*: an all-reduce or broadcast of a (d, r) basis counts d·r,
    a gather of m bases counts m·d·r, and each ring hop counts d·r.  This
    is the paper's own accounting (Section 2.1 / Remark 2), independent of
    wire precision, and what the comm table prints; at ``comm_bits=32``
    the compatibility identity ``bits == words * 32`` holds exactly.
  * ``CommCost.hlo_bits`` breaks the same schedule down by HLO collective
    kind in *operand bits per device* — ``hlo_bytes`` (bits // 8) is
    exactly what ``repro.launch.hlo_analysis.collective_bytes`` measures
    on the partitioned module.  The measured check in
    ``bench_comm.comm_measured`` asserts compiled HLO against this.
    ``hlo_words`` (bits // 32) survives as the legacy f32 view, exact
    only at 32 bits.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from repro.comm.membership import Membership, pod_membership, resolve_membership
from repro.comm.quantize import message_bits, resolve_comm_bits

__all__ = [
    "DATA_AXIS",
    "POD_AXIS",
    "MODEL_AXIS",
    "TOPOLOGIES",
    "TOPOLOGY_CHOICES",
    "resolve_topology",
    "broadcast_from",
    "CommCost",
    "comm_cost",
    "paper_coordinator_words",
    "fan_projector_words",
]

# Single home of the mesh axis names (satellite of the hier topology:
# the 2-D (pod, local) mesh, the collectives inside shard_map, and the
# launch-layer mesh builders must all agree on these strings, so they
# are constants here rather than literals scattered per module).  The
# hierarchical topology's *local* axis is the ``DATA_AXIS`` — the same
# axis every flat topology aggregates over — and its pod axis is
# ``POD_AXIS``, matching ``make_production_mesh(multi_pod=True)``.
DATA_AXIS = "data"
POD_AXIS = "pod"
MODEL_AXIS = "model"

TOPOLOGIES = ("psum", "gather", "ring", "hier")

# The single home of the *accepted-values* listing (registry entries plus
# the "auto" switch).  ``resolve_topology``'s error message, both CLIs'
# ``choices=``, and the planner registry (``repro.plan.TOPOLOGY_CHOICES``
# re-exports this object) all read this tuple, so they cannot drift.
TOPOLOGY_CHOICES = TOPOLOGIES + ("auto",)


def resolve_topology(topology: str, backend: str = "xla") -> str:
    """Resolve a ``topology=`` switch to a concrete registry entry.

    ``"auto"`` keeps the historical backend pairing — "gather" when the
    resolved backend is "pallas" (the kernels run on the gathered stack),
    "psum" otherwise — so the topology axis is opt-in.  Any explicit
    topology is honoured under any backend.  The cost-model-driven
    choice lives above this in ``repro.plan`` (``plan="auto"`` on the
    aggregation entry points); this function stays the legacy-pairing
    resolver that the planner's ``plan=None`` path delegates to.
    """
    if topology == "auto":
        from repro.kernels.ops import resolve_backend

        return "gather" if resolve_backend(backend) == "pallas" else "psum"
    if topology not in TOPOLOGIES:
        raise ValueError(
            f"topology must be one of {TOPOLOGY_CHOICES}, got {topology!r}"
        )
    return topology


def broadcast_from(x: jax.Array, axis_name: str, src: int = 0) -> jax.Array:
    """Broadcast shard ``src``'s value to all shards along ``axis_name``.

    One all-reduce of ``x.size`` words (vs. an all-gather of m * x.size).
    """
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis_name)


# ---------------------------------------------------------------------------
# Analytic cost model.


@dataclasses.dataclass(frozen=True)
class CommCost:
    """Communication bill of one estimation (n_iter rounds) per topology.

    ``bits`` is the wire total at ``comm_bits`` precision; ``words`` the
    precision-independent logical payload (module docstring conventions);
    ``hlo_bits`` the per-device HLO operand-bit breakdown by collective
    kind, matching ``hlo_analysis.collective_bytes`` keys via the
    ``hlo_bytes`` property.
    """

    topology: str
    comm_bits: int
    words: int
    bits: int
    hlo_bits: Dict[str, int]
    # Two-level schedules only: the same hlo_bits, split by mesh level —
    # {"intra": {kind: bits}, "inter": {kind: bits}}.  The inter level's
    # "collective-permute" entry is *exactly* the slow-link ring-hop bill
    # (no intra collective ever lowers to a permute), so the per-level
    # prediction is HLO-verifiable even though compiled modules group
    # bytes by collective kind, not by axis.  ``None`` for the flat
    # topologies.
    levels: Dict[str, Dict[str, int]] | None = None

    @property
    def hlo_bytes(self) -> Dict[str, int]:
        """Per-device operand bytes by collective kind (bits // 8) —
        directly comparable to ``hlo_analysis.collective_bytes``."""
        return {k: v // 8 for k, v in self.hlo_bits.items()}

    @property
    def level_bytes(self) -> Dict[str, Dict[str, int]] | None:
        """Per-level ``hlo_bytes`` view (two-level topologies only)."""
        if self.levels is None:
            return None
        return {
            lv: {k: v // 8 for k, v in kinds.items()}
            for lv, kinds in self.levels.items()
        }

    @property
    def hlo_words(self) -> Dict[str, int]:
        """Legacy f32 operand-word view (bits // 32).  Exact at
        ``comm_bits=32``; kept for pre-bits consumers."""
        return {k: v // 32 for k, v in self.hlo_bits.items()}


def comm_cost(
    topology: str,
    *,
    m: int,
    d: int,
    r: int,
    n_iter: int = 1,
    ref_broadcast: bool = True,
    comm_bits=32,
    membership: Membership | None = None,
    pods: int | None = None,
) -> CommCost:
    """Bits a topology moves for ``n_iter`` refinement rounds.

    ``ref_broadcast=False`` drops the initial reference broadcast
    (psum/ring only), the ``ref=``-supplied case of the collectives
    (e.g. the eigen-compressed optimizer aligning to last period's basis).
    The gather topology never broadcasts: the reference is a row of the
    gathered stack.  Every message — broadcast, psum round, gathered
    contribution, ring hop — costs ``message_bits(d, r, comm_bits)`` on
    the wire (the int8 tier's f32[r] scale collectives included); the
    int8 psum rounds spend their 32·r overhead on the shared-scale
    max-all-reduce instead of a per-message scale, same total.

    ``membership`` prices the degraded-mesh program *as compiled* — the
    physical wire, what ``hlo_analysis.collective_bytes`` measures:

      * psum / gather are unchanged: the all-reduce / all-gather still
        runs over the full physical axis (dead shards contribute masked
        zeros / dropped rows), so per-device operand bytes do not move;
      * the ring genuinely shrinks — its permutation is built over the
        survivors only, so a round is n·(m'-1) hop messages — and adds
        one exact f32 d·r sync broadcast per estimation so dead shards
        leave holding the survivors' basis (the rejoin reference,
        ``repro.comm.ring``).

    This is deliberately distinct from *re-planning* at m', which prices
    the fresh m'-shard job (``plan_aggregation(m=m')``) the masked round
    is contractually equivalent to — see ``repro.runtime.elastic``.

    ``topology="hier"`` additionally needs ``pods=p`` (m = p * local, the
    2-D mesh's pod-major flattening).  Its bill is two-level and lands in
    ``CommCost.levels``:

      * **intra** (fast link, always exact f32): one d·r broadcast stage
        of the reference plus one masked d·r psum per round, over the
        ``local`` axis — skipped entirely when local == 1;
      * **inter** (slow link, at ``comm_bits``): one wire-precision
        broadcast stage of the reference, then n·(p'-1) ring-hop
        messages over the ``pod`` axis (p' = active pods), plus — only
        when a whole pod is dead — one exact f32 d·r resync broadcast
        down from the first surviving pod, the "broadcast back down"
        that re-replicates the answer mesh-wide.  A dead shard inside a
        live pod costs nothing extra: the intra-pod all-reduce already
        hands every local slot the pod sum.
    """
    t = resolve_topology(topology)
    bits_per = resolve_comm_bits(comm_bits)
    mem = resolve_membership(membership, m)
    n = max(n_iter, 1)
    basis = d * r
    msg = message_bits(d, r, bits_per)
    bcast_w = basis if ref_broadcast else 0
    bcast_b = msg if ref_broadcast else 0
    if t == "hier":
        if pods is None:
            raise ValueError("topology='hier' needs pods= (m = pods * local)")
        p = int(pods)
        if p < 1 or m % p:
            raise ValueError(
                f"pods={pods} does not tile m={m} into equal pods"
            )
        local = m // p
        pmem = pod_membership(mem, p)
        hops = pmem.m_active - 1 if p > 1 else 0
        # Intra level: exact f32, skipped when the local axis is trivial.
        intra_ar = (bcast_w + n * basis) * 32 if local > 1 else 0
        # Inter level: the ref-broadcast stage and the per-round hops at
        # wire precision, plus the degraded resync (exact f32, only when
        # a whole pod is dead — its devices saw no ring traffic).
        inter_bcast = bcast_b if p > 1 else 0
        hop_bits = n * hops * msg
        sync_w = 0 if (pmem.is_full or p == 1) else basis
        inter_ar = inter_bcast + sync_w * 32
        words = (
            (bcast_w if local > 1 else 0)
            + (bcast_w if p > 1 else 0)
            + n * ((basis if local > 1 else 0) + hops * basis)
            + sync_w
        )
        bits = intra_ar + inter_ar + hop_bits
        levels = {
            "intra": {"all-reduce": intra_ar},
            "inter": {"all-reduce": inter_ar, "collective-permute": hop_bits},
        }
        return CommCost(
            "hier", bits_per, words, bits,
            {"all-reduce": intra_ar + inter_ar, "collective-permute": hop_bits},
            levels=levels,
        )
    if t == "psum":
        words = bcast_w + n * basis
        bits = bcast_b + n * msg
        return CommCost("psum", bits_per, words, bits, {"all-reduce": bits})
    if t == "gather":
        # Every shard contributes its operand once; rounds are free.
        return CommCost(
            "gather", bits_per, m * basis, m * msg, {"all-gather": msg}
        )
    hops = mem.m_active - 1
    hop_bits = n * hops * msg
    # Degraded ring only: one exact f32 broadcast from the first survivor
    # after the rounds, so every physical shard (the dead ones included)
    # holds the survivors' answer — the basis a recovering shard aligns to.
    sync_w = 0 if mem.is_full else basis
    sync_b = sync_w * 32
    return CommCost(
        "ring", bits_per,
        bcast_w + n * hops * basis + sync_w,
        bcast_b + hop_bits + sync_b,
        {"all-reduce": bcast_b + sync_b, "collective-permute": hop_bits},
    )


def paper_coordinator_words(m: int, d: int, r: int) -> int:
    """The paper's hub-and-spoke presentation: m bases up, one back."""
    return m * d * r + d * r


def fan_projector_words(d: int) -> int:
    """Fan et al. 2019 baseline: one d x d spectral-projector all-reduce."""
    return d * d
