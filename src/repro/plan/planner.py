"""Cost-model-driven execution planner for the aggregation hot path.

The repo's aggregation takes five orthogonal switches — ``backend``
("xla" | "pallas"), ``topology`` ("psum" | "gather" | "ring"),
``polar`` ("svd" | "newton-schulz"), ``orth`` ("qr" | "cholesky-qr2"),
``comm_bits`` (32 | 16 | 8 wire precision) — plus the ring's
``ring_chunk``.  Until this module they were independent knobs resolved
by ad-hoc rules (``resolve_backend``'s on-TPU test,
``resolve_topology``'s historical pairing) or picked blind.  The
planner makes the choice one documented, machine-checkable decision:
given (m, d, r, n_iter, device kind) it scores **every valid cell** of
the cube with

  * the analytic bits-per-round communication model
    (``repro.comm.comm_cost`` — the §2.2 table, verified byte-for-byte
    against compiled HLO by CI), and
  * a compute/bandwidth/latency roofline priced by the per-device-kind
    constants of ``repro.plan.roofline`` (optionally refined from a
    recorded ``BENCH_aggregate.json`` via ``repro.plan.calibration``),

then picks the cheapest feasible cell and the ring's chunk size by the
d·r-vs-per-hop-latency rule (``choose_ring_chunk``).  DESIGN.md
§"Planner" documents the scoring formula; ``tests/test_plan.py`` pins
golden decisions, monotonicity, and the legacy-parity guarantees.

Entry points: every aggregation function takes ``plan=``:

  * ``plan=None``    — the legacy path, byte-identical to before: the
                       per-knob arguments resolve through
                       ``resolve_backend`` / ``resolve_topology``
                       exactly as they always did (``resolve_plan``
                       funnels that resolution through here, so there
                       is one decision layer either way).
  * ``plan="auto"``  — the planner decides every knob the caller left
                       free; a concrete per-knob argument (e.g.
                       ``backend="pallas"``) is honoured as a *pin* and
                       only the remaining axes are scored.  Exception:
                       ``comm_bits`` defaults to a **pin at 32** — wire
                       precision changes the numbers on the wire, so the
                       planner only trades it when the caller passes
                       ``comm_bits="auto"`` explicitly.
  * ``plan=Plan(...)`` — a fully resolved plan (e.g. from
                       ``plan_aggregation`` or a previous ``--explain``
                       run) used verbatim.

The scored table is printable via ``explain()`` (the ``--explain`` flag
of ``repro.launch.eigen`` and ``repro.launch.dryrun --paper-pca``); the
chosen cell's ``words`` is ``comm_cost(...).words`` by construction, so
the printed prediction can never drift from the verified model.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple, Union

from repro.comm.membership import Membership, resolve_membership
from repro.comm.quantize import COMM_BITS, COMM_BITS_CHOICES, resolve_comm_bits
from repro.comm.ring import DEFAULT_RING_CHUNK, chunk_spans
from repro.comm.topology import TOPOLOGIES, TOPOLOGY_CHOICES, comm_cost
from repro.core.orthonorm import ORTH_METHODS
from repro.core.procrustes import DEFAULT_NS_ITERS, POLAR_METHODS
from repro.plan.calibration import Calibration
from repro.plan.roofline import DeviceModel, device_model
from repro.kernels.ops import BACKENDS as BACKEND_CHOICES  # includes "auto"
from repro.kernels.procrustes_align import fused_ring_vmem_bytes

__all__ = [
    "Plan",
    "CellScore",
    "BACKENDS_CONCRETE",
    "BACKEND_CHOICES",
    "TOPOLOGY_CHOICES",
    "POLAR_CHOICES",
    "ORTH_CHOICES",
    "COMM_BITS",
    "COMM_BITS_CHOICES",
    "PLAN_CHOICES",
    "MIN_RING_CHUNK",
    "choose_ring_chunk",
    "stacked_round_flops",
    "score_cells",
    "plan_aggregation",
    "resolve_plan",
    "explain",
    "format_plan_table",
]

# The valid-values registry, one home per axis: the base vocabularies
# live next to their implementations (``repro.kernels.ops.BACKENDS``,
# ``repro.comm.topology.TOPOLOGY_CHOICES``, ``repro.core.procrustes
# .POLAR_METHODS``, ``repro.core.orthonorm.ORTH_METHODS``) and the
# planner re-exports them, so CLI ``choices=``, error messages, and the
# planner's own cell enumeration can never drift apart.
BACKENDS_CONCRETE = tuple(b for b in BACKEND_CHOICES if b != "auto")
POLAR_CHOICES = POLAR_METHODS + ("auto",)
ORTH_CHOICES = ORTH_METHODS + ("auto",)
PLAN_CHOICES = ("none", "auto")  # CLI spelling; "none" -> plan=None

# Operation-count constants of the scoring model (see DESIGN.md
# §"Planner").  SVD flop coefficient is the usual dense-SVD ~26·r³;
# CholeskyQR2 lowers to ~10 XLA ops (two passes of gram / trace / chol /
# solve / guard-select).
_SVD_FLOP_COEFF = 26.0
_CHOLQR2_XLA_OPS = 10
_BASE_STAGE_OPS = 3  # gram, average, apply — the plain-jnp round stages

MIN_RING_CHUNK = 256


def choose_ring_chunk(
    d: int, r: int, device: Optional[DeviceModel] = None,
    *, bw: Optional[float] = None,
) -> int:
    """The d·r-vs-per-hop-latency rule for the ring's chunk size.

    A chunk of ``c`` rows puts ``c·r`` f32 words on the wire per
    transfer; below the link's latency-bandwidth product the hop is
    latency-bound and further chunking only adds hops.  So the chunk is
    the smallest row count whose payload covers that product —
    ``ceil(coll_latency · bw / (4 r))`` rows — floored at
    ``MIN_RING_CHUNK`` (keep several chunks in flight for the pipeline
    to overlap at large d) and capped at ``d`` (a basis smaller than the
    product ships as one transfer per hop).

    ``bw`` overrides the link bandwidth the rule prices against; the
    default is the flat ring's ``device.net_bw``.  The hierarchical
    topology's inter-pod ring passes ``bw=device.dcn_bw`` — the slow
    link it actually rides — which grows the chunk on a slow fabric
    (fewer, fuller transfers per hop).
    """
    device = device or device_model("cpu")
    latency_rows = math.ceil(
        device.coll_latency_s * (bw or device.net_bw) / (4.0 * max(r, 1))
    )
    return max(1, min(d, max(latency_rows, MIN_RING_CHUNK)))


def _polar_flops(polar: str, r: int) -> float:
    if polar == "svd":
        return _SVD_FLOP_COEFF * r**3
    return 4.0 * r**3 * DEFAULT_NS_ITERS  # two r x r matmuls per NS step


def _orth_flops(orth: str, d: int, r: int) -> float:
    # Thin Householder QR ~ 4dr²; CholeskyQR2 = 2 passes of (gram 2dr² +
    # solve dr²) ~ 6dr² (r³ terms negligible at d >> r).
    return (4.0 if orth == "qr" else 6.0) * d * r * r


def stacked_round_flops(
    *, m: int, d: int, r: int, n_iter: int, polar: str, orth: str
) -> float:
    """Per-device flops of ``n_iter`` stacked refinement rounds — the
    planner's compute model for the gather/stacked form, shared with
    ``repro.plan.calibration`` so calibration prices the same work."""
    n = max(n_iter, 1)
    per_round = (
        4.0 * m * d * r * r          # Gram + apply over the stack
        + m * _polar_flops(polar, r)
        + _orth_flops(orth, d, r)
    )
    return n * per_round


@dataclasses.dataclass(frozen=True)
class CellScore:
    """One scored cell of the (backend x topology x polar x orth x
    comm_bits) cube."""

    backend: str
    topology: str
    polar: str
    orth: str
    comm_bits: int
    ring_chunk: int
    words: int            # logical collective payload (comm_cost.words)
    bits: int             # wire bits at comm_bits (comm_cost.bits)
    flops: float          # predicted per-device flops
    wire_bytes: float     # predicted per-device wire bytes
    hbm_bytes: float      # predicted per-device HBM bytes streamed
    comm_s: float
    compute_s: float
    memory_s: float
    latency_s: float
    total_s: float
    feasible: bool
    note: str = ""


@dataclasses.dataclass(frozen=True)
class Plan:
    """A fully resolved aggregation execution plan.

    Hashable (usable as a jit-static argument) and concrete: every knob
    has a registry value, ``ring_chunk`` is an int even when the ring is
    not chosen (it is what the ring *would* use).  The prediction fields
    are provenance, not behavior — two plans that differ only there run
    the same program, so they are excluded from equality/hashing
    (``compare=False``) and cannot cause a jit retrace.
    """

    backend: str
    topology: str
    polar: str
    orth: str
    ring_chunk: int
    comm_bits: int = 32  # wire precision: part of the program, so compared
    # Pod count of the 2-D (pod, local) mesh — nonzero iff topology is
    # "hier" (it changes the traced program, so it is compared).  Flat
    # plans keep 0 even when planned with ``pods=`` given, so a flat
    # winner on a multi-pod mesh hashes identically to the 1-D plan.
    pods: int = 0
    words: int = dataclasses.field(default=0, compare=False)
    bits: int = dataclasses.field(default=0, compare=False)
    flops: float = dataclasses.field(default=0.0, compare=False)
    total_s: float = dataclasses.field(default=0.0, compare=False)
    device_kind: str = dataclasses.field(default="", compare=False)
    source: str = dataclasses.field(default="pinned", compare=False)


def _validate_pin(value: Optional[str], name: str, choices: Sequence[str]):
    """A knob value is a pin iff concrete; None/"auto" mean free."""
    if value is None or value == "auto":
        return None
    if value not in choices:
        raise ValueError(
            f"{name} must be one of {tuple(choices) + ('auto',)}, got {value!r}"
        )
    return value


def score_cells(
    *,
    m: int,
    d: int,
    r: int,
    n_iter: int = 1,
    device: Optional[DeviceModel] = None,
    device_kind: Optional[str] = None,
    backend: Optional[str] = None,
    topology: Optional[str] = None,
    polar: Optional[str] = None,
    orth: Optional[str] = None,
    ring_chunk: Optional[int] = None,
    comm_bits=None,
    ref_broadcast: bool = True,
    context: str = "collective",
    calibration: Optional[Calibration] = None,
    pods: Optional[int] = None,
) -> List[CellScore]:
    """Score every cell of the cube compatible with the given pins.

    Enumeration order is the tie-break: backends in registry order (xla
    first), then topologies (psum first), polars, orths, comm_bits (32
    first) — so exact score ties resolve to the conservative cell
    deterministically.  ``comm_bits=None`` pins the exact wire (32); the
    precision axis is scored only on an explicit ``comm_bits="auto"``.
    ``context="stacked"`` scores the already-gathered form (topology
    fixed, zero communication, wire precision moot).  Returns cells
    sorted by (feasibility, predicted seconds, enumeration order).

    ``pods`` declares the physical mesh a 2-D (pods, m/pods) shape.  It
    unlocks the "hier" cells (absent from the enumeration otherwise —
    hier cannot run on a 1-D mesh) and re-prices every *flat* cell's
    wire at ``device.dcn_bw``: a flat collective over a multi-pod mesh
    crosses the slow fabric, that is the hier trade being scored.  With
    ``dcn_bw == ici_bw`` (every pre-split model) the flat re-pricing is
    byte-identical, so existing golden plans do not move.
    """
    if context not in ("collective", "stacked"):
        raise ValueError(f"context must be collective|stacked, got {context!r}")
    if device is None:
        device = device_model(device_kind or _default_device_kind())
    if calibration is not None and calibration.applies_to(device.kind):
        device = device.calibrated(
            dispatch_s=calibration.dispatch_s,
            flops_per_s=calibration.flops_per_s,
        )
    pin_b = _validate_pin(backend, "backend", BACKENDS_CONCRETE)
    pin_t = _validate_pin(topology, "topology", TOPOLOGIES)
    pin_p = _validate_pin(polar, "polar", POLAR_METHODS)
    pin_o = _validate_pin(orth, "orth", ORTH_METHODS)
    if pods is not None:
        pods = int(pods)
        if pods < 1 or (m >= 1 and m % pods):
            raise ValueError(
                f"pods={pods} does not tile m={m} into equal pods"
            )
    if pin_t == "hier" and (pods is None or context == "stacked"):
        raise ValueError(
            "topology='hier' needs pods= (a 2-D (pod, local) mesh) and "
            "the collective context"
        )
    backends = (pin_b,) if pin_b else BACKENDS_CONCRETE
    if pin_t:
        topos = (pin_t,)
    elif context == "stacked":
        topos = ("gather",)
    elif pods is not None:
        topos = TOPOLOGIES
    else:
        topos = tuple(t for t in TOPOLOGIES if t != "hier")
    polars = (pin_p,) if pin_p else POLAR_METHODS
    orths = (pin_o,) if pin_o else ORTH_METHODS
    if comm_bits == "auto" and context == "collective":
        cbs = COMM_BITS
    else:
        cbs = (resolve_comm_bits(None if comm_bits == "auto" else comm_bits),)

    scored: List[CellScore] = []
    for b in backends:
        for t in topos:
            for p in polars:
                for o in orths:
                    for cb in cbs:
                        scored.append(_score_one(
                            b, t, p, o, cb,
                            m=m, d=d, r=r, n_iter=n_iter, device=device,
                            ring_chunk=ring_chunk,
                            ref_broadcast=ref_broadcast,
                            context=context,
                            backend_pinned=pin_b is not None,
                            topology_pinned=pin_t is not None,
                            pods=pods,
                        ))
    # Stable sort: feasible first, then cheapest; enumeration order
    # breaks exact ties.
    scored.sort(key=lambda c: (not c.feasible, c.total_s))
    return scored


def _default_device_kind() -> str:
    """The chip's own ``device_kind`` on a TPU (``device_model`` knows
    which generations it can price); the backend name elsewhere."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return jax.devices()[0].device_kind
    return backend


def _score_one(
    b: str, t: str, p: str, o: str, cb: int,
    *,
    m: int, d: int, r: int, n_iter: int,
    device: DeviceModel,
    ring_chunk: Optional[int],
    ref_broadcast: bool,
    context: str,
    backend_pinned: bool,
    topology_pinned: bool,
    pods: Optional[int] = None,
) -> CellScore:
    n = max(n_iter, 1)
    basis = d * r
    hier = t == "hier"
    n_pods = int(pods) if (hier and pods) else 0
    n_local = m // n_pods if n_pods else 0
    # The hier cell's ring rides the DCN, so its chunk is sized against
    # that link's latency-bandwidth product; flat rings keep the ICI rule
    # (their execution path never sees the pod split).
    chunk = ring_chunk if ring_chunk else choose_ring_chunk(
        d, r, device, bw=device.dcn_bw if hier else None
    )
    nchunks = len(chunk_spans(d, chunk))
    on_tpu = device.kind == "tpu"
    # The fully fused one-launch round exists on the stacked form
    # (DESIGN.md §3.2): pallas + newton-schulz + cholesky-qr2 + gather.
    fused = b == "pallas" and p == "newton-schulz" and o == "cholesky-qr2" and t == "gather"
    # Its ring-scheduled sibling (§3.3) consumes the staged wire inside
    # the same launch — the hop loop is the kernel grid, the running V̄
    # stays VMEM-resident.
    fused_ring = (
        b == "pallas" and p == "newton-schulz" and o == "cholesky-qr2"
        and t == "ring" and context == "collective"
    )
    # Every other ring cell's hop compute is plain jnp regardless of
    # backend (no stacked operand for the streaming kernels —
    # repro.comm.ring docstring).
    ring = t == "ring" and context == "collective" and not fused_ring
    kernels_in_play = b == "pallas" and not ring

    feasible = True
    notes: List[str] = []
    if b == "pallas" and not on_tpu:
        if backend_pinned:
            notes.append("interpret-mode kernels (correctness path)")
        else:
            feasible = False
            notes.append("pallas compiles on TPU only")
    if fused_ring:
        # §3.3: the wire-width hop buffers plus the f32 running V̄ / ref /
        # out tiles live in VMEM for the whole launch; past the envelope
        # the one-launch schedule cannot be scheduled at all (the compiled
        # kernel raises on the same rule).
        vmem_bytes = fused_ring_vmem_bytes(d, r, cb)
        if vmem_bytes > device.vmem_cap_bytes:
            feasible = False
            notes.append(
                f"fused-ring working set {vmem_bytes/2**20:.1f}MiB over the "
                f"{device.vmem_cap_bytes/2**20:.0f}MiB VMEM envelope"
            )

    if t == "psum" and cb == 8 and m > 126 and context == "collective":
        # The shared-scale int8 psum sums s8 payloads on the wire; its
        # overflow headroom rule needs m <= 126 (repro.comm.quantize).
        feasible = False
        notes.append("int8 psum overflow headroom needs m <= 126")

    # ---- communication ---------------------------------------------------
    intra_bytes = inter_bytes = 0.0
    if context == "stacked":
        words, bits, wire_bytes, colls = 0, 0, 0.0, 0
    else:
        cost = comm_cost(
            t, m=m, d=d, r=r, n_iter=n, ref_broadcast=ref_broadcast,
            comm_bits=cb, pods=n_pods if hier else None,
        )
        words = cost.words
        bits = cost.bits
        wire_bytes = float(sum(cost.hlo_bytes.values()))
        bcast = 1 if ref_broadcast else 0
        if hier:
            # Two-level bill: each level priced against its own link
            # below.  Collective count: the intra psum schedule (bcast
            # stage + n rounds) when the local axis is real, plus the
            # inter ring (bcast stage + n·(p-1) hops) when pods > 1;
            # int8 only doubles the pod-level broadcast (hop scales
            # pipeline with the chunk permutes, intra is always f32).
            intra_bytes = float(sum(cost.level_bytes["intra"].values()))
            inter_bytes = float(sum(cost.level_bytes["inter"].values()))
            colls = ((bcast + n) if n_local > 1 else 0) + (
                (bcast + n * (n_pods - 1)) if n_pods > 1 else 0
            )
            if cb == 8 and n_pods > 1:
                colls += bcast
        else:
            colls = {
                "psum": bcast + n,
                "gather": 1,
                "ring": bcast + n * (m - 1),  # chunk permutes pipeline per hop
            }[t]
            if cb == 8:
                # The f32[r] scale rides as a second small collective per
                # message (psum's shared-scale pmax, gather's scale gather,
                # the broadcast's scale psum); ring hops pipeline theirs with
                # the chunk permutes, so only the broadcast doubles there.
                colls += {"psum": bcast + n, "gather": 1, "ring": bcast}[t]
        if fused_ring:
            # Hops are consumed inside the launch (the same (m-1)·d·r
            # wire volume, since an all-gather lowers to the ring's m-1
            # hops): one staged gather per round under error feedback,
            # or a single gather for all rounds at exact precision (the
            # payload is round-invariant); int8's scales gather rides
            # per message, as does the broadcast's scale psum.
            gathers = 1 if cb == 32 else n
            colls = bcast + gathers + ((bcast + gathers) if cb == 8 else 0)
    if m <= 1:
        # A 1-shard axis puts nothing on the wire; every schedule
        # degenerates to the serial rounds.
        words_wire, colls = 0.0, 0
        intra_bytes = inter_bytes = 0.0
    else:
        words_wire = wire_bytes
    if hier:
        intra_comm_s = intra_bytes / device.ici_bw
        inter_comm_s = inter_bytes / device.dcn_bw
        comm_s = intra_comm_s + inter_comm_s + colls * device.coll_latency_s
    else:
        # A flat collective on a declared multi-pod mesh crosses the slow
        # fabric end to end, so ``pods=`` re-prices its whole wire at the
        # DCN; without the split (dcn_bw == net_bw) this is the same
        # number, so pod-less scoring is byte-identical.
        intra_comm_s = inter_comm_s = 0.0
        wire_bw = device.dcn_bw if pods is not None else device.net_bw
        comm_s = words_wire / wire_bw + colls * device.coll_latency_s

    # ---- compute ---------------------------------------------------------
    # hier computes like psum: one aligned basis per device per round,
    # never a stacked operand.
    bases = 1 if ((t == "psum" or hier) and context == "collective") else m
    flops = n * (
        4.0 * bases * d * r * r
        + bases * _polar_flops(p, r)
        + _orth_flops(o, d, r)
    )
    compute_s = flops / device.peak_flops
    if kernels_in_play and not on_tpu:
        compute_s *= device.interpret_penalty

    # ---- memory ----------------------------------------------------------
    if fused_ring:
        # §3.3: the resident V̄ reclaims the fused round's 4x vs-stream —
        # each hop's wire payload streams from HBM exactly once, at wire
        # width, and only the ref read + out write touch HBM at f32.
        hbm_bytes = n * (bases * basis * (cb / 8.0) + 2 * basis * 4.0)
    else:
        stream_passes = 4 if fused else 2  # §3.2: fused streams vs 4x
        hbm_bytes = n * (stream_passes * bases + 2) * basis * 4.0
    memory_s = hbm_bytes / device.hbm_bw
    stack_bytes = m * basis * 4.0
    if t == "gather" and context == "collective" and stack_bytes > 0.25 * device.hbm_cap_bytes:
        if topology_pinned:
            notes.append(f"(m,d,r) stack {stack_bytes/2**30:.1f}GiB is memory-hostile")
        else:
            feasible = False
            notes.append(f"(m,d,r) stack {stack_bytes/2**30:.1f}GiB over memory budget")

    # ---- fixed latency (ops, launches, LAPACK calls) ---------------------
    polar_ops = 0 if p == "svd" else 2 * DEFAULT_NS_ITERS
    orth_ops = 0 if o == "qr" else _CHOLQR2_XLA_OPS
    polar_lapack = 1 if p == "svd" else 0
    orth_lapack = 1 if o == "qr" else 0
    if ring:
        # m-1 serial hops (chunked stages) plus the own-basis contribution.
        ops = n * (
            (m - 1) * (2 * nchunks + polar_ops)
            + (_BASE_STAGE_OPS + polar_ops)
            + orth_ops
        )
        launches = 0
        lapack = n * (m * polar_lapack + orth_lapack)
    elif b == "pallas":
        if fused or fused_ring:
            ops, launches, lapack = 0, n, 0
        else:
            launches = n * 2  # gram(+fused NS) kernel + apply kernel
            ops = n * orth_ops
            lapack = n * (polar_lapack + orth_lapack)
    else:
        ops = n * (_BASE_STAGE_OPS + polar_ops + orth_ops)
        launches = 0
        lapack = n * (polar_lapack + orth_lapack)
    if hier and n_pods > 1:
        # The inter-pod hop loop dispatches a permute + accumulate per
        # chunk per hop (no per-hop Procrustes — payloads are pre-aligned).
        ops += n * (n_pods - 1) * 2 * nchunks
    if cb != 32 and context == "collective":
        # Encode/decode overhead of the wire codec (cast for bf16; scale +
        # stochastic round + convert for int8).  Small by design, but it
        # makes 32 strictly cheapest when the wire saves nothing (m <= 1),
        # so "auto" never quantizes for free.
        ops += (1 if cb == 16 else 3) * (n + 1)
    latency_s = (
        ops * device.op_latency_s
        + launches * device.launch_latency_s
        + lapack * device.lapack_latency_s
    )

    # ---- total -----------------------------------------------------------
    if (ring or fused_ring) and m > 1:
        # The ring's selling point: the wire overlaps the Gram phase
        # (in-kernel, the hop DMA overlaps the previous hop's MXU work),
        # so comm and compute race instead of adding.
        total_s = max(comm_s, compute_s, memory_s) + latency_s
    elif hier and m > 1:
        # Only the slow-link ring overlaps compute (the hops have no
        # compute dependency until the round's mean); the intra-pod psum
        # gates the hops and the dispatches are serial, so both add.
        total_s = (
            max(inter_comm_s, compute_s, memory_s)
            + intra_comm_s + colls * device.coll_latency_s + latency_s
        )
    else:
        total_s = comm_s + max(compute_s, memory_s) + latency_s

    return CellScore(
        backend=b, topology=t, polar=p, orth=o, comm_bits=cb,
        ring_chunk=chunk,
        words=words, bits=bits, flops=flops,
        wire_bytes=wire_bytes, hbm_bytes=hbm_bytes,
        comm_s=comm_s, compute_s=compute_s, memory_s=memory_s,
        latency_s=latency_s, total_s=total_s,
        feasible=feasible, note="; ".join(notes),
    )


def plan_aggregation(
    *,
    m: int,
    d: int,
    r: int,
    n_iter: int = 1,
    device_kind: Optional[str] = None,
    backend: Optional[str] = None,
    topology: Optional[str] = None,
    polar: Optional[str] = None,
    orth: Optional[str] = None,
    ring_chunk: Optional[int] = None,
    comm_bits=None,
    ref_broadcast: bool = True,
    context: str = "collective",
    calibration: Optional[Calibration] = None,
    pods: Optional[int] = None,
) -> Plan:
    """Score the cube and return the cheapest feasible plan.

    Pins (concrete knob values) restrict the enumeration; ``None`` /
    ``"auto"`` axes are planned — except ``comm_bits``, where ``None``
    pins 32 and only ``"auto"`` frees the precision axis (wire precision
    changes the numbers, so quantizing is never implicit).  If the pins
    force every cell
    infeasible (e.g. ``backend="pallas"`` off-TPU), the cheapest pinned
    cell is returned with its note — pins are a user decision the
    planner annotates rather than overrides.

    Degenerate axis: on a 1-shard mesh every schedule is the same
    program (zero words on the wire), so rather than let float ties pick
    an arbitrary winner the planner keeps the legacy
    ``resolve_topology`` pairing — which is also the guarantee the
    parity suite pins (``plan="auto"`` reproduces today's picks on a
    1-device mesh).
    """
    pin_t = _validate_pin(topology, "topology", TOPOLOGIES)
    degenerate_axis = context == "collective" and m <= 1 and pin_t is None

    def _choose(topo_pin):
        cells = score_cells(
            m=m, d=d, r=r, n_iter=n_iter, device_kind=device_kind,
            backend=backend, topology=topo_pin, polar=polar, orth=orth,
            ring_chunk=ring_chunk, comm_bits=comm_bits,
            ref_broadcast=ref_broadcast,
            context=context, calibration=calibration, pods=pods,
        )
        return cells[0]  # sorted feasible-first, cheapest-first

    if degenerate_axis:
        dev = device_model(device_kind or _default_device_kind())
        b_guess = _validate_pin(backend, "backend", BACKENDS_CONCRETE) or (
            "pallas" if dev.kind == "tpu" else "xla"
        )
        best = _choose("gather" if b_guess == "pallas" else "psum")
        if best.backend != b_guess:
            # The scorer disagreed with the guessed backend (e.g. a
            # calibration made the kernels lose on their home device):
            # re-pin the topology from the backend that actually won, so
            # the returned pair is always a legacy pairing.
            best = _choose("gather" if best.backend == "pallas" else "psum")
    else:
        best = _choose(topology)
    return Plan(
        backend=best.backend, topology=best.topology, polar=best.polar,
        orth=best.orth, ring_chunk=best.ring_chunk,
        comm_bits=best.comm_bits,
        pods=(pods or 0) if best.topology == "hier" else 0,
        words=best.words, bits=best.bits,
        flops=best.flops, total_s=best.total_s,
        device_kind=device_kind or _default_device_kind(),
        source="planner",
    )


def resolve_plan(
    plan: Union[None, str, Plan],
    *,
    m: int,
    d: int,
    r: int,
    n_iter: int = 1,
    backend: Optional[str] = None,
    topology: Optional[str] = None,
    polar: Optional[str] = None,
    orth: Optional[str] = None,
    ring_chunk: Optional[int] = None,
    comm_bits=None,
    ref_broadcast: bool = True,
    context: str = "collective",
    device_kind: Optional[str] = None,
    calibration: Optional[Calibration] = None,
    membership: Optional[Membership] = None,
    pods: Optional[int] = None,
) -> Plan:
    """The single resolution funnel every aggregation entry point calls.

    ``plan=None`` reproduces the legacy per-knob resolution exactly
    (``resolve_backend`` + ``resolve_topology`` + the documented
    defaults), so existing callers see byte-identical behavior;
    ``plan="auto"`` runs the planner over the free axes with concrete
    knob values as pins; a ``Plan`` instance is used verbatim.

    ``membership`` (``repro.comm.Membership``) is the degraded-mesh view:
    *planning* paths (``plan="auto"`` and the legacy "auto"-knob
    sub-case) score the cube at the survivor count m' — the fresh
    m'-shard job the masked round is contractually equivalent to, which
    also re-checks the int8-psum overflow headroom at m' — while the
    legacy path's provenance fields price the *physical wire* via
    ``comm_cost(..., membership=)`` (what compiled HLO measures).

    ``pods`` declares the 2-D (pods, m/pods) mesh (see ``score_cells``).
    With pods given, planning paths score at the *physical* m — the pod
    tiling is a physical-mesh property, and survivor counts need not
    tile into pods — while membership still prices the legacy path's
    provenance wire.
    """
    from repro.comm.topology import resolve_topology
    from repro.kernels.ops import resolve_backend

    if isinstance(plan, Plan):
        return plan
    mem = resolve_membership(membership, m)
    m_eff = mem.m_active if pods is None else m
    if plan is None:
        # Legacy defaults: an unspecified backend is the documented
        # "xla" default; "auto" resolves by the on-TPU rule as always.
        b = resolve_backend(backend if backend is not None else "xla")
        t = (
            resolve_topology(topology or "auto", b)
            if context == "collective" else "gather"
        )
        p = polar or "svd"
        o = orth or "qr"
        if t == "hier" and (pods is None or pods < 1 or m % pods):
            raise ValueError(
                "topology='hier' needs pods= (m = pods * local); got "
                f"pods={pods!r} for m={m}"
            )
        if "auto" in (p, o) or comm_bits == "auto":
            # New-style "auto" polar/orth/comm_bits under the legacy
            # path: a single-knob plan with everything else pinned as
            # resolved — including the legacy ring chunk, so only the
            # free knob differs from a plain plan=None resolution.
            return plan_aggregation(
                m=m_eff, d=d, r=r, n_iter=n_iter, device_kind=device_kind,
                backend=b, topology=t if context == "collective" else None,
                polar=p, orth=o,
                ring_chunk=ring_chunk or DEFAULT_RING_CHUNK,
                comm_bits=comm_bits,
                ref_broadcast=ref_broadcast, context=context,
                calibration=calibration, pods=pods,
            )
        cb = resolve_comm_bits(comm_bits)
        if context == "collective":
            cost = comm_cost(t, m=m, d=d, r=r, n_iter=max(n_iter, 1),
                             ref_broadcast=ref_broadcast, comm_bits=cb,
                             membership=mem,
                             pods=pods if t == "hier" else None)
            cost_words, cost_bits = cost.words, cost.bits
        else:
            cost_words, cost_bits = 0, 0
        return Plan(
            backend=b, topology=t, polar=p, orth=o,
            ring_chunk=ring_chunk or DEFAULT_RING_CHUNK, comm_bits=cb,
            pods=(pods or 0) if t == "hier" else 0,
            words=cost_words, bits=cost_bits, device_kind=device_kind or "",
            source="legacy",
        )
    if plan == "auto":
        return plan_aggregation(
            m=m_eff, d=d, r=r, n_iter=n_iter, device_kind=device_kind,
            backend=backend, topology=topology, polar=polar, orth=orth,
            ring_chunk=ring_chunk, comm_bits=comm_bits,
            ref_broadcast=ref_broadcast,
            context=context, calibration=calibration, pods=pods,
        )
    raise ValueError(
        f"plan must be None, 'auto', or a Plan, got {plan!r}"
    )


# ---------------------------------------------------------------------------
# Explanation / table rendering (the CLIs' --explain).


def format_plan_table(cells: Sequence[CellScore], chosen: Plan) -> str:
    """Render a scored-cell table plus the chosen-cell summary line.

    The ``words`` / ``bits`` columns are ``comm_cost(...)`` verbatim for
    every cell, so the printed prediction matches the verified §2.2
    model by construction; the acceptance test re-derives the chosen
    cell's words and bits and compares byte for byte.
    """
    def is_chosen(c: CellScore) -> bool:
        return (
            c.backend == chosen.backend and c.topology == chosen.topology
            and c.polar == chosen.polar and c.orth == chosen.orth
            and c.comm_bits == chosen.comm_bits
        )

    hdr = (
        f"{'backend':<8} {'topology':<8} {'polar':<14} {'orth':<13} "
        f"{'cbits':>5} {'chunk':>6} {'words':>12} {'bits':>14} "
        f"{'flops':>10} {'comm_us':>9} "
        f"{'comp_us':>9} {'mem_us':>8} {'lat_us':>8} {'total_us':>9}  note"
    )
    lines = [hdr, "-" * len(hdr)]
    for c in cells:
        mark = "*" if is_chosen(c) else (" " if c.feasible else "x")
        lines.append(
            f"{c.backend:<8} {c.topology:<8} {c.polar:<14} {c.orth:<13} "
            f"{c.comm_bits:>5} {c.ring_chunk:>6} {c.words:>12} "
            f"{c.bits:>14} {c.flops:>10.3g} "
            f"{c.comm_s*1e6:>9.2f} {c.compute_s*1e6:>9.2f} "
            f"{c.memory_s*1e6:>8.2f} {c.latency_s*1e6:>8.2f} "
            f"{c.total_s*1e6:>9.2f}  {mark} {c.note}"
        )
    # The chosen line's numbers come from its scored cell, so a legacy /
    # pinned Plan (which carries no prediction of its own) still prints
    # honest figures; ``words`` stays comm_cost-exact by construction.
    chosen_cell = next((c for c in cells if is_chosen(c)), None)
    words = chosen_cell.words if chosen_cell else chosen.words
    bits = chosen_cell.bits if chosen_cell else chosen.bits
    flops = chosen_cell.flops if chosen_cell else chosen.flops
    total_s = chosen_cell.total_s if chosen_cell else chosen.total_s
    runner = next(
        (c for c in cells if c.feasible and not is_chosen(c)), None
    )
    why = ""
    if runner is not None and chosen_cell is not None:
        # The decisive term is where the cheaper of the two cells wins,
        # whichever side that is (a pinned/legacy chosen cell can be the
        # expensive one, with `runner` being the planner's actual pick).
        hi, lo = (
            (runner, chosen_cell)
            if runner.total_s >= chosen_cell.total_s
            else (chosen_cell, runner)
        )
        deltas = {
            "comm": hi.comm_s - lo.comm_s,
            "compute": hi.compute_s - lo.compute_s,
            "memory": hi.memory_s - lo.memory_s,
            "latency": hi.latency_s - lo.latency_s,
        }
        decisive = max(deltas, key=lambda k: deltas[k])
        label = (
            "runner-up"
            if chosen_cell.feasible and is_chosen(cells[0])
            else "planner pick"
        )
        why = (
            f"; {label} {runner.backend}/{runner.topology}/{runner.polar}/"
            f"{runner.orth} at {runner.total_s*1e6:.2f}us (decisive term: "
            f"{decisive})"
        )
    lines.append(
        f"chosen: {chosen.backend}/{chosen.topology}/{chosen.polar}/"
        f"{chosen.orth} ring_chunk={chosen.ring_chunk} "
        f"comm_bits={chosen.comm_bits} "
        f"words={words} bits={bits} flops={flops:.6g} "
        f"predicted_total_us={total_s*1e6:.2f}{why}"
    )
    return "\n".join(lines)


def explain(
    *,
    m: int,
    d: int,
    r: int,
    n_iter: int = 1,
    device_kind: Optional[str] = None,
    backend: Optional[str] = None,
    topology: Optional[str] = None,
    polar: Optional[str] = None,
    orth: Optional[str] = None,
    ring_chunk: Optional[int] = None,
    comm_bits=None,
    ref_broadcast: bool = True,
    context: str = "collective",
    calibration: Optional[Calibration] = None,
    plan: Union[None, str, Plan] = "auto",
    pods: Optional[int] = None,
) -> Tuple[Plan, str]:
    """Score the cube and render the table; returns (plan, table_text).

    This is the single rendering behind both CLIs' ``--explain``.
    ``plan`` picks which cell the table marks chosen: the default
    ``"auto"`` is the planner's pick; pass a pre-resolved ``Plan`` (or
    ``None`` for the legacy resolution) to render the table around the
    cell that will actually run.
    """
    kwargs = dict(
        m=m, d=d, r=r, n_iter=n_iter, device_kind=device_kind,
        backend=backend, topology=topology, polar=polar, orth=orth,
        ring_chunk=ring_chunk, comm_bits=comm_bits,
        ref_broadcast=ref_broadcast,
        context=context, calibration=calibration, pods=pods,
    )
    cells = score_cells(**kwargs)
    chosen = resolve_plan(plan, **kwargs)
    header = (
        f"# plan[{chosen.source}]: m={m} d={d} r={r} n_iter={n_iter} "
        + (f"pods={pods} " if pods else "")
        + f"device={device_kind or _default_device_kind()}"
        + (f" calibration={calibration.source}" if calibration else "")
    )
    return chosen, header + "\n" + format_plan_table(cells, chosen)
