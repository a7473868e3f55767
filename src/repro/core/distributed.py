"""The paper's algorithm mapped onto a JAX device mesh (shard_map).

Communication topology is a first-class switch here, independent of the
compute backend (see DESIGN.md §2.1 and ``repro.comm``).  Every collective
entry point takes ``topology=`` ("psum" | "gather" | "ring" | "auto"):

  * ``"psum"``   — broadcast shard 0's basis as the reference, align
                   locally, one d·r all-reduce per round.
  * ``"gather"`` — the paper's coordinator form: one all-gather of the m
                   local bases per shard, then the stacked rounds run
                   replicated and communication-free
                   (``repro.core.eigenspace.refinement_rounds``).
  * ``"ring"``   — the overlapped schedule (``repro.comm.ring``): bases
                   circulate a chunked ppermute ring and every shard
                   consumes its neighbor's basis the hop it arrives, so
                   communication overlaps the Gram phase and the (m, d, r)
                   stack is never materialized.
  * ``"auto"``   — the historical backend pairing (gather under "pallas",
                   psum otherwise), so topology is opt-in.

Backend dispatch is orthogonal: ``backend=`` ("xla" | "pallas" | "auto")
selects the compute path — under "pallas" the shard-local covariance, the
gather topology's stacked rounds, and the psum topology's per-shard align
(``repro.kernels.ops.align_one``) all route through the Pallas kernels
(compiled on TPU, interpret mode elsewhere).  ``polar=`` ("svd" |
"newton-schulz") and ``orth=`` ("qr" | "cholesky-qr2") select the round's
r x r rotation method and final orthonormalization; the
(pallas, gather, newton-schulz, cholesky-qr2) cell runs each round as a
single fused kernel launch (DESIGN.md §3.2), and the
(pallas, ring, newton-schulz, cholesky-qr2) cell fuses the ring's hop
schedule into that launch too — one kernel = one round *including the
wire consumption* (``repro.comm.ring.fused_ring_rounds``, DESIGN.md
§3.3).  Every
(backend x topology x polar x orth) cell computes the same estimator — the
parity suites (``tests/test_topology.py``,
``tests/test_backend_invariance.py``) assert it.  A fifth orthogonal axis,
``comm_bits=`` (32 | 16 | 8 | "auto"), sets the wire precision the chosen
topology moves its payloads at (``repro.comm.quantize``): at 32 the
collectives are bit-identical to before; at 16/8 the psum and ring
schedules carry per-shard error feedback and parity holds to the
bit-keyed tolerances in ``repro.comm.PARITY_TOL``.

All collective functions here are written to be called *inside*
``shard_map`` with a named mesh axis; the ``distributed_pca`` driver wraps
them for end-to-end use.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import (
    DATA_AXIS,
    POD_AXIS,
    broadcast_from,
    fused_ring_rounds,
    get_codec,
    hier_rounds,
    resolve_topology,
    ring_rounds,
    wire_broadcast,
    wire_psum_mean,
)
from repro.comm.membership import Membership, resolve_membership
from repro.comm.quantize import from_wire, shard_key, to_wire
from repro.core import procrustes
from repro.core.covariance import empirical_covariance
from repro.core.eigenspace import refinement_rounds
from repro.core.orthonorm import orthonormalize, resolve_orth
from repro.core.precision import highest_precision
from repro.core.rowop import check_standardize, row_operator
from repro.core.subspace import (
    local_eigenbasis,
    subspace_finish,
    subspace_iteration,
    subspace_start,
    subspace_steps,
)

__all__ = [
    "broadcast_from",
    "procrustes_average_collective",
    "sign_average_collective",
    "distributed_pca",
    "distributed_pca_from_covs",
]

# Stochastic-rounding stream salts, one per collective site ("PSUM"/"GATR"):
# shards fold their axis index (and round counter) into these.
_PSUM_SALT = 0x5053554D
_GATHER_SALT = 0x47415452

# The ``job`` id of each ``distributed_pca`` call in this process, carried
# by the call's profiler spans.
_JOB_IDS = itertools.count()


def _align_local(
    v: jax.Array, ref: jax.Array, *, backend: str, polar: str
) -> jax.Array:
    """One shard's Procrustes align for the psum topology, backend-routed."""
    if backend == "pallas":
        from repro.kernels import ops as kops

        return kops.align_one(v, ref, polar=polar, use_kernel=True)
    return procrustes.align(v, ref, polar=polar)


@highest_precision
def procrustes_average_collective(
    v_local: jax.Array,
    *,
    axis_name: str,
    n_iter: int = 1,
    ref: jax.Array | None = None,
    backend: str | None = None,
    polar: str | None = None,
    orth: str | None = None,
    topology: str | None = None,
    ring_chunk: int | None = None,
    comm_bits=None,
    plan=None,
    membership: Membership | None = None,
    pod_axis: str | None = None,
) -> jax.Array:
    """Algorithm 1 (n_iter=1) / Algorithm 2 (n_iter>1) across a mesh axis.

    Args:
      v_local: (d, r) local leading eigenbasis on each shard.
      axis_name: mesh axis playing the role of "machines" (the *local*
        axis of the (pod, local) pair under ``topology="hier"``).
      n_iter: refinement rounds.  Each round costs one d·r psum under the
        psum topology, (m-1)·d·r ring-hop words under the ring topology,
        and is communication-free under gather (the stack is already
        there).
      ref: optional externally supplied reference (e.g. previous training
        step's basis, used by the eigen-compressed optimizer); defaults to
        shard 0's solution as in the paper.
      backend: compute path, "xla" | "pallas" | "auto" (kernels on TPU).
        Default "xla".
      polar: "svd" | "newton-schulz" polar factor (``repro.core.procrustes``).
        Default "svd".
      orth: "qr" | "cholesky-qr2" per-round orthonormalization
        (``repro.core.orthonorm``).  Default "qr".
      topology: communication schedule, "psum" | "gather" | "ring" |
        "auto" (see module docstring / ``repro.comm``).  Independent of
        ``backend``.  Default "auto" (the historical pairing).
      ring_chunk: rows per circulating chunk of the ring schedule (the
        comm/compute overlap granularity; need not divide d).  Default:
        the planner's d·r-vs-latency rule under ``plan="auto"``,
        ``repro.comm.DEFAULT_RING_CHUNK`` otherwise.
      comm_bits: wire precision of the collective payloads — 32 | 16 | 8 |
        "auto" (``repro.comm.quantize``).  Default 32 (exact wire, adds no
        ops); lossy tiers run with per-shard error feedback under psum and
        ring, and "auto" lets the planner trade precision against
        bandwidth.  Orthogonal to every other knob.
      plan: ``None`` — legacy per-knob resolution, byte-identical to
        before; ``"auto"`` — the ``repro.plan`` cost model scores the
        (backend x topology x polar x orth x comm_bits) cube for this
        (m, d, r) and decides every knob left free (concrete knob
        arguments are pins); a ``repro.plan.Plan`` — used verbatim.
      membership: jit-static active-shard mask (``repro.comm.Membership``;
        ``None`` = all alive, byte-identical to before).  Every topology
        honors it: psum masks dead contributions to exact zeros and
        divides by m', gather drops dead rows of the stack with static
        indexing before the rounds, the ring links the survivors only
        (m'-1 traced hops) and syncs its answer mesh-wide afterwards.
        The reference default becomes the *first survivor's* basis.  The
        contract: the masked round over the survivors is the round a
        fresh m'-shard job would run (see ``repro.comm.membership``).
        Planning paths (``plan="auto"`` / legacy provenance) price the
        collective at m'.  Under ``topology="hier"`` the mask is over
        the flattened pod-major axis and applies per level
        (``repro.comm.hier``).
      pod_axis: second mesh axis of the 2-D (pod, local) pair — required
        by (and only meaningful for) ``topology="hier"``, where the
        machine count is ``axis_size(pod_axis) * axis_size(axis_name)``.

    Returns the replicated (d, r) Procrustes-fixed average.
    """
    from repro.plan.planner import resolve_plan

    d, r = v_local.shape
    pods = jax.lax.axis_size(pod_axis) if pod_axis is not None else None
    if topology == "hier" and pod_axis is None:
        # The post-resolution coupling check below also covers this, but
        # resolve_plan would name the missing ``pods=`` first; the actual
        # fix for a collective caller is the missing mesh axis.
        raise ValueError(
            "topology='hier' and pod_axis= go together: the hierarchical "
            "schedule needs the 2-D (pod, local) mesh axes "
            "(got pod_axis=None)"
        )
    m_total = (pods or 1) * jax.lax.axis_size(axis_name)
    mem = resolve_membership(membership, m_total)
    pl = resolve_plan(
        plan, m=mem.m, d=d, r=r, n_iter=n_iter,
        backend=backend, topology=topology, polar=polar, orth=orth,
        ring_chunk=ring_chunk, comm_bits=comm_bits,
        ref_broadcast=(ref is None), membership=mem, pods=pods,
    )
    backend, topo, polar, orth = pl.backend, pl.topology, pl.polar, pl.orth
    procrustes.resolve_polar(polar)
    resolve_orth(orth)
    resolve_topology(topo, backend)
    if (topo == "hier") != (pod_axis is not None):
        raise ValueError(
            "topology='hier' and pod_axis= go together: the hierarchical "
            "schedule needs the 2-D (pod, local) mesh axes, and no flat "
            f"topology can span one (got topology={topo!r}, "
            f"pod_axis={pod_axis!r})"
        )
    codec = get_codec(pl.comm_bits)
    if topo == "hier":
        return hier_rounds(
            v_local, ref, pod_axis=pod_axis, local_axis=axis_name,
            n_iter=n_iter, backend=backend, polar=polar, orth=orth,
            chunk=pl.ring_chunk, comm_bits=pl.comm_bits, membership=mem,
        )
    if topo == "gather":
        # Coordinator topology, replicated on every shard: gather the m
        # local bases once (at wire precision — each shard encodes its own
        # contribution, so the gathered payload is s8/bf16 plus the int8
        # tier's (m, r) scale gather), then run the backend-dispatched
        # stacked rounds (the loop lives in ``eigenspace.refinement_rounds``
        # and is communication-free, so there is no error-feedback state).
        if codec.lossy:
            key = (
                shard_key(axis_name, _GATHER_SALT)
                if codec.stochastic else None
            )
            data, scale = codec.encode(v_local.astype(jnp.float32), key=key)
            g = from_wire(
                jax.lax.all_gather(to_wire(data), axis_name), codec
            )  # (m, d, r) wire dtype
            if scale is None:
                vs = codec.decode(g)
            else:
                gs = jax.lax.all_gather(scale, axis_name)  # (m, r)
                vs = codec.decode(g, gs[:, None, :])
            # Decoding lands in f32; the stacked rounds must run at the
            # payload's dtype (a bf16 basis gathered at bf16 wire must
            # not silently upcast the whole estimation to f32 — the same
            # dtype-follows-payload rule the ring's chunk buffers obey).
            vs = vs.astype(v_local.dtype)
        else:
            vs = jax.lax.all_gather(v_local, axis_name)  # (m, d, r)
        if not mem.is_full:
            # Static survivor indexing: the all-gather still runs over the
            # full axis (dead rows cost the same wire either way), but the
            # stacked rounds see exactly the (m', d, r) stack a fresh
            # m'-shard job would gather — row 0 is the first survivor, so
            # the default reference follows the membership contract.
            vs = vs[jnp.asarray(mem.indices)]
        return refinement_rounds(
            vs, ref, n_iter=n_iter, backend=backend, polar=polar, orth=orth
        )
    if topo == "ring":
        if (
            backend == "pallas"
            and polar == "newton-schulz"
            and orth == "cholesky-qr2"
        ):
            # The ("pallas", "ring") execution cell: the hop schedule is
            # fused INTO one Pallas launch per round (DESIGN.md §3.3) —
            # per-hop payload chunks double-buffer through VMEM scratch
            # while the previous hop's Gram/polar/accumulate holds the
            # MXU, and the running V̄ stays chunk-resident so the round
            # streams each basis from HBM exactly once.  The cell pins
            # the matmul-only round methods (the kernel fuses them); any
            # other (polar, orth) pair keeps the jnp schedule below.
            return fused_ring_rounds(
                v_local, ref, axis_name=axis_name, n_iter=n_iter,
                chunk=pl.ring_chunk, comm_bits=pl.comm_bits, membership=mem,
            )
        return ring_rounds(
            v_local, ref, axis_name=axis_name, n_iter=n_iter,
            polar=polar, orth=orth, chunk=pl.ring_chunk,
            comm_bits=pl.comm_bits, membership=mem,
        )
    m = mem.m_active
    base_key = (
        shard_key(axis_name, _PSUM_SALT) if codec.stochastic else None
    )
    if ref is None:
        bkey = jax.random.fold_in(base_key, 0) if codec.stochastic else None
        # The lossy tiers decode to f32; keep the reference (and hence
        # every aligned product) at the payload's dtype.
        ref = wire_broadcast(
            v_local, axis_name, codec, src=mem.first_active, key=bkey
        ).astype(v_local.dtype)
    alive = None
    if not mem.is_full:
        # Traced per-shard gate folded from the static mask: dead shards
        # contribute exact zeros (which quantize to zero at every wire
        # tier, and add nothing to the int8 colmax pmax), so the
        # all-reduce still runs over the full axis while the mean and the
        # overflow headroom are taken over the m' survivors.
        alive = jnp.asarray(mem.active)[jax.lax.axis_index(axis_name)]
    err = jnp.zeros(v_local.shape, jnp.float32) if codec.lossy else None
    for k in range(max(n_iter, 1)):
        aligned = _align_local(v_local, ref, backend=backend, polar=polar)
        if codec.lossy:
            # Sum at wire precision with error feedback: what this round's
            # encoding drops rides into the next round's send, so the
            # decoded contributions telescope across rounds.
            rkey = (
                jax.random.fold_in(base_key, k + 1)
                if codec.stochastic else None
            )
            send = aligned.astype(jnp.float32) + err
            if alive is not None:
                send = jnp.where(alive, send, jnp.zeros_like(send))
            vbar, err = wire_psum_mean(send, axis_name, m, codec, key=rkey)
            vbar = vbar.astype(v_local.dtype)
        else:
            contrib = aligned.astype(v_local.dtype)
            if alive is not None:
                contrib = jnp.where(alive, contrib, jnp.zeros_like(contrib))
            vbar = jax.lax.psum(contrib, axis_name) / m
        ref = orthonormalize(vbar, orth=orth)
    return ref


def sign_average_collective(v_local: jax.Array, *, axis_name: str) -> jax.Array:
    """Rank-1 sign-fixing (Garber et al.) across a mesh axis."""
    m = jax.lax.axis_size(axis_name)
    ref = broadcast_from(v_local, axis_name, src=0)
    fixed = procrustes.sign_fix(v_local, ref)
    vbar = jax.lax.psum(fixed, axis_name) / m
    return vbar / jnp.linalg.norm(vbar)


def _device_bytes(device) -> int | None:
    """The device's memory in bytes, where its runtime reports it (a CPU
    reports none; a described, unattached chip raises)."""
    try:
        stats = device.memory_stats()
    except jax.errors.JaxRuntimeError:
        return None
    return int(stats["bytes_limit"]) if stats else None


def _local_form(d: int, device_bytes: int | None) -> str:
    """The local solve's form for rows of width d: ``"rows"``
    (``repro.core.rowop``, no (d, d) array) where the (d, d) float32
    covariance would not fit in ``device_bytes``, else ``"cov"`` (form
    XᵀX/n, then solve on it)."""
    if device_bytes is not None and 4 * d * d > device_bytes:
        return "rows"
    return "cov"


def _shard_form(samples, mesh) -> str:
    """``_local_form`` of ``samples``' rows on the mesh's devices."""
    return _local_form(samples.shape[-1], _device_bytes(mesh.devices.flat[0]))


def _check_local_solve(form: str, solver: str, standardize) -> None:
    check_standardize(standardize)
    if form == "rows" and solver != "subspace":
        raise ValueError(
            f"solver={solver!r} needs the (d, d) covariance, and these rows "
            "take the row form (a (d, d) float32 matrix would not fit on "
            "the device); use solver='subspace'"
        )


def _local_pca_basis(
    x_shard: jax.Array,
    r: int,
    *,
    solver: str,
    iters: int,
    backend: str,
    form: str = "cov",
    standardize: str | None = None,
) -> jax.Array:
    """One shard's leading (d, r) basis, in the ``form`` ``_local_form``
    chose: the row form iterates on Xᵀ(XQ)/n, the covariance form on
    XᵀX/n.  ``standardize`` maps the raw rows to X first (folded into the
    products in the row form)."""
    _check_local_solve(form, solver, standardize)
    if form == "rows":
        v, _ = subspace_iteration(row_operator(x_shard, standardize), r,
                                  iters=iters)
        return v
    if standardize is not None:
        x_shard = row_operator(x_shard, standardize).standardized()
    cov = empirical_covariance(x_shard, backend=backend)
    v, _ = local_eigenbasis(cov, r, method=solver, iters=iters)
    return v


def _agg_axes(mesh, data_axis: str, hier: bool):
    """(shard axes, machine count, pod count) of the aggregation.

    Flat topologies aggregate over ``data_axis`` only (a 'pod' axis, if
    present, stays a batch-parallel bystander exactly as before); the
    hierarchical topology spans (pod, local) and counts both.
    """
    if not hier:
        return (data_axis,), mesh.shape[data_axis], None
    if POD_AXIS not in mesh.axis_names:
        raise ValueError(
            f"topology='hier' needs a mesh with a {POD_AXIS!r} axis "
            f"(got axes {tuple(mesh.axis_names)}); build one with "
            "repro.launch.mesh.make_aggregation_mesh(pods=...)"
        )
    pods = mesh.shape[POD_AXIS]
    return (POD_AXIS, data_axis), pods * mesh.shape[data_axis], pods


def _resolve_job(mesh, data_axis: str, d: int, r: int, *, plan,
                 topology=None, membership=None, job=None, **knobs):
    """The prologue every entry point shares: the aggregation's mesh axes,
    its membership, and the plan resolved at that membership.

    The aggregation spans both axes of the (pod, local) pair (``hier``)
    when the caller asked for the hierarchical schedule: a
    ``topology="hier"`` pin or a resolved hier ``Plan``.  ``knobs`` go to
    ``resolve_plan`` as given.  With a ``job`` id the plan resolution is
    the profiler span ``repro.pca.plan``.  Returns (hier, pods,
    membership, plan).
    """
    from repro.plan.planner import Plan, resolve_plan

    hier = topology == "hier" or (
        isinstance(plan, Plan) and plan.topology == "hier")
    _, m, pods = _agg_axes(mesh, data_axis, hier)
    mem = resolve_membership(membership, m)
    span = (contextlib.nullcontext() if job is None else
            jax.profiler.TraceAnnotation("repro.pca.plan", job=job))
    with span:
        pl = resolve_plan(plan, m=mem.m, d=d, r=r, topology=topology,
                          membership=mem, pods=pods, **knobs)
    return hier, pods, mem, pl


@dataclasses.dataclass(frozen=True)
class _LocalStage:
    """The shard-local stage of a mesh program: one shard's (d, r) basis.

    ``kind="rows"``: raw rows through ``_local_pca_basis`` (``backend``,
    ``form`` and ``standardize`` as there); ``kind="covs"``: stacked
    (m_local, d, d) local matrices, averaged, then solved.

    The slices of a ``covs`` subspace solve cut into programs of their own
    (``SubspaceService.refresh``), each ``iters`` steps long:
    ``"covs_head"`` starts the solve as ``subspace_iteration`` does,
    ``"covs_steps"`` continues it from the running basis, and
    ``"covs_tail"`` continues it and finishes (Rayleigh--Ritz).  The head
    and the middle slices return the running, unfinished basis.
    """

    kind: str
    r: int
    solver: str
    iters: int
    backend: str | None = None
    form: str = "cov"
    standardize: str | None = None

    @property
    def takes_basis(self) -> bool:
        """Whether the stage continues a solve: it then takes each
        shard's running (d, r) basis as a second, sharded argument."""
        return self.kind in ("covs_steps", "covs_tail")

    def __call__(self, x_shard: jax.Array,
                 q: jax.Array | None = None) -> jax.Array:
        if self.kind == "rows":
            return _local_pca_basis(
                x_shard, self.r, solver=self.solver, iters=self.iters,
                backend=self.backend, form=self.form,
                standardize=self.standardize,
            )
        # x_shard: (m_local, d, d); m_local == 1 when m == mesh size.
        x = jnp.mean(x_shard, axis=0)
        if self.kind == "covs":
            v, _ = local_eigenbasis(x, self.r, method=self.solver,
                                    iters=self.iters)
            return v
        if self.kind == "covs_head":
            q = subspace_start(x, self.r)
        q = subspace_steps(x, q, self.iters)
        if self.kind == "covs_tail":
            q, _ = subspace_finish(x, q)
        return q


@functools.lru_cache(maxsize=32)
def _mesh_program(
    mesh: jax.sharding.Mesh,
    data_axis: str,
    hier: bool,
    local: _LocalStage | None,
    agg: tuple | None,
    ndim: int,
    with_ref: bool = False,
):
    """The jitted ``shard_map`` of a local stage and an aggregation over
    the mesh, built once per set of static inputs: the one place a mesh
    program of this package is built.

    ``local`` maps each shard's ndim-dimensional block to its basis
    (``None``: the blocks are (1, d, r) bases already); a stage that
    ``takes_basis`` also takes the running bases, (shards, d, r) sharded
    along the leading axis, as the second argument.  ``agg`` is
    (n_iter, plan, membership) for ``procrustes_average_collective``, or
    ``None`` to return the local bases; ``with_ref`` takes the
    aggregation's reference as a last, replicated argument.  The result
    is (shards, d, r), sharded along its leading axis.

    The arguments are everything the traced function closes over, so a
    repeated call reuses one ``jax.jit`` object and JAX dispatches it
    without tracing or lowering again.  Shapes, dtypes and shardings stay
    out of the key: ``jax.jit`` keys its executables on them itself.
    """
    axes, _, _ = _agg_axes(mesh, data_axis, hier)
    takes_basis = local is not None and local.takes_basis

    def shard_fn(x_shard: jax.Array, *ref: jax.Array) -> jax.Array:
        if local is None:
            v = x_shard[0]
        elif takes_basis:
            q, *ref = ref
            v = local(x_shard, q[0])
        else:
            v = local(x_shard)
        if agg is not None:
            n_iter, pl, mem = agg
            v = procrustes_average_collective(
                v, axis_name=data_axis, n_iter=n_iter,
                ref=ref[0] if with_ref else None, plan=pl, membership=mem,
                pod_axis=POD_AXIS if hier else None,
            )
        return v[None]  # keep a sharded leading axis

    spec_in = P(axes, *(None,) * (ndim - 1))
    in_specs = (
        (spec_in,)
        + ((P(axes, None, None),) if takes_basis else ())
        + ((P(None, None),) if with_ref else ())
    )
    return jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh, in_specs=in_specs,
            out_specs=P(axes, None, None), check_vma=False,
        )
    )


def distributed_pca(
    samples: jax.Array,
    mesh: jax.sharding.Mesh,
    r: int,
    *,
    data_axis: str = DATA_AXIS,
    n_iter: int = 1,
    solver: str = "eigh",
    iters: int = 30,
    backend: str | None = None,
    polar: str | None = None,
    orth: str | None = None,
    topology: str | None = None,
    comm_bits=None,
    plan=None,
    membership: Membership | None = None,
    standardize: str | None = None,
) -> jax.Array:
    """End-to-end one-shot distributed PCA on a mesh.

    ``samples`` (N, d) are sharded along the leading axis over ``data_axis``;
    each shard finds its local top-r basis, and the mesh runs the
    Procrustes-fixed average.

    The local solve takes one of two forms, chosen from the shapes alone
    (``_local_form``).  The covariance form forms XᵢᵀXᵢ/n and solves on it.
    The row form (``repro.core.rowop``) iterates on Xᵢᵀ(XᵢQ)/n and never
    holds a (d, d) array; rows whose (d, d) float32 covariance would not
    fit on the device take it (at UK Biobank's d=147,604 it is 87 GB).
    The row form runs ``solver="subspace"`` only: ``"eigh"`` raises there.

    ``standardize`` states how the raw rows map to the estimator's X:
    ``None`` (they are X) or ``"patterson"`` (int8 genotype counts
    standardized per shard by Patterson et al. 2006 eq. 3,
    ``repro.data.genotypes``; in the row form the map is folded into the
    products, so no floating (n, d) copy is made).

    ``backend`` selects the compute path — ``"pallas"`` kernels both the
    shard-local covariance stage and the aggregation (see module
    docstring) — ``polar`` the rotation method, ``orth`` the per-round
    orthonormalization, ``topology`` the communication schedule the
    aggregation runs over, and ``comm_bits`` the wire precision of its
    payloads.
    ``plan=None|"auto"|Plan`` resolves all five through the execution
    planner (``repro.plan``): the plan is resolved once here at the
    driver level — so a planned ``backend`` also routes the shard-local
    covariance stage — and passed to the collective verbatim.
    ``membership`` masks dead shards out of the aggregation (the
    collective output stays mesh-replicated, so the returned row is valid
    whichever shards died).

    ``topology="hier"`` (pinned, or via a hier ``Plan``) expects a 2-D
    ``(pod, data)`` mesh — ``repro.launch.mesh.make_aggregation_mesh`` —
    and shards the samples over both axes pod-major, so ``membership``
    then describes all pods*local machines in that order.  Returns the
    (d, r) estimate.

    The jitted mesh program is built once per set of static arguments
    (mesh, ``data_axis``, resolved plan and membership, ``r``, ``n_iter``,
    ``solver``, ``iters``, ``samples.ndim``, the local form and
    ``standardize``; ``_mesh_program``), so a repeated call dispatches it
    without tracing or lowering again.

    The call's host time is the profiler span ``repro.pca``; plan
    resolution (``repro.pca.plan``) and the program's enqueue
    (``repro.pca.dispatch``) nest in it, all three with the call's ``job``
    id.  ``repro.pca.dispatch`` also carries ``built``: 1 when this call
    built the program, whose first call traces, lowers and fetches the
    executable inside the span; 0 when it reused one; and ``form``, the
    local solve's form (``cov`` or ``rows``).
    """
    job = next(_JOB_IDS)
    with jax.profiler.TraceAnnotation("repro.pca", job=job):
        form = _shard_form(samples, mesh)
        hier, _, mem, pl = _resolve_job(
            mesh, data_axis, samples.shape[-1], r, plan=plan,
            topology=topology, membership=membership, job=job,
            n_iter=n_iter, backend=backend, polar=polar, orth=orth,
            comm_bits=comm_bits,
        )
        local = _LocalStage("rows", r, solver, iters, backend=pl.backend,
                            form=form, standardize=standardize)
        misses = _mesh_program.cache_info().misses
        fn = _mesh_program(mesh, data_axis, hier, local, (n_iter, pl, mem),
                           samples.ndim)
        built = int(_mesh_program.cache_info().misses > misses)
        with jax.profiler.TraceAnnotation(
            "repro.pca.dispatch", job=job, built=built, form=form
        ):
            return fn(samples)[0]


def distributed_pca_from_covs(
    covs: jax.Array,
    mesh: jax.sharding.Mesh,
    r: int,
    *,
    data_axis: str = DATA_AXIS,
    n_iter: int = 1,
    solver: str = "eigh",
    iters: int = 30,
    backend: str | None = None,
    polar: str | None = None,
    orth: str | None = None,
    topology: str | None = None,
    comm_bits=None,
    plan=None,
    membership: Membership | None = None,
    ref: jax.Array | None = None,
) -> jax.Array:
    """Same as ``distributed_pca`` but from pre-formed local matrices (m, d, d).

    This is the paper's abstract setting (each machine holds a noisy X̂ⁱ),
    useful when the local matrices are not covariances (e.g. quadratic
    sensing's D_N, HOPE proximity matrices).  ``plan`` / ``comm_bits`` /
    ``membership`` / ``topology="hier"`` as in ``distributed_pca``
    (resolved once at the driver level); its program is built once and
    reused the same way, one for calls with ``ref`` and one without.

    ``ref`` optionally supplies the (d, r) alignment reference instead of
    the first active shard's basis — the streaming service passes its
    previously served basis here so consecutive refreshes never flip sign
    or rotation (``repro.stream.service``).  It enters the shard program
    as a replicated argument, not a closure capture, so one traced
    program serves every refresh, and the plan is priced with
    ``ref_broadcast=False`` (no reference broadcast round on the wire).
    """
    hier, _, mem, pl = _resolve_job(
        mesh, data_axis, covs.shape[-1], r, plan=plan, topology=topology,
        membership=membership, n_iter=n_iter, backend=backend, polar=polar,
        orth=orth, comm_bits=comm_bits, ref_broadcast=(ref is None),
    )
    with_ref = ref is not None
    local = _LocalStage("covs", r, solver, iters)
    fn = _mesh_program(mesh, data_axis, hier, local, (n_iter, pl, mem),
                       covs.ndim, with_ref)
    return (fn(covs, ref) if with_ref else fn(covs))[0]
