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
from repro.core.subspace import local_eigenbasis

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


def _local_pca_basis(
    x_shard: jax.Array,
    r: int,
    *,
    solver: str,
    iters: int,
    backend: str,
) -> jax.Array:
    cov = empirical_covariance(x_shard, backend=backend)
    v, _ = local_eigenbasis(cov, r, method=solver, iters=iters)
    return v


def _hier_requested(topology, plan) -> bool:
    """True when the caller asked for the hierarchical schedule (an
    explicit ``topology="hier"`` pin or a resolved hier ``Plan``) — the
    driver then aggregates over *both* mesh axes of the (pod, local)
    pair instead of ``data_axis`` alone."""
    from repro.plan.planner import Plan

    return topology == "hier" or (
        isinstance(plan, Plan) and plan.topology == "hier"
    )


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
) -> jax.Array:
    """End-to-end one-shot distributed PCA on a mesh.

    ``samples`` (N, d) are sharded along the leading axis over ``data_axis``;
    each shard forms its local covariance, local top-r basis, and the mesh
    runs the Procrustes-fixed average.  ``backend`` selects the compute
    path — ``"pallas"`` kernels both the shard-local covariance stage and
    the aggregation (see module docstring) — ``polar`` the rotation
    method, ``orth`` the per-round orthonormalization, ``topology``
    the communication schedule the aggregation runs over, and
    ``comm_bits`` the wire precision of its payloads.
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
    """
    from repro.plan.planner import resolve_plan

    hier = _hier_requested(topology, plan)
    axes, m, pods = _agg_axes(mesh, data_axis, hier)
    mem = resolve_membership(membership, m)
    pl = resolve_plan(
        plan, m=mem.m, d=samples.shape[-1], r=r,
        n_iter=n_iter, backend=backend, topology=topology,
        polar=polar, orth=orth, comm_bits=comm_bits, membership=mem,
        pods=pods,
    )

    def shard_fn(x_shard: jax.Array) -> jax.Array:
        v = _local_pca_basis(
            x_shard, r, solver=solver, iters=iters, backend=pl.backend
        )
        out = procrustes_average_collective(
            v, axis_name=data_axis, n_iter=n_iter, plan=pl, membership=mem,
            pod_axis=POD_AXIS if hier else None,
        )
        return out[None]  # keep a sharded leading axis; identical on every shard

    spec_in = P(axes, *(None,) * (samples.ndim - 1))
    fn = jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh, in_specs=spec_in,
            out_specs=P(axes, None, None), check_vma=False
        )
    )
    stacked = fn(samples)
    return stacked[0]


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
    (resolved once at the driver level).

    ``ref`` optionally supplies the (d, r) alignment reference instead of
    the first active shard's basis — the streaming service passes its
    previously served basis here so consecutive refreshes never flip sign
    or rotation (``repro.stream.service``).  It enters the shard program
    as a replicated argument, not a closure capture, so one traced
    program serves every refresh, and the plan is priced with
    ``ref_broadcast=False`` (no reference broadcast round on the wire).
    """
    from repro.plan.planner import resolve_plan

    hier = _hier_requested(topology, plan)
    axes, m, pods = _agg_axes(mesh, data_axis, hier)
    mem = resolve_membership(membership, m)
    pl = resolve_plan(
        plan, m=mem.m, d=covs.shape[-1], r=r,
        n_iter=n_iter, backend=backend, topology=topology,
        polar=polar, orth=orth, comm_bits=comm_bits, membership=mem,
        pods=pods, ref_broadcast=(ref is None),
    )

    def shard_fn(cov_shard: jax.Array, ref_arg: jax.Array | None) -> jax.Array:
        # cov_shard: (m_local, d, d); m_local == 1 when m == mesh size.
        cov = jnp.mean(cov_shard, axis=0)
        v, _ = local_eigenbasis(cov, r, method=solver, iters=iters)
        out = procrustes_average_collective(
            v, axis_name=data_axis, n_iter=n_iter, ref=ref_arg, plan=pl,
            membership=mem, pod_axis=POD_AXIS if hier else None,
        )
        return out[None]

    if ref is None:
        fn = jax.jit(
            jax.shard_map(
                lambda c: shard_fn(c, None),
                mesh=mesh,
                in_specs=P(axes, None, None),
                out_specs=P(axes, None, None),
                check_vma=False,
            )
        )
        return fn(covs)[0]
    fn = jax.jit(
        jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(axes, None, None), P(None, None)),
            out_specs=P(axes, None, None),
            check_vma=False,
        )
    )
    return fn(covs, ref)[0]
