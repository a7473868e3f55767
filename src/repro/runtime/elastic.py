"""Elastic aggregation runtime: membership-masked rounds + re-plan on change.

The paper's estimator assumes all m shards answer every round; production
rounds race preemptions and stragglers.  This module turns the repo's
dead-reckoning fault machinery (``runtime.fault``, ``runtime.straggler``)
into *live* aggregation behavior:

  * a per-round ``Membership`` mask is derived from the injector's
    (shard, round) schedule — a dead shard is masked out of the
    collectives (``repro.core.distributed``), not crashed, which is how a
    preempted host looks to the survivors;
  * consecutive rounds under the *same* membership run as **one**
    collective call (one jitted shard_map), so the lossy tiers'
    error-feedback residual telescopes within the group and resets —
    zeros at call entry — exactly when membership changes.  The stale
    residual describes quantization debt owed to a mesh that no longer
    exists; carrying it across a change would smear a dead shard's
    last-round encoding error into the survivors' average;
  * every membership change, and every ``StragglerMonitor`` escalation,
    routes through the re-plan hook (``replan``): the cost model re-prices
    the knob cube at the survivor count m' (``plan_aggregation(m=m')`` —
    the fresh m'-shard job the masked round is contractually equivalent
    to, which also re-checks the int8-psum overflow headroom at m');
  * a recovered shard rejoins by Procrustes-aligning to the current
    basis: each group after the first passes the running estimate as
    ``ref``, the same machinery ``optim.eigen_compress`` trusts across
    basis refreshes, so a rejoining shard's stale local basis is rotated
    into the survivors' frame before it is trusted in the average.

The semantic contract (tested by ``tests/test_elastic.py``): a run with
shard k killed before round t equals the composed serial oracle — t full
rounds, then n-t rounds over the survivors' stack with the round-t basis
as reference — within ``PARITY_TOL[comm_bits]`` for every topology.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.comm.membership import Membership
from repro.plan.planner import Plan, plan_aggregation
from repro.runtime.straggler import StepTimer

__all__ = [
    "RoundEvent",
    "ElasticReport",
    "replan",
    "transition_reason",
    "elastic_pca",
]


def replan(
    membership: Membership,
    *,
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
    calibration=None,
    pods: Optional[int] = None,
) -> Plan:
    """The degradation re-plan hook: price the cube at the survivor count.

    This is ``plan_aggregation`` verbatim with ``m = membership.m_active``
    — the fresh m'-shard job the masked round computes.  Knob arguments
    are pins exactly as in ``plan_aggregation`` (an infeasible pin, e.g.
    int8 psum past the m' headroom bound, is annotated or dropped by the
    planner's usual rules; ``pods`` keeps a hier pin priceable).  The
    elastic runner's membership-change path, its straggler-escalation
    path, and the streaming service's elastic refresh
    (``repro.stream.service``) all call this.

    With ``pods`` pinned the price point is the *physical* m: the
    hierarchical schedule keeps running on the full (pods x local) mesh
    with the dead shard masked inside its pod — the survivor count does
    not re-tile the mesh, so pricing at m' would reject perfectly valid
    degraded states (m'=7 on a 4x2 mesh).
    """
    return plan_aggregation(
        m=membership.m if pods else membership.m_active, d=d, r=r,
        n_iter=n_iter,
        device_kind=device_kind, backend=backend, topology=topology,
        polar=polar, orth=orth, ring_chunk=ring_chunk, comm_bits=comm_bits,
        ref_broadcast=ref_broadcast, calibration=calibration, pods=pods,
    )


def transition_reason(
    prev: Optional[Membership], new: Membership
) -> Optional[str]:
    """Classify a membership edge: "failure" | "recovery" | None (no change).

    A transition with *any* newly dead shard is a "failure" (even if other
    shards recovered in the same step — the failure is what invalidates
    the error-feedback residual and the headroom bound); a pure rejoin is
    a "recovery".  ``prev=None`` (no prior membership) is not an edge.
    """
    if prev is None or new == prev:
        return None
    newly_dead = set(new.dead) - set(prev.dead)
    return "failure" if newly_dead else "recovery"


@dataclasses.dataclass(frozen=True)
class RoundEvent:
    """One (re-)planning decision: which rounds it covers and why."""

    round_index: int          # first round the decision applies to
    rounds: int               # length of the first group run under it
    reason: str               # "initial" | "failure" | "recovery" | "straggler"
    membership: Membership
    plan: Plan


@dataclasses.dataclass
class ElasticReport:
    """What an elastic run did: the estimate plus its decision log."""

    basis: jax.Array                  # (d, r) final estimate, replicated
    events: List[RoundEvent]
    rounds: int                       # total refinement rounds run
    replans: int                      # re-plan hook invocations (events - 1 at most)
    final_membership: Membership


def elastic_pca(
    samples: jax.Array,
    mesh: jax.sharding.Mesh,
    r: int,
    *,
    data_axis: str = "data",
    n_iter: int = 1,
    solver: str = "eigh",
    iters: int = 30,
    injector: Optional[Any] = None,
    monitor: Optional[Any] = None,
    timer: Optional[Any] = None,
    max_group: Optional[int] = None,
    backend: Optional[str] = None,
    polar: Optional[str] = None,
    orth: Optional[str] = None,
    topology: Optional[str] = None,
    ring_chunk: Optional[int] = None,
    comm_bits=None,
    plan=None,
    device_kind: Optional[str] = None,
    calibration=None,
) -> ElasticReport:
    """``distributed_pca`` that survives shard deaths, rejoins, stragglers.

    The local bases are computed once (each shard keeps its data and its
    local top-r solution for the whole run); the refinement rounds are
    then scheduled in *groups* of consecutive rounds sharing one
    membership, each group one jitted shard_map collective:

      * ``injector`` (``runtime.fault.FailureInjector``) supplies the
        (shard, round) kill/recover schedule via ``membership_at``;
        ``None`` means all m shards stay up;
      * ``monitor`` (``runtime.straggler.StragglerMonitor``) is fed each
        group's wall time from ``timer`` (a ``StepTimer``-shaped object,
        injectable for tests); an escalation marks a pending re-plan that
        is honoured at the next group boundary, with the user's own
        ``on_escalate`` callback still invoked;
      * ``max_group`` caps the rounds fused into one call (default: no
        cap) so monitor feedback gets a word in edgeways on long runs;
      * knob arguments and ``plan=`` resolve the *initial* plan exactly
        as ``distributed_pca`` would (including a degraded round-0
        membership); every later membership change or escalation calls
        the ``replan`` hook with the same knobs as pins, priced at the
        remaining rounds.

    The first group runs with the paper's default reference (first
    survivor's basis, one broadcast); every later group passes the
    running estimate as ``ref`` — so there is exactly one broadcast per
    run and a recovered shard re-enters by Procrustes-aligning to the
    current basis.  Error-feedback state (comm_bits < 32) lives and dies
    with each group's call: telescoping within a group, a clean zero
    residual whenever membership changes.
    """
    from repro.core.distributed import _local_pca_basis
    from repro.plan.planner import resolve_plan

    m = mesh.shape[data_axis]
    d = samples.shape[-1]
    n_iter = max(n_iter, 1)
    timer = timer or StepTimer()
    if isinstance(plan, Plan):
        pins = dict(
            backend=plan.backend, topology=plan.topology, polar=plan.polar,
            orth=plan.orth, ring_chunk=plan.ring_chunk,
            comm_bits=plan.comm_bits,
        )
    else:
        pins = dict(
            backend=backend, topology=topology, polar=polar, orth=orth,
            ring_chunk=ring_chunk, comm_bits=comm_bits,
        )

    def membership_at(t: int) -> Membership:
        if injector is None:
            return Membership.full(m)
        return injector.membership_at(t, m)

    pending = {"replan": False}
    if monitor is not None:
        user_cb = monitor.on_escalate

        def _escalate(step: int, dt: float):
            pending["replan"] = True
            if user_cb is not None:
                user_cb(step, dt)

        monitor.on_escalate = _escalate

    mem0 = membership_at(0)
    pl = resolve_plan(
        plan, m=m, d=d, r=r, n_iter=n_iter, ref_broadcast=True,
        device_kind=device_kind, calibration=calibration,
        membership=mem0, **pins,
    )

    # Local stage, once: each shard's covariance + top-r basis, stacked
    # sharded along the axis.  The planned backend routes it, like the
    # driver in ``core.distributed``.
    local_fn = jax.jit(
        jax.shard_map(
            lambda x: _local_pca_basis(
                x, r, solver=solver, iters=iters, backend=pl.backend
            )[None],
            mesh=mesh,
            in_specs=P(data_axis, *(None,) * (samples.ndim - 1)),
            out_specs=P(data_axis, None, None),
            check_vma=False,
        )
    )
    v_stack = local_fn(samples)  # (m, d, r)

    def run_group(ref, mem: Membership, g: int, group_plan: Plan):
        from repro.core.distributed import procrustes_average_collective

        if ref is None:
            def fn(v_blk):
                out = procrustes_average_collective(
                    v_blk[0], axis_name=data_axis, n_iter=g,
                    plan=group_plan, membership=mem,
                )
                return out[None]

            wrapped = jax.shard_map(
                fn, mesh=mesh, in_specs=P(data_axis, None, None),
                out_specs=P(data_axis, None, None), check_vma=False,
            )
            return jax.jit(wrapped)(v_stack)

        def fn(v_blk, ref_arr):
            out = procrustes_average_collective(
                v_blk[0], axis_name=data_axis, n_iter=g, ref=ref_arr,
                plan=group_plan, membership=mem,
            )
            return out[None]

        wrapped = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(data_axis, None, None), P(None, None)),
            out_specs=P(data_axis, None, None), check_vma=False,
        )
        return jax.jit(wrapped)(v_stack, ref)

    events: List[RoundEvent] = []
    replans = 0
    ref = None
    cur_mem: Optional[Membership] = None
    t = 0
    while t < n_iter:
        mem = membership_at(t)
        remaining = n_iter - t
        if cur_mem is None:
            reason = "initial"
        elif transition_reason(cur_mem, mem) is not None:
            reason = transition_reason(cur_mem, mem)
        elif pending["replan"]:
            reason = "straggler"
        else:
            reason = None
        if reason is not None and reason != "initial":
            pl = replan(
                mem, d=d, r=r, n_iter=remaining, ref_broadcast=False,
                device_kind=device_kind, calibration=calibration, **pins,
            )
            replans += 1
        pending["replan"] = False
        cur_mem = mem
        # Group extent: same membership, capped so the monitor is heard.
        cap = remaining if max_group is None else min(max_group, remaining)
        g = 1
        while g < cap and membership_at(t + g) == mem:
            g += 1
        if reason is not None:
            events.append(RoundEvent(
                round_index=t, rounds=g, reason=reason,
                membership=mem, plan=pl,
            ))
        stacked = run_group(ref, mem, g, pl)
        # Every topology leaves the answer mesh-replicated (the masked
        # ring syncs it explicitly), so any row works; the first
        # survivor's is the canonical one.
        ref = stacked[mem.first_active]
        t += g
        if monitor is not None:
            monitor.record(t, timer.lap())

    return ElasticReport(
        basis=ref, events=events, rounds=n_iter, replans=replans,
        final_membership=cur_mem,
    )
