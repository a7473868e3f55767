"""The streaming subspace service: refresh loop + collective-free queries.

``SubspaceService`` keeps the paper's estimator live over a row stream
(ROADMAP item 1).  Three moving parts:

  * **state** — one merge-able accumulator per shard
    (``repro.stream.accumulator``), held stacked ``(m, ...)`` and updated
    through a single vmapped donated jit per ``observe`` call, with dead
    shards mask-frozen so a preempted host's state neither grows nor
    poisons anything while it is out;
  * **refresh** — on a cadence (every ``cadence`` observed steps) or when
    the drift metric crosses ``drift_threshold``, the service runs the
    paper's aggregation over the accumulated per-shard covariances: local
    top-r eigenbasis, then ``procrustes_average_collective`` with the
    *previously served basis* as ``ref``.  That reference choice is the
    continuity contract: ``polar(A R) = polar(A) R`` makes the averaged
    subspace invariant to the reference rotation, so consecutive
    refreshes on stationary data agree element-wise (no sign or rotation
    flips) — the same machinery ``optim.eigen_compress`` trusts across
    basis refreshes, now load-bearing for a service whose clients hold
    projections from the previous basis.  The mesh program comes from
    ``core.distributed``'s shared builder, cached on (plan, membership,
    has-ref), and is reused every refresh — the reference enters as a
    replicated *argument*, never a closure capture;
  * **queries** — ``project(queries)`` is a plain replicated matmul
    against the served basis, double-buffered: a refresh writes the new
    basis into the back buffer and flips the front index only when the
    collective has returned, so a query never observes a half-written
    refresh.  The steady-state query program contains zero collectives
    (``tests/test_stream.py`` pins this on the jaxpr).

Drift metric: with C̄ the masked mean of the per-shard covariances and V
the served basis, ``drift = ||(I - V Vᵀ) C̄ V||_F / ||C̄ V||_F`` — the
relative mass of C̄'s action on V that leaks out of the served subspace.
Stationary data keeps it near the sampling-noise floor; a moved spectrum
pushes it up, which is the refresh trigger (and the positive control in
the tests).  It is a host-side jitted sketch — two (d, d)·(d, r)
products, no collectives — so checking it every step is cheap relative
to a refresh.

Elastic membership: ``set_membership`` classifies the edge via
``runtime.elastic.transition_reason``, re-prices the knob cube at the
survivor count via ``runtime.elastic.replan`` (``ref_broadcast=False`` —
the service always has a reference in steady state), logs a
``RoundEvent``, and on a *failure* refreshes immediately so the dead
shard's contribution leaves the served basis now rather than at the next
cadence tick.  A recovery waits for the cadence: the rejoiner's frozen
accumulator is valid, merely stale, and re-enters by Procrustes-aligning
to the served basis like any other shard.

Staleness/drift/refresh metrics live in ``stats``, with the count of
queries and of the blocking device reads the service made (refresh's two,
drift's one) and the host seconds spent in those reads.  Each host step
is a profiler span (``repro.service.observe``, ``.update``, ``.refresh``
with its ``.wait_count``, ``.dispatch`` and ``.wait_jump``, ``.project``,
``.drift``) carrying its ``step``, ``refresh`` or ``query`` id.  Design:
DESIGN.md §10.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.comm import DATA_AXIS
from repro.comm.membership import Membership, resolve_membership
from repro.core.distributed import _LocalStage, _mesh_program, _resolve_job
from repro.plan.planner import Plan, plan_pins
from repro.runtime.elastic import RoundEvent, replan, transition_reason
from repro.stream.accumulator import update as _acc_update

__all__ = ["SubspaceService", "basis_jump", "project"]


def basis_jump(u: jax.Array, v: jax.Array) -> jax.Array:
    """Element-wise Frobenius distance ||u - v||_F between served bases.

    Deliberately *not* a subspace distance: a sign or rotation flip
    between refreshes leaves the subspace fixed but registers here.
    This is the quantity the refresh-continuity contract bounds — clients
    holding projections from the previous basis care about the element-
    wise change, not the subspace change.
    """
    return jnp.linalg.norm(jnp.asarray(u) - jnp.asarray(v))


def project(queries: jax.Array, basis: jax.Array) -> jax.Array:
    """Batched projection (batch, d) @ (d, r) onto a served basis.

    The steady-state query path: a replicated matmul, no collectives —
    the service jits exactly this function.
    """
    return queries @ basis


_project_jit = jax.jit(project)


def _masked_update(state, batch, alive):
    """One shard's accumulator transition, frozen (identity) when dead."""
    new = _acc_update(state, batch)
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(alive, n, o), new, state
    )


# One program per (state dtype x chunk shape): all m shards' states
# advance in one donated launch, dead shards mask-frozen.
_update_all = jax.jit(jax.vmap(_masked_update), donate_argnums=0)


@jax.jit
def _safe_covs(state):
    """Per-shard covariances (m, d, d); an empty accumulator reads as zeros."""
    n = jnp.maximum(state["count"], 1)
    return state["gram"] / n[:, None, None]


@jax.jit
def _mean_cov(covs, counts, active):
    """Masked mean covariance over active shards that have seen rows."""
    w = (active & (counts > 0)).astype(covs.dtype)
    tot = jnp.maximum(jnp.sum(w), 1)
    return jnp.einsum("m,mij->ij", w, covs) / tot


@jax.jit
def _drift_metric(cov, v):
    """||(I - V Vᵀ) C V||_F / ||C V||_F — leakage of C's action on V."""
    cv = cov @ v
    resid = cv - v @ (v.T @ cv)
    den = jnp.maximum(jnp.linalg.norm(cv), jnp.finfo(cv.dtype).tiny)
    return jnp.linalg.norm(resid) / den


class SubspaceService:
    """Long-lived distributed eigenspace estimate over a row stream.

    >>> svc = SubspaceService(mesh, d=64, r=4, cadence=4)
    >>> for chunk in stream:            # chunk: (m, n_k, d) per-shard rows
    ...     svc.observe(chunk)          # accumulates; refreshes when due
    >>> svc.project(queries)            # (batch, r), zero collectives
    >>> svc.stats["staleness"], svc.stats["refreshes"]

    Knob arguments (``backend`` / ``topology`` / ``polar`` / ``orth`` /
    ``comm_bits`` / ``plan`` / ``membership``) mean exactly what they mean
    on ``distributed_pca``; the plan is resolved once per membership with
    ``ref_broadcast=False`` (steady state supplies the reference, so no
    broadcast round is priced).  ``topology="hier"`` expects the 2-D
    (pod, data) mesh, as in the one-shot driver.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        d: int,
        r: int,
        *,
        data_axis: str = DATA_AXIS,
        n_iter: int = 1,
        cadence: int = 8,
        drift_threshold: Optional[float] = None,
        solver: str = "eigh",
        iters: int = 30,
        backend: Optional[str] = None,
        polar: Optional[str] = None,
        orth: Optional[str] = None,
        topology: Optional[str] = None,
        ring_chunk: Optional[int] = None,
        comm_bits=None,
        plan=None,
        membership: Optional[Membership] = None,
        dtype=jnp.float32,
        device_kind: Optional[str] = None,
        calibration=None,
    ):
        if cadence < 1:
            raise ValueError(f"cadence must be >= 1 (got {cadence})")
        self.mesh, self.d, self.r = mesh, d, r
        self.data_axis = data_axis
        self.n_iter = max(n_iter, 1)
        self.cadence = cadence
        self.drift_threshold = drift_threshold
        self.solver, self.iters = solver, iters
        self._pins = plan_pins(
            plan, backend=backend, topology=topology, polar=polar, orth=orth,
            ring_chunk=ring_chunk, comm_bits=comm_bits,
        )
        self._device_kind = device_kind
        self._calibration = calibration
        self._hier, self._pods, self._mem, self._plan = _resolve_job(
            mesh, data_axis, d, r, plan=plan, membership=membership,
            n_iter=self.n_iter, ref_broadcast=False, device_kind=device_kind,
            calibration=calibration, **self._pins,
        )
        self.m = self._mem.m
        dt = jnp.dtype(dtype)
        self._state = {
            "count": jnp.zeros((self.m,), dt),
            "sum": jnp.zeros((self.m, d), dt),
            "gram": jnp.zeros((self.m, d, d), dt),
        }
        # Double buffer: queries read _buffers[_front] in one load; a
        # refresh writes the back buffer and flips _front afterwards.
        self._buffers: List[Optional[jax.Array]] = [None, None]
        self._front = 0
        self._step = 0
        self._last_refresh_step = 0
        self._refreshes = 0
        self._replans = 0
        self._events: List[RoundEvent] = []
        self._last_drift: Optional[float] = None
        self._last_jump: Optional[float] = None
        # ``project`` runs on callers' threads: its count takes the lock.
        self._query_lock = threading.Lock()
        self._queries = 0
        self._syncs = 0
        self._sync_wait_s = 0.0

    # -- ingest ------------------------------------------------------------

    def observe(self, batches) -> "SubspaceService":
        """Fold one step of per-shard rows in; refresh if due.

        ``batches``: (m, n_k, d) — row chunk per shard (a list of m
        (n_k, d) arrays is stacked).  Each distinct n_k compiles its own
        update program, so feed fixed-size chunks in steady state.  Dead
        shards' rows are ignored (their accumulators stay frozen).
        """
        step = self._step + 1
        with jax.profiler.TraceAnnotation("repro.service.observe", step=step):
            if isinstance(batches, (list, tuple)):
                batches = jnp.stack([jnp.asarray(b) for b in batches])
            batches = jnp.asarray(batches)
            if batches.ndim != 3 or batches.shape[0] != self.m \
                    or batches.shape[2] != self.d:
                raise ValueError(
                    f"expected (m={self.m}, n_k, d={self.d}) per-shard "
                    f"chunks, got {batches.shape}"
                )
            with jax.profiler.TraceAnnotation("repro.service.update",
                                              step=step):
                alive = jnp.asarray(self._mem.active)
                self._state = _update_all(self._state, batches, alive)
            self._step = step
            if self._refresh_due():
                self.refresh()
        return self

    def _refresh_due(self) -> bool:
        if self.basis is None:
            return True  # first basis: serve as soon as there is data
        if self._step - self._last_refresh_step >= self.cadence:
            return True
        if self.drift_threshold is not None:
            return self.drift() > self.drift_threshold
        return False

    # -- refresh -----------------------------------------------------------

    def refresh_fn(self, *, with_ref: bool = True):
        """The jitted mesh program one refresh runs (for dryrun/tests).

        ``with_ref=True`` is the steady-state program
        ``fn(covs, ref) -> (m, d, r)``; ``with_ref=False`` the bootstrap
        program ``fn(covs)`` that broadcasts the first survivor's basis.
        Both come from ``core.distributed``'s program builder, cached on
        (plan, membership, with_ref): every steady-state refresh reuses one
        compiled program, the reference riding in as a replicated argument.
        """
        local = _LocalStage("covs", self.r, self.solver, self.iters)
        return _mesh_program(
            self.mesh, self.data_axis, self._hier, local,
            (self.n_iter, self._plan, self._mem), 3, with_ref,
        )

    def _sync(self, read):
        """Run ``read``, a blocking device read, and count its wait."""
        t0 = time.perf_counter()
        try:
            return read()
        finally:
            self._syncs += 1
            self._sync_wait_s += time.perf_counter() - t0

    def refresh(self) -> jax.Array:
        """Run one aggregation round now and swap the served basis."""
        n = self._refreshes
        with jax.profiler.TraceAnnotation("repro.service.refresh",
                                          step=self._step, refresh=n):
            with jax.profiler.TraceAnnotation(
                    "repro.service.refresh.wait_count", refresh=n):
                rows = self._sync(
                    lambda: float(jnp.sum(self._state["count"])))
            if rows == 0:
                raise ValueError("refresh before any data: observe() first")
            prev = self._buffers[self._front]
            with jax.profiler.TraceAnnotation(
                    "repro.service.refresh.dispatch", refresh=n):
                covs = _safe_covs(self._state)
                if prev is None:
                    stacked = self.refresh_fn(with_ref=False)(covs)
                else:
                    stacked = self.refresh_fn(with_ref=True)(covs, prev)
                new = stacked[self._mem.first_active]
            if prev is not None:
                with jax.profiler.TraceAnnotation(
                        "repro.service.refresh.wait_jump", refresh=n):
                    self._last_jump = self._sync(
                        lambda: float(basis_jump(prev, new)))
            back = 1 - self._front
            self._buffers[back] = new
            self._front = back  # swap only after the collective returned
            self._refreshes += 1
            self._last_refresh_step = self._step
        return new

    # -- elastic membership ------------------------------------------------

    def set_membership(self, membership) -> "SubspaceService":
        """Adopt a new shard mask: replan at m', refresh now on failure.

        The edge is classified by ``runtime.elastic.transition_reason``
        and logged as a ``RoundEvent``.  A failure purges the dead
        shard's contribution from the served basis immediately; a
        recovery waits for the cadence (the rejoiner's frozen accumulator
        is valid, merely stale).
        """
        mem = resolve_membership(membership, self.m)
        reason = transition_reason(self._mem, mem)
        if reason is None:
            return self
        self._mem = mem
        self._plan = replan(
            mem, d=self.d, r=self.r, n_iter=self.n_iter,
            ref_broadcast=False, device_kind=self._device_kind,
            calibration=self._calibration, pods=self._pods, **self._pins,
        )
        self._replans += 1
        self._events.append(RoundEvent(
            round_index=self._step, rounds=self.n_iter, reason=reason,
            membership=mem, plan=self._plan,
        ))
        if reason == "failure" and self.basis is not None:
            self.refresh()
        return self

    # -- queries -----------------------------------------------------------

    def project(self, queries: jax.Array) -> jax.Array:
        """Project (batch, d) query rows onto the served basis -> (batch, r)."""
        with self._query_lock:
            query = self._queries
            self._queries += 1
        with jax.profiler.TraceAnnotation("repro.service.project",
                                          query=query):
            v = self._buffers[self._front]  # single front read: no torn swap
            if v is None:
                raise RuntimeError(
                    "no basis served yet: observe() some data (or refresh()) "
                    "first"
                )
            return _project_jit(queries, v)

    @property
    def query_fn(self):
        """The jitted steady-state query path ``(queries, basis) -> proj``.

        Exposed so tests/dryrun can assert its jaxpr holds zero
        collectives.
        """
        return _project_jit

    # -- metrics -----------------------------------------------------------

    def drift(self) -> float:
        """Current drift of the served basis against the accumulated C̄."""
        v = self._buffers[self._front]
        if v is None:
            raise RuntimeError("no basis served yet; drift is undefined")
        with jax.profiler.TraceAnnotation("repro.service.drift",
                                          step=self._step):
            covs = _safe_covs(self._state)
            cbar = _mean_cov(
                covs, self._state["count"], jnp.asarray(self._mem.active)
            )
            self._last_drift = self._sync(
                lambda: float(_drift_metric(cbar, v.astype(cbar.dtype))))
        return self._last_drift

    @property
    def basis(self) -> Optional[jax.Array]:
        """The currently served (d, r) basis (None before the first refresh)."""
        return self._buffers[self._front]

    @property
    def membership(self) -> Membership:
        return self._mem

    @property
    def plan(self) -> Plan:
        return self._plan

    @property
    def stats(self) -> Dict[str, Any]:
        """Service health: staleness / drift / refresh, query and
        blocking-read counters / plan."""
        return {
            "step": self._step,
            "rows_seen": int(jnp.sum(self._state["count"])),
            "refreshes": self._refreshes,
            "queries": self._queries,
            "syncs": self._syncs,
            "sync_wait_s": self._sync_wait_s,
            "staleness": self._step - self._last_refresh_step,
            "cadence": self.cadence,
            "drift": self._last_drift,
            "drift_threshold": self.drift_threshold,
            "last_jump": self._last_jump,
            "m_active": self._mem.m_active,
            "replans": self._replans,
            "events": [e.reason for e in self._events],
            "plan": self._plan,
        }
