"""Distributed eigenspace estimators (serial reference implementations).

Implements the paper's Algorithm 1 (Procrustes fixing) and Algorithm 2
(iterative refinement), the naive-averaging strawman, the centralized
estimator, and the spectral-projector-averaging baseline of Fan et al. 2019
("[20]" in the paper).  These are the oracles the ``shard_map`` runtime in
``repro.core.distributed`` is tested against, and what the paper-figure
benchmarks run.

All functions take local solutions as a stacked array ``vs`` of shape
(m, d, r) — machine-major — and are jit-friendly.  How that stack comes to
exist on a mesh is the *communication topology*'s business
(``repro.comm``): the gather topology materializes it and delegates here;
the psum and ring topologies never form it and run the same round body
shard-locally in ``repro.core.distributed`` / ``repro.comm.ring``.

The aggregation hot path takes three switches (see DESIGN.md §3):

  * ``backend=`` ("xla" | "pallas" | "auto"): "pallas" streams the
    bandwidth-bound stages through the ``repro.kernels.procrustes_align``
    Pallas kernels (compiled on TPU, interpret mode elsewhere); "auto"
    picks the kernels on TPU and the pure-XLA path elsewhere.
  * ``polar=`` ("svd" | "newton-schulz"): how the r x r orthogonal polar
    factor is computed.  "svd" is the paper's closed form; on the pallas
    backend it is a stage that round-trips through XLA.  "newton-schulz"
    is matmul-only and fuses into the Gram kernel.
  * ``orth=`` ("qr" | "cholesky-qr2"): how the averaged basis is
    re-orthonormalized at the end of each round.  "qr" is the paper's thin
    Householder QR (always an XLA stage); "cholesky-qr2" is matmul +
    triangular-solve only (``repro.core.orthonorm``) and, combined with
    ``polar="newton-schulz"`` on the pallas backend, folds the *entire*
    round into a single kernel launch
    (``repro.kernels.procrustes_align.fused_round``) — no SVD, no
    Householder QR, no XLA compute anywhere in a refinement round.  The
    same kernel combination on the *ring* topology has a ring-scheduled
    sibling (``fused_ring_round``, DESIGN.md §3.3) whose grid drives the
    hops themselves: the staged wire payloads are consumed inside the
    launch and the running V̄ never leaves VMEM.

All round structure funnels through one round-body dispatch
(``refinement_rounds``); every cell of the (backend x polar x orth) cube
computes the same estimator (the differential tests assert parity to 1e-5
f64 subspace distance); "pallas" accumulates in f32.  Instead of picking
the switches by hand, pass ``plan="auto"`` and the cost-model planner
(``repro.plan``) scores the cube and decides; ``plan=None`` keeps the
per-knob legacy behavior exactly.

Paper-anchor map (Algorithm 1 = one-shot Procrustes fixing; Algorithm 2
= iterative refinement; README.md's paper→code table points here):

  * step 1, local solve:    ``repro.core.subspace.local_eigenbasis``
                            (per-machine top-r eigenbasis), batched by
                            ``local_bases``.
  * step 2, alignment:      the Procrustes problem eq. (5) with closed
                            form eq. (6) — ``repro.core.procrustes
                            .procrustes_rotation`` / ``align_batch``.
  * step 3, averaging:      V̄ = (1/m) Σᵢ Vᵢ Zᵢ — the ``jnp.mean`` of the
                            aligned stack inside ``refinement_rounds``
                            (contrast eq. (3), ``naive_average``'s
                            unaligned mean that Fig. 1 shows collapsing).
  * step 4, re-orthonormalization: thin QR of V̄ —
                            ``repro.core.orthonorm.orthonormalize``.
  * Algorithm 2:            repeat steps 2–4 with the previous output as
                            the reference — the ``n_iter`` loop of
                            ``refinement_rounds`` / ``iterative_refinement``.
  * communication accounting (§2.1 / Remark 2): ``repro.comm.comm_cost``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import procrustes
from repro.core.orthonorm import (
    orthonormalize,
    qr_orthonormalize,
    resolve_orth,
)
from repro.core.precision import highest_precision
from repro.core.subspace import local_eigenbasis

__all__ = [
    "qr_orthonormalize",
    "naive_average",
    "procrustes_fix_average",
    "refinement_rounds",
    "iterative_refinement",
    "projector_average",
    "central_estimate",
    "local_bases",
]


def local_bases(
    xhats: jax.Array, r: int, *, method: str = "eigh", iters: int = 30
) -> jax.Array:
    """Compute each machine's leading r-dim eigenbasis. xhats: (m, d, d)."""
    f = lambda x: local_eigenbasis(x, r, method=method, iters=iters)[0]
    return jax.vmap(f)(xhats)


def naive_average(vs: jax.Array, *, orth: str = "qr") -> jax.Array:
    """Eq. (3): average the raw local bases, then orthonormalize.

    The strawman the paper shows fails: with adversarial (or random) rotations
    the average can collapse toward zero / an arbitrary subspace — under any
    ``orth=`` method, since the collapse happens before orthonormalization.
    """
    return orthonormalize(jnp.mean(vs, axis=0), orth=orth)


def _rounds_pallas(
    vs: jax.Array, ref: jax.Array, *, n_iter: int, polar: str, orth: str
) -> jax.Array:
    """Kernel-dispatched round loop (Algorithm 1 body x ``n_iter``).

    ``polar="newton-schulz", orth="cholesky-qr2"``: the fully fused path —
    one ``pallas_call`` per round, XLA-free between launches (the loop
    lives inside ``kernels.fused_round`` so padding happens once).
    Other cells run the per-stage kernels with the r x r polar and/or the
    final orthonormalization as XLA stages between launches.
    """
    from repro.kernels import ops as kops

    if polar == "newton-schulz" and orth == "cholesky-qr2":
        return kops.fused_round(vs, ref, n_iter=n_iter, use_kernel=True)
    out = ref
    for _ in range(max(n_iter, 1)):
        if polar == "newton-schulz":
            z = kops.batched_gram_polar(vs, out, use_kernel=True)
        else:
            g = kops.batched_gram(vs, out, use_kernel=True)  # (m, r, r) f32
            u, _, wt = jnp.linalg.svd(g, full_matrices=False)  # stays in XLA
            z = u @ wt
        vbar = kops.align_average(vs, z, use_kernel=True)  # (d, r) f32
        out = orthonormalize(vbar, orth=orth).astype(vs.dtype)
    return out


def _rounds_xla(
    vs: jax.Array, ref: jax.Array, *, n_iter: int, polar: str, orth: str
) -> jax.Array:
    """Pure-jnp round loop: align, average, orthonormalize, repeat."""
    out = ref
    for _ in range(max(n_iter, 1)):
        aligned = procrustes.align_batch(vs, out, polar=polar)
        out = orthonormalize(jnp.mean(aligned, axis=0), orth=orth)
    return out


@highest_precision
def refinement_rounds(
    vs: jax.Array,
    ref: jax.Array | None = None,
    *,
    n_iter: int = 1,
    backend: str | None = None,
    polar: str | None = None,
    orth: str | None = None,
    plan=None,
) -> jax.Array:
    """The single home of the round structure: run the Algorithm-1 body
    (steps 2–4: align to ``ref``, average, orthonormalize) ``n_iter``
    times over an already-stacked (m, d, r) ``vs``, re-using each output
    as the next reference (Algorithm 2), dispatched on
    ``backend``/``polar``/``orth``.  Both ``iterative_refinement`` and
    the gather-topology branch of
    ``repro.core.distributed.procrustes_average_collective`` call this.

    ``plan=None|"auto"|repro.plan.Plan`` resolves the switches through
    the execution planner (``repro.plan.resolve_plan``): ``None`` keeps
    the documented legacy defaults ("xla", "svd", "qr"); ``"auto"``
    scores the (backend x polar x orth) cube for this (m, d, r) with
    concrete knob arguments as pins.
    """
    from repro.plan.planner import resolve_plan

    m, d, r = vs.shape
    pl = resolve_plan(
        plan, m=m, d=d, r=r, n_iter=n_iter,
        backend=backend, polar=polar, orth=orth, context="stacked",
    )
    backend, polar, orth = pl.backend, pl.polar, pl.orth
    procrustes.resolve_polar(polar)
    resolve_orth(orth)
    if ref is None:
        ref = vs[0]
    rounds = _rounds_pallas if backend == "pallas" else _rounds_xla
    return rounds(vs, ref, n_iter=n_iter, polar=polar, orth=orth)


def procrustes_fix_average(
    vs: jax.Array,
    ref: jax.Array | None = None,
    *,
    backend: str | None = None,
    polar: str | None = None,
    orth: str | None = None,
    plan=None,
) -> jax.Array:
    """Algorithm 1 (one-shot Procrustes fixing): align every local basis
    to ``ref`` (eq. (5)/(6)), average, orthonormalize — exactly one
    refinement round.

    Args:
      vs:  (m, d, r) stacked local solutions (Algorithm 1 step 1 output).
      ref: (d, r) reference solution; defaults to ``vs[0]`` per the paper.
      backend: "xla" (pure jnp), "pallas" (kernel stages), or "auto"
        (kernels on TPU, XLA elsewhere).  Default "xla".
      polar: "svd" (the closed form, eq. (6)) or "newton-schulz"
        (matmul-only).  Default "svd".
      orth: "qr" (thin Householder QR, the paper's step 4) or
        "cholesky-qr2" (matmul + triangular solve; fully fused on the
        pallas backend).  Default "qr".  See the module docstring.
      plan: ``None`` (legacy per-knob resolution) | ``"auto"`` (the
        ``repro.plan`` cost model decides the free knobs) | a
        ``repro.plan.Plan``.
    """
    return refinement_rounds(
        vs, ref, n_iter=1, backend=backend, polar=polar, orth=orth, plan=plan
    )


@functools.partial(
    jax.jit, static_argnames=("n_iter", "backend", "polar", "orth", "plan")
)
def iterative_refinement(
    vs: jax.Array,
    n_iter: int = 2,
    *,
    backend: str | None = None,
    polar: str | None = None,
    orth: str | None = None,
    plan=None,
) -> jax.Array:
    """Algorithm 2: repeat Algorithm 1, re-using the output as the reference.

    ``n_iter=1`` is exactly Algorithm 1 with the default reference.
    ``backend`` / ``polar`` / ``orth`` / ``plan`` are threaded through
    every round's aggregation (see ``refinement_rounds``).
    """
    return refinement_rounds(
        vs, n_iter=n_iter, backend=backend, polar=polar, orth=orth, plan=plan
    )


def projector_average(vs: jax.Array, r: int) -> jax.Array:
    """Fan et al. 2019 baseline: average spectral projectors, take top-r.

    Forms ``(1/m) sum_i V_i V_i^T`` (d x d) and returns its leading r-dim
    eigenspace.  O(m d^2 r) — the cost the paper's Remark 1 contrasts with.
    """
    m, d, _ = vs.shape
    p = jnp.einsum("mdr,mer->de", vs, vs) / m
    lam, vec = jnp.linalg.eigh(p)
    return vec[:, ::-1][:, :r]


def central_estimate(
    xhats: jax.Array, r: int, *, method: str = "eigh", iters: int = 30
) -> Tuple[jax.Array, jax.Array]:
    """Centralized oracle: top-r eigenspace of the mean of the local matrices.

    In the distributed-PCA setting this is the estimator with access to all
    ``m * n`` samples (the paper's "Central" label).
    """
    return local_eigenbasis(jnp.mean(xhats, axis=0), r, method=method, iters=iters)
