"""Eigen-compressed data-parallel gradient aggregation (role R2).

This is the paper's technique doing production work inside ``train_step``:

  * Each data-parallel shard computes its LOCAL gradient ``G_i`` for a
    compressible weight (2-D, large).  The top-r left eigenbasis of
    ``G_i G_i^T`` is a rotation-ambiguous subspace estimate — exactly the
    paper's setting with X̂ⁱ = G_i G_i^T.
  * Every ``refresh_every`` steps the shards combine their local bases with
    **Algorithm 1/2** (Procrustes-fixed average over the ``data`` axis) into
    a shared projection basis P (d x r).
  * On every step the DP all-reduce runs on ``P^T G_i`` (r x n) instead of
    G_i (d x n): an r/d communication compression of the dominant training
    collective.  Per-shard error feedback (a la PowerSGD) keeps the
    compression unbiased over time.

Why Procrustes fixing is load-bearing: without it, each shard's local basis
is an arbitrary rotation of the others, and averaging bases (or switching
which shard's basis is broadcast) either collapses (paper Fig. 1) or makes
the low-rank moments/error-feedback state inconsistent across refreshes.
Aligning to the PREVIOUS period's basis (the ``ref`` argument the collective
accepts) additionally keeps Adam's low-rank moments valid across refreshes —
a beyond-paper use of the same primitive.  The streaming subspace service
(``repro.stream.service``) leans on the same ref-continuity contract for its
serve path: its refreshes pass the previously *served* basis as ``ref`` so
clients never observe a sign/rotation flip, and ``tests/test_stream.py``
pins the contract as a regression test for both consumers.

All functions here run INSIDE ``shard_map`` with a manual ``data`` axis
(see launch/train.py's hybrid train_step).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.distributed import procrustes_average_collective


@dataclasses.dataclass(frozen=True)
class EigenCompressConfig:
    rank: int = 128
    refresh_every: int = 100
    min_dim: int = 1024      # compress only if leading dim >= min_dim
    power_iters: int = 4     # subspace iterations on G G^T (implicit)
    n_iter: int = 1          # Algorithm 1 (1) / Algorithm 2 (>1)
    # Communication schedule of the basis-refresh collective (repro.comm).
    # "psum" is right for the in-train-step setting: the refresh aligns to
    # an existing reference most steps, so a round is one d*r all-reduce.
    topology: str = "psum"
    # Execution plan of the refresh collective: None (legacy; the knobs
    # above apply as-is), "auto" (the repro.plan cost model decides the
    # free knobs, with `topology` as a pin), or a concrete repro.plan.Plan.
    plan: Optional[Any] = None
    # Wire precision of the refresh collective's payloads (32 | 16 | 8 |
    # "auto"; repro.comm.quantize).  The lossy tiers carry their own
    # per-round error feedback inside the collective, independent of the
    # gradient-level `error_feedback` below.
    comm_bits: Any = 32
    # Active-shard mask of the refresh collective (repro.comm.Membership;
    # None = all alive).  Under a degraded mesh the refresh averages the
    # survivors' bases only — a dead DP shard neither pollutes the shared
    # basis nor blocks the refresh — and a shard that comes back simply
    # re-aligns to `prev_basis` like everyone else (the collective's
    # `ref` machinery).  The config stays hashable: Membership is frozen.
    membership: Optional[Any] = None
    error_feedback: bool = True
    bf16_psum: bool = False  # bf16 all-reduce for UNcompressed leaves


def compressible(path: str, value) -> bool:
    """Policy: 2-D (or stacked 3-D) matmul weights; excludes embeddings'
    vocab axis handling, norms, biases, and diagonal SSM cores (see
    DESIGN.md §Arch-applicability)."""
    if value.ndim not in (2, 3):
        return False
    return True  # size gate applied by caller with the config


def _local_basis(g: jax.Array, r: int, iters: int, key) -> jax.Array:
    """Top-r left singular basis of G (d x n) via implicit subspace iteration
    on G G^T: Q <- qr(G (G^T Q)). Matmul+QR only (MXU-friendly)."""
    d = g.shape[0]
    q = jax.random.normal(key, (d, r), dtype=jnp.float32)
    q, _ = jnp.linalg.qr(q)
    gf = g.astype(jnp.float32)

    def body(_, q):
        z = gf @ (gf.T @ q)
        q, _ = jnp.linalg.qr(z)
        return q

    return jax.lax.fori_loop(0, iters, body, q)


def init_state(param: jax.Array, cfg: EigenCompressConfig) -> Dict[str, Any]:
    """Low-rank state for one compressed leaf (leading dims may be stacked)."""
    *lead, d, n = param.shape
    r = min(cfg.rank, d, n)
    return {
        "basis": jnp.zeros((*lead, d, r), jnp.float32),
        "m": jnp.zeros((*lead, r, n), jnp.float32),
        "v": jnp.zeros((*lead, r, n), jnp.float32),
        # per-shard error feedback (kept sharded over 'data' by the caller)
        "err": jnp.zeros_like(param, dtype=jnp.float32),
        "initialized": jnp.zeros((), jnp.bool_),
    }


def refresh_basis(
    g_local: jax.Array,
    prev_basis: jax.Array,
    initialized: jax.Array,
    *,
    axis_name: str,
    cfg: EigenCompressConfig,
    key,
) -> jax.Array:
    """Procrustes-fixed average of per-shard gradient eigenbases.

    Supports stacked (L, d, n) leaves by vmapping the whole pipeline.
    The previous period's basis is used as the alignment reference once
    available (keeps low-rank moments consistent); the first refresh uses
    shard 0's solution, exactly Algorithm 1.
    """

    def one(g, prev, k):
        v_loc = _local_basis(g, prev.shape[-1], cfg.power_iters, k)
        # Align against previous basis when initialized, else shard-0 default.
        v_prev = procrustes_average_collective(
            v_loc, axis_name=axis_name, n_iter=cfg.n_iter, ref=prev,
            topology=cfg.topology, comm_bits=cfg.comm_bits, plan=cfg.plan,
            membership=cfg.membership,
        )
        v_new = procrustes_average_collective(
            v_loc, axis_name=axis_name, n_iter=cfg.n_iter,
            topology=cfg.topology, comm_bits=cfg.comm_bits, plan=cfg.plan,
            membership=cfg.membership,
        )
        return jnp.where(initialized, v_prev, v_new)

    if g_local.ndim == 2:
        return one(g_local, prev_basis, key)
    keys = jax.random.split(key, g_local.shape[0])
    return jax.vmap(one)(g_local, prev_basis, keys)


def compress_and_reduce(
    g_local: jax.Array,
    state: Dict[str, Any],
    *,
    axis_name: str,
) -> Tuple[jax.Array, jax.Array]:
    """Per-step path: error-feedback add, project, psum, decompress.

    Returns (g_hat_global, g_low_global): the decompressed global gradient
    (d x n) and the low-rank coordinates (r x n) the Adam moments live in.
    Communication: psum of r*n words instead of d*n.
    """
    m = jax.lax.axis_size(axis_name)  # static: no all-reduce on the wire
    g_eff = g_local.astype(jnp.float32) + state["err"]
    p = state["basis"]
    if g_local.ndim == 2:
        g_low = p.T @ g_eff
        g_low = jax.lax.psum(g_low, axis_name) / m
        g_hat = p @ g_low
    else:
        g_low = jnp.einsum("ldr,ldn->lrn", p, g_eff)
        g_low = jax.lax.psum(g_low, axis_name) / m
        g_hat = jnp.einsum("ldr,lrn->ldn", p, g_low)
    return g_hat, g_low


def new_error(
    g_local: jax.Array, state: Dict[str, Any], cfg: EigenCompressConfig
) -> jax.Array:
    """Error feedback: what the projection dropped from THIS shard's grad."""
    if not cfg.error_feedback:
        return jnp.zeros_like(state["err"])
    g_eff = g_local.astype(jnp.float32) + state["err"]
    p = state["basis"]
    if g_local.ndim == 2:
        kept = p @ (p.T @ g_eff)
    else:
        kept = jnp.einsum("ldr,lrn->ldn", p, jnp.einsum("ldr,ldn->lrn", p, g_eff))
    return g_eff - kept
