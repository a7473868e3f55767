"""Local top-r eigensolvers.

The paper computes each machine's leading invariant subspace with a dense
eigendecomposition.  On TPU the MXU-friendly choice is blocked subspace
(orthogonal) iteration — matmul + QR only — so that is our default for large
``d``; ``eigh`` remains available as the exact fallback.  A final
Rayleigh–Ritz rotation sorts the basis by eigenvalue, which also makes the
subspace-iteration output comparable (up to rotation) with ``eigh``'s.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.precision import highest_precision

__all__ = [
    "top_r_eigh",
    "subspace_start",
    "subspace_steps",
    "subspace_finish",
    "subspace_iteration",
    "local_eigenbasis",
]


def top_r_eigh(x: jax.Array, r: int) -> Tuple[jax.Array, jax.Array]:
    """Exact top-r eigenpairs of a symmetric matrix via full ``eigh``.

    Returns (V, lam) with V (d, r), lam (r,) sorted descending.
    """
    lam, vec = jnp.linalg.eigh(x)
    v = vec[:, ::-1][:, :r]
    return v, lam[::-1][:r]


@highest_precision
def subspace_start(
    x: jax.Array,
    r: int,
    *,
    key: jax.Array | None = None,
    v0: jax.Array | None = None,
) -> jax.Array:
    """The orthonormal (d, r) start of ``subspace_iteration``: ``v0``, or a
    normal draw from ``key`` (default ``PRNGKey(0)``), through QR."""
    d = x.shape[0]
    if v0 is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        v0 = jax.random.normal(key, (d, r), dtype=x.dtype)
    q, _ = jnp.linalg.qr(v0)
    return q


@highest_precision
def subspace_steps(x: jax.Array, q: jax.Array, iters: int) -> jax.Array:
    """``iters`` steps of orthogonal iteration from the basis q:
    q <- qr(x @ q)."""

    def body(_, q):
        z = x @ q
        q, _ = jnp.linalg.qr(z)
        return q

    return jax.lax.fori_loop(0, iters, body, q)


@highest_precision
def subspace_finish(x: jax.Array, q: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Rayleigh--Ritz: rotate the basis q to (approximate) eigenvectors,
    sorted by Ritz value, descending.  Returns (V, lam)."""
    h = q.T @ (x @ q)
    h = 0.5 * (h + h.T)
    lam, w = jnp.linalg.eigh(h)
    order = jnp.argsort(lam)[::-1]
    return q @ w[:, order], lam[order]


@functools.partial(jax.jit, static_argnames=("r", "iters"))
@highest_precision
def subspace_iteration(
    x: jax.Array,
    r: int,
    *,
    iters: int = 30,
    key: jax.Array | None = None,
    v0: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Blocked orthogonal iteration for the leading r-dim invariant subspace.

    Matmul + QR only (MXU-friendly); fixed ``iters`` keeps it jittable with a
    static cost.  Convergence is linear with rate ``|lam_{r+1}/lam_r|``; the
    eigengap assumption of the paper (Assumption 1) is exactly what makes this
    fast.  A final Rayleigh–Ritz step returns an eigen-ordered basis.

    It is ``subspace_start``, ``subspace_steps`` and ``subspace_finish`` in
    one program; a caller may run the pieces as programs of their own
    (``SubspaceService.refresh``, cut into slices): the same operations in
    the same order.

    Returns (V, lam): V (d, r) orthonormal, lam (r,) Ritz values descending.
    Every product runs at HIGHEST precision (``repro.core.precision``).
    """
    q = subspace_start(x, r, key=key, v0=v0)
    return subspace_finish(x, subspace_steps(x, q, iters))


def local_eigenbasis(
    x: jax.Array,
    r: int,
    *,
    method: str = "eigh",
    iters: int = 30,
    key: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Dispatch between exact ``eigh`` and subspace iteration."""
    if method == "eigh":
        return top_r_eigh(x, r)
    if method == "subspace":
        return subspace_iteration(x, r, iters=iters, key=key)
    raise ValueError(f"unknown eigensolver method: {method!r}")
