"""Subspace distances and spectral diagnostics used throughout the paper."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import highest_precision

__all__ = [
    "dist_2",
    "dist_f",
    "subspace_dist64",
    "intdim",
    "eigengap",
    "principal_angles_sin",
]


@highest_precision
def _gram_singulars(u: jax.Array, v: jax.Array) -> jax.Array:
    """Singular values of u^T v (cosines of principal angles), clipped to [0,1].

    The Gram runs at HIGHEST precision (``repro.core.precision``): a
    distance should not carry the rounding of a TPU's default product.
    """
    s = jnp.linalg.svd(u.T @ v, compute_uv=False)
    return jnp.clip(s, 0.0, 1.0)


def dist_2(u: jax.Array, v: jax.Array) -> jax.Array:
    """Spectral subspace distance ``||UU^T - VV^T||_2`` (paper's dist_2).

    For orthonormal U, V with the same number of columns this equals the sine
    of the largest principal angle; computed via the (r x r) Gram SVD rather
    than forming d x d projectors.
    """
    u = jnp.atleast_2d(u.T).T  # promote (d,) -> (d, 1)
    v = jnp.atleast_2d(v.T).T
    c = _gram_singulars(u, v)
    cmin = jnp.min(c)
    return jnp.sqrt(jnp.maximum(1.0 - cmin * cmin, 0.0))


def subspace_dist64(u, v) -> float:
    """``dist_2`` in f64 on the host, re-orthonormalizing both arguments.

    The f32 ``dist_2`` bottoms out at ~sqrt(f32 eps) ~= 3.5e-4 (a cosine
    that rounds to 1 reads as angle 0 only below that); the parity suites
    and benchmarks assert agreement at 1e-5, so they measure here.  Inputs
    need not be orthonormal — each is QR'd first, making this a pure
    column-span distance.  NumPy, not jittable.
    """
    import numpy as np

    u = np.linalg.qr(np.asarray(u, np.float64))[0]
    v = np.linalg.qr(np.asarray(v, np.float64))[0]
    c = np.clip(np.linalg.svd(u.T @ v, compute_uv=False), 0.0, 1.0)
    return float(np.sqrt(max(1.0 - c.min() ** 2, 0.0)))


def dist_f(u: jax.Array, v: jax.Array) -> jax.Array:
    """Frobenius projector distance ``||UU^T - VV^T||_F`` (used by Fan et al.).

    Equals ``sqrt(2) * || sin(Theta) ||_F = sqrt(2 (r - ||U^T V||_F^2))``.
    """
    u = jnp.atleast_2d(u.T).T
    v = jnp.atleast_2d(v.T).T
    r = u.shape[1]
    c = _gram_singulars(u, v)
    return jnp.sqrt(jnp.maximum(2.0 * (r - jnp.sum(c * c)), 0.0))


def principal_angles_sin(u: jax.Array, v: jax.Array) -> jax.Array:
    """Sines of all principal angles between span(u) and span(v)."""
    c = _gram_singulars(u, v)
    return jnp.sqrt(jnp.maximum(1.0 - c * c, 0.0))


def intdim(a: jax.Array) -> jax.Array:
    """Intrinsic dimension ``intdim(A) = Tr(A) / ||A||_2`` of a PSD matrix."""
    return jnp.trace(a) / jnp.linalg.norm(a, ord=2)


def eigengap(eigvals: jax.Array, r: int) -> jax.Array:
    """``lambda_r - lambda_{r+1}`` for eigenvalues sorted descending."""
    s = jnp.sort(eigvals)[::-1]
    return s[r - 1] - s[r]
