"""Synthetic data generators from the paper's experimental section.

Covariance models (M1)/(M2) from Section 3, the non-Gaussian sphere mixture
D_k from eq. (35), and quadratic-sensing measurements from eq. (38)/(39).

Note on (M2): the paper writes the trailing eigenvalues as
``(1 - delta) * alpha**(i - r)`` but states that "both constructions ensure
the eigengap is exactly delta", which requires the first trailing eigenvalue
to be ``1 - delta``; we therefore use exponent ``i - r - 1`` (first trailing
value = 1 - delta), matching the stated eigengap.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "random_orthogonal",
    "spectrum_m1",
    "spectrum_m2",
    "covariance_from_spectrum",
    "sample_gaussian",
    "make_dk_atoms",
    "sample_dk",
    "quadratic_sensing_measurements",
    "truncated_second_moment",
]


_HAAR_PANEL = 128
_HAAR_PANEL_MIN_D = 2048  # below this a d x d QR compiles fast enough


def random_orthogonal(key: jax.Array, d: int, dtype=jnp.float32) -> jax.Array:
    """Haar random orthogonal matrix: the Q of a Gaussian's QR, with signs
    fixed so that R's diagonal is positive (Mezzadri 2007)."""
    g = jax.random.normal(key, (d, d), dtype=dtype)
    if d < _HAAR_PANEL_MIN_D or d % _HAAR_PANEL:
        q, r = jnp.linalg.qr(g)
        return q * jnp.sign(jnp.diagonal(r))[None, :]
    return _panel_qr_q(g)


@jax.jit
def _panel_qr_q(g: jax.Array) -> jax.Array:
    """The same Q for a wide d, panel by panel: each 128-column panel is
    projected off the columns already made, twice (block Gram–Schmidt
    with reorthogonalization), then thin-QR'd with its signs fixed.  Only
    matmuls and tall-skinny QRs: a d x d QR takes minutes to compile for a
    TPU at d=8192, this takes seconds."""
    d = g.shape[0]
    hi = jax.lax.Precision.HIGHEST

    def panel(k, q):
        p = jax.lax.dynamic_slice_in_dim(g, k * _HAAR_PANEL, _HAAR_PANEL, 1)
        for _ in range(2):  # q's columns not yet made are zero
            p = p - jnp.dot(q, jnp.dot(q.T, p, precision=hi), precision=hi)
        qp, rp = jnp.linalg.qr(p)
        qp = qp * jnp.sign(jnp.diagonal(rp))[None, :]
        return jax.lax.dynamic_update_slice_in_dim(q, qp, k * _HAAR_PANEL, 1)

    return jax.lax.fori_loop(0, d // _HAAR_PANEL, panel, jnp.zeros_like(g))


def spectrum_m1(
    d: int, r: int, *, lam_l: float = 0.5, lam_h: float = 1.0, delta: float = 0.2
) -> jax.Array:
    """(M1): r principal eigenvalues linearly spaced in [lam_l, lam_h];
    trailing eigenvalues (lam_l - delta) * 0.9**(i - r - 1). Gap == delta."""
    if r > 1:
        head = lam_h - (lam_h - lam_l) * jnp.arange(r) / (r - 1)
    else:
        head = jnp.array([lam_h])
    tail = (lam_l - delta) * 0.9 ** jnp.arange(d - r)
    return jnp.concatenate([head, tail])


def spectrum_m2(d: int, r: int, r_star: float, *, delta: float = 0.25) -> jax.Array:
    """(M2): principal eigenvalues 1; trailing decay rate alpha solving
    (1 - delta) / (1 - alpha) = r_star - r, so intdim ~= r_star. Gap == delta."""
    if not r_star > r + (1.0 - delta):
        raise ValueError(f"need r_star > r + 1 - delta, got r_star={r_star}, r={r}")
    alpha = 1.0 - (1.0 - delta) / (r_star - r)
    head = jnp.ones((r,))
    tail = (1.0 - delta) * alpha ** jnp.arange(d - r)
    return jnp.concatenate([head, tail])


def covariance_from_spectrum(
    key: jax.Array, tau: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sigma = U diag(tau) U^T with Haar U (paper eq. (34)).

    Returns (sigma, v1, factor) where v1 is the leading-r ground truth (the
    caller slices the columns it needs) and ``factor = U diag(sqrt(tau))`` is
    the sampling factor (x = factor @ z, z ~ N(0, I)).
    """
    d = tau.shape[0]
    u = random_orthogonal(key, d)
    sigma = (u * tau[None, :]) @ u.T
    factor = u * jnp.sqrt(tau)[None, :]
    return sigma, u, factor


def sample_gaussian(key: jax.Array, factor: jax.Array, n: int) -> jax.Array:
    """n samples of x = factor @ z, z ~ N(0, I_d). Returns (n, d)."""
    d = factor.shape[1]
    z = jax.random.normal(key, (n, d), dtype=factor.dtype)
    return z @ factor.T


def make_dk_atoms(key: jax.Array, d: int, k: int) -> jax.Array:
    """k atoms y_i uniform on sqrt(d) * S^{d-1} (paper eq. (35))."""
    g = jax.random.normal(key, (k, d))
    y = g / jnp.linalg.norm(g, axis=1, keepdims=True)
    return y * jnp.sqrt(d)


def sample_dk(key: jax.Array, atoms: jax.Array, n: int) -> jax.Array:
    """n draws from Unif{y_1..y_k}. Returns (n, d)."""
    k = atoms.shape[0]
    idx = jax.random.randint(key, (n,), 0, k)
    return atoms[idx]


def quadratic_sensing_measurements(
    key: jax.Array, x_sharp: jax.Array, n: int, *, noise: float = 0.0
) -> Tuple[jax.Array, jax.Array]:
    """Quadratic sensing (eq. 38): y_i = ||X#^T a_i||^2 + noise, a_i ~ N(0, I).

    Returns (a, y): a (n, d), y (n,).
    """
    d = x_sharp.shape[0]
    ka, kn = jax.random.split(key)
    a = jax.random.normal(ka, (n, d))
    y = jnp.sum((a @ x_sharp) ** 2, axis=1)
    if noise > 0:
        y = y + noise * jax.random.normal(kn, (n,))
    return a, y


def truncated_second_moment(
    a: jax.Array, y: jax.Array, *, tau: float | None = None
) -> jax.Array:
    """Spectral-init matrix D_N (eq. 39) with truncation T(y) = y * 1{y <= tau}.

    Default threshold: tau = 3 * mean(y) (standard truncated spectral init).
    """
    if tau is None:
        tau = 3.0 * jnp.mean(y)
    ty = jnp.where(y <= tau, y, 0.0)
    n = a.shape[0]
    return (a.T * ty[None, :]) @ a / n
