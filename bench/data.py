"""The benchmark's own copy of the spiked-covariance generator.

Copied from the program's ``repro/data/synthetic.py`` (``spectrum_m1``,
the panel-by-panel Haar factor, ``sample_gaussian``) and frozen here, so
that the rows every cell runs on stay the same whatever the program's copy
becomes.  Model (M1) of arXiv 2009.02436 §3, eq. (34): Sigma = U diag(tau)
U^T with Haar U; rows are x = U diag(sqrt(tau)) z, z ~ N(0, I).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HAAR_PANEL = 128


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number: the low 32 bits make
    the key and the bits above them are folded in, so seeds past 2**32
    neither overflow nor collide."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0 (got {seed})")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def spectrum_m1(d: int, r: int, *, lam_l: float = 0.5, lam_h: float = 1.0,
                delta: float = 0.2) -> jax.Array:
    """(M1): r leading eigenvalues spaced linearly over [lam_l, lam_h]; the
    trailing ones (lam_l - delta) * 0.9**i.  The eigengap is delta."""
    if r > 1:
        head = lam_h - (lam_h - lam_l) * jnp.arange(r) / (r - 1)
    else:
        head = jnp.array([lam_h])
    tail = (lam_l - delta) * 0.9 ** jnp.arange(d - r)
    return jnp.concatenate([head, tail])


def haar_orthogonal(key: jax.Array, d: int) -> jax.Array:
    """Haar orthogonal (d, d): the Q of a Gaussian's QR with R's diagonal
    made positive (Mezzadri 2007).  Built panel by panel (block
    Gram-Schmidt, twice, then a thin QR per 128-column panel) where d is a
    multiple of 128, since a d x d QR at d=8192 compiles for minutes on a
    TPU."""
    g = jax.random.normal(key, (d, d), dtype=jnp.float32)
    if d % HAAR_PANEL:
        q, r = jnp.linalg.qr(g)
        return q * jnp.sign(jnp.diagonal(r))[None, :]
    hi = jax.lax.Precision.HIGHEST

    def panel(k, q):
        p = jax.lax.dynamic_slice_in_dim(g, k * HAAR_PANEL, HAAR_PANEL, 1)
        for _ in range(2):  # columns of q not made yet are zero
            p = p - jnp.dot(q, jnp.dot(q.T, p, precision=hi), precision=hi)
        qp, rp = jnp.linalg.qr(p)
        qp = qp * jnp.sign(jnp.diagonal(rp))[None, :]
        return jax.lax.dynamic_update_slice_in_dim(q, qp, k * HAAR_PANEL, 1)

    return jax.lax.fori_loop(0, d // HAAR_PANEL, panel, jnp.zeros_like(g))


def sampling_factor(key: jax.Array, d: int, r: int, spectrum: dict) -> jax.Array:
    """U diag(sqrt(tau)) for the (M1) spectrum a configuration states
    (``lam_l``, ``lam_h``, ``delta``)."""
    if spectrum["model"] != "M1":
        raise ValueError(f"no generator for spectrum {spectrum['model']!r}")
    tau = spectrum_m1(d, r, lam_l=spectrum["lam_l"], lam_h=spectrum["lam_h"],
                      delta=spectrum["delta"])
    return haar_orthogonal(key, d) * jnp.sqrt(tau)[None, :]


def sample_gaussian(key: jax.Array, factor: jax.Array, n: int) -> jax.Array:
    """n rows x = factor @ z, z ~ N(0, I_d): (n, d)."""
    z = jax.random.normal(key, (n, factor.shape[1]), dtype=factor.dtype)
    return z @ factor.T
