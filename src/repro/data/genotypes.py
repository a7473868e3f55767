"""Genotype matrices for ancestry PCA: a seeded generator and the
standardization biobank PCA applies.

Sources:

* Deployment: UK Biobank's genetic PCA, Bycroft et al., *Nature*
  562:203–209 (2018), Methods, "Principal component analysis": 40 PCs over
  147,604 LD-pruned markers and 407,219 unrelated participants.
* Standardization: EIGENSOFT, Patterson, Price & Reich, *PLoS Genet.*
  2:e190 (2006), eq. 3.  With C the (n, d) count matrix (0, 1 or 2 copies
  of the reference allele per participant and marker),
  μⱼ = Σᵢ Cᵢⱼ / n, pⱼ = (1 + Σᵢ Cᵢⱼ) / (2 + 2n) and
  Xᵢⱼ = (Cᵢⱼ − μⱼ) / √(pⱼ(1 − pⱼ)).  A monomorphic marker (every count
  equal) standardizes to zero: C − μ vanishes and p stays inside (0, 1).
* Genotypes: the admixture model of Pritchard, Stephens & Donnelly,
  *Genetics* 155:945–959 (2000), over K populations whose allele
  frequencies follow the Balding–Nichols model (Balding & Nichols,
  *Genetica* 96:3–12, 1995): an ancestral frequency pⱼ ~ U(p_lo, p_hi),
  population k's frequency fₖⱼ ~ Beta(pⱼ(1 − F)/F, (1 − pⱼ)(1 − F)/F)
  with F = F_ST, participant i's ancestry aᵢ ~ Dirichlet(α·𝟙_K), and
  Cᵢⱼ ~ Binomial(2, aᵢ·f_j).

UK Biobank's genotypes are not public; the generator stands in for them
at their widths.  ``genotype_rows`` draws counts in row blocks on the
device, so no (n, d) floating array is ever held whole.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "population_frequencies",
    "genotype_rows",
    "patterson_moments",
    "patterson",
]

# Rows drawn per block: the block's (rows, d) float32 frequencies and
# uniforms stay a few hundred MB at d=147,604.
ROW_BLOCK = 1024


def population_frequencies(key: jax.Array, d: int, k: int, *, fst: float,
                           p_lo: float = 0.05, p_hi: float = 0.95
                           ) -> jax.Array:
    """(k, d) float32 allele frequencies of k populations drawn from one
    ancestral population under the Balding–Nichols model."""
    kp, kf = jax.random.split(key)
    p = jax.random.uniform(kp, (d,), jnp.float32, p_lo, p_hi)
    scale = (1.0 - fst) / fst
    return jax.random.beta(kf, p * scale, (1.0 - p) * scale, (k, d),
                           jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "alpha", "block"))
def genotype_rows(key: jax.Array, freqs: jax.Array, n: int, *,
                  alpha: float, block: int = ROW_BLOCK) -> jax.Array:
    """(n, d) int8 counts of n admixed participants: ancestries
    aᵢ ~ Dirichlet(alpha·𝟙), counts Binomial(2, aᵢ·f) as two Bernoulli
    draws, made ``block`` rows at a time (the last block ends at row n and
    redraws the rows it shares with the one before it)."""
    k, d = freqs.shape
    block = min(block, n)
    ka, kc = jax.random.split(key)
    ancestry = jax.random.dirichlet(ka, jnp.full((k,), alpha, jnp.float32),
                                    (n,))

    def draw(i, out):
        start = jnp.minimum(i * block, n - block)
        a = jax.lax.dynamic_slice_in_dim(ancestry, start, block)
        pi = jnp.matmul(a, freqs, precision=jax.lax.Precision.HIGHEST)
        u = jax.random.uniform(jax.random.fold_in(kc, i), (2, block, d))
        c = (u[0] < pi).astype(jnp.int8) + (u[1] < pi).astype(jnp.int8)
        return jax.lax.dynamic_update_slice_in_dim(out, c, start, 0)

    return jax.lax.fori_loop(0, -(-n // block), draw,
                             jnp.zeros((n, d), jnp.int8))


def patterson_moments(counts: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(μ, √(p(1 − p))) of Patterson et al. eq. 3, each (d,) float32, from
    exact integer column sums of the (n, d) counts."""
    n = counts.shape[0]
    s = jnp.sum(counts, axis=0, dtype=jnp.int32).astype(jnp.float32)
    p = (1.0 + s) / (2.0 + 2.0 * n)
    return s / n, jnp.sqrt(p * (1.0 - p))


def patterson(counts: jax.Array) -> jax.Array:
    """The standardized (n, d) float32 rows X of Patterson et al. eq. 3."""
    mean, scale = patterson_moments(counts)
    return (counts.astype(jnp.float32) - mean) / scale
