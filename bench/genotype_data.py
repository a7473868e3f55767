"""The benchmark's own copy of the genotype generator.

Copied from the program's ``repro/data/genotypes.py`` and frozen here, so
that the genotypes every genotype cell runs on stay the same whatever the
program's copy becomes.  The Pritchard-Stephens-Donnelly admixture model
(2000) over K populations with Balding-Nichols allele frequencies:
ancestral p_j ~ U(p_lo, p_hi), population frequencies
f_kj ~ Beta(p_j (1 - F) / F, (1 - p_j)(1 - F) / F) with F = F_ST,
ancestries a_i ~ Dirichlet(alpha 1), counts C_ij ~ Binomial(2, a_i . f_j),
int8, drawn in row blocks on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024


def population_frequencies(key: jax.Array, d: int, k: int, *, fst: float,
                           p_lo: float, p_hi: float) -> jax.Array:
    """(k, d) float32 allele frequencies under the Balding-Nichols model."""
    kp, kf = jax.random.split(key)
    p = jax.random.uniform(kp, (d,), jnp.float32, p_lo, p_hi)
    scale = (1.0 - fst) / fst
    return jax.random.beta(kf, p * scale, (1.0 - p) * scale, (k, d),
                           jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "alpha", "block"))
def genotype_rows(key: jax.Array, freqs: jax.Array, n: int, *,
                  alpha: float, block: int = ROW_BLOCK) -> jax.Array:
    """(n, d) int8 counts of n admixed participants, ``block`` rows at a
    time (the last block ends at row n)."""
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


def sites(key: jax.Array, cfg: dict, count: int, shards: int):
    """``count`` sets of ``shards`` sites each, ``rows_per_shard`` rows per
    site, all drawn from one set of populations (one deployment): a tuple
    of (shards * rows_per_shard, d) int8 arrays."""
    g = cfg["genotypes"]
    if g["model"] != "admixture":
        raise ValueError(f"no generator for genotypes {g['model']!r}")
    kf, kc = jax.random.split(key)
    freqs = population_frequencies(kf, cfg["d"], g["populations"],
                                   fst=g["fst"], p_lo=g["p_lo"],
                                   p_hi=g["p_hi"])
    n = cfg["rows_per_shard"]
    out = []
    for kset in jax.random.split(kc, count):
        out.append(jnp.concatenate([
            genotype_rows(k, freqs, n, alpha=g["alpha"])
            for k in jax.random.split(kset, shards)]))
    return tuple(out)
