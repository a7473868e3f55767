"""The local solve's row form: v ↦ Xᵀ(Xv)/n without forming XᵀX.

The covariance form of the local solve (``repro.core.covariance``, then
``repro.core.subspace``) builds the (d, d) matrix XᵀX/n and iterates on
it.  Where (d, d) float32 would not fit on the device, the solve instead
applies the rows twice per iteration: Xᵀ(XQ)/n costs 4·n·d·r FLOP and one (n, r) intermediate, and
no (d, d) array ever exists.

``RowOperator`` holds the shard's rows as they arrive and a per-column
affine map, so that the rows the estimator sees are X = (C − 𝟙μᵀ) S⁻¹:

* plain float rows: μ = 0, S = I (no map is applied);
* genotype counts under ``standardize="patterson"``
  (``repro.data.genotypes.patterson``): μ the site's column means and
  S = diag(√(p(1 − p))), Patterson, Price & Reich (2006) eq. 3.

The map is folded into the two products, so no standardized (n, d) copy is
ever written: Xq = C(S⁻¹q) − 𝟙(μᵀS⁻¹q) and Xᵀz = S⁻¹(Cᵀz − μ(𝟙ᵀz)).
Integer rows are widened inside each product; every product runs at the
precision the caller traces it at (``subspace_iteration`` traces at
HIGHEST).

A ``RowOperator`` is a pytree and supports ``op @ q``, ``op.shape`` and
``op.dtype``, so ``subspace_iteration`` runs on it unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.data.genotypes import patterson_moments

__all__ = ["RowOperator", "row_operator", "check_standardize"]

# The standardizations ``distributed_pca`` accepts besides ``None``:
# name -> (rows -> (mean, scale)).
STANDARDIZATIONS = {"patterson": patterson_moments}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowOperator:
    """v ↦ Xᵀ(Xv)/n for X = (rows − 𝟙 mean) diag(inv_scale).

    ``mean`` and ``inv_scale`` are (d,) float32, or None for plain rows.
    """

    rows: jax.Array
    mean: Optional[jax.Array] = None
    inv_scale: Optional[jax.Array] = None

    @property
    def shape(self):
        d = self.rows.shape[1]
        return (d, d)

    @property
    def dtype(self):
        return jnp.promote_types(self.rows.dtype, jnp.float32)

    def standardized(self) -> jax.Array:
        """X itself, (n, d): for the covariance form, which forms XᵀX."""
        x = self.rows.astype(self.dtype)
        if self.mean is not None:
            x = x - self.mean
        if self.inv_scale is not None:
            x = x * self.inv_scale
        return x

    def __matmul__(self, q: jax.Array) -> jax.Array:
        if self.inv_scale is not None:
            q = q * self.inv_scale[:, None]
        z = jnp.matmul(self.rows, q, preferred_element_type=self.dtype)
        if self.mean is not None:
            z = z - (self.mean @ q)[None, :]
        # z = X q; now Xᵀ z.
        w = jnp.matmul(self.rows.T, z, preferred_element_type=self.dtype)
        if self.mean is not None:
            w = w - self.mean[:, None] * jnp.sum(z, axis=0)[None, :]
        if self.inv_scale is not None:
            w = w * self.inv_scale[:, None]
        return w / self.rows.shape[0]


def check_standardize(standardize: Optional[str]) -> None:
    if standardize is not None and standardize not in STANDARDIZATIONS:
        raise ValueError(
            f"unknown standardization {standardize!r}; expected None or one "
            f"of {tuple(STANDARDIZATIONS)}"
        )


def row_operator(rows: jax.Array, standardize: Optional[str] = None
                 ) -> RowOperator:
    """The row form of one shard's rows, under ``standardize`` (None or
    ``"patterson"``)."""
    check_standardize(standardize)
    if standardize is None:
        return RowOperator(rows)
    mean, scale = STANDARDIZATIONS[standardize](rows)
    return RowOperator(rows, mean, 1.0 / scale)
